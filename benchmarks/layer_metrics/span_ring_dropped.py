"""Spans the process's ring has pushed out since it was last cleared
(``tracing.SPANS.dropped``), read after the run. Anything but 0 means that
every metric read from spans is over part of its window: the ring holds
50,000 and a run writes 15 to 20 thousand."""


def read(run):
    from ray_tpu.util import tracing

    return getattr(tracing.SPANS, "dropped", None)
