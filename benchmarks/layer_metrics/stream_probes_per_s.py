"""Probes of the replica a second, in the process that steps the engine: a
stream whose ``reader.read`` ran into its 2 s window asks the object store
whether its call has returned (``RoutedStream._probe``, ``ray_tpu.get`` with
a 50 ms time-out), and every stream that waits does so. Sum over the
``serve.stream`` spans that share the window of ``probes`` times the shared
part of the span's length, over the window."""
from harness import request_path


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    probes = 0.0
    for s in rp.es.named("serve.stream", overlap=True):
        shared = min(s["ts"] + s["dur"], rp.hi) - max(s["ts"], rp.lo)
        if s["dur"] > 0 and "probes" in s["args"]:
            probes += s["args"]["probes"] * shared / s["dur"]
    return probes / rp.window_s
