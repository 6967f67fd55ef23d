"""Device time of the history-plus-suffix program over its runs, from the
trace's ``XLA Modules`` line: what one chunk of a long prompt (its
attention over the pages before it and the carry of the state by slot
among it) costs on the chip. ``None`` where no such program ran in the
traced stretch."""


def read(run):
    if run.trace is None:
        return None
    secs, runs = run.trace.program("prefill_suffix")
    return secs / runs * 1e3 if runs else None
