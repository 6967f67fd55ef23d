"""Device time of the decode program over its runs, from the trace's
``XLA Modules`` line: what one token of every live stream costs on the
chip, host time between steps left out."""


def read(run):
    if run.trace is None:
        return None
    secs, runs = run.trace.program("decode_step")
    return secs / runs * 1e3 if runs else None
