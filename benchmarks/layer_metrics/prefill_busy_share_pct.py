"""Share of the device's busy time in the traced stretch that the two
prefill programs took (the first program of a prompt and the
history-plus-suffix program of its chunks), from the trace's ``XLA
Modules`` line: how much of the chip's work is reading prompts and not
advancing answers."""


def read(run):
    tr = run.trace
    if tr is None or not tr.busy_s:
        return None
    secs, runs = tr.program("prefill")
    return 100.0 * secs / tr.busy_s if runs else None
