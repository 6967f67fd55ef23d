"""Share of the traced stretch in which no operation ran on the device:
1 - union of the operations' intervals over the traced window."""


def read(run):
    tr = run.trace
    if tr is None or not tr.window_s or not tr.busy_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
