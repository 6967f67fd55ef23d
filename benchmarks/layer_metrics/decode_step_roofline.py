"""Share of the roofline the decode program reaches: the least time the
chip could take for the step's ALGORITHMIC work (every weight read once,
each live context's K and V read once, the step's FLOPs; the larger of
bytes over peak bandwidth and FLOPs over peak rate) over the program's
measured device time. The count comes from ``harness/counts.py`` and the
live context lengths; it does not look at how the step is implemented."""
from harness import counts


def read(run):
    if run.trace is None or run.capture is None:
        return None
    secs, runs = run.trace.program("decode_step")
    t0, t1 = run.capture
    steps = [(b, ctx) for t, b, ctx in run.decode_log if t0 <= t < t1 and b]
    if not runs or not steps:
        return None
    least = sum(
        counts.least_seconds(
            *counts.decode_step_work(run.cfg, b, ctx), run.peaks
        )
        for b, ctx in steps
    ) / len(steps)
    return 100.0 * least / (secs / runs)
