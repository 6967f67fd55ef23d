"""Share of the roofline the decode program reaches: the least time the
chip could take for the step's ALGORITHMIC work (every weight read once,
each live context's K and V read once, the step's FLOPs; the larger of
bytes over peak bandwidth and FLOPs over peak rate) over the program's
measured device time. The count is the configuration's family's
(``families/<family>.py`` ``decode_step_work``) over the step's live
context lengths; it does not look at how the step is implemented."""
from harness import peaks, spec


def read(run):
    if run.trace is None or run.capture is None:
        return None
    secs, runs = run.trace.program("decode_step")
    t0, t1 = run.capture
    steps = [ctxs for t, ctxs in run.decode_log if t0 <= t < t1 and ctxs]
    if not runs or not steps:
        return None
    family = spec.load_family(run.cfg, run.base)
    least = sum(
        peaks.least_seconds(
            *family.decode_step_work(run.cfg, ctxs), run.peaks
        )
        for ctxs in steps
    ) / len(steps)
    return 100.0 * least / (secs / runs)
