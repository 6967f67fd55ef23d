"""Device time of the prefill programs over the prompt tokens they took
(padded to pages, as the engine runs them), per 1,000 tokens, inside the
traced stretch."""


def read(run):
    if run.trace is None or run.capture is None:
        return None
    secs, runs = run.trace.program("prefill")
    t0, t1 = run.capture
    tokens = [n for t, n in run.prefill_log if t0 <= t < t1]
    if not runs or not tokens:
        return None
    # the log and the trace may differ by a run at either edge
    per_run = sum(tokens) / len(tokens)
    return secs / (runs * per_run) * 1e6
