"""Time to first token, first stamp - due, 90th percentile over the
requests due inside the window. A request still unanswered at the close
counts with the wait it had had by then. Not read where the mix offers
more than the engine completes: there it is the queue's length."""
from harness import stats


def read(run):
    if not run.mix.get("ttft_reported", True):
        return None
    waits = []
    for c in run.clients:
        if not run.t_open <= c.due_abs < run.t_close or c.error:
            continue
        first = c.stamps[0] if c.stamps else run.t_close
        waits.append((first - c.due_abs) * 1e3)
    return stats.percentile(waits, 90.0)
