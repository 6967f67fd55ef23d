"""How late the open-loop generator ran: sent - due, 95th percentile over
the requests due inside the window. A starved generator must not be read
as a fast server."""
from harness import stats


def read(run):
    late = [
        (c.sent - c.due_abs) * 1e3 for c in run.clients
        if c.sent is not None and run.t_open <= c.due_abs < run.t_close
    ]
    return stats.percentile(late, 95.0)
