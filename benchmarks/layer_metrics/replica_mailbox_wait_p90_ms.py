"""How long a dispatched request lies in the replica actor's mailbox before
one of its ``max_concurrency`` threads takes it up: the start of its first
``replica.stream`` less the end of the router's dispatch (``serve.stream``'s
start + ``dispatch_ms``), 90th percentile over the requests dispatched
inside the window that a thread did take up. A thread can begin the call
while the dispatch still does its books: such a wait is 0. Above the knee
this is where the requests in flight wait, not in the engine's queue."""
from harness import request_path, stats


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    return stats.percentile(
        [
            max(0.0, r.taken_up - r.dispatched) * 1e-3 for r in rp.requests
            if rp.inside(r.dispatched) and r.taken_up is not None
        ],
        90.0,
    )
