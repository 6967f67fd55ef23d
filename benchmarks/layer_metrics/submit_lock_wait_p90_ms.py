"""How long ``submit()`` waits for the engine's lock before a request is
registered (``submit_lock_wait_ms`` of its ``engine.request`` span: two
clock reads around the acquisition), 90th percentile over the requests
submitted inside the window. Part of a slow first token, and one leg of a
freed slot's hand-over."""
from harness import request_path, stats


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    return stats.percentile(
        [
            s["args"]["submit_lock_wait_ms"]
            for s in rp.es.named("engine.request")
            if "submit_lock_wait_ms" in s["args"]
        ],
        90.0,
    )
