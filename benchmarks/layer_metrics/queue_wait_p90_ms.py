"""Time from ``submit()`` to the start of a request's admission
(``queue_wait_ms`` of its ``engine.request`` span), 90th percentile over
the requests submitted inside the window."""
from harness import engine_spans, stats


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    return stats.percentile(
        [s["args"]["queue_wait_ms"] for s in es.named("engine.request")], 90.0
    )
