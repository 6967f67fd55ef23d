"""How long a request's stream thread waits for the engine lock, mean over
acquisitions: sum of ``lock_wait_ms`` over sum of ``lock_acquires`` of the
``engine.request`` spans that share any part of the window (an answer cut
at the close ends after it)."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    reqs = [s["args"] for s in es.named("engine.request", overlap=True)]
    acquires = sum(a["lock_acquires"] for a in reqs)
    if not acquires:
        return None
    return sum(a["lock_wait_ms"] for a in reqs) / acquires
