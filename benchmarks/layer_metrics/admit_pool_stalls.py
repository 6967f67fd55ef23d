"""Admissions the pool stalled inside the window: ``engine.admit`` spans
with ``pool_stall`` (a free slot and a queued request, and the pool had
not the pages). ``None`` only where the ring holds no engine span."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    return float(sum(
        1 for s in es.named("engine.admit") if s["args"]["pool_stall"]
    ))
