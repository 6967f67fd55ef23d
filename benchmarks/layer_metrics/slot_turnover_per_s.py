"""Slots that changed hands a second: requests the engine admitted inside
the window (sum of ``admitted`` over the window's ``engine.admit`` spans)
over the window's length. Each is a prefill under the engine's lock, a
slot's table and, of a model with state by slot, a slot's state written
anew; ``None`` where the ring holds no engine span."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    admitted = sum(
        s["args"].get("admitted", 0) for s in es.named("engine.admit")
    )
    return admitted / es.window_s
