"""Share of the pages reserved for live slots that hold a token: mean over
the window's ``engine.decode`` spans of ``pages_written`` over
``pages_reserved``. Admission reserves prompt + ``max_new`` pages at once;
what is reserved and unwritten holds other requests out of the pool."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    return engine_spans.mean([
        100.0 * s["args"]["pages_written"] / s["args"]["pages_reserved"]
        for s in es.named("engine.decode") if s["args"]["pages_reserved"]
    ])
