"""Live slots in a decode step, mean over the window's steps: ``live`` of
the program's ``engine.decode`` spans. The same number as
``decode_batch_mean``, which counts it in the benchmark's wrapper."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    return engine_spans.mean(
        [float(s["args"]["live"]) for s in es.named("engine.decode")]
    )
