"""Share of the window in which live slots waited for an admission: the
summed duration of the ``engine.admit`` spans that admitted a request
(``admitted`` >= 1) while slots were decoding (``live`` >= 1), over the
window. A prefill runs inside ``_admit`` and no slot decodes meanwhile."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    stalled = sum(
        s["dur"] for s in es.named("engine.admit")
        if s["args"]["admitted"] >= 1 and s["args"]["live"] >= 1
    )
    return 100.0 * stalled * 1e-6 / es.window_s
