"""Host time of an engine step that ran a decode: the ``engine.step``
span's duration less the two waits for the device inside it
(``engine.readback``, the step's tokens, and ``engine.first_token``, a
prefill's logits), mean over the window's steps. An upper bound on what
the device idles for a step: dispatches overlap the device's work."""
from harness import engine_spans

WAITS = ("engine.readback", "engine.first_token")


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    host = []
    for step in es.named("engine.step"):
        if not es.under(step, ("engine.decode",)):
            continue
        waits = sum(s["dur"] for s in es.under(step, WAITS))
        host.append((step["dur"] - waits) * 1e-3)
    return engine_spans.mean(host)
