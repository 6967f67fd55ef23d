"""Share of a decode step's bytes that is a parallel layer's Mamba-2 state
by slot: over the window's ``engine.decode`` spans, the sum of
``ssm_state_bytes`` (each live slot's state read once and written once,
from the state's shapes) over the sum of the configuration's family's
``decode_step_work`` bytes at each step's live contexts (every weight once,
each live context's K and V, the state). The family's count is linear in
the contexts, so a step's ``ctx`` (the sum of its live contexts) spread
over its ``live`` slots gives the bytes the contexts one by one give.
State does not grow with the context, so the share falls as contexts grow.
``None``, never 0, where the spans carry no such count (a model without a
parallel layer, the spans of a program that has none)."""
from harness import engine_spans, spec


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    steps = [
        s["args"] for s in es.named("engine.decode")
        if "ssm_state_bytes" in s["args"] and s["args"].get("live")
    ]
    if not steps:
        return None
    family = spec.load_family(run.cfg, run.base)
    state = total = 0
    for a in steps:
        live, ctx = a["live"], a["ctx"]
        contexts = [ctx // live + (i < ctx % live) for i in range(live)]
        state += a["ssm_state_bytes"]
        total += family.decode_step_work(run.cfg, contexts)[1]
    return 100.0 * state / total if total else None
