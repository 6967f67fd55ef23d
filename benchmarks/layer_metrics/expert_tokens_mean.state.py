"""Tokens a held expert sees in a decode step, mean over the window's
``engine.decode`` spans: ``moe_pairs_held`` over held experts x expert
layers, the held experts asked of the configuration's family
(``experts_held``). With every expert held it is batch x experts a token
over the router's width; ``None`` where the spans carry no such count."""
from harness import engine_spans, spec


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    pairs = [
        s["args"]["moe_pairs_held"] for s in es.named("engine.decode")
        if "moe_pairs_held" in s["args"]
    ]
    if not pairs:
        return None
    family = spec.load_family(run.cfg, run.base)
    held = family.experts_held(run.cfg) * family.expert_layers(run.cfg)
    return sum(pairs) / (held * len(pairs))
