"""Share of the held experts that a decode step's live tokens chose, over
the window's ``engine.decode`` spans: sum of ``moe_experts_hit`` over held
experts x expert layers x steps, the held experts asked of the
configuration's family (``experts_held``; ``moe_experts_hit_pct`` reads
another family's key). What a step has to read of the expert weights;
``None`` where the spans carry no such count."""
from harness import engine_spans, spec


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    hits = [
        s["args"]["moe_experts_hit"] for s in es.named("engine.decode")
        if "moe_experts_hit" in s["args"]
    ]
    if not hits:
        return None
    family = spec.load_family(run.cfg, run.base)
    held = family.experts_held(run.cfg) * family.expert_layers(run.cfg)
    return 100.0 * sum(hits) / (held * len(hits))
