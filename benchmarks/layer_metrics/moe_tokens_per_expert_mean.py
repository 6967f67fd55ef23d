"""Tokens a held expert sees in a decode step, mean over the window's
``engine.decode`` spans: ``moe_pairs_held`` (token-expert pairs whose
expert is held here, summed over the expert layers) over held experts x
expert layers. How near the cell's batch comes to what an expert sees in
the stated deployment; ``None`` where the spans carry no such count."""
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
    held = run.cfg["n_routed_experts"] * family.expert_layers(run.cfg)
    return sum(pairs) / (held * len(pairs))
