"""Share of the per-sequence bytes a decode step touches that are state by
slot and not paged K and V, mean over the window's ``engine.decode`` spans:
each live slot's state read once and written once (``state_slots_written``
counts a layer a live slot; the family's ``state_bytes_per_slot`` is over
all the layers that keep state) against that plus the live pages' K and V
(``full_pages`` x the page's tokens x the family's ``kv_bytes_per_token``).
State does not grow with the context, so the share falls as contexts grow;
``None`` where the spans carry no state counts (a model without any, the
parent's spans)."""
from harness import engine_spans, spec


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    steps = [
        s["args"] for s in es.named("engine.decode")
        if "state_slots_written" in s["args"] and s["args"].get("state_layers")
    ]
    if not steps:
        return None
    family = spec.load_family(run.cfg, run.base)
    a_slot = family.state_bytes_per_slot(run.cfg)
    a_page = (
        run.cfg["deployment"]["page_size"]
        * family.kv_bytes_per_token(run.cfg)["full"]
    )
    shares = []
    for a in steps:
        state = 2 * a_slot * a["state_slots_written"] / a["state_layers"]
        both = state + a["full_pages"] * a_page
        if both:
            shares.append(100.0 * state / both)
    return engine_spans.mean(shares)
