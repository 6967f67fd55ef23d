"""Share of the live KV bytes that lie in window-class pages, mean over
the window's ``engine.decode`` spans: ``window_pages`` x the bytes a token
holds in windowed layers over that plus ``full_pages`` x the bytes it
holds in full layers (the family's ``kv_bytes_per_token``). A windowed
layer's ring stops growing at the window, so the share falls as contexts
grow; ``None`` where the spans carry no pages by class."""
from harness import engine_spans, spec


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    steps = [
        s["args"] for s in es.named("engine.decode")
        if "window_pages" in s["args"]
    ]
    if not steps:
        return None
    per_token = spec.load_family(run.cfg, run.base).kv_bytes_per_token(run.cfg)
    shares = []
    for a in steps:
        window = a["window_pages"] * per_token["window"]
        both = window + a["full_pages"] * per_token["full"]
        if both:
            shares.append(100.0 * window / both)
    return engine_spans.mean(shares)
