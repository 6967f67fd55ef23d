"""Share of a decode step's attention layers of the ``full`` class of KV
page that ran in the Pallas paged-attention kernel, over the window's
``engine.decode`` spans: sum of ``attn_kernel_layers`` over sum of
``attn_full_layers``, both summed on the device by ``decode_step``. 100
where every such layer read live pages where they lie; anything less means
a step fell back to gathering whole tables; ``None`` where the spans carry
no such count (a program from before the kernel)."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    steps = [
        s["args"] for s in es.named("engine.decode")
        if s["args"].get("attn_full_layers")
    ]
    if not steps:
        return None
    return (
        100.0 * sum(a["attn_kernel_layers"] for a in steps)
        / sum(a["attn_full_layers"] for a in steps)
    )
