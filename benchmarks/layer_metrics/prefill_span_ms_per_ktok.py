"""What a prompt costs the slots, chunks included: the summed duration of
the window's ``engine.prefill`` spans (the engine's lock held from the
prompt's first program to its last chunk's, every live slot stalled
meanwhile) and of the ``engine.first_token`` spans that follow them (the
programs are dispatched ahead of the device, and the host waits for the
prompt's last logits there), over the real prompt tokens they took
(``true_len``), per 1,000 tokens. ``prefill_device_ms_per_ktok`` reads the
log of the first program alone and would read low where prompts are
chunked. ``None`` where the ring holds no engine span or the spans carry
no ``true_len`` (the parent of the PR that added it)."""
from harness import engine_spans


def read(run):
    es = engine_spans.load(run)
    if es is None:
        return None
    spans = [
        s for s in es.named("engine.prefill") if s["args"].get("true_len")
    ]
    tokens = sum(s["args"]["true_len"] for s in spans)
    if not tokens:
        return None
    waits = es.named("engine.first_token")
    # durations are the ring's microseconds: us / token = ms / 1,000 tokens
    return sum(s["dur"] for s in (*spans, *waits)) / tokens
