"""How long a token that is on the host waits for its consumer: sum of
``pickup_lag_ms`` over sum of ``new_tokens`` of the ``engine.request`` spans
that share the window. ``stream_rid`` adds, for each token it yields, the
moment of the hand-over less the end of the readback of the step that made
it (the first token: less the end of its prefill). With one request thread a
slot this is the turn at the lock seen from the token's side; it stays a
measure when stream threads take no lock."""
from harness import request_path


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    reqs = [
        s["args"] for s in rp.es.named("engine.request", overlap=True)
        if "pickup_lag_ms" in s["args"]
    ]
    tokens = sum(a.get("new_tokens", 0) for a in reqs)
    if not tokens:
        return None
    return sum(a["pickup_lag_ms"] for a in reqs) / tokens
