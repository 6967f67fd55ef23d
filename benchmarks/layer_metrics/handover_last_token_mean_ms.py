"""First leg of a freed slot's hand-over: from the end of a finished
``engine.request`` (the slot is free) to the end of the ``replica.stream``
above it: the turn its thread waits at the engine's lock to see the last
token, the write of it and the channel's close. Mean over the requests
that finished inside the window."""
from harness import engine_spans, request_path


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    return engine_spans.mean(rp.last_token_ms())
