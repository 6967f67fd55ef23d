"""Second leg of a freed slot's hand-over: on one replica thread, from the
end of a ``replica.stream`` inside the window to the start of the next, over
the gaps at whose start that next request had been dispatched (it lay in
the actor's mailbox: the thread was waited for). 0 where no call waited for
a thread, as below a cell's knee."""
from harness import engine_spans, request_path


def read(run):
    rp = request_path.load(run)
    if rp is None:
        return None
    return engine_spans.mean(rp.next_call_ms()) or 0.0
