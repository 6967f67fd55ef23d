"""How much of the schedule the engine had not reached by the close: of
the requests sent before the window closed, the share that had no first
token by then. In a cell judged on ``tokens_per_s`` above its knee the
schedule has to outlast the engine; where this reads 0 every request
offered had been taken up, the engine ran out of work, and the cell's
``tokens_per_s`` is the schedule's number, not the engine's."""


def read(run):
    sent = [c for c in run.clients
            if c.sent is not None and c.sent < run.t_close]
    if not sent:
        return None
    waiting = sum(
        1 for c in sent if not c.stamps or c.stamps[0] >= run.t_close
    )
    return 100.0 * waiting / len(sent)
