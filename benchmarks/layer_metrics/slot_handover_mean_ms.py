"""How long a freed slot stands empty while a request waits for it: slot by
slot, from the end of an ``engine.request`` to the admission
(``ts`` + ``queue_wait_ms``) of the next one that names the same ``slot``,
over the gaps at whose start the requests dispatched and not yet admitted
were at least the empty slots (a request waited that no other empty slot
could take). The tenants are the whole ring's, the ramp-in's too; a gap that
an edge of the window cuts, or that no tenant ended before the close,
counts for its part inside the window, so where slots stand empty for whole
windows (the engine fills the lowest free one) this reads under
``slots_idle_with_backlog_pct`` x slots / ``slot_turnover_per_s``, the same
wait by Little's law, by the share of its gaps that the close cuts. 0 where
no slot fell free beside a request that waited for it, as below a cell's
knee. Its legs are metrics of their own (``handover_last_token_mean_ms``,
``handover_next_call_mean_ms``, ``submit_lock_wait_p90_ms``,
``queue_wait_p90_ms``); ``harness/request_path.py`` logs their means beside
this value and the Little's-law one (``"phase": "handover_legs"``)."""
from harness import engine_spans, request_path


def read(run):
    rp = request_path.load(run)
    gaps = None if rp is None else rp.handover_ms()
    if gaps is None:
        return None
    return engine_spans.mean(gaps) or 0.0
