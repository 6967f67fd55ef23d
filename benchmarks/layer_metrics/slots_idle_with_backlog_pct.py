"""The share of the engine's slots that stand empty while requests the
router has dispatched wait for one: the integral over the window of
min(empty slots, backlog) over slots times the window. A slot is taken from
its request's admission (``engine.request``'s start + ``queue_wait_ms``) to
that span's end; a request is backlog from the end of its dispatch to its
admission (``harness/request_path.py``); ``slots`` is what ``engine.decode``
states. What keeps a slot empty beside a waiting request is the hand-over
(``slot_handover_mean_ms`` and its legs) or, where the pool's pages and not
the slots are the capacity, the pool: in ``olmo-hybrid-7b-l16.longdoc`` 7 of
16 slots are empty with requests in the engine's own queue, so read this
beside ``admit_pool_stalls.delta`` there."""
from harness import request_path


def read(run):
    rp = request_path.load(run)
    idle = None if rp is None else rp.idle_with_backlog()
    if idle is None:
        return None
    return 100.0 * idle / (rp.slots * (rp.hi - rp.lo))
