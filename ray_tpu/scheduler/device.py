"""Device-resident scheduler state: the live runtime's default placement path.

SURVEY §7.6: the head (and the single-process runtime) drain their
pending-lease queues through the shape-grouped waterfall kernel
(`hybrid_schedule_shapes`, scheduler/hybrid.py) with the cluster resource
arrays kept resident on the scheduler device. Per round the host ships only

  - dirty availability rows (delta sync, donated-buffer scatter), and
  - the batch's unique demand shapes + per-request shape ids,

and reads back one int32 node row per request. Full re-uploads happen only
on topology changes (node add/remove, array growth) tracked by
``ClusterView.topo_version``.

Pipelined rounds (ISSUE 6): ``schedule_async`` dispatches the kernel and
starts an async device→host copy of the placement rows, returning a
``PendingRound`` handle; the avail chain means round N+1 can be
dispatched immediately — its kernel consumes round N's ``avail_out``
device buffer without waiting for N's readback to materialize on the
host (the data dependency alone sequences the rounds on device). ``scheduler/pipeline.py`` drains the handles on a completion
thread, so the blocking readback disappears from the dispatch path
entirely. ``schedule()`` (dispatch + immediate ``result()``) remains the
synchronous fallback (``RAY_TPU_SCHED_PIPELINE=0``).

Beyond the lease round, the same resident arrays and dirty-row protocol
now feed the other two scheduling consumers: the PG bundle kernels read
``resident_arrays()`` (no per-PG re-upload of the cluster matrices), and
the unpark estimator's per-shape slot counts come from one batched
``shape_slots`` dispatch. Repeatedly-unplaceable demand parks in an
on-device ring (one resident row per resource shape) and retries via a
count-driven kernel (``ring_schedule``) whose readback is per-node
placement counts — no demand matrix is ever re-uploaded for parked work.

Platform choice: ``RAY_TPU_SCHED_PLATFORM`` names the XLA platform whose
first device backs the scheduler ("cpu" default, "tpu" for the attached
chip). A platform that is named and cannot be had is an error: the
scheduler never moves to another device, or to the host model, on its own.
The default stays "cpu": which platform wins at which round size has not
been measured (ROADMAP Speed 6), and on a one-chip host a head that takes
the chip leaves none for a TPU worker (README, "Who owns the chip"). The
code is the same on either platform.

All shapes are bucketed (requests, unique shapes → next power of two; node
rows, resource columns → the ClusterView capacity arrays, which already grow
by doubling) so steady-state rounds hit the jit cache. A persistent XLA
compilation cache makes the first round of a fresh process cheap too, and
``prewarm()`` background-compiles the bucket grid so first-touch rounds
after a topology change stop paying the compile spike inline.

Reference semantics anchor: cluster_lease_manager.cc:196 (shape-queue drain),
hybrid_scheduling_policy.cc:96-181 (scoring), batched per SURVEY §7.6. The
reference's "prefer local node" tie-break (hybrid_scheduling_policy.cc:96)
is deliberately disabled here: placement is computed centrally, where no
node is "local"; a fixed prefer row would funnel every sub-threshold request
onto one node.
"""
from __future__ import annotations

import atexit
import logging
import threading
import time
from typing import Dict, Optional

import numpy as np

from ray_tpu.util.compile_cache import configure_compile_cache
from ray_tpu.util.metrics import Histogram as _MetricHistogram

logger = logging.getLogger(__name__)

_BIG = 1e18  # padding demand: larger than any node total → never placed

# Round-latency decomposition (satellite: sched_round_ms alone hid where a
# slow round spent its time). upload = dirty-row/ring pushes + demand
# device_puts (host-blocking); kernel = dispatch → computation-done as
# observed at harvest (exact in synchronous mode and whenever the pipeline
# is the bottleneck; an idle pipeline harvesting late overstates it);
# readback = host materialization of the async device→host copy.
SCHED_UPLOAD_MS = _MetricHistogram(
    "sched_upload_ms",
    "Per-round host→device sync cost: dirty-row scatter pushes + demand "
    "shape/id uploads, in ms.",
    boundaries=(0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 25, 100, 500),
)
SCHED_KERNEL_MS = _MetricHistogram(
    "sched_kernel_ms",
    "Per-round kernel latency (dispatch to computation-ready) in ms.",
    boundaries=(0.1, 0.25, 0.5, 1, 2, 5, 10, 25, 50, 100, 500, 5000),
)
SCHED_READBACK_MS = _MetricHistogram(
    "sched_readback_ms",
    "Per-round placement readback materialization cost in ms.",
    boundaries=(0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 25, 100, 500),
)


def device_scheduler_default() -> bool:
    """Default ON: the XLA kernels ARE the product scheduler;
    RAY_TPU_DEVICE_SCHEDULER=0/false/no/off selects the NumPy golden model
    (kept for differential testing)."""
    from ray_tpu.config import cfg

    return cfg.device_scheduler


def _bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def pad_scatter(rows: np.ndarray, vals: np.ndarray):
    """Bucket-pad a scatter-set's (rows, vals) by repeating row 0 — a
    duplicate scatter-set of one row with identical values is
    deterministic, and padding keeps the jit cache keyed on bucket sizes
    only. The ONE encoding of that invariant, shared by the avail delta
    path, the ring flush, and the autoscaler's DeltaBinPacker."""
    pad = _bucket(rows.shape[0], 1) - rows.shape[0]
    if pad:
        rows = np.concatenate([rows, np.repeat(rows[:1], pad)])
        vals = np.concatenate([vals, np.repeat(vals[:1], pad, axis=0)])
    return rows, vals


def elastic_pack_solve(
    avail: np.ndarray,
    shapes: np.ndarray,
    counts: np.ndarray,
    *,
    iters: int = 24,
):
    """One batched ``solve_pack_counts`` for the unified elasticity plane,
    with both axes bucket-padded (node rows with zero capacity, shape rows
    with zero count) so the jit cache stays keyed on bucket sizes only —
    tick latency must not pay a re-trace every time demand churn changes
    U or the fleet changes N. Returns host-side
    ``(placed f32[U], per_node f32[U, N])`` trimmed back to true sizes."""
    n, r = int(avail.shape[0]), int(avail.shape[1])
    u = int(shapes.shape[0])
    if n == 0 or u == 0:
        return (
            np.zeros((u,), dtype=np.float32),
            np.zeros((u, n), dtype=np.float32),
        )
    np_pad = _bucket(n) - n
    up_pad = _bucket(u) - u
    if np_pad:
        avail = np.concatenate(
            [avail, np.zeros((np_pad, r), dtype=np.float32)]
        )
    if up_pad:
        shapes = np.concatenate(
            [shapes, np.zeros((up_pad, r), dtype=np.float32)]
        )
        counts = np.concatenate(
            [counts, np.zeros((up_pad,), dtype=np.float32)]
        )
    from .binpack import solve_pack_counts

    res = solve_pack_counts(
        np.asarray(avail, dtype=np.float32),
        np.asarray(shapes, dtype=np.float32),
        np.asarray(counts, dtype=np.float32),
        iters=int(iters),
    )
    placed = np.asarray(res.placed)[:u]
    per_node = np.asarray(res.per_node)[:u, :n]
    return placed.astype(np.float32), per_node.astype(np.float32)


_jitted = None
_jitted_lock = threading.Lock()

# Interpreter-exit guard for prewarm threads: a jit compile still running
# inside XLA's C++ thread pool while CPython tears down aborts the process
# with "terminate called without an active exception". The flag stops the
# warm loop between compiles; the join bounds how long exit waits for the
# one compile that may be mid-flight.
_shutting_down = False
_live_prewarms: list = []


def _drain_prewarms() -> None:
    global _shutting_down
    _shutting_down = True
    for t in list(_live_prewarms):
        t.join(timeout=30.0)


atexit.register(_drain_prewarms)


def _jitted_fns():
    """Process-wide jitted kernels: every DeviceSchedulerState (one per
    Runtime/HeadServer, and tests create many) must share one jit cache, or
    each instance re-traces and re-compiles identical programs."""
    global _jitted
    with _jitted_lock:
        if _jitted is None:
            import jax

            from .hybrid import (
                hybrid_schedule_shapes_multi_impl,
                ring_schedule_impl,
                shape_slots_impl,
            )

            # NO donation anywhere in the round chain: donating avail made
            # jax block each dispatch until the donated buffer's producer
            # (the previous round's kernel) finished — serializing dispatch
            # with execution and erasing the pipeline's overlap entirely.
            # Round ordering needs only the data dependency (round N+1's
            # avail input IS round N's avail_out); the cost of not reusing
            # the buffer in place is one f32[C,R] allocation per round
            # (~1 MB at 10k nodes) — noise next to the overlap it buys.
            kernel = jax.jit(
                hybrid_schedule_shapes_multi_impl,
                static_argnames=(
                    "spread_threshold", "weights", "preempt", "explain",
                ),
            )
            push = jax.jit(
                lambda avail, rows, vals: avail.at[rows].set(vals),
            )
            ring = jax.jit(
                ring_schedule_impl,
                static_argnames=("spread_threshold", "weights", "preempt"),
            )
            slots = jax.jit(shape_slots_impl)
            _jitted = (kernel, push, ring, slots)
        return _jitted


def score_weights_from_cfg():
    """The round kernels' multi-objective weights (hybrid.ScoreWeights)
    from config — static under jit, so a weight edit is a one-time
    recompile, not a per-round upload."""
    from ray_tpu.config import cfg

    from .hybrid import ScoreWeights

    return ScoreWeights(
        util=float(cfg.sched_w_util),
        het=float(cfg.sched_w_het),
        frag=float(cfg.sched_w_frag),
        starve=float(cfg.sched_w_starve),
        locality=float(cfg.sched_w_locality),
    )


class LazyDeviceState:
    """Deferred DeviceSchedulerState construction: JAX backend init happens
    on the first scheduling round, never in a HeadServer/Runtime
    constructor. ``get()`` returns the device state (None when the device
    scheduler is off) or raises what its construction raised — every later
    call raises the same error again; nothing is substituted for a device
    that could not be had."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._result: Optional[DeviceSchedulerState] = None
        self._error: Optional[BaseException] = None

    def get(self) -> Optional["DeviceSchedulerState"]:
        if not self.enabled:
            return None
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._result is None:
                try:
                    self._result = DeviceSchedulerState()
                except Exception as exc:
                    self._error = exc
                    raise
            return self._result


class PendingRound:
    """Handle to a dispatched scheduling round.

    The kernel is in flight (or done) on the device and an async
    device→host copy of the placement rows has been requested;
    ``result()`` blocks only on THIS round's completion — later rounds
    already dispatched keep executing behind it (avail chain).
    """

    __slots__ = (
        "_node", "_b", "_preempt", "_u", "_terms", "dispatched_at", "ctx",
    )

    def __init__(
        self, node, b: int, ctx=None, preempt=None, u: int = 0, terms=None
    ):
        self._node = node
        self._b = b
        self._preempt = preempt  # int32[U_pad] device, or None
        self._u = u              # real (unpadded) shape count
        self._terms = terms      # f32[B_pad, 5] device, or None
        self.dispatched_at = time.perf_counter()
        self.ctx = ctx  # opaque caller payload (e.g. the round's specs)

    def result(self) -> np.ndarray:
        """int32[B] node row per request (-1 = unplaceable now)."""
        node = self._node
        if node is None:
            raise RuntimeError("PendingRound.result() consumed twice")
        node.block_until_ready()
        SCHED_KERNEL_MS.observe(
            (time.perf_counter() - self.dispatched_at) * 1e3
        )
        t0 = time.perf_counter()
        rows = np.asarray(node)[: self._b]
        SCHED_READBACK_MS.observe((time.perf_counter() - t0) * 1e3)
        self._node = None  # drop the device buffer eagerly
        return rows

    def preempt_rows(self) -> Optional[np.ndarray]:
        """int32[U] per-shape nominated victim node (-1 = none), or None
        when the round dispatched without preemption. Call after
        ``result()`` — the kernel has finished, so this materializes
        without a wait (it rode the same async host copy)."""
        p = self._preempt
        if p is None:
            return None
        self._preempt = None
        return np.asarray(p)[: self._u]

    def terms_rows(self) -> Optional[np.ndarray]:
        """f32[B, 5] per-request cost attribution (hybrid.TERM_NAMES
        order; zero rows for unplaced requests), or None when the round
        dispatched without explain. Like ``preempt_rows``: call after
        ``result()`` — it rode the same async host copy."""
        t = self._terms
        if t is None:
            return None
        self._terms = None
        return np.asarray(t)[: self._b]


class DeviceSchedulerState:
    """Resident mirror of a ClusterView on one XLA device + the jitted
    scheduling round.

    Sync protocol (host view stays canonical, fed by agent reports):
      - every host mutation of an availability row marks it dirty;
      - ``sync(view)`` pushes dirty rows (or everything when topo_version
        moved) before a round;
      - the kernel's in-round deductions live in the round's avail_out
        buffer, which becomes the resident avail; the host applies the
        same deductions to its mirror (marking those rows dirty), so the
        next sync is an idempotent overwrite and the two copies cannot
        silently diverge FROM EACH OTHER: whatever the host mirror holds
        is what lands on device. The mirror itself can be transiently
        stale vs reality while rounds are in flight — an agent report
        (``update_available``) that predates an undelivered round's
        grants re-pushes the pre-grant value until that round's
        completion re-applies its deduction; pipelining widens this
        window from sub-round to ``depth`` rounds. That staleness is the
        documented trust model (resources.py): a resulting over-grant is
        caught by the agents' exact grant-or-reject and respilled, and
        the next authoritative report overwrites the row either way.

    Thread contract: ``sync`` under the caller's view lock; ``_lock``
    serializes device-buffer swaps (dirty push, round dispatch, ring
    round) and is held only across the dispatch + swap — never across a
    readback (the pre-pipeline code blocked every concurrent sync/push on
    the running round's host materialization).
    """

    def __init__(self, platform: Optional[str] = None):
        import jax

        configure_compile_cache()
        if platform is None:
            from ray_tpu.config import cfg

            platform = cfg.sched_platform
        # raises RuntimeError when the named platform has no backend here
        self.device = jax.devices(platform)[0]
        self._jax = jax
        self._totals = None  # f32[C,R] device
        self._avail = None   # f32[C,R] device, donated through every round
        self._alive = None   # bool[C] device
        self._ntypes = None  # int32[C] device node-type ids
        self._thr = None     # f32[T,R] device per-type throughput factors
        self._synced_topo = -1
        self._seed = 0
        self._lock = threading.Lock()
        self._kernel, self._push, self._ring_kernel, self._slots_kernel = (
            _jitted_fns()
        )
        # delta-sync / round accounting, surfaced via QueryState("sched")
        self.stats: Dict[str, int] = {
            "full_syncs": 0,
            "delta_pushes": 0,
            "delta_rows": 0,
            "delta_rows_hwm": 0,
            "rounds": 0,
            "ring_rounds": 0,
            "prewarmed": 0,
        }
        # --- parked-demand ring (device-resident shapes) ---
        from ray_tpu.config import cfg

        self.ring_slots = max(0, int(cfg.sched_ring_slots))
        self._ring_rows: Optional[np.ndarray] = None   # host mirror [S,R]
        self._ring_dev = None                          # f32[S,R] device
        self._ring_keys: Dict[object, int] = {}        # shape key -> slot
        self._ring_free: list = list(range(self.ring_slots))
        self._ring_dirty: set = set()
        self._prewarm_thread: Optional[threading.Thread] = None

    # -- sync ----------------------------------------------------------

    def sync(self, view) -> None:
        """Bring the device mirror up to date. Caller holds the view lock."""
        t0 = time.perf_counter()
        with self._lock:
            if view.topo_version != self._synced_topo:
                self._full_sync(view)
            elif view.dirty_rows:
                self._push_dirty(view)
            else:
                return
        SCHED_UPLOAD_MS.observe((time.perf_counter() - t0) * 1e3)

    def _full_sync(self, view) -> None:
        put = self._jax.device_put
        self._totals = put(np.ascontiguousarray(view.totals), self.device)
        self._avail = put(np.ascontiguousarray(view.avail), self.device)
        self._alive = put(np.ascontiguousarray(view.alive), self.device)
        # heterogeneity inputs ride the same full-sync (type registration
        # bumps topo_version): node-type ids at node capacity, throughput
        # factors bucket-padded on the type axis with all-ones rows (no
        # node references a pad type, and the pad keeps the jit cache
        # keyed on bucket sizes)
        ntypes = getattr(view, "node_types", None)
        if ntypes is None:
            self._ntypes = put(
                np.zeros(view.totals.shape[0], dtype=np.int32), self.device
            )
            self._thr = put(
                np.ones((1, view.totals.shape[1]), dtype=np.float32),
                self.device,
            )
        else:
            self._ntypes = put(np.ascontiguousarray(ntypes), self.device)
            t = len(view.type_names)
            t_pad = _bucket(t, 1)
            thr = np.ones(
                (t_pad, view.totals.shape[1]), dtype=np.float32
            )
            thr[:t] = view.type_throughput[:t, : view.totals.shape[1]]
            self._thr = put(thr, self.device)
        self._synced_topo = view.topo_version
        view.dirty_rows.clear()
        self.stats["full_syncs"] += 1
        # resource-axis growth invalidates the resident ring rows too
        if self._ring_rows is not None and (
            self._ring_rows.shape[1] != view.totals.shape[1]
        ):
            widened = np.zeros(
                (self.ring_slots, view.totals.shape[1]), dtype=np.float32
            )
            widened[:, : self._ring_rows.shape[1]] = self._ring_rows
            self._ring_rows = widened
            self._ring_dev = None  # re-upload lazily at next ring round
        self.prewarm(view.totals.shape[0], view.totals.shape[1])

    def _scatter_push(self, dev, rows: np.ndarray, vals: np.ndarray):
        """Bucket-padded scatter-set of ``rows``/``vals`` into ``dev``
        (``pad_scatter`` invariant)."""
        rows, vals = pad_scatter(rows, vals)
        put = self._jax.device_put
        return self._push(dev, put(rows, self.device), put(vals, self.device))

    def _push_dirty(self, view) -> None:
        rows = np.fromiter(view.dirty_rows, dtype=np.int32)
        view.dirty_rows.clear()
        vals = view.avail[rows].copy()
        self.stats["delta_pushes"] += 1
        self.stats["delta_rows"] += int(rows.shape[0])
        # high-water mark: the largest single delta push — a growing HWM
        # (→ node count) means the delta protocol has degraded to
        # full-matrix traffic and autoscaler/report churn needs a look
        # (surfaced via head QueryState("sched"))
        if int(rows.shape[0]) > self.stats["delta_rows_hwm"]:
            self.stats["delta_rows_hwm"] = int(rows.shape[0])
        self._avail = self._scatter_push(self._avail, rows, vals)

    def invalidate(self) -> None:
        """Force the next sync() to full-upload from the host mirror.

        Failure-path escape hatch: a dispatched round's deductions are
        already committed to the resident avail (``avail_out`` swap at
        dispatch), so a round that DIES before its readback leaves
        phantom deductions on device that the host mirror (canonical)
        never applied — and the dirty-row delta path would never
        overwrite rows no host mutation touches. One full re-upload
        restores device == host; later in-flight rounds re-apply their
        own deductions through their completions as usual."""
        with self._lock:
            self._synced_topo = -1

    def resident_arrays(self):
        """(totals, avail, alive) device refs for read-only kernel
        consumers (PG bundle packing, autoscaler residual packing, slot
        estimation). Caller must have sync()ed under its view lock;
        deductions flow back through the host mirror's dirty rows,
        exactly like lease-round grants."""
        return self._totals, self._avail, self._alive

    # -- the scheduling round ------------------------------------------

    def schedule_async(
        self,
        demands: Optional[np.ndarray] = None,
        spread_threshold: float = 0.5,
        ctx=None,
        shapes=None,
        ages: Optional[np.ndarray] = None,
        weights=None,
        locality: Optional[np.ndarray] = None,
    ) -> PendingRound:
        """Dispatch a placement round without blocking on its readback.

        f32[B,R] demands → PendingRound whose ``result()`` yields int32[B]
        node rows (-1 = unplaceable now). The caller must have called
        sync() under its view lock; R must match the synced arrays'
        resource axis. The avail chain makes round ordering the dispatch
        order: a later round's kernel consumes this round's deducted
        availability even before anything is read back.

        ``shapes``: optional precomputed ``(shape_rows f32[U,R],
        shape_ids int32[B])`` dedupe (hardest-first order) — the head
        caches dense rows per resource shape, so steady rounds skip the
        O(B·R) ``np.unique`` pass here entirely. ``demands`` may then be
        None.

        ``ages``: optional f32[U] normalized wait-age per shape (rounds
        parked / sched_starve_rounds). Uploading ages arms preemption
        nomination (cfg.sched_preempt): ``PendingRound.preempt_rows()``
        then yields the per-shape victim-node nominations. ``weights``:
        hybrid.ScoreWeights override (default: the cfg knobs).

        ``locality``: optional f32[U, N'] per-shape per-node locality
        fraction (head._round_shapes: input bytes resident per node,
        row-normalized). Uploaded — and traced into the kernel — only
        when the resolved weights carry locality > 0, so the default
        config never pays the extra upload and keeps the pre-locality
        program byte-for-byte.
        """
        from ray_tpu.config import cfg

        r = self._totals.shape[1]
        if shapes is not None:
            shape_demands, shape_ids = shapes
        else:
            from .hybrid import dedupe_shapes

            assert demands.shape[1] == r, (demands.shape, r)
            shape_demands, shape_ids = dedupe_shapes(demands)
        b = shape_ids.shape[0]
        u = shape_demands.shape[0]
        assert shape_demands.shape[1] == r, (shape_demands.shape, r)
        if weights is None:
            weights = score_weights_from_cfg()
        preempt = bool(cfg.sched_preempt) and ages is not None
        explain = bool(cfg.sched_explain)

        u_pad = _bucket(u + 1, 2)
        b_pad = _bucket(b)
        sd = np.full((u_pad, r), _BIG, dtype=np.float32)
        sd[:u] = shape_demands
        sids = np.full(b_pad, u_pad - 1, dtype=np.int32)  # padding → BIG shape
        sids[:b] = shape_ids
        age_vec = np.zeros(u_pad, dtype=np.float32)
        if ages is not None:
            age_vec[:u] = ages

        put = self._jax.device_put
        t_up = time.perf_counter()
        sd_dev = put(sd, self.device)
        sids_dev = put(sids, self.device)
        ages_dev = put(age_vec, self.device)
        loc_dev = None
        if locality is not None and getattr(weights, "locality", 0.0):
            # pad shapes with zero rows (no locality data → neutral);
            # clip/zero-pad the node axis to the resident capacity so a
            # view growth between round prep and dispatch cannot feed
            # the kernel a mis-shaped matrix
            c = int(self._totals.shape[0])
            loc = np.zeros((u_pad, c), dtype=np.float32)
            nn = min(int(locality.shape[1]), c)
            loc[:u, :nn] = locality[:u, :nn]
            loc_dev = put(loc, self.device)
        SCHED_UPLOAD_MS.observe((time.perf_counter() - t_up) * 1e3)
        with self._lock:
            self._seed += 1
            self.stats["rounds"] += 1
            res = self._kernel(
                self._totals,
                self._avail,
                self._alive,
                self._ntypes,
                self._thr,
                sd_dev,
                sids_dev,
                ages_dev,
                np.uint32(self._seed & 0xFFFFFFFF),
                spread_threshold=spread_threshold,
                weights=weights,
                preempt=preempt,
                locality=loc_dev,
                explain=explain,
            )
            self._avail = res.avail_out
        node = res.node
        node.copy_to_host_async()
        if preempt:
            res.preempt_node.copy_to_host_async()
        if explain:
            res.terms.copy_to_host_async()
        return PendingRound(
            node,
            b,
            ctx=ctx,
            preempt=res.preempt_node if preempt else None,
            u=u,
            terms=res.terms if explain else None,
        )

    def schedule(self, demands: np.ndarray, spread_threshold: float = 0.5):
        """Synchronous round: dispatch + immediate readback (the
        RAY_TPU_SCHED_PIPELINE=0 path, and the single-process runtime)."""
        return self.schedule_async(demands, spread_threshold).result()

    # -- parked-demand ring --------------------------------------------

    def ring_park(self, key, dense_row: np.ndarray) -> bool:
        """Pin a resource shape in the on-device ring. Idempotent per key;
        returns False when the ring is full (caller falls back to the
        re-upload path for that shape)."""
        if self.ring_slots <= 0:
            return False
        with self._lock:
            if key in self._ring_keys:
                return True
            if not self._ring_free:
                return False
            r = self._totals.shape[1] if self._totals is not None else None
            if r is None or dense_row.shape[0] != r:
                return False
            if self._ring_rows is None or self._ring_rows.shape[1] != r:
                self._ring_rows = np.zeros(
                    (self.ring_slots, r), dtype=np.float32
                )
                self._ring_dev = None
            slot = self._ring_free.pop()
            self._ring_keys[key] = slot
            self._ring_rows[slot] = dense_row
            self._ring_dirty.add(slot)
            return True

    def ring_drop(self, key) -> None:
        """Release a shape's ring slot (its parked queue drained)."""
        with self._lock:
            slot = self._ring_keys.pop(key, None)
            if slot is not None:
                self._ring_rows[slot] = 0.0
                self._ring_dirty.add(slot)
                self._ring_free.append(slot)

    def ring_occupancy(self) -> int:
        return len(self._ring_keys)

    def ring_keys(self) -> list:
        """Snapshot of the currently-pinned shape keys (for the head's
        parked-set reconciliation sweep)."""
        with self._lock:
            return list(self._ring_keys)

    def ring_slot_of(self, key) -> Optional[int]:
        return self._ring_keys.get(key)

    def _ring_flush_locked(self) -> None:
        """Upload dirty ring rows (scatter, bucketed like avail pushes).
        Caller holds self._lock."""
        put = self._jax.device_put
        if self._ring_dev is None:
            if self._ring_rows is None:
                self._ring_rows = np.zeros(
                    (self.ring_slots, self._totals.shape[1]), dtype=np.float32
                )
            self._ring_dev = put(self._ring_rows, self.device)
            self._ring_dirty.clear()
            return
        if not self._ring_dirty:
            return
        rows = np.fromiter(self._ring_dirty, dtype=np.int32)
        self._ring_dirty.clear()
        vals = self._ring_rows[rows].copy()
        self._ring_dev = self._scatter_push(self._ring_dev, rows, vals)

    def ring_schedule(
        self,
        counts_by_slot: Dict[int, int],
        spread_threshold: float = 0.5,
        ages_by_slot: Optional[Dict[int, float]] = None,
        weights=None,
    ):
        """Place parked demand straight from the resident ring.

        ``counts_by_slot``: pending request count per ring slot. Returns
        (placed int64[S], per_node int32[S,N], preempt int32[S]) — the
        caller assigns its FIFO-parked specs rank-by-rank across
        ``per_node`` and leaves the remainder parked; ``preempt`` carries
        per-slot victim-node nominations (-1 = none) when
        ``ages_by_slot`` was supplied and preemption is on. Only the
        count (and age) vectors (S values) cross the host→device
        boundary; the shapes are already resident.
        """
        from ray_tpu.config import cfg

        t_up = time.perf_counter()
        counts = np.zeros(self.ring_slots, dtype=np.int32)
        for slot, c in counts_by_slot.items():
            counts[slot] = min(int(c), np.iinfo(np.int32).max)
        ages = np.zeros(self.ring_slots, dtype=np.float32)
        if ages_by_slot:
            for slot, a in ages_by_slot.items():
                ages[slot] = float(a)
        if weights is None:
            weights = score_weights_from_cfg()
        preempt = bool(cfg.sched_preempt) and ages_by_slot is not None
        put = self._jax.device_put
        with self._lock:
            self._ring_flush_locked()
            counts_dev = put(counts, self.device)
            ages_dev = put(ages, self.device)
            SCHED_UPLOAD_MS.observe((time.perf_counter() - t_up) * 1e3)
            self._seed += 1
            self.stats["ring_rounds"] += 1
            t_k = time.perf_counter()
            res = self._ring_kernel(
                self._totals,
                self._avail,
                self._alive,
                self._ntypes,
                self._thr,
                self._ring_dev,
                counts_dev,
                ages_dev,
                np.uint32(self._seed & 0xFFFFFFFF),
                spread_threshold=spread_threshold,
                weights=weights,
                preempt=preempt,
            )
            self._avail = res.avail_out
        placed = np.asarray(res.placed)
        per_node = np.asarray(res.per_node)
        preempt_rows = np.asarray(res.preempt_node)
        SCHED_KERNEL_MS.observe((time.perf_counter() - t_k) * 1e3)
        return placed, per_node, preempt_rows

    # -- unpark slot estimation ----------------------------------------

    def shape_slots(self, shapes: np.ndarray) -> np.ndarray:
        """int64[S] grantable-slot estimate per demand shape, computed on
        the resident arrays (one dispatch replaces S host NumPy scans).
        Shapes are bucket-padded with _BIG rows (0 slots) for jit reuse."""
        s = shapes.shape[0]
        r = self._totals.shape[1]
        s_pad = _bucket(s, 1)
        mat = np.full((s_pad, r), _BIG, dtype=np.float32)
        mat[:s] = shapes
        with self._lock:
            res = self._slots_kernel(
                self._totals,
                self._avail,
                self._alive,
                self._jax.device_put(mat, self.device),
            )
        return np.asarray(res)[:s].astype(np.int64)

    # -- jit prewarm ----------------------------------------------------

    def prewarm(self, n_cap: int, r: int, spread_threshold: float = 0.5):
        """Background-compile the round kernel across the bucketed
        (batch, unique-shape) grid for the CURRENT array geometry, so the
        first real round at each size hits the jit (or persistent) cache
        instead of paying a multi-second trace+compile inside the
        scheduler loop. Idempotent per geometry; re-armed by _full_sync
        when the node-capacity axis grows. No-op while a warm thread for
        any geometry is still running (the persistent cache makes
        stragglers cheap)."""
        from ray_tpu.config import cfg

        if not cfg.sched_prewarm:
            return
        if self._prewarm_thread is not None and self._prewarm_thread.is_alive():
            return
        key = (n_cap, r)
        if getattr(self, "_prewarmed_geometry", None) == key:
            return
        self._prewarmed_geometry = key

        def _warm():
            try:
                max_b = _bucket(int(cfg.sched_max_batch))
                b_sizes, b = [], 8
                while b <= max_b:
                    b_sizes.append(b)
                    b *= 4  # every other bucket: 8,32,128,512,2048(,8192)
                if b_sizes[-1] != max_b:
                    b_sizes.append(max_b)
                totals = np.ones((n_cap, r), dtype=np.float32)
                avail = np.ones((n_cap, r), dtype=np.float32)
                alive = np.ones(n_cap, dtype=bool)
                put = self._jax.device_put
                dev_t = put(totals, self.device)
                dev_al = put(alive, self.device)
                # nothing donates the avail buffer anymore: one upload
                # serves the whole grid (was ~2.5 MB re-put per cell,
                # contending with real rounds' uploads after every
                # topology change)
                dev_av = put(avail, self.device)
                # warm the exact variant real rounds dispatch: current
                # weights, preemption armed iff the head will arm it,
                # type axis at the CURRENT resident bucket (weights and
                # preempt are static — another variant would compile a
                # program no round ever runs)
                weights = score_weights_from_cfg()
                preempt_flag = bool(cfg.sched_preempt)
                explain_flag = bool(cfg.sched_explain)
                t_pad = (
                    self._thr.shape[0] if self._thr is not None else 1
                )
                dev_nt = put(np.zeros(n_cap, dtype=np.int32), self.device)
                dev_thr = put(
                    np.ones((t_pad, r), dtype=np.float32), self.device
                )
                for u_pad in (2, 4, 8, 16):
                    sd = np.full((u_pad, r), _BIG, dtype=np.float32)
                    sd[0, 0] = 1.0
                    sd_dev = put(sd, self.device)
                    ages_dev = put(
                        np.zeros(u_pad, dtype=np.float32), self.device
                    )
                    for b_pad in b_sizes:
                        if _shutting_down:
                            return
                        sids = np.zeros(b_pad, dtype=np.int32)
                        res = self._kernel(
                            dev_t,
                            dev_av,
                            dev_al,
                            dev_nt,
                            dev_thr,
                            sd_dev,
                            put(sids, self.device),
                            ages_dev,
                            np.uint32(1),
                            spread_threshold=spread_threshold,
                            weights=weights,
                            preempt=preempt_flag,
                            explain=explain_flag,
                        )
                        res.node.block_until_ready()
                        self.stats["prewarmed"] += 1
            except Exception:  # noqa: BLE001 - warm-up is best-effort
                logger.debug("scheduler jit prewarm failed", exc_info=True)
            finally:
                try:
                    _live_prewarms.remove(threading.current_thread())
                except ValueError:  # pragma: no cover
                    pass

        self._prewarm_thread = threading.Thread(
            target=_warm, name="sched-prewarm", daemon=True
        )
        _live_prewarms.append(self._prewarm_thread)
        self._prewarm_thread.start()
