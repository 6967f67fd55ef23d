"""Streaming shuffle on the zero-copy plane (ISSUE 13 / ROADMAP 5).

Covers: vectorized partitioning parity with the row loop (cross-dtype
hash equality included), the scheduler kernel's locality term (steering
+ weight-0 bit-equivalence), head-path locality routing of dep-carrying
tasks, shuffle content-exactness under the transport kill switch, eager
partition frees, prefetching ingest, and mid-shuffle node death
reconstructing only the lost partitions via lineage.
"""
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.data.shuffle import (
    _compute_parts,
    _hash_dests,
    _stable_hash,
)


# ---------------------------------------------------------------------------
# vectorized partitioning (pure host)
# ---------------------------------------------------------------------------


def test_hash_dests_matches_stable_hash_across_dtypes():
    num_parts = 7
    cases = [
        np.array([0, 1, -1, 5, -17, 2**40, -(2**40)], dtype=np.int64),
        np.array([3, 1, 4, 1, 5], dtype=np.int32),
        np.array([0, 1, 2, 255], dtype=np.uint8),
        np.array([True, False, True]),
        np.array([1.0, -2.0, 3.5, -0.0, 1e300, np.nan, np.inf]),
        np.array([1.5, 2.25], dtype=np.float32),
    ]
    for arr in cases:
        dest = _hash_dests(arr, num_parts)
        assert dest is not None, arr.dtype
        expected = [_stable_hash(v) % num_parts for v in arr]
        assert dest.tolist() == expected, arr.dtype


def test_cross_dtype_keys_co_partition():
    """1, 1.0 and np.float64(1.0) must land in the same partition on
    BOTH paths (the regression the scalar digest pins)."""
    num_parts = 13
    variants = [1, 1.0, np.float64(1.0), np.int32(1), np.float32(1.0), True]
    scalar = {_stable_hash(v) % num_parts for v in variants}
    assert len(scalar) == 1
    for v in variants:
        dest = _hash_dests(np.array([v]), num_parts)
        assert dest is not None
        assert dest[0] == next(iter(scalar))


def _parts_with(vector: bool, *args, **kwargs):
    os.environ["RAY_TPU_DATA_VECTOR_SHUFFLE"] = "1" if vector else "0"
    try:
        return _compute_parts(*args, **kwargs)
    finally:
        os.environ.pop("RAY_TPU_DATA_VECTOR_SHUFFLE", None)


@pytest.mark.parametrize("mode", ["random", "hash", "range"])
def test_vector_partition_matches_row_loop(mode):
    rng = np.random.default_rng(5)
    arr = rng.integers(-1000, 1000, size=2000).astype(np.int64)
    bounds = [-500, 0, 250, 700] if mode == "range" else None
    for block in (arr, arr.tolist()):
        fast = _parts_with(True, block, 6, mode, None, bounds, 42)
        slow = _parts_with(False, block, 6, mode, None, bounds, 42)
        assert len(fast) == len(slow) == 6
        for f, s in zip(fast, slow):
            assert [int(x) for x in f] == [int(x) for x in s]


def test_vector_partition_ndarray_stays_ndarray():
    arr = np.arange(512, dtype=np.float64)
    parts = _parts_with(True, arr, 4, "hash", None, None, None)
    assert all(isinstance(p, np.ndarray) for p in parts)
    assert sum(len(p) for p in parts) == 512
    merged = np.sort(np.concatenate(parts))
    assert np.array_equal(merged, arr)


def test_vector_partition_dict_rows_with_key_fn():
    rows = [{"k": i % 17, "v": i} for i in range(500)]
    fast = _parts_with(True, rows, 5, "hash", lambda r: r["k"], None, None)
    slow = _parts_with(False, rows, 5, "hash", lambda r: r["k"], None, None)
    assert fast == slow


def test_range_mode_nan_keys_match_row_loop():
    """NaN keys: the row loop's `bound <= nan` is always False (→ part
    0) while raw searchsorted would send NaN to the LAST partition —
    the vector path must pin the row-loop behavior."""
    arr = np.array([1.0, -2.5, np.nan, 7.0, np.nan, 3.25, np.inf, -np.inf])
    bounds = [0.0, 2.0, 5.0]
    fast = _parts_with(True, arr, 4, "range", None, bounds, None)
    slow = _parts_with(False, arr.tolist(), 4, "range", None, bounds, None)
    for f, s in zip(fast, slow):
        np.testing.assert_array_equal(
            np.asarray(list(f), dtype=float), np.asarray(s, dtype=float)
        )


def test_reduce_sorted_ndarray_fast_path_is_1d_only():
    """np.sort's axis=-1 on 2-D partitions would reorder values WITHIN
    rows (silent corruption): multi-dim partitions must not take the
    sorted fast path (the generic path raises, as pre-PR)."""
    from ray_tpu.data.shuffle import _reduce_sorted

    one_d = _reduce_sorted._fn(None, False, np.array([3.0, 1.0]), np.array([2.0]))
    assert np.array_equal(one_d, np.array([1.0, 2.0, 3.0]))
    with pytest.raises(ValueError):
        _reduce_sorted._fn(
            None, False, np.array([[3, 1], [1, 9]]), np.array([[2, 5]])
        )


def test_non_numeric_keys_fall_back_to_row_loop():
    rows = ["a", "b", "a", "c"] * 10
    fast = _parts_with(True, rows, 3, "hash", None, None, None)
    slow = _parts_with(False, rows, 3, "hash", None, None, None)
    assert fast == slow


# ---------------------------------------------------------------------------
# kernel locality term
# ---------------------------------------------------------------------------


def _kernel_inputs():
    import jax.numpy as jnp

    def J(x):
        return jnp.asarray(x)

    totals = J(np.array([[8.0, 0.0], [8.0, 0.0]], dtype=np.float32))
    alive = J(np.array([True, True]))
    ntypes = J(np.zeros(2, dtype=np.int32))
    thr = J(np.ones((1, 2), dtype=np.float32))
    sd = J(np.array([[1.0, 0.0]], dtype=np.float32))
    sids = J(np.zeros(4, dtype=np.int32))
    ages = J(np.zeros(1, dtype=np.float32))
    return totals, alive, ntypes, thr, sd, sids, ages


def test_locality_term_steers_to_partition_heavy_node():
    import jax.numpy as jnp

    from ray_tpu.scheduler.hybrid import (
        ScoreWeights,
        hybrid_schedule_shapes_multi_impl,
    )

    totals, alive, ntypes, thr, sd, sids, ages = _kernel_inputs()
    loc = jnp.asarray(np.array([[0.0, 1.0]], dtype=np.float32))
    res = hybrid_schedule_shapes_multi_impl(
        totals, totals, alive, ntypes, thr, sd, sids, ages, np.uint32(3),
        weights=ScoreWeights(1.0, 0.0, 0.0, 0.0, 2.0),
        locality=loc,
    )
    assert np.asarray(res.node).tolist() == [1, 1, 1, 1]


def test_locality_weight_zero_bit_equivalent():
    import jax.numpy as jnp

    from ray_tpu.scheduler.hybrid import (
        ScoreWeights,
        hybrid_schedule_shapes_multi_impl,
    )

    totals, alive, ntypes, thr, sd, sids, ages = _kernel_inputs()
    loc = jnp.asarray(np.array([[0.0, 1.0]], dtype=np.float32))
    base = hybrid_schedule_shapes_multi_impl(
        totals, totals, alive, ntypes, thr, sd, sids, ages, np.uint32(9)
    )
    w0 = hybrid_schedule_shapes_multi_impl(
        totals, totals, alive, ntypes, thr, sd, sids, ages, np.uint32(9),
        weights=ScoreWeights(1.0, 0.0, 0.0, 0.0, 0.0),
        locality=loc,
    )
    assert np.array_equal(np.asarray(base.node), np.asarray(w0.node))
    assert np.array_equal(np.asarray(base.avail_out), np.asarray(w0.avail_out))


def test_all_zero_locality_rows_are_neutral():
    """A shape with no located inputs (all-zero loc row) must place
    exactly like the locality-free program even at weight > 0 — the
    bonus form's invariant."""
    import jax.numpy as jnp

    from ray_tpu.scheduler.hybrid import (
        ScoreWeights,
        hybrid_schedule_shapes_multi_impl,
    )

    totals, alive, ntypes, thr, sd, sids, ages = _kernel_inputs()
    zeros = jnp.asarray(np.zeros((1, 2), dtype=np.float32))
    base = hybrid_schedule_shapes_multi_impl(
        totals, totals, alive, ntypes, thr, sd, sids, ages, np.uint32(11)
    )
    wloc = hybrid_schedule_shapes_multi_impl(
        totals, totals, alive, ntypes, thr, sd, sids, ages, np.uint32(11),
        weights=ScoreWeights(1.0, 0.0, 0.0, 0.0, 3.0),
        locality=zeros,
    )
    assert np.array_equal(np.asarray(base.node), np.asarray(wloc.node))


# ---------------------------------------------------------------------------
# head-path locality routing
# ---------------------------------------------------------------------------


def _make_payload(kb):
    import numpy as _np

    return _np.zeros(kb * 128, dtype=_np.float64)  # kb KiB


def _consume_payload(arr):
    import numpy as _np

    return _np.zeros(32 * 1024, dtype=_np.float64)  # >inline: gets a location


def test_head_locality_routes_consumer_to_data_node():
    """With sched_w_locality > 0, a task whose (sealed, located) dep
    lives on node A runs on node A — its output seals there."""
    from ray_tpu.cluster import Cluster
    from ray_tpu.core.runtime import set_runtime
    from ray_tpu.core.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    os.environ["RAY_TPU_SCHED_W_LOCALITY"] = "4.0"
    c = Cluster()
    node_a = c.add_node({"CPU": 4.0}, num_workers=2)
    c.add_node({"CPU": 4.0}, num_workers=2)
    rt = c.client()
    set_runtime(rt)
    try:
        make = ray_tpu.remote(_make_payload).options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(node_a)
        )
        dep = make.remote(1024)  # 1 MiB, seals on node A
        ray_tpu.wait([dep], timeout=60)
        # the directory must hold the location before the consumers submit
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            locs = rt.object_locations([dep]).get(dep.hex) or []
            if node_a in locs:
                break
            time.sleep(0.1)
        assert node_a in (rt.object_locations([dep]).get(dep.hex) or [])

        consume = ray_tpu.remote(_consume_payload)
        outs = [consume.remote(dep) for _ in range(4)]
        ray_tpu.get(outs, timeout=60)
        out_locs = rt.object_locations(outs)
        on_a = sum(
            1 for r in outs if node_a in (out_locs.get(r.hex) or [])
        )
        assert on_a == len(outs), (
            f"only {on_a}/{len(outs)} consumers ran on the data node "
            f"({out_locs})"
        )
    finally:
        os.environ.pop("RAY_TPU_SCHED_W_LOCALITY", None)
        set_runtime(None)
        c.shutdown()


# ---------------------------------------------------------------------------
# shuffle correctness across the transport fallback matrix
# ---------------------------------------------------------------------------


def _run_cluster_shuffle():
    from ray_tpu import data as rd
    from ray_tpu.cluster import Cluster
    from ray_tpu.core.runtime import set_runtime

    c = Cluster()
    c.add_node({"CPU": 4.0}, num_workers=2)
    c.add_node({"CPU": 4.0}, num_workers=2)
    rt = c.client()
    set_runtime(rt)
    try:
        arr = np.arange(20000, dtype=np.float64)
        ds = rd.from_numpy_blocks(arr, override_num_blocks=8).random_shuffle(
            seed=11
        )
        rows = np.concatenate(
            [np.asarray(list(b)) for b in ds.iter_blocks()]
        )
        grouped = (
            rd.range(2000, override_num_blocks=4)
            .map(lambda x: {"k": x % 10, "v": x})
            .groupby("k")
            .count()
            .take_all()
        )
        counts = {r["k"]: r["count"] for r in grouped}
        return rows, counts
    finally:
        set_runtime(None)
        rt.shutdown()
        c.shutdown()


@pytest.mark.parametrize("native_net", ["1", "0"])
def test_shuffle_content_exact_under_transport_killswitch(
    native_net, monkeypatch
):
    """Socket plane on AND chunked-RPC fallback (RAY_TPU_NATIVE_NET=0):
    identical, content-exact shuffle output either way."""
    monkeypatch.setenv("RAY_TPU_NATIVE_NET", native_net)
    rows, counts = _run_cluster_shuffle()
    assert np.array_equal(np.sort(rows), np.arange(20000, dtype=np.float64))
    assert counts == {i: 200 for i in range(10)}


# ---------------------------------------------------------------------------
# eager frees + prefetching ingest
# ---------------------------------------------------------------------------


def test_eager_free_releases_partitions_as_reduces_seal():
    from ray_tpu.data.shuffle import SHUFFLE_PARTS_FREED, shuffle_blocks

    rt = ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4})
    try:
        base = SHUFFLE_PARTS_FREED.value()
        blocks = [list(range(i * 100, (i + 1) * 100)) for i in range(4)]
        refs = shuffle_blocks(blocks, 4, mode="random", seed=0)
        got = ray_tpu.get(refs, timeout=60)
        assert sorted(x for part in got for x in part) == list(range(400))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if SHUFFLE_PARTS_FREED.value() - base >= 16:  # 4 maps x 4 parts
                break
            time.sleep(0.2)
        assert SHUFFLE_PARTS_FREED.value() - base >= 16, (
            "map partitions were not freed as reduces sealed"
        )
    finally:
        ray_tpu.shutdown()


def test_iter_batches_prefetch_matches_sync():
    from ray_tpu import data as rd

    rt = ray_tpu.init(num_nodes=2, resources_per_node={"CPU": 4})
    try:
        ds = rd.from_numpy_blocks(
            np.arange(30000, dtype=np.int64), override_num_blocks=10
        ).random_shuffle(seed=3)
        ds = ds.materialize()
        sync = np.concatenate(
            [b["data"] for b in ds.iter_batches(batch_size=4096)]
        )
        pre = np.concatenate(
            [
                b["data"]
                for b in ds.iter_batches(batch_size=4096, prefetch_batches=3)
            ]
        )
        assert np.array_equal(sync, pre)
        assert np.array_equal(np.sort(sync), np.arange(30000))
    finally:
        ray_tpu.shutdown()


def test_trainer_dataset_shards_stream_batches():
    from ray_tpu import data as rd
    from ray_tpu.train import JaxTrainer, ScalingConfig

    rt = ray_tpu.init(num_nodes=2, resources_per_node={"CPU": 4})
    try:
        ds = rd.from_numpy_blocks(
            np.arange(4000, dtype=np.float64), override_num_blocks=8
        ).random_shuffle(seed=2)

        def loop(config):
            from ray_tpu import train

            it = train.get_dataset_shard("train")
            seen = 0
            for batch in it.iter_batches(batch_size=256):
                seen += len(batch["data"])
            train.report({"rows": seen})

        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2),
            datasets={"train": ds},
        ).fit()
        assert result.error is None
        # rank-0 report carries its shard; both shards partition the rows
        assert 0 < result.metrics["rows"] < 4000
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# chaos: mid-shuffle node death reconstructs only the lost partitions
# ---------------------------------------------------------------------------


_CHAOS_ROWS = 120_000  # ~960KB blocks → ~160KB partitions (> inline max)


def _block_at(i):
    import numpy as _np

    return _np.arange(
        i * _CHAOS_ROWS, (i + 1) * _CHAOS_ROWS, dtype=_np.float64
    )


def test_node_death_mid_shuffle_reconstructs_only_lost_partitions():
    from ray_tpu.cluster import Cluster
    from ray_tpu.cluster.head import OBJECTS_RECONSTRUCTED
    from ray_tpu.core.runtime import set_runtime
    from ray_tpu.core.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )
    from ray_tpu.data.shuffle import _partition_block, _reduce_concat

    c = Cluster()
    nodes = [c.add_node({"CPU": 2.0}, num_workers=2) for _ in range(3)]
    rt = c.client()
    set_runtime(rt)
    try:
        n_blocks, n_parts = 6, 6
        make = ray_tpu.remote(_block_at)
        blocks = [
            make.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    nodes[i % 3], soft=True
                )
            ).remote(i)
            for i in range(n_blocks)
        ]
        ray_tpu.wait(blocks, num_returns=n_blocks, timeout=120)
        map_refs = [
            _partition_block.options(num_returns=n_parts).remote(
                b, n_parts, "random", None, None, 100 + i
            )
            for i, b in enumerate(blocks)
        ]
        flat = [r for m in map_refs for r in m]
        ready, _ = ray_tpu.wait(
            flat, num_returns=len(flat), timeout=180
        )
        assert len(ready) == len(flat), "map stage did not finish"

        base = sum(OBJECTS_RECONSTRUCTED.values_by_label().values())
        # kill the node holding the most partitions: its (sole-copy)
        # partitions and pinned input blocks are lost mid-shuffle
        locs = rt.object_locations(flat)
        by_node = {}
        for r in flat:
            for nid in locs.get(r.hex) or []:
                by_node[nid] = by_node.get(nid, 0) + 1
        victim = max(by_node, key=by_node.get)
        lost_parts = by_node[victim]
        assert lost_parts < len(flat)  # the kill must not hold everything
        c.kill_node(victim)

        reduces = [
            _reduce_concat.remote(*[m[p] for m in map_refs])
            for p in range(n_parts)
        ]
        out = ray_tpu.get(reduces, timeout=300)
        rows = np.sort(np.concatenate([np.asarray(list(p)) for p in out]))
        assert np.array_equal(
            rows, np.arange(n_blocks * _CHAOS_ROWS, dtype=np.float64)
        ), "shuffle lost or duplicated rows across the node death"

        delta = (
            sum(OBJECTS_RECONSTRUCTED.values_by_label().values()) - base
        )
        # only the victim's partitions (plus their lost input blocks'
        # lineage) re-executed — NOT the whole map stage
        assert delta >= 1, "nothing was reconstructed?"
        assert delta < len(flat), (
            f"reconstructed {delta} objects — looks like the whole map "
            f"stage re-ran ({len(flat)} partitions total)"
        )
    finally:
        set_runtime(None)
        c.shutdown()
