"""Disaggregated multi-model serving (prefill/decode split + hot-swap).

Correctness bar for the KV handoff: a stream decoded from ADOPTED
prefill pages must be bit-identical to the same request served
monolithically — the handoff is a memory transport, not a math change —
with the device plane on AND off, and across a mid-handoff connection
drop (striped fetch resumes, adopted stream still exact). Plus: the
page-pool double-free guard, adopt refusal paths (geometry/model
mismatch fall back to local re-prefill), weights hot-swap drain/epoch
semantics, model-aware replica routing (NoReplicasForModel), the serve
pressure -> demand-row -> bin-pack capacity loop, and the fleet budget
reply carrying the capacity hint.
"""
import os
import tempfile
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm.continuous import ContinuousBatchingEngine, PagedKVPool
from ray_tpu.llm.engine import GenerationConfig
from ray_tpu.models import transformer as tfm


@pytest.fixture(scope="module")
def small():
    cfg = tfm.ModelConfig(
        vocab_size=96,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        dtype=jnp.float32,
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("n_pages", 32)
    return ContinuousBatchingEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# page-pool double-free guard
# ---------------------------------------------------------------------------
def test_pool_double_free_raises(small):
    cfg, _ = small
    pool = PagedKVPool(cfg, n_pages=8, page=8).classes["full"]
    pages = pool.alloc(3)
    pool.free(pages)
    with pytest.raises(ValueError):
        pool.free(pages)  # already back on the free list
    fresh = pool.alloc(2)
    with pytest.raises(ValueError):
        pool.free([fresh[0], fresh[0]])  # duplicate within one call
    with pytest.raises(ValueError):
        pool.free([0])  # the scratch page is never allocatable
    with pytest.raises(ValueError):
        pool.free([99])  # out of range
    # the guard must not corrupt the free list: remaining pages still
    # allocate exactly once each
    pool.free([fresh[1]])
    assert pool.alloc(pool.free_pages) is not None


def test_pool_free_set_tracks_alloc(small):
    cfg, _ = small
    pool = PagedKVPool(cfg, n_pages=8, page=8).classes["full"]
    a = pool.alloc(4)
    b = pool.alloc(3)
    assert not set(a) & set(b)
    assert pool.free_pages == 0
    pool.free(a)
    pool.free(b)
    assert pool.free_pages == 7


# ---------------------------------------------------------------------------
# KV handoff: bit-identical vs monolithic (device plane on AND off)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("plane", ["0", "1"], ids=["host", "device"])
def test_handoff_stream_bit_identical(small, monkeypatch, plane):
    monkeypatch.setenv("RAY_TPU_DEVICE_PLANE", plane)
    cfg, params = small
    prompt = [1, 5, 9, 2, 17, 23, 4, 31, 8]
    gen = GenerationConfig(max_new_tokens=12, temperature=0.8, seed=9)

    mono = _engine(cfg, params)
    want = list(mono.stream_ids(list(prompt), gen))

    pre = _engine(cfg, params)
    dec = _engine(cfg, params)
    manifest, k, v = pre.prefill_extract(list(prompt), gen)
    # the prefill worker reclaims its pages after the gather
    assert pre.pool.free_pages == pre.pool.usable_pages
    free_before = dec.pool.free_pages
    rid = dec.adopt_pages(manifest, k, v)
    assert rid is not None
    got = list(dec.stream_rid(rid))
    assert got == want
    # decode never ran a prefill program, and its pages came back
    assert dec.stats()["full_prefill_count"] == 0
    assert dec.stats()["adopted_count"] == 1
    assert dec.pool.free_pages == free_before


def test_handoff_interleaves_with_local_requests(small):
    """An adopted request decodes in the same batch as locally admitted
    ones, and neither stream corrupts the other."""
    cfg, params = small
    gen = GenerationConfig(max_new_tokens=10, temperature=0.0)
    pa, pb = [3, 3, 7, 12], [11, 12, 13, 14, 15, 16, 17]

    mono = _engine(cfg, params)
    want_a, want_b = mono.generate_ids([pa, pb], gen)

    pre = _engine(cfg, params)
    dec = _engine(cfg, params)
    manifest, k, v = pre.prefill_extract(list(pa), gen)
    rid_a = dec.adopt_pages(manifest, k, v)
    assert rid_a is not None
    rid_b = dec.submit(list(pb), gen)
    while rid_a not in dec.results or rid_b not in dec.results:
        dec.step()
    assert dec.results.pop(rid_a) == want_a
    assert dec.results.pop(rid_b) == want_b


def test_adopt_refuses_mismatches(small):
    """Geometry or model mismatches refuse (return None) instead of
    grafting garbage — the serving layer then re-prefills locally."""
    cfg, params = small
    gen = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompt = [1, 2, 3, 4, 5]
    pre = _engine(cfg, params)

    manifest, k, v = pre.prefill_extract(list(prompt), gen)
    bad_page = dict(manifest, page=manifest["page"] * 2)
    dec = _engine(cfg, params)
    assert dec.adopt_pages(bad_page, k, v) is None

    manifest2, k2, v2 = pre.prefill_extract(list(prompt), gen)
    bad_model = dict(manifest2, model="some-other-weights")
    assert dec.adopt_pages(bad_model, k2, v2) is None
    # refusals must not leak pool pages
    assert dec.pool.free_pages == dec.pool.usable_pages

    # pool backpressure: a pool without room for the prompt pages refuses
    manifest3, k3, v3 = pre.prefill_extract(list(range(1, 21)), gen)
    tiny = _engine(cfg, params, n_pages=2)  # 1 usable page, prompt needs 3
    assert tiny.adopt_pages(manifest3, k3, v3) is None


# ---------------------------------------------------------------------------
# mid-handoff connection drop: striped fetch resumes, stream stays exact
# ---------------------------------------------------------------------------
def test_mid_handoff_conn_drop_stream_exact(small, monkeypatch):
    """Ship a sealed (manifest, k, v) handoff over the striped peer
    plane, sever the server's data sockets mid-transfer, and verify the
    resumed fetch adopts into a decode engine whose stream is
    bit-identical to the monolithic run."""
    from ray_tpu.cluster import device_plane as dp
    from ray_tpu.cluster import serialization as wire
    from ray_tpu.cluster import transport as tp
    from ray_tpu.native.shm_store import NativeObjectStore

    monkeypatch.setenv("RAY_TPU_DEVICE_PLANE", "1")
    # many small stripes so the chaos drop lands mid-transfer
    monkeypatch.setenv("RAY_TPU_NET_STRIPE_BYTES", str(1 << 12))
    monkeypatch.setenv("RAY_TPU_NET_STRIPE_CONNS", "2")
    cfg, params = small
    prompt = list(range(1, 25))  # 24 tokens -> 3 pages of KV to ship
    gen = GenerationConfig(max_new_tokens=10, temperature=0.7, seed=3)

    mono = _engine(cfg, params, n_pages=64)
    want = list(mono.stream_ids(list(prompt), gen))

    pre = _engine(cfg, params, n_pages=64)
    manifest, k, v = pre.prefill_extract(list(prompt), gen)

    store = NativeObjectStore(
        path=os.path.join(
            tempfile.gettempdir(),
            f"t_disagg_{os.getpid()}_{time.time_ns()}.shm",
        ),
        capacity=1 << 26,
    )
    srv = tp.DataPlaneServer(store, "nodesrv", "tok-secret", lambda: 100)
    link = tp.PeerLink(
        "lk0", "nodesrv", srv.endpoint, "tok-secret", 100, "nodecli"
    )
    oid = "h" * 28
    try:
        parts, total = wire.dumps_parts((manifest, k, v))
        store.put_frames(oid, parts)
        got: dict = {}

        def pull():
            got["data"] = tp.fetch_bytes(link, oid, land="device")

        t = threading.Thread(target=pull)
        t.start()
        for _ in range(3):
            time.sleep(0.02)
            srv.chaos_drop()
        t.join(timeout=60)
        assert not t.is_alive()
        assert srv.stats["chaos_drops"] >= 1
        assert len(got["data"]) == total
        with dp.landing("device"):
            m2, k2, v2 = wire.loads(memoryview(got["data"]))
        dec = _engine(cfg, params, n_pages=64)
        rid = dec.adopt_pages(m2, k2, v2)
        assert rid is not None
        assert list(dec.stream_rid(rid)) == want
        assert dec.stats()["full_prefill_count"] == 0
    finally:
        link.close()
        srv.close()
        store.close(unlink=True)


# ---------------------------------------------------------------------------
# weights hot-swap: drain + epoch fence
# ---------------------------------------------------------------------------
def test_swap_params_drains_then_bumps_epoch(small):
    cfg, params = small
    alt = tfm.init_params(cfg, jax.random.PRNGKey(41))
    prompt = [2, 4, 6, 8]
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0)

    want_old = _engine(cfg, params).generate_ids([prompt], gen)[0]
    want_new = _engine(cfg, alt).generate_ids([prompt], gen)[0]
    assert want_old != want_new  # different weights, different stream

    eng = _engine(cfg, params)
    rid = eng.submit(list(prompt), gen)
    eng.step()  # request is mid-generation when the swap arrives
    assert eng.weights_epoch == 0
    epoch = eng.swap_params(alt, model_id="alt")
    assert epoch == 1 and eng.model_id == "alt"
    # the in-flight request finished ON THE OLD WEIGHTS (drain), so its
    # tokens are exactly the old-weights stream — no mid-stream cross
    assert rid in eng.results
    assert eng.results.pop(rid) == want_old
    # requests after the swap decode on the new weights
    assert eng.generate_ids([prompt], gen)[0] == want_new


def test_swap_blocks_admission_until_done(small):
    """Requests queued during a swap admit on the NEW weights."""
    cfg, params = small
    alt = tfm.init_params(cfg, jax.random.PRNGKey(41))
    gen = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompt = [9, 9, 1]
    want_new = _engine(cfg, alt).generate_ids([prompt], gen)[0]
    eng = _engine(cfg, params)
    eng._swapping = True
    rid = eng.submit(list(prompt), gen)
    eng.step()
    assert all(not s.active for s in eng.slots)  # parked, not admitted
    eng._swapping = False
    eng.swap_params(alt, model_id="alt")
    while rid not in eng.results:
        eng.step()
    assert eng.results.pop(rid) == want_new


# ---------------------------------------------------------------------------
# model-aware routing
# ---------------------------------------------------------------------------
def _bare_replica_set(models, n=2):
    from ray_tpu.serve.deployment import _Replica, _ReplicaSet

    rs = _ReplicaSet.__new__(_ReplicaSet)
    rs.dep = SimpleNamespace(name="dep", models=models)
    rs.lock = threading.Lock()
    rs.replicas = [_Replica(actor=None) for _ in range(n)]
    return rs


def test_pick_replica_unknown_model_raises():
    from ray_tpu.serve import NoReplicasForModel

    rs = _bare_replica_set(models=["m0", "m1"])
    with pytest.raises(NoReplicasForModel) as ei:
        rs._pick_replica(model="nope")
    assert ei.value.deployment == "dep"
    assert ei.value.model == "nope"


def test_pick_replica_cold_model_marks_victim():
    rs = _bare_replica_set(models=["m0", "m1"], n=3)
    rs.replicas[0].model = "m0"
    rs.replicas[0].ongoing = 0
    rs.replicas[1].ongoing = 5
    rs.replicas[2].ongoing = 1
    # cold model prefers a never-swapped replica (model=None), least
    # loaded, and marks it so a concurrent same-model pick routes there
    r = rs._pick_replica(model="m1")
    assert r is rs.replicas[2]
    assert r.model == "m1"
    # same model now routes within its replica set, not a new victim
    assert rs._pick_replica(model="m1") is rs.replicas[2]


def test_pick_replica_all_draining_raises():
    from ray_tpu.serve import NoReplicasForModel

    rs = _bare_replica_set(models=["m0"], n=2)
    for r in rs.replicas:
        r.draining = True
    with pytest.raises(NoReplicasForModel):
        rs._pick_replica(model="m0")


# ---------------------------------------------------------------------------
# serve pressure -> demand rows -> capacity plan
# ---------------------------------------------------------------------------
def test_pressure_rollup_merges_routers():
    from ray_tpu.scheduler.serve_demand import pressure_rollup

    reports = {
        "r1": {"pressure": {"a": {"waiting": 2, "waiting_tokens": 100}}},
        "r2": {
            "pressure": {
                "a": {"waiting": 1, "waiting_tokens": 50},
                "b": {"waiting": 3, "waiting_tokens": 900},
            }
        },
        "r3": {},  # router with no pressure entry
    }
    got = pressure_rollup(reports)
    assert got == {
        "a": {"waiting": 3, "waiting_tokens": 150},
        "b": {"waiting": 3, "waiting_tokens": 900},
    }


def test_pressure_to_demand_rows_replica_equivalents():
    from ray_tpu.scheduler.serve_demand import pressure_to_demand_rows

    demands, owners = pressure_to_demand_rows(
        {
            # 9000 tokens / 4096 per replica -> ceil = 3 rows
            "a": {"waiting": 1, "waiting_tokens": 9000},
            # 9 waiting / 8 per replica -> ceil = 2 rows
            "b": {"waiting": 9, "waiting_tokens": 10},
        },
        tokens_per_replica=4096.0,
        queue_per_replica=8.0,
    )
    assert demands.shape == (5, 1)
    assert owners == ["a", "a", "a", "b", "b"]
    # cap: one flooding tenant cannot blow up the kernel batch
    demands, owners = pressure_to_demand_rows(
        {"flood": {"waiting": 10_000, "waiting_tokens": 0}}, max_rows=16
    )
    assert demands.shape == (16, 1)


def test_capacity_plan_places_through_binpack():
    from ray_tpu.scheduler.serve_demand import capacity_plan

    assert capacity_plan([4.0], {}) is None  # no pressure: idle path
    plan = capacity_plan(
        [2.0, 1.0],
        {
            "a": {"waiting": 0, "waiting_tokens": 9000},  # 3 rows
            "b": {"waiting": 9, "waiting_tokens": 0},  # 2 rows
        },
    )
    assert plan["replicas_wanted"] == 5
    assert plan["replicas_placeable"] == 3  # 3 CPUs of residual room
    assert plan["unfulfilled"] == 2
    assert sum(plan["by_tenant"].values()) == 3
    # no capacity at all: everything unfulfilled, nothing placed
    starved = capacity_plan([], {"a": {"waiting": 9, "waiting_tokens": 0}})
    assert starved["replicas_placeable"] == 0
    assert starved["unfulfilled"] == starved["replicas_wanted"]


def test_admission_exports_pressure_by_tenant():
    from ray_tpu.serve.admission import AdmissionController

    ctl = AdmissionController(max_inflight=1, wait_timeout_s=5.0)
    first = ctl.admit("a", cost=3)
    parked = threading.Event()
    done: dict = {}

    def blocked():
        parked.set()
        done["ticket"] = ctl.admit("b", cost=17)

    t = threading.Thread(target=blocked)
    t.start()
    parked.wait(timeout=5)
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        p = ctl.pressure_by_tenant()
        if p:
            break
        time.sleep(0.01)
    assert p == {"b": {"waiting": 1, "waiting_tokens": 17}}
    first.done()
    t.join(timeout=10)
    assert not t.is_alive()
    done["ticket"].done()
    assert ctl.pressure_by_tenant() == {}


def test_local_fleet_budget_carries_capacity_hint():
    from ray_tpu.serve.fleet import _LocalFleetCoordinator

    coord = _LocalFleetCoordinator()
    epoch = coord.join("dep", "r1")["epoch"]
    reply = coord.budget(
        "dep", "r1", epoch,
        usage={"a": 4},
        waiting={"a": 2},
        weights={},
        pressure={"a": {"waiting": 20, "waiting_tokens": 50_000}},
    )
    hint = reply.get("capacity_hint")
    assert hint is not None
    assert hint["replicas_wanted"] >= 3  # 50k tokens of queued prefill
    assert hint["replicas_wanted"] == (
        hint["replicas_placeable"] + hint["unfulfilled"]
    )
    # no pressure -> no hint (the idle path skips the kernel)
    reply = coord.budget(
        "dep", "r1", epoch, usage={}, waiting={}, weights={}, pressure={}
    )
    assert reply.get("capacity_hint") is None


def test_slo_autoscaler_capacity_block(small):
    """A fresh zero-placeable capacity hint holds an upscale the SLO
    signals would otherwise fire; headroom releases it."""
    from ray_tpu.serve.slo_autoscaler import SLOAutoscaler, SLOConfig

    hint = {"replicas_placeable": 0}
    added = []
    router = SimpleNamespace(
        _rs=SimpleNamespace(
            dep=SimpleNamespace(name="dep"),
            num_replicas=1,
            add_replica=lambda: added.append(1),
        ),
        capacity_hint=lambda: hint,
    )
    clock = [0.0]
    scaler = SLOAutoscaler(
        router,
        SLOConfig(max_replicas=4, upscale_delay_s=1.0),
        metrics_fn=lambda: {
            "inflight": 100, "replicas": 1, "ttft_p50_ms": 0.0,
        },
        clock=lambda: clock[0],
    )
    assert scaler.tick() == "hold"  # arms the over-window
    clock[0] = 2.0
    assert scaler.tick() == "hold-capacity"
    assert not added and scaler.capacity_blocks == 1
    hint = None  # stale/absent hint must never block
    router.capacity_hint = lambda: hint
    clock[0] = 4.0
    assert scaler.tick() == "up"
    assert added == [1]
