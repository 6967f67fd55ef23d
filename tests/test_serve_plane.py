"""Production serving plane (PR 8): lease-routed ingress, admission
control, shm prefix cache, push-plane streaming, SLO autoscaling.

Fast tier covers each subsystem plus the zero-head-RPC steady-state
claim on a live cluster; the slow tier SIGKILLs a replica mid-stream
under the chaos orchestrator and asserts failover with no duplicated or
dropped acked tokens, replica backfill, and zero arena zombies.
"""
import os
import tempfile
import time

import pytest

import ray_tpu
from ray_tpu.core.runtime import set_runtime


def _wait_for(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# admission control (pure units)
# ---------------------------------------------------------------------------
def test_token_bucket_rate_and_burst():
    from ray_tpu.serve.admission import TokenBucket

    now = [0.0]
    b = TokenBucket(rate=10.0, burst=2.0, clock=lambda: now[0])
    assert b.try_take() and b.try_take()
    assert not b.try_take(), "burst exhausted"
    now[0] += 0.1  # one token refills at 10/s
    assert b.try_take()
    assert not b.try_take()
    assert b.next_available_s() == pytest.approx(0.1, abs=0.02)


def test_admission_sheds_typed_overloaded_at_depth():
    from ray_tpu.serve.admission import AdmissionController, Overloaded

    ctl = AdmissionController(max_inflight=2, wait_cap=0)
    t1 = ctl.admit()
    t2 = ctl.admit()
    with pytest.raises(Overloaded) as ei:
        ctl.admit()
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_s > 0
    t1.done()
    t3 = ctl.admit()  # released depth admits again
    t3.done()
    t2.done()
    stats = ctl.stats()
    assert stats["sheds"] == 1 and stats["admitted"] == 3
    assert stats["inflight"] == 0


def test_admission_wfq_weights_order_grants():
    """Under contention, a weight-3 tenant drains ~3x the requests of a
    weight-1 tenant (WFQ virtual-finish-time order)."""
    import threading

    from ray_tpu.serve.admission import AdmissionController

    ctl = AdmissionController(
        max_inflight=1,
        wait_cap=64,
        wait_timeout_s=30.0,
        tenant_weights={"gold": 3.0, "bronze": 1.0},
    )
    gate = ctl.admit()  # hold the only slot so everyone parks
    grants = []
    lock = threading.Lock()

    def one(tenant):
        t = ctl.admit(tenant)
        with lock:
            grants.append(tenant)
        t.done()  # release immediately: next waiter pumps

    threads = [
        threading.Thread(target=one, args=(t,))
        for t in ["gold"] * 6 + ["bronze"] * 6
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)  # everyone parked
    gate.done()
    for t in threads:
        t.join(timeout=30)
    assert len(grants) == 12
    # in the first 8 grants, gold (weight 3) should hold ~3:1 majority
    head = grants[:8]
    assert head.count("gold") >= 5, f"WFQ order violated: {grants}"


# ---------------------------------------------------------------------------
# prefix cache (store-level + engine-level)
# ---------------------------------------------------------------------------
@pytest.fixture()
def shm_store():
    from ray_tpu.native import NativeObjectStore

    path = os.path.join(
        tempfile.gettempdir(), f"serve_pfx_test_{os.getpid()}.shm"
    )
    store = NativeObjectStore(path=path, capacity=32 << 20)
    yield store
    store.close(unlink=True)


def test_prefix_cache_hit_is_view_not_copy(shm_store):
    import numpy as np

    from ray_tpu.serve.prefix_cache import SharedPrefixCache

    cache = SharedPrefixCache(shm_store, page_size=4, model_sig="sig")
    # big enough for the wire format's out-of-band path (>= 4 KiB per
    # buffer): that's what makes a hit a zero-copy arena view
    k = np.arange(
        2 * 2 * 2 * 4 * 128, dtype=np.float32
    ).reshape(2, 2, 2, 4, 128)
    v = k + 1.0
    tokens = list(range(8))  # 2 full pages
    assert cache.insert(tokens, k, v)
    hit = cache.lookup(tokens + [99, 98])  # longer prompt, shared prefix
    assert hit is not None and hit.tokens == 8
    # READ-ONLY VIEWS over the arena — not copies
    assert not hit.k.flags["OWNDATA"] and not hit.k.flags["WRITEABLE"]
    assert not hit.v.flags["OWNDATA"] and not hit.v.flags["WRITEABLE"]
    with pytest.raises((ValueError, RuntimeError)):
        hit.k[0, 0, 0, 0, 0] = 5.0
    np.testing.assert_array_equal(np.asarray(hit.k), k)
    # delete-under-pin defers the free (zombie semantics): the pinned
    # view stays byte-correct until released
    ins_oid = next(iter(cache._mine))
    shm_store.delete(ins_oid)
    np.testing.assert_array_equal(np.asarray(hit.v), v)
    hit.release()
    # shorter prompts than a full page never hit
    assert cache.lookup([0, 1, 2]) is None


def test_prefix_cache_deterministic_ids_no_side_index(shm_store):
    """The arena IS the index: a second cache instance (another replica)
    sees the first's entries with zero coordination."""
    import numpy as np

    from ray_tpu.serve.prefix_cache import SharedPrefixCache

    a = SharedPrefixCache(shm_store, page_size=4, model_sig="m1")
    b = SharedPrefixCache(shm_store, page_size=4, model_sig="m1")
    other = SharedPrefixCache(shm_store, page_size=4, model_sig="m2")
    k = np.ones((1, 1, 1, 4, 2), dtype=np.float32)
    assert a.insert([5, 6, 7, 8], k, k)
    hit = b.lookup([5, 6, 7, 8, 9])
    assert hit is not None and hit.tokens == 4
    hit.release()
    # duplicate insert is a benign no-op (first writer wins)
    assert not b.insert([5, 6, 7, 8], k, k)
    # a different model signature never collides
    assert other.lookup([5, 6, 7, 8, 9]) is None


def test_engine_prefix_cache_skips_prefill_and_matches(shm_store):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    from ray_tpu.llm.engine import GenerationConfig
    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.prefix_cache import SharedPrefixCache

    cfg = tfm.ModelConfig(
        vocab_size=64, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=96, dtype=jnp.float32,
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(2))
    gen = GenerationConfig(max_new_tokens=10, temperature=0.0)
    prompt = [3, 5, 7, 9, 11, 2, 4, 6, 8, 1, 3, 5, 7, 2, 9, 4, 6, 1]

    ref = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32
    )
    want = ref.generate_ids([list(prompt)], gen)[0]
    cache = SharedPrefixCache(shm_store, page_size=8, model_sig="eng")
    a = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32,
        prefix_cache=cache,
    )
    assert a.generate_ids([list(prompt)], gen)[0] == want
    assert cache.inserts == 1
    # replica B: same node, fresh engine — the hit skips FULL prefill
    b = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32,
        prefix_cache=cache,
    )
    full_prefills = {"n": 0}
    orig = b._prefill

    def counting(*args, **kw):
        full_prefills["n"] += 1
        return orig(*args, **kw)

    b._prefill = counting
    assert b.generate_ids([list(prompt)], gen)[0] == want
    assert full_prefills["n"] == 0, "cache hit must skip full prefill"
    assert cache.hits >= 1
    assert b.stats()["prefix_cache"]["hits"] >= 1


# ---------------------------------------------------------------------------
# push-plane stream transport (sink + writer units)
# ---------------------------------------------------------------------------
def test_stream_sink_push_ordering_and_cancel():
    from ray_tpu.experimental import ChannelClosed as RingClosed
    from ray_tpu.serve.router import (
        ChannelClosed,
        PushWriter,
        StreamSink,
    )

    sink = StreamSink()
    try:
        sid, stream = sink.open()
        w = PushWriter(sink.address, sid)
        for i in range(5):
            w.write(i)
        w.close_channel()
        got = []
        while True:
            try:
                got.append(stream.read(timeout=5))
            except ChannelClosed:
                break
        assert got == [0, 1, 2, 3, 4]
        # cancel propagation: a discarded stream rejects further pushes
        # (spaced past the writer's micro-batch window so every write
        # flushes and observes the cancel reply)
        sid2, _stream2 = sink.open()
        w2 = PushWriter(sink.address, sid2)
        w2.write("x")
        sink.discard(sid2)
        with pytest.raises(RingClosed):
            for _ in range(10):
                w2.write("y")
                time.sleep(0.01)
    finally:
        sink.stop()


def test_relay_fallback_bounded_and_cancellable():
    """The legacy polling relay (RAY_TPU_SERVE_PUSH_STREAMS=0 fallback):
    cancel drops buffered items and pushes -1 back at the writer."""
    import asyncio

    from ray_tpu.serve.proxy import _StreamRelayActor

    actor = _StreamRelayActor(max_buffer=8)

    async def drive():
        assert await actor.push(0, ["a", "b"]) == 2
        await actor.cancel()
        assert await actor.push(1, ["c"]) == -1  # writer must stop
        assert await actor.depth() == -1
        items, ended = await actor.pop(timeout=0.05)
        assert items == [] and ended

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# SLO autoscaler (in-process runtime)
# ---------------------------------------------------------------------------
def test_slo_autoscaler_scales_up_then_drains():
    import ray_tpu.serve as serve
    from ray_tpu.serve.slo_autoscaler import SLOAutoscaler, SLOConfig

    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 8})
    try:

        @serve.deployment(name="scaled", num_replicas=1)
        class Echo:
            def __call__(self, payload):
                return payload

        serve.run(Echo.bind())
        router = serve.get_router("scaled")
        rs = router._rs
        metrics = {"inflight": 50, "ttft_p50_ms": 0.0}
        now = [0.0]
        scaler = SLOAutoscaler(
            router,
            SLOConfig(
                min_replicas=1,
                max_replicas=3,
                target_queue_per_replica=4.0,
                upscale_delay_s=1.0,
                downscale_delay_s=1.0,
            ),
            metrics_fn=lambda: {
                **metrics, "replicas": rs.num_replicas,
            },
            clock=lambda: now[0],
        )
        assert scaler.tick() == "hold"  # arms the over-window
        now[0] += 2.0
        assert scaler.tick() == "up"
        assert rs.num_replicas == 2
        assert rs.target == 2
        # sustained idleness drains one replica gracefully
        metrics["inflight"] = 0
        scaler.tick()
        now[0] += 2.0
        assert scaler.tick() == "down"
        _wait_for(
            lambda: rs.num_replicas == 1, msg="drained replica removed"
        )
        assert rs.target == 1
        assert scaler.state()["scale_ups"] == 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# cluster tier: zero head RPCs, streaming, failover
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.cluster import Cluster

    c = Cluster(use_device_scheduler=False)
    c.add_node({"CPU": 8.0}, num_workers=3)
    c.add_node({"CPU": 8.0}, num_workers=3)
    yield c
    c.shutdown()


@pytest.fixture()
def client(cluster):
    import ray_tpu.serve as serve

    rt = cluster.client()
    set_runtime(rt)
    yield rt
    serve.shutdown()
    set_runtime(None)
    rt.shutdown()


class _EchoServer:
    def __call__(self, payload):
        return {"echo": payload}


def test_unary_zero_head_rpcs_steady_state(cluster, client):
    """Steady-state routed requests ride the direct channels: the head's
    per-request surfaces (lease submissions, object waits, actor
    creations) must NOT grow with request count."""
    import ray_tpu.serve as serve
    from ray_tpu.cluster.rpc import HANDLER_STATS

    app = serve.deployment(name="echo", num_replicas=2)(_EchoServer).bind()
    serve.run(app)
    router = serve.get_router("echo")
    # warm: replica actors alive, direct channels resolved
    for i in range(8):
        assert router.call({"i": i}, timeout=60)["echo"]["i"] == i
    _wait_for(
        lambda: any(
            not k.startswith("lease:") and getattr(c, "_worker", None)
            for k, c in client._direct_channels.items()
        ),
        msg="a warm direct actor channel",
    )

    def head_counters():
        snap = HANDLER_STATS.snapshot()
        names = (
            "SubmitLease", "WaitObjectBatch", "WaitObject", "PutObject",
            "GrantTaskLease", "CreateActor", "WaitActor", "LocateObjects",
        )
        return {
            n: (snap.get(n) or {}).get("count", 0) for n in names
        }, cluster.head.metrics["leases_submitted"]

    before, leases_before = head_counters()
    n = 100
    reqs = [router.submit({"i": i}) for i in range(n)]
    for i, r in enumerate(reqs):
        assert r.result(60)["echo"]["i"] == i
    after, leases_after = head_counters()
    growth = {k: after[k] - before[k] for k in after if after[k] > before[k]}
    assert sum(growth.values()) < n // 2, (
        f"per-request head RPCs in steady state: {growth}"
    )
    assert leases_after - leases_before < n // 2, (
        "routed requests fell back to head-scheduled leases"
    )
    from ray_tpu.serve.router import SERVE_LEASE_HITS

    assert SERVE_LEASE_HITS.value({"deployment": "echo"}) > 0
    stats = router.stats()
    assert stats["codes"].get("200", 0) >= n
    assert len(stats["replicas"]) == 2
    # the completion watcher drains ongoing counts asynchronously —
    # wait for the drain rather than racing it on a loaded box
    _wait_for(
        lambda: all(
            r["ongoing"] == 0 for r in router.stats()["replicas"]
        ),
        msg="replica ongoing counts drained",
    )


class _SlowTokenServer:
    """Streams tokens slowly enough that a client disconnect lands
    mid-generation; counts writes so the test can observe the abort."""

    def __init__(self):
        self.written = 0

    def stream_to(self, writer, request):
        from ray_tpu.experimental import ChannelClosed

        n = int(request.get("n", 100))
        try:
            for i in range(n):
                writer.write(f"tok{i}")
                self.written += 1
                time.sleep(0.03)
            writer.close_channel()
        except ChannelClosed:
            pass  # consumer cancelled: stop generating
        return self.written

    def count(self):
        return self.written


def test_stream_end_to_end_and_admission_shed(cluster, client, monkeypatch):
    """Full stream through the router (push transport), then a shed:
    depth-capped admission rejects the second concurrent stream with a
    typed Overloaded before any replica work is accepted."""
    import ray_tpu.serve as serve
    from ray_tpu.serve.admission import AdmissionController, Overloaded
    from ray_tpu.serve.router import ChannelClosed

    monkeypatch.setenv("RAY_TPU_SERVE_SHM_STREAMS", "0")
    app = serve.deployment(name="tok", num_replicas=1)(
        _SlowTokenServer
    ).bind()
    serve.run(app)
    router = serve.get_router("tok")
    router.admission = AdmissionController(max_inflight=1, wait_cap=0)
    stream = router.stream({"n": 5})
    with pytest.raises(Overloaded):
        router.stream({"n": 5})
    got = list(stream)
    assert got == [f"tok{i}" for i in range(5)]
    # finished stream released its admission slot
    assert router.admission.stats()["inflight"] == 0
    second = router.stream({"n": 2})
    assert list(second) == ["tok0", "tok1"]


def test_disconnect_mid_stream_stops_generation(cluster, client, monkeypatch):
    import ray_tpu.serve as serve

    monkeypatch.setenv("RAY_TPU_SERVE_SHM_STREAMS", "0")
    app = serve.deployment(name="aborted", num_replicas=1)(
        _SlowTokenServer
    ).bind()
    handle = serve.run(app)
    router = serve.get_router("aborted")
    stream = router.stream({"n": 300})
    for _ in range(3):
        stream.read(timeout=30)
    stream.close()  # cancel: the sink now rejects the replica's pushes
    # generation must stop well short of 300 writes
    time.sleep(1.0)
    c1 = ray_tpu.get(handle.count.remote(), timeout=30)
    time.sleep(1.0)
    c2 = ray_tpu.get(handle.count.remote(), timeout=30)
    assert c2 == c1, "replica kept generating after client disconnect"
    assert c2 < 300


def test_query_state_serve_surface(cluster, client):
    """The router's periodic report lands in head QueryState('serve')."""
    import ray_tpu.serve as serve

    app = serve.deployment(name="observed", num_replicas=1)(
        _EchoServer
    ).bind()
    serve.run(app)
    router = serve.get_router("observed")
    assert router.call({"x": 1}, timeout=60)["echo"]["x"] == 1

    def reported():
        state = client.query_state("serve")
        return "observed" in (state or {}).get("deployments", {})

    _wait_for(reported, timeout=15.0, msg="serve state reported to head")
    blob = client.query_state("serve")["deployments"]["observed"]
    assert blob["admission"]["admitted"] >= 1
    assert len(blob["replicas"]) == 1
    assert "lease_hit_rate" in blob and "ttft_ms" in blob


# ---------------------------------------------------------------------------
# slow tier: replica SIGKILL mid-stream under the chaos orchestrator
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_replica_kill_mid_stream_recovers():
    """Open-loop verified streams + two replica_kill faults: streams
    fail over with resume_from (no duplicated/dropped acked tokens),
    the replica set backfills, and no arena pins leak."""
    import jax
    import jax.numpy as jnp

    import ray_tpu.serve as serve
    from ray_tpu.chaos import (
        ChaosOrchestrator,
        ChaosWorkload,
        SERVE_MIX,
        ServeStreamWorkload,
        make_plan,
    )
    from ray_tpu.cluster import Cluster
    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    from ray_tpu.llm.engine import GenerationConfig
    from ray_tpu.llm.serving import build_llm_deployment
    from ray_tpu.models import transformer as tfm

    mcfg = tfm.ModelConfig(
        vocab_size=64, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=96, dtype=jnp.float32,
    )
    prompt = "chaos stream"
    max_new = 10
    # the deterministic reference sequence (replicas init params from
    # PRNGKey(0) when params=None — same weights everywhere)
    ref_engine = ContinuousBatchingEngine(
        mcfg, None, max_batch=2, page_size=8, n_pages=64
    )
    gen = GenerationConfig(max_new_tokens=max_new, temperature=0.0, seed=0)
    expected = [
        ref_engine.tokenizer.decode([int(t)])
        for t in ref_engine.stream_ids(
            ref_engine.tokenizer.encode(prompt), gen
        )
    ]
    assert len(expected) == max_new

    cluster = Cluster(use_device_scheduler=False)
    cluster.add_node({"CPU": 8.0}, num_workers=3)
    cluster.add_node({"CPU": 8.0}, num_workers=3)
    rt = cluster.client()
    set_runtime(rt)
    try:
        app = build_llm_deployment(
            mcfg,
            name="chaos-llm",
            num_replicas=2,
            max_batch=2,
            page_size=8,
            n_pages=64,
        )
        serve.run(app)
        router = serve.get_router("chaos-llm")
        assert router.resumable
        payload = {"prompt": prompt, "max_new_tokens": max_new}
        workload = ServeStreamWorkload(
            router, payload, expected, concurrency=2
        )
        workload.start()
        # warm: both replicas compiled, streams completing
        _wait_for(
            lambda: workload.completed >= 2,
            timeout=180.0,
            msg="warm serve streams",
        )
        assert not workload.verify_failures
        plan = make_plan(
            seed=11, num_faults=2, mix=SERVE_MIX, allow=("replica_kill",),
            min_delay_s=0.5, max_delay_s=1.0,
        )
        assert plan.counts() == {"replica_kill": 2}
        chaos_wl = ChaosWorkload(rt, payload_bytes=150_000, num_actors=1)
        orch = ChaosOrchestrator(
            cluster,
            chaos_wl,
            plan,
            node_resources={"CPU": 8.0},
            convergence_budget_s=120.0,
            serve_adapter=workload,
        )
        result = orch.run()
        workload.stop()
        assert result.ok, result.summary()
        assert not workload.verify_failures, workload.verify_failures
        assert workload.completed >= 3
        # acceptance: no leaked pins anywhere (SIGKILLed replicas'
        # prefix-cache pins were replayed from their pin logs)
        assert result.arena_zombies_after == 0
    finally:
        workload.stop()
        serve.shutdown()
        set_runtime(None)
        try:
            rt.shutdown()
        finally:
            cluster.shutdown()
