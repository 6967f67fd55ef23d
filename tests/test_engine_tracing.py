"""Spans and counters from the router's admission down to the serving
engine and back out to the stream's consumer (``util/tracing.span``): the
tree they make, the counts and waits they carry, the profiler's copy of
them, and that recording them changes nothing."""
import glob
import os
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm.continuous import ContinuousBatchingEngine
from ray_tpu.llm.engine import GenerationConfig
from ray_tpu.models import transformer as tfm
from ray_tpu.util import tracing

# every engine span that is a ``with`` block, so also a profiler annotation
ANNOTATED = (
    "engine.step", "engine.admit", "engine.prefill", "engine.first_token",
    "engine.prefix_insert", "engine.decode", "engine.readback",
)
PAGE = 8


class _DictPrefixCache:
    """The engine's side of the shared prefix cache, never a hit."""

    def __init__(self):
        self.blocks = {}

    def lookup(self, tokens, max_tokens=None):
        return None

    def contains_prefix(self, tokens):
        return tuple(tokens) in self.blocks

    def insert(self, tokens, k, v):
        self.blocks[tuple(tokens)] = (k, v)
        return True

    def stats(self):
        return {"blocks": len(self.blocks)}


@pytest.fixture(scope="module")
def toy():
    cfg = tfm.ModelConfig(
        vocab_size=64, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=96, dtype=jnp.float32,
    )
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(2))


@pytest.fixture(autouse=True)
def empty_ring():
    tracing.SPANS.clear()
    yield
    tracing.SPANS.clear()


def make_engine(toy, **kw):
    cfg, params = toy
    kw.setdefault("max_batch", 4)
    kw.setdefault("n_pages", 32)
    return ContinuousBatchingEngine(cfg, params, page_size=PAGE, **kw)


def engine_spans(name=None):
    spans = tracing.SPANS.slices(cat="engine")
    return [s for s in spans if name is None or s["name"] == name]


PROMPTS = [[1, 5, 9, 2], [3, 3, 7], list(range(1, 20)), [2]]
GEN = GenerationConfig(max_new_tokens=6, temperature=0.0)


# -- the recorder -------------------------------------------------------------
def test_span_records_ids_parent_trace_and_maps_the_perf_clock():
    token = tracing.start_trace()
    try:
        t_a = time.perf_counter()
        with tracing.span("outer", "t", x=1) as outer:
            with tracing.span("inner", "t") as inner:
                inner.set(y=2)
            life = tracing.span("life", "t").begin()
        life.end(end="finished")
        t_b = time.perf_counter()
        trace_id = tracing.current()["trace_id"]
    finally:
        tracing.uninstall(token)
    by = {s["name"]: s for s in tracing.SPANS.slices(cat="t")}
    assert set(by) == {"outer", "inner", "life"}
    assert by["inner"]["args"]["parent"] == by["outer"]["args"]["id"]
    assert by["life"]["args"]["parent"] == by["outer"]["args"]["id"]
    assert "parent" not in by["outer"]["args"]
    assert by["outer"]["args"]["x"] == 1 and by["inner"]["args"]["y"] == 2
    assert by["life"]["args"]["end"] == "finished"
    assert {s["args"]["trace_id"] for s in by.values()} == {trace_id}
    assert len({s["args"]["id"] for s in by.values()}) == 3
    # the public anchor maps perf_counter stamps onto the ring's epoch ts
    lo = (tracing.PERF_EPOCH_S + t_a) * 1e6
    hi = (tracing.PERF_EPOCH_S + t_b) * 1e6
    for s in by.values():
        assert lo <= s["ts"] and s["ts"] + s["dur"] <= hi
    assert abs(by["outer"]["ts"] * 1e-6 - time.time()) < 5.0
    assert outer and inner  # a recording span is truthy


def test_a_full_ring_counts_the_spans_it_pushes_out():
    ring = tracing.SpanBuffer(max_spans=4)
    assert ring.dropped == 0
    for i in range(6):
        with ring.span(f"s{i}", "t"):
            pass
    assert ring.dropped == 2
    assert [s["name"] for s in ring.slices()] == ["s2", "s3", "s4", "s5"]
    ring.clear()
    assert ring.dropped == 0 and ring.slices() == []


def test_span_off_records_nothing_and_is_falsy(monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_SPANS", "0")
    with tracing.span("off", "t", x=1) as sp:
        sp.set(y=2)
    tracing.span("off2", "t").begin().end(z=3)
    assert not sp
    assert tracing.SPANS.slices() == []


# -- the tree -----------------------------------------------------------------
def test_every_engine_span_lies_inside_its_parent(toy):
    eng = make_engine(toy, max_batch=2, prefix_cache=_DictPrefixCache())
    outs = [None] * len(PROMPTS)

    def client(i):
        with tracing.span("replica.stream", "engine"):
            outs[i] = list(eng.stream_ids(PROMPTS[i], GEN))

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(len(PROMPTS))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert all(len(o) == GEN.max_new_tokens for o in outs)
    spans = engine_spans()
    by_id = {s["args"]["id"]: s for s in spans}
    assert {s["name"] for s in spans} == {*ANNOTATED, "engine.request",
                                          "replica.stream"}
    parents = {
        "engine.step": {"replica.stream"}, "engine.request": {"replica.stream"},
        "engine.admit": {"engine.step"}, "engine.decode": {"engine.step"},
        "engine.readback": {"engine.step"}, "engine.prefill": {"engine.admit"},
        "engine.first_token": {"engine.admit"},
        "engine.prefix_insert": {"engine.admit"},
    }
    for s in spans:
        if s["name"] == "replica.stream":
            assert "parent" not in s["args"]
            continue
        parent = by_id[s["args"]["parent"]]
        assert parent["name"] in parents[s["name"]], (s["name"], parent["name"])
        assert parent["ts"] <= s["ts"] + 0.5
        assert s["ts"] + s["dur"] <= parent["ts"] + parent["dur"] + 0.5
        if s["name"] != "engine.request":  # that one may end on any thread
            assert s["tid"] == parent["tid"]


def run_to_end(toy, end):
    """One request brought to the named end; returns (engine, tokens)."""
    eng = make_engine(toy)
    gen = GenerationConfig(max_new_tokens=12, temperature=0.0)
    if end == "finished":
        return eng, len(eng.generate_ids([PROMPTS[0]], gen)[0])
    if end == "cancelled":
        stream = eng.stream_ids(PROMPTS[0], gen)
        got = [next(stream) for _ in range(3)]
        stream.close()
        return eng, len(got)
    rid = eng.submit(PROMPTS[0], gen)
    for _ in range(4):
        eng.step()
    eng._force_evict_active()
    return eng, len(eng.results[rid])


@pytest.mark.parametrize("end", ["finished", "cancelled", "evicted"])
def test_one_request_span_with_its_end(toy, end):
    eng, tokens = run_to_end(toy, end)
    (req,) = engine_spans("engine.request")
    a = req["args"]
    assert a["end"] == end and a["rid"] == 0
    assert a["prompt_tokens"] == len(PROMPTS[0])
    # a cancelled stream had read 3 tokens; the engine may be one step ahead
    assert a["new_tokens"] >= tokens and a["new_tokens"] <= 12
    if end != "cancelled":
        assert a["new_tokens"] == tokens
    assert a["queue_wait_ms"] >= 0 and a["prefill_ms"] > 0
    assert req["dur"] * 1e-3 >= a["queue_wait_ms"] + a["prefill_ms"] - 1e-3
    assert eng._live == {} and eng.pool.free_pages == eng.pool.usable_pages


def test_a_request_cancelled_in_the_queue_ends_with_no_tokens(toy):
    eng = make_engine(toy, max_batch=1)
    first = eng.submit(PROMPTS[0], GEN)
    queued = eng.submit(PROMPTS[1], GEN)
    eng.step()
    eng._cancel(queued)
    while first not in eng.results:
        eng.step()
    ends = {s["args"]["rid"]: s["args"] for s in engine_spans("engine.request")}
    assert ends[queued]["end"] == "cancelled"
    assert ends[queued]["new_tokens"] == 0 and ends[queued]["prefill_ms"] == 0
    assert ends[first]["end"] == "finished"


# -- the counts ---------------------------------------------------------------
def test_decode_spans_count_every_token_and_page(toy):
    eng = make_engine(toy, max_batch=2)
    outs = eng.generate_ids(PROMPTS, GEN)
    decodes = [s["args"] for s in engine_spans("engine.decode")]
    produced = sum(len(o) for o in outs)
    assert sum(d["live"] for d in decodes) == produced - len(PROMPTS)
    for d in decodes:
        assert 1 <= d["live"] <= 2
        assert d["pages_written"] <= d["pages_reserved"] <= eng.pool.usable_pages
        assert d["ctx"] <= d["pages_written"] * PAGE
    assert decodes[0]["queued"] == 2 and decodes[-1]["queued"] == 0
    prefills = [s["args"] for s in engine_spans("engine.prefill")]
    assert sorted(p["t_pad"] for p in prefills) == [8, 8, 8, 24]
    assert all(p["hit_tokens"] == 0 for p in prefills)
    admits = [s["args"] for s in engine_spans("engine.admit")]
    assert sum(a["admitted"] for a in admits) == len(PROMPTS)
    assert all(a["pool_stall"] == 0 for a in admits)
    st = eng.stats()
    assert st["admit_pool_stalls"] == 0 and st["lock_acquires"] == 0
    waits = sum(
        s["args"]["queue_wait_ms"] for s in engine_spans("engine.request"))
    assert st["queue_wait_s"] == pytest.approx(waits * 1e-3, abs=1e-6)


def test_a_chunked_prompts_prefill_span_counts_the_keys_its_chunks_walk(
    toy, monkeypatch
):
    """One prefill program takes 16 tokens at the toy's 4 heads, the rest
    of a prompt goes in chunks of 8 that walk the slot's table of 12 pages
    in blocks of 2 pages: the span carries the keys a layer scored, whole
    blocks as far as each chunk's keys go, beside the keys of the whole
    table as many times; an unchunked prompt's carries zeros."""
    from ray_tpu.llm import continuous

    monkeypatch.setattr(continuous, "PREFILL_SCORES_BYTES", 4 * 4 * 16 * 16)
    monkeypatch.setattr(continuous, "ATTN_BLOCK_BYTES", 4 * 8 * 4 * 16)
    eng = make_engine(toy, max_batch=2)
    assert (eng.max_prefill_tokens, eng.prefill_chunk) == (16, 8)
    assert eng.max_pages_per_seq == 12
    long, short = list(range(1, 46)), [7, 8, 9]
    eng.generate_ids([long, short], GEN)
    chunked, whole = (s["args"] for s in engine_spans("engine.prefill"))
    # 45 tokens: 48 padded, 16 in the prefill program, chunks at 16, 24,
    # 32 and 40, whose keys end at 24, 32, 40 and 48: 2, 2, 3, 3 blocks of 16
    assert (chunked["t_pad"], chunked["head"], chunked["chunks"]) == (48, 16, 5)
    assert chunked["attn_keys_walked"] == (2 + 2 + 3 + 3) * 16
    assert chunked["attn_keys_table"] == 4 * 12 * PAGE
    assert chunked["attn_keys_walked"] <= chunked["attn_keys_table"]
    assert (whole["chunks"], whole["head"]) == (1, 8)
    assert (whole["attn_keys_walked"], whole["attn_keys_table"]) == (0, 0)


def test_a_pool_too_small_for_two_requests_stalls_the_admit(toy):
    # each request reserves ceil((3 + 16) / 8) = 3 pages; 5 are usable
    eng = make_engine(toy, n_pages=6)
    gen = GenerationConfig(max_new_tokens=16, temperature=0.0)
    eng.generate_ids([[5, 6, 7], [5, 6, 8]], gen)
    admits = [s["args"] for s in engine_spans("engine.admit")]
    stalls = [a for a in admits if a["pool_stall"]]
    # the first admits one request and stalls on the second; then one
    # stalled admit a step until the first answer frees its pages
    assert (stalls[0]["admitted"], stalls[0]["live"]) == (1, 0)
    assert len(stalls) > 2
    assert all(a["admitted"] == 0 and a["live"] == 1 for a in stalls[1:])
    assert sum(a["admitted"] for a in admits) == 2
    assert eng.stats()["admit_pool_stalls"] == len(stalls)
    assert all(d["live"] == 1 for d in
               (s["args"] for s in engine_spans("engine.decode")))


def test_stream_threads_lock_wait_lands_on_the_request_and_in_stats(toy):
    eng = make_engine(toy, max_batch=2)
    outs = []
    gen = GenerationConfig(max_new_tokens=48, temperature=0.0)
    # the lock is unfair: the other threads' steps could bring a request to
    # its end before its own thread has had a turn, and its span then
    # closes with no acquisition (one run in twenty here, however long the
    # answers). So no step runs while a live request's thread has yet to
    # take the lock: a turn that finds one only lets go again
    step = eng.step

    def step_once_every_stream_has_had_a_turn():
        if all(r.lock_acquires for r in eng._live.values()):
            return step()
        return []

    eng.step = step_once_every_stream_has_had_a_turn

    def client(p):
        outs.append(list(eng.stream_ids(p, gen)))

    threads = [threading.Thread(target=client, args=(p,)) for p in PROMPTS]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert len(outs) == len(PROMPTS)
    reqs = [s["args"] for s in engine_spans("engine.request")]
    assert len(reqs) == len(PROMPTS)
    st = eng.stats()
    for a in reqs:
        assert a["lock_acquires"] >= 1
        assert 0 <= a["lock_wait_max_ms"] <= a["lock_wait_ms"] + 1e-9
    # the acquisition on which a stream finds its answer finished comes
    # after the request's end: stats() has it, the span has not
    assert st["lock_acquires"] >= sum(a["lock_acquires"] for a in reqs)
    assert st["lock_wait_s"] * 1e3 >= sum(a["lock_wait_ms"] for a in reqs) - 1e-6


def test_a_prefix_hit_is_on_the_prefill_span():
    from ray_tpu.native import NativeObjectStore
    from ray_tpu.serve.prefix_cache import SharedPrefixCache

    cfg = tfm.ModelConfig(
        vocab_size=64, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=96, dtype=jnp.float32,
    )
    toy = (cfg, tfm.init_params(cfg, jax.random.PRNGKey(2)))
    path = os.path.join(
        tempfile.gettempdir(), f"engine_tracing_{os.getpid()}.shm")
    store = NativeObjectStore(path=path, capacity=32 << 20)
    try:
        cache = SharedPrefixCache(store, page_size=PAGE, model_sig="trc")
        prompt = [3, 5, 7, 9, 11, 2, 4, 6, 8, 1, 3, 5, 7, 2, 9, 4, 6, 1]
        a = make_engine(toy, prefix_cache=cache)
        want = a.generate_ids([prompt], GEN)
        (insert,) = engine_spans("engine.prefix_insert")
        assert insert["args"]["pages"] == 2
        tracing.SPANS.clear()
        b = make_engine(toy, prefix_cache=cache)
        assert b.generate_ids([prompt], GEN) == want
        (prefill,) = engine_spans("engine.prefill")
        assert prefill["args"] | {"id": 0, "parent": 0} == {
            "t_pad": 8, "true_len": 2, "hit_tokens": 16, "chunks": 1,
            "id": 0, "parent": 0}
        (insert,) = engine_spans("engine.prefix_insert")
        assert insert["args"]["pages"] == 0  # the hit covered them
    finally:
        store.close(unlink=True)


# -- the waits on a request's way in and a token's way out ----------------------
def test_submits_wait_for_the_lock_is_on_the_request(toy):
    eng = make_engine(toy)
    reached, rids = threading.Event(), []

    def submitter():
        reached.set()
        rids.append(eng.submit(PROMPTS[0], GEN))

    held = 0.1
    with eng._lock:
        t = threading.Thread(target=submitter)
        t.start()
        assert reached.wait(30)
        time.sleep(held)
    t.join(30)
    assert not t.is_alive() and rids == [0]
    free = eng.submit(PROMPTS[1], GEN)
    while not {0, free} <= set(eng.results):
        eng.step()
    waits = {s["args"]["rid"]: s["args"]["submit_lock_wait_ms"]
             for s in engine_spans("engine.request")}
    assert waits[0] >= held * 1e3 * 0.5
    assert 0 <= waits[free] < held * 1e3 * 0.5


def test_a_freed_slots_next_request_names_it_and_the_legs_lie_in_order(toy):
    eng = make_engine(toy, max_batch=1)

    def replica_call(prompt):
        with tracing.span("replica.stream", "engine"):
            return list(eng.stream_ids(prompt, GEN))

    assert len(replica_call(PROMPTS[0])) == GEN.max_new_tokens
    assert len(replica_call(PROMPTS[1])) == GEN.max_new_tokens
    first, second = sorted(
        engine_spans("engine.request"), key=lambda s: s["args"]["rid"])
    call_1, call_2 = sorted(engine_spans("replica.stream"), key=lambda s: s["ts"])
    assert first["args"]["slot"] == second["args"]["slot"] == 0
    assert second["args"]["parent"] == call_2["args"]["id"]
    # the slot falls free; its thread sees the last token and closes; the
    # thread takes up the next call; that call submits; it is admitted
    legs = [
        first["ts"] + first["dur"],
        call_1["ts"] + call_1["dur"],
        call_2["ts"],
        second["ts"],
        second["ts"] + second["args"]["queue_wait_ms"] * 1e3,
    ]
    assert legs == sorted(legs), legs
    assert all(d["args"]["slots"] == 1 for d in engine_spans("engine.decode"))


def test_a_tokens_wait_for_its_consumer_is_on_the_request(toy):
    eng = make_engine(toy)
    nap = 0.05
    t0 = time.perf_counter()
    stream = eng.stream_ids(PROMPTS[0], GEN)
    got = [next(stream)]
    time.sleep(nap)  # the second token is on the host and waits for this
    got += list(stream)
    life_ms = (time.perf_counter() - t0) * 1e3
    assert len(got) == GEN.max_new_tokens
    (req,) = engine_spans("engine.request")
    a = req["args"]
    assert 0 <= a["pickup_lag_max_ms"] <= a["pickup_lag_ms"] <= life_ms
    assert a["pickup_lag_max_ms"] >= nap * 1e3
    # a request whose answer nobody streams has no consumer to wait for
    tracing.SPANS.clear()
    eng.generate_ids([PROMPTS[1]], GEN)
    (req,) = engine_spans("engine.request")
    assert "pickup_lag_ms" not in req["args"]


# -- the admission layer --------------------------------------------------------
def admits():
    return [s for s in tracing.SPANS.slices(cat="serve")
            if s["name"] == "serve.admit"]


def test_the_admission_span_counts_in_flight_up_to_the_bound():
    from ray_tpu.serve.admission import AdmissionController, Overloaded

    ctl = AdmissionController(max_inflight=3, wait_timeout_s=0.05)
    tickets = [ctl.admit("a") for _ in range(3)]
    with pytest.raises(Overloaded):
        ctl.admit("b")
    threading.Timer(0.05, tickets[0].done).start()
    ctl.admit("c", timeout_s=30).done()
    args = [s["args"] for s in admits()]
    assert [a["outcome"] for a in args] == [
        "fast", "fast", "fast", "shed:timeout", "waited"]
    assert [a["inflight"] for a in args] == [1, 2, 3, 3, 3]
    assert [a["tenant"] for a in args] == ["a", "a", "a", "b", "c"]
    assert all(a["waiting"] == 0 and "id" in a for a in args)
    shed, waited = admits()[3:]
    assert shed["dur"] >= 0.05e6 and waited["dur"] >= 0.04e6
    assert admits()[0]["dur"] < 0.04e6


def test_a_shed_request_leaves_its_admission_and_no_stream():
    from types import SimpleNamespace

    from ray_tpu.serve.admission import AdmissionController, Overloaded
    from ray_tpu.serve.router import ServeRouter

    router = ServeRouter(
        SimpleNamespace(dep=SimpleNamespace(name="full")),
        admission=AdmissionController(max_inflight=1, wait_timeout_s=0.02),
    )
    held = router.admission.admit()
    for call in (router.stream, router.submit):
        with pytest.raises(Overloaded):
            call({"prompt": "no room"})
    held.done()
    spans = tracing.SPANS.slices(cat="serve")
    assert [s["name"] for s in spans] == ["serve.admit"] * 3
    assert [s["args"]["outcome"] for s in spans] == [
        "fast", "shed:timeout", "shed:timeout"]
    # each shed request under a trace of its own, as an admitted one is
    assert len({s["args"]["trace_id"] for s in spans[1:]}) == 2


# -- router to engine: one trace ------------------------------------------------
@pytest.mark.parametrize("kind", ["sync", "async"])
def test_an_actor_method_runs_in_its_submitters_trace(kind):
    """The in-process runtime installs the submitter's trace around an
    actor method as it does around a task."""
    import ray_tpu

    class Sync:
        def trace(self):
            return tracing.current()

    class Async:
        async def trace(self):
            return tracing.current()

    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 2})
    try:
        actor = ray_tpu.remote(Sync if kind == "sync" else Async).remote()
        with tracing.installed(tracing.child_context("driver-span")) as mine:
            ref = actor.trace.remote()
        seen = ray_tpu.get(ref, timeout=60)
        assert seen["trace_id"] == mine["trace_id"]
        assert seen["parent_id"] == "driver-span"
        assert tracing.current() is None
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("how", ["stream", "unary"])
def test_a_requests_spans_share_one_trace_id_from_router_to_engine(how):
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.llm.serving import build_llm_deployment

    cfg = tfm.ModelConfig(
        vocab_size=300, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=96, dtype=jnp.float32,
    )
    ray_tpu.init(num_nodes=1, resources_per_node={"CPU": 4})
    try:
        serve.run(build_llm_deployment(
            cfg, name="traced", max_batch=2,
            page_size=PAGE, n_pages=32, prefix_cache=False,
        ))
        router = serve.get_router("traced")
        payload = {"prompt": "trace me", "max_new_tokens": 5}
        t_a = time.perf_counter()
        if how == "stream":
            assert len(list(router.stream(payload))) == 5
            names = {"serve.admit", "serve.stream", "replica.stream",
                     "engine.request", "engine.step", "engine.decode",
                     "engine.prefill"}
        else:
            assert router.call(payload, timeout=120)["generated_text"]
            names = {"serve.admit", "serve.unary", "engine.request",
                     "engine.step", "engine.decode", "engine.prefill"}
        deadline = time.time() + 10
        while time.time() < deadline:  # the router's span lands at _finish
            spans = [s for s in tracing.SPANS.slices() if s["name"] in names]
            if names <= {s["name"] for s in spans}:
                break
            time.sleep(0.05)
        assert names <= {s["name"] for s in spans}
        t_b = time.perf_counter()
        ids = {s["args"].get("trace_id") for s in spans}
        assert len(ids) == 1 and None not in ids, ids
        by = {s["name"]: s for s in spans}
        # the router's span is a span: an id, and the one clock of the rest
        routed = by["serve.stream" if how == "stream" else "serve.unary"]
        assert "id" in routed["args"] and routed["args"]["code"] == "200"
        assert routed["pid"] == "serve:traced"
        lo = (tracing.PERF_EPOCH_S + t_a) * 1e6
        hi = (tracing.PERF_EPOCH_S + t_b) * 1e6
        assert lo <= by["serve.admit"]["ts"] <= routed["ts"]
        assert routed["ts"] + routed["dur"] <= hi
        assert by["serve.admit"]["args"]["outcome"] == "fast"
        assert routed["ts"] <= by["engine.request"]["ts"]
        if how == "stream":
            (stream,) = [s for s in spans if s["name"] == "replica.stream"]
            assert stream["args"]["tokens"] == 5 and stream["args"]["skip"] == 0
            assert stream["pid"] == "serve:traced"
            request = by["engine.request"]["args"]
            assert request["parent"] == stream["args"]["id"]
            assert request["rid"] == stream["args"]["rid"]
            assert request["slot"] in (0, 1)
            a = routed["args"]
            assert a["delivered"] == 5 and a["failovers"] == 0
            assert a["transport"] in ("shm", "push", "relay")
            assert 0 < a["dispatch_ms"] <= routed["dur"] * 1e-3
            # the replica may take the call up while the dispatch still
            # does its books, not before the dispatch began
            assert routed["ts"] <= stream["ts"]
            assert a["ttft_ms"] > 0 and a["probe_ms"] >= 0
            assert a["probes"] >= a["read_timeouts"] >= 0
            stats = ray_tpu.get(
                serve.get_deployment_handle("traced").serve_stats.remote(),
                timeout=30,
            )
            assert {"admit_pool_stalls", "lock_wait_s", "lock_acquires",
                    "queue_wait_s"} <= set(stats)
            assert stats["lock_acquires"] >= 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- the profiler's copy ------------------------------------------------------
def test_the_profilers_host_plane_holds_each_engine_span(toy, tmp_path):
    from jax.profiler import ProfileData

    eng = make_engine(toy, max_batch=2, prefix_cache=_DictPrefixCache())
    eng.generate_ids(PROMPTS[:2], GEN)  # compiled before the capture
    tracing.SPANS.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.generate_ids([[4, 4, 2], [9, 1, 7]], GEN)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns))
    assert set(events) == set(ANNOTATED)
    for name in ANNOTATED:
        ring = sorted((s["ts"], s["dur"]) for s in engine_spans(name))
        traced = sorted(events[name])
        assert len(ring) == len(traced), name
        for (_, dur_us), (_, dur_ns) in zip(ring, traced):
            assert abs(dur_us * 1e3 - dur_ns) < 1e6, (name, dur_us, dur_ns)
    # the request's life is no annotation: it is in the ring alone
    assert len(engine_spans("engine.request")) == 2


# -- recording changes nothing --------------------------------------------------
def test_tokens_are_the_same_with_spans_off_and_nothing_is_recorded(
        toy, monkeypatch):
    on = make_engine(toy, max_batch=2).generate_ids(PROMPTS, GEN)
    assert engine_spans("engine.decode")
    tracing.SPANS.clear()
    monkeypatch.setenv("RAY_TPU_TRACE_SPANS", "0")
    eng = make_engine(toy, max_batch=2)
    off = eng.generate_ids(PROMPTS, GEN)
    streamed = list(eng.stream_ids(PROMPTS[0], GEN))
    assert off == on and streamed == on[0]
    from ray_tpu.serve.admission import AdmissionController

    AdmissionController().admit("quiet").done()
    assert tracing.SPANS.slices() == [] and tracing.SPANS.dropped == 0
    # the running totals are the operator's and do not hang on the switch
    assert eng.stats()["lock_acquires"] >= 1


def test_a_second_traced_batch_compiles_nothing(toy):
    import jax.monitoring as monitoring

    eng = make_engine(toy, max_batch=2)
    eng.generate_ids(PROMPTS, GEN)
    compiles = []

    def listener(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        n = len(engine_spans())
        eng.generate_ids(PROMPTS, GEN)
        assert len(engine_spans()) > n
        assert compiles == []
    finally:
        monitoring.unregister_event_duration_listener(listener)
