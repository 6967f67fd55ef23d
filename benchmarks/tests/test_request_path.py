"""The readers of a request's way from the router to its slot and back
(``harness/request_path.py``): each on a hand-made ring of three requests
over two slots with answers counted by hand, silent on a ring whose router
spans carry no ``id``, found by name from a copy of the toy
``BENCHMARK.json`` with their entries added and read in a toy run."""
import json
import os
import types

import pytest

import run as bench_run
from harness import peaks, request_path, spec

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy", "BENCHMARK.json")
CELL = "toy-gqa.toy"
NEW = [
    "router_inflight_max", "replica_mailbox_wait_p90_ms",
    "stream_probes_per_s", "submit_lock_wait_p90_ms",
    "slots_idle_with_backlog_pct", "slot_handover_mean_ms",
    "handover_last_token_mean_ms", "handover_next_call_mean_ms",
    "token_pickup_lag_mean_ms", "span_ring_dropped",
]
RUN = types.SimpleNamespace(t_open=0.0, t_close=10.0, capture=None)


def reader(name):
    return spec.load_reader(name, os.path.join(HERE, "toy"))


# -- a ring by hand ---------------------------------------------------------------

_ids = iter(range(1, 1000))


def span(name, start_s, end_s, cat="engine", tid=0, **args):
    return {
        "name": name, "cat": cat, "ph": "X", "ts": start_s * 1e6,
        "dur": (end_s - start_s) * 1e6, "pid": "serve:toy", "tid": tid,
        "args": dict(args, id=next(_ids)),
    }


def routed(trace, *, admit, inflight, stream, dispatch_ms, probes, tid,
           replica, request, lock_ms, queue_ms, slot, end, tokens, lag_ms):
    """The four spans of one request; each of ``stream``, ``replica`` and
    ``request`` is ``(start, end)`` in seconds."""
    call = span("replica.stream", *replica, tid=tid, trace_id=trace, rid=slot)
    return [
        span("serve.admit", admit, admit, "serve", trace_id=trace,
             tenant="default", outcome="fast", waiting=0, inflight=inflight),
        span("serve.stream", *stream, "serve", trace_id=trace, code="200",
             dispatch_ms=dispatch_ms, transport="shm", probes=probes,
             read_timeouts=probes, probe_ms=0.1 * probes),
        call,
        span("engine.request", *request, trace_id=trace,
             parent=call["args"]["id"], submit_lock_wait_ms=lock_ms,
             queue_wait_ms=queue_ms, slot=slot, end=end, new_tokens=tokens,
             pickup_lag_ms=lag_ms, pickup_lag_max_ms=lag_ms),
    ]


@pytest.fixture
def ring(monkeypatch):
    """Window [0, 10) s, two slots, two replica threads. A has slot 0 from
    1.5 to 5.0; B slot 1 from 2.5 until it is evicted after the close; C is
    dispatched at 4.5, lies in the mailbox until thread 1 is done with A,
    and is admitted into slot 0 at 6.0; a fourth request is shed."""
    from ray_tpu.util import tracing

    monkeypatch.setattr(tracing, "PERF_EPOCH_S", 0.0)
    tracing.SPANS.clear()
    spans = [
        span("engine.decode", 1.6, 1.61, live=1, slots=2),
        *routed("a", admit=0.9, inflight=1, stream=(1.0, 5.3), dispatch_ms=100,
                probes=2, tid=1, replica=(1.2, 5.2), request=(1.3, 5.0),
                lock_ms=50, queue_ms=200, slot=0, end="finished", tokens=10,
                lag_ms=30),
        *routed("b", admit=1.9, inflight=2, stream=(2.0, 12.2), dispatch_ms=0,
                probes=51, tid=2, replica=(2.0, 12.1), request=(2.1, 12.0),
                lock_ms=100, queue_ms=400, slot=1, end="evicted", tokens=20,
                lag_ms=70),
        *routed("c", admit=3.9, inflight=3, stream=(4.0, 9.6), dispatch_ms=500,
                probes=0, tid=1, replica=(5.6, 9.5), request=(5.7, 9.0),
                lock_ms=20, queue_ms=300, slot=0, end="finished", tokens=10,
                lag_ms=20),
        span("serve.admit", 7.0, 9.0, "serve", trace_id="d", tenant="default",
             outcome="shed:timeout", waiting=0, inflight=3),
    ]
    for s in spans:
        tracing.SPANS.append(s)
    yield tracing.SPANS
    tracing.SPANS.clear()


BY_HAND = {
    "router_inflight_max": 3,
    # waits of 100, 0 and 1,100 ms: the 90th percentile lies 0.8 of the way
    # from the second to the third
    "replica_mailbox_wait_p90_ms": 900.0,
    # A's 2 probes; of B's 51 over 10.2 s the 8.0 s inside the window: 40
    "stream_probes_per_s": 4.2,
    "submit_lock_wait_p90_ms": 90.0,  # of 20, 50, 100
    # a request waits beside an empty slot in [1.1, 1.5), [2.0, 2.5) and
    # [5.0, 6.0) (in [4.5, 5.0) both slots are taken): 1.9 of 2 x 10 slot-s
    "slots_idle_with_backlog_pct": 9.5,
    # slot 0 from A's end at 5.0 to C's admission at 6.0, C waiting and
    # slot 1 taken; when C left it at 9.0 nobody waited; slot 1 is B's
    "slot_handover_mean_ms": 1000.0,
    "handover_last_token_mean_ms": 350.0,   # A 200, C 500; B did not finish
    "handover_next_call_mean_ms": 400.0,    # thread 1: 5.2 -> 5.6, C waiting
    "token_pickup_lag_mean_ms": 3.0,        # 120 ms over 40 tokens
    "span_ring_dropped": 0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_ring_of_three_requests_over_two_slots(ring, name):
    assert reader(name)(RUN) == pytest.approx(BY_HAND[name], abs=1e-6)


def test_the_four_spans_of_a_request_are_joined_and_its_legs_add_up(ring):
    rp = request_path.load(RUN)
    assert rp.slots == 2 and rp.window_s == 10.0
    a, b, c = sorted(rp.requests, key=lambda r: r.stream["ts"])
    assert [r.admit["args"]["inflight"] for r in (a, b, c)] == [1, 2, 3]
    assert c.request["args"]["parent"] == c.replica["args"]["id"]
    assert (c.dispatched, c.taken_up, c.submitted, c.admitted) == (
        4.5e6, 5.6e6, 5.7e6, 6.0e6)
    assert c.backlog == (4.5e6, 6.0e6)
    assert rp.legs() == pytest.approx({
        "slot_handover": 1000.0, "gaps": 1,
        "idle_over_admissions": 1900.0 / 3, "admissions": 3,
        "last_token": 350.0, "next_call": 400.0, "submit": 100.0,
        "queue_wait": 300.0, "sum_of_legs": 1150.0,
    })
    # the one freed slot's own chain, from A's end at 5.0 to C's admission
    # at 6.0: A's thread sees its last token, takes up C, C has its turn in
    # submit(), C is admitted
    assert rp.handover_ms() == [(c.admitted - 5.0e6) * 1e-3]
    assert 200.0 + 400.0 + 100.0 + 300.0 == (c.admitted - 5.0e6) * 1e-3


def test_a_request_that_never_had_a_slot_is_backlog_until_it_gives_up(ring):
    """E is dispatched at 9.0, as C leaves slot 0, and is cancelled in the
    engine's queue at 9.2; an unrouted request (no ``serve.stream``: no
    backlog) takes slot 0 at 9.8. Slot 0 stood empty beside a waiting
    request for 0.2 s more, not for 0.8; its gap, at whose start E waited,
    is the 0.8 s to its next tenant, and the gap that tenant leaves at
    9.95, with nobody waiting, is none."""
    call = span("replica.stream", 9.0, 9.3, tid=3, trace_id="e")
    for s in (
        span("serve.stream", 9.0, 9.3, "serve", trace_id="e", dispatch_ms=0),
        call,
        span("engine.request", 9.05, 9.2, trace_id="e",
             parent=call["args"]["id"], queue_wait_ms=150, end="cancelled"),
        span("engine.request", 9.7, 9.95, queue_wait_ms=100, slot=0,
             end="finished", new_tokens=1),
    ):
        ring.append(s)
    rp = request_path.load(RUN)
    assert len(rp.requests) == 4 and len(rp.tenancies) == 4
    assert [rp.backlog.at(t) for t in (8.9e6, 9.0e6, 9.3e6)] == [0, 1, 0]
    assert rp.idle_with_backlog() == pytest.approx(2.1e6)
    assert reader("slots_idle_with_backlog_pct")(RUN) == pytest.approx(10.5)
    assert rp.handover_ms() == pytest.approx([1000.0, 800.0])
    assert reader("slot_handover_mean_ms")(RUN) == pytest.approx(900.0)


def test_a_gap_that_an_edge_of_the_window_cuts_counts_for_its_part_inside(
        monkeypatch):
    """Two slots; slot 1 is taken all along. Slot 0's tenant of the ramp-in
    leaves it at -1.0 with P waiting, which has it from 2.0 to 7.0: 2.0 s
    of that gap lie in the window. Q, dispatched at 6.0, is admitted into
    it at 11.0, after the close at 10.0: 3.0 s of that one."""
    from ray_tpu.util import tracing

    monkeypatch.setattr(tracing, "PERF_EPOCH_S", 0.0)
    tracing.SPANS.clear()
    common = dict(inflight=1, probes=0, lock_ms=0, end="finished", tokens=5,
                  lag_ms=0, dispatch_ms=0)
    spans = [
        span("engine.decode", 1.0, 1.01, live=1, slots=2),
        span("engine.request", -3.0, -1.0, queue_wait_ms=0, slot=0,
             end="finished", new_tokens=5),
        span("engine.request", -3.0, 20.0, queue_wait_ms=0, slot=1,
             end="finished", new_tokens=5),
        *routed("p", admit=-1.5, stream=(-1.5, 7.2), tid=1,
                replica=(1.8, 7.1), request=(1.9, 7.0), queue_ms=100, slot=0,
                **common),
        *routed("q", admit=6.0, stream=(6.0, 12.2), tid=1,
                replica=(7.3, 12.1), request=(7.4, 12.0), queue_ms=3600,
                slot=0, **common),
    ]
    for s in spans:
        tracing.SPANS.append(s)
    try:
        rp = request_path.load(RUN)
        assert rp.handover_ms() == pytest.approx([2000.0, 3000.0])
        assert reader("slot_handover_mean_ms")(RUN) == pytest.approx(2500.0)
        # the same five slot-seconds over the one admission inside
        assert rp.legs()["idle_over_admissions"] == pytest.approx(5000.0)
        # a third slot that nobody ever took: the one waiting request could
        # have had it, so neither gap is a hand-over
        tracing.SPANS.append(span("engine.decode", 1.1, 1.11, live=1, slots=3))
        assert request_path.load(RUN).handover_ms() == []
        assert reader("slot_handover_mean_ms")(RUN) == 0.0
    finally:
        tracing.SPANS.clear()


def test_count_and_pieces():
    count = request_path.Count([(1.0, 3.0), (2.0, 5.0), (5.0, 6.0), (7.0, 7.0)])
    assert [count.at(t) for t in (0.5, 1.0, 2.5, 3.0, 5.0, 6.0)] == [
        0, 1, 2, 1, 1, 0]
    other = request_path.Count([(2.0, 4.0)])
    assert list(request_path.pieces(1.5, 4.5, count, other)) == [
        (0.5, 1, 0), (1.0, 2, 1), (1.0, 1, 1), (0.5, 1, 0)]


def test_one_request_alone(monkeypatch):
    """No call waited for a thread: that leg reads 0. No ``engine.decode``
    span states the slots: no share and no hand-over."""
    from ray_tpu.util import tracing

    monkeypatch.setattr(tracing, "PERF_EPOCH_S", 0.0)
    tracing.SPANS.clear()
    for s in routed("a", admit=0.9, inflight=1, stream=(1.0, 5.3),
                    dispatch_ms=100, probes=0, tid=1, replica=(1.2, 5.2),
                    request=(1.3, 5.0), lock_ms=5, queue_ms=2, slot=0,
                    end="finished", tokens=10, lag_ms=3):
        tracing.SPANS.append(s)
    try:
        assert reader("handover_next_call_mean_ms")(RUN) == 0.0
        assert reader("handover_last_token_mean_ms")(RUN) == pytest.approx(200.0)
        assert reader("slots_idle_with_backlog_pct")(RUN) is None
        assert reader("slot_handover_mean_ms")(RUN) is None
        # no slot fell free beside a request that waited for it
        tracing.SPANS.append(span("engine.decode", 1.6, 1.61, live=1, slots=2))
        assert reader("slot_handover_mean_ms")(RUN) == 0.0
        assert request_path.load(RUN).legs()["idle_over_admissions"] == (
            pytest.approx(202.0))  # it waited 202 ms beside two empty slots
    finally:
        tracing.SPANS.clear()


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_on_a_ring_whose_router_spans_have_no_id(name):
    """The parent's ring: ``serve_stream`` recorded on the epoch clock with
    no ``id``, engine spans as they were, and no ``dropped`` on the ring."""
    from ray_tpu.util import tracing

    tracing.SPANS.clear()
    tracing.SPANS.record("serve_stream", "serve", 0.0, 1.0, trace_id="a")
    tracing.SPANS.append(span("engine.request", 0.1, 0.9, trace_id="a",
                              queue_wait_ms=1.0, new_tokens=3))
    try:
        assert request_path.load(RUN) is None
        if name == "span_ring_dropped":  # the ring's own count, not a span's
            assert reader(name)(types.SimpleNamespace()) == 0
            bare = types.SimpleNamespace(SPANS=object())
            assert getattr(bare.SPANS, "dropped", None) is None
        else:
            assert reader(name)(RUN) is None
    finally:
        tracing.SPANS.clear()


# -- the entries --------------------------------------------------------------------


def test_every_new_entry_has_a_file_and_no_list_of_cells():
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = by_name[name]
        assert "workloads" not in m and m["moves"] == "tokens_per_s"
        assert (m["better"], m["source"]) == ("lower", "program_counter")
        assert m["layer"] in layers  # a layer the benchmark already names
        assert callable(spec.load_reader(name, spec.BENCH_DIR))
        assert not os.path.exists(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".json"))
    # so every cell reports them: each reports tokens_per_s
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert set(NEW) <= {m["name"] for m in cell.per_layer}, w["name"]


# -- a toy run ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(TOY) as f:
        bench = json.load(f)
    bench["per_layer"] += [real[name] for name in NEW]
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell(CELL, str(path))
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    res = bench_run.run_cell(
        cell, 62, 3.0, True, jax.devices()[:1], peaks.PEAKS["TPU v5 lite"])
    assert res["correct"] is True
    return res["metrics"]


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_a_number_in_the_toy_run(traced, name):
    assert name in traced, sorted(traced)
    assert traced[name]["value"] >= 0
    if name == "span_ring_dropped":
        assert traced[name]["value"] == 0
    if name == "slots_idle_with_backlog_pct":
        assert traced[name]["value"] <= 100.0
