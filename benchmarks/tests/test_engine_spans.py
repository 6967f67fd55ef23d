"""The readers of the program's own spans (``engine.*``): found by name from
a copy of the toy ``BENCHMARK.json`` with their entries added, read in a toy
run, silent on a ring that holds no engine span."""
import json
import os
import types

import pytest

import run as bench_run
from harness import engine_spans, peaks, spec

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy", "BENCHMARK.json")
CELL = "toy-gqa.toy"
NEW = [
    "engine_lock_wait_mean_ms", "engine_lock_wait_mean_ms.sat",
    "queue_wait_p90_ms", "engine_step_host_ms", "engine_step_host_ms.sat",
    "decode_stalled_by_admit_pct", "kv_pages_written_pct",
    "admit_pool_stalls", "engine_decode_batch_mean",
]


@pytest.fixture(scope="module")
def toy_with_entries(tmp_path_factory):
    """The toy benchmark plus the real file's entries of the new metrics,
    each listing the toy cell: files that are there, entries alone added."""
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(TOY) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name in NEW:
        assert real[name]["moves"] in e2e
        bench["per_layer"].append(dict(real[name], workloads=[CELL]))
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


@pytest.fixture(scope="module")
def traced(toy_with_entries):
    import jax

    cell = spec.load_cell(CELL, toy_with_entries)
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    res = bench_run.run_cell(
        cell, 61, 3.0, True, jax.devices()[:1], peaks.PEAKS["TPU v5 lite"])
    assert res["correct"] is True
    return res["metrics"]


@pytest.mark.parametrize("name", NEW)
def test_reader_is_found_by_name_and_reads_the_toy_run(traced, name):
    base = os.path.join(HERE, "toy")
    assert callable(spec.load_reader(name, base))
    assert name in traced, sorted(traced)
    assert traced[name]["value"] >= 0


def test_engine_batch_is_the_wrappers_batch_exactly(traced):
    assert (traced["engine_decode_batch_mean"]["value"]
            == traced["decode_batch_mean"]["value"])
    assert traced["engine_lock_wait_mean_ms.sat"] == traced["engine_lock_wait_mean_ms"]
    assert traced["kv_pages_written_pct"]["value"] <= 100.0
    assert traced["decode_stalled_by_admit_pct"]["value"] < 100.0


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_on_a_ring_without_engine_spans(name):
    from ray_tpu.util import tracing

    tracing.SPANS.clear()
    tracing.SPANS.record("serve_stream", "serve", 0.0, 1.0)
    run = types.SimpleNamespace(t_open=0.0, t_close=1.0, capture=None)
    assert engine_spans.load(run) is None
    assert spec.load_reader(name, os.path.join(HERE, "toy"))(run) is None


def test_window_and_tree_of_the_helper():
    span = lambda name, i, ts, dur, parent=None, **a: {
        "name": name, "ts": ts, "dur": dur,
        "args": dict(a, id=i, **({} if parent is None else {"parent": parent}))}
    es = engine_spans.EngineSpans([
        span("engine.step", 1, 10.0, 50.0),
        span("engine.admit", 2, 11.0, 20.0, parent=1),
        span("engine.first_token", 3, 12.0, 5.0, parent=2),
        span("engine.readback", 4, 40.0, 15.0, parent=1),
        span("engine.step", 5, 100.0, 10.0),
        span("engine.request", 6, 5.0, 6.0),
    ], 10.0, 100.0)
    assert [s["args"]["id"] for s in es.named("engine.step")] == [1]
    assert es.named("engine.request") == []
    assert len(es.named("engine.request", overlap=True)) == 1
    step = es.named("engine.step")[0]
    waits = es.under(step, ("engine.readback", "engine.first_token"))
    assert sorted(s["args"]["id"] for s in waits) == [3, 4]
    assert es.window_s == pytest.approx(90e-6)
