"""``decode_attn_kernel_pct`` (PR 32): found by name with its aliases, read
off a ring made by hand with and without the step's attention counts, and
in a toy run on the CPU, where the engine takes the XLA formulation and the
metric therefore reads 0: a fallen-back cell is seen, not passed over."""
import json
import os
import types

import pytest

import run as bench_run
from harness import engine_spans, peaks, spec

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_BASE = os.path.join(HERE, "toy")
NAME = "decode_attn_kernel_pct"


def _ring(steps):
    """A ring of ``engine.decode`` spans inside the window, one for each
    dict of args, as ``tracing.SPANS`` holds them."""
    from ray_tpu.util import tracing

    tracing.SPANS.clear()
    for i, args in enumerate(steps):
        tracing.SPANS.record(
            "engine.decode", "engine", tracing.PERF_EPOCH_S + 0.1 * (i + 1),
            0.01, id=i + 1, live=2, **args,
        )
    return types.SimpleNamespace(t_open=0.0, t_close=10.0, capture=None)


def test_reader_and_aliases_are_found_by_name():
    reader = spec.load_reader(NAME, TOY_BASE)
    assert callable(reader)
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"].startswith(NAME)}
    assert sorted(entries) == [
        NAME, NAME + ".moe", NAME + ".sat", NAME + ".state"]
    cells = {c["name"] for c in bench["workloads"]}
    listed = [c for m in entries.values() for c in m["workloads"]]
    assert sorted(listed) == sorted(cells)  # every cell, once
    roofline = {m["name"]: m for m in bench["per_layer"]
                if m["name"].startswith("decode_step_roofline")}
    for name, m in entries.items():
        twin = roofline[name.replace(NAME, "decode_step_roofline")]
        assert (m["layer"], m["moves"], m["workloads"]) == (
            twin["layer"], twin["moves"], twin["workloads"])
        # an alias loads the one reader's file
        assert (spec.load_reader(name, TOY_BASE).__code__.co_filename
                == reader.__code__.co_filename)


@pytest.mark.parametrize(
    "steps, want",
    [
        ([{"attn_kernel_layers": 16, "attn_full_layers": 16}] * 3, 100.0),
        ([{"attn_kernel_layers": 2, "attn_full_layers": 2},
          {"attn_kernel_layers": 0, "attn_full_layers": 2}], 50.0),
        ([{"attn_kernel_layers": 0, "attn_full_layers": 24}], 0.0),
        ([{}, {}], None),  # the parent's spans: no such args
        ([], None),
    ],
    ids=["all-kernel", "half-fell-back", "gather", "no-args", "no-spans"],
)
def test_reader_on_a_toy_ring(steps, want):
    got = spec.load_reader(NAME, TOY_BASE)(_ring(steps))
    assert got == want
    if not steps:
        assert engine_spans.load(_ring(steps)) is None


def test_a_cpu_run_reads_zero_not_a_hundred(tmp_path):
    """The toy cell with the metric's real entry added: the CPU engine
    gathers, the counter says so, and the line carries 0."""
    import jax

    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(TOY_BASE, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell_name = bench["workloads"][0]["name"]
    bench["per_layer"].append(dict(real[NAME], workloads=[cell_name]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell(cell_name, str(path))
    res = bench_run.run_cell(
        cell, 62, 3.0, True, jax.devices()[:1], peaks.PEAKS["TPU v5 lite"])
    assert res["correct"] is True
    assert res["metrics"][NAME] == {"value": 0.0, "unit": "%"}
