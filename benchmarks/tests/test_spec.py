"""``BENCHMARK.json`` against the limits of the benchmark's contract that
can be checked without a run, and the data-driven lookup of files."""
import json
import os
import re

import pytest

from harness import spec

ROOT = spec.REPO_ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CHECK_KEYS = {
    "tail_over", "tail_share", "mean_gap", "sample_min_tokens",
    "sample_max_requests",
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_shape_of_the_file(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        for w in m.get("workloads", cells):
            reported = e2e[m["moves"]].get("workloads", cells)
            assert w in reported, (m["name"], w)


def test_every_cell_and_metric_finds_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.cfg["name"] == w["config"]
        assert cell.cell["config"] == w["config"]
        assert cell.cell["traffic"] == w["traffic"]
        assert set(cell.cell["check"]) == CHECK_KEYS
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"], cell.base))
        for name in spec.FAMILY_PROVIDES:
            assert callable(getattr(spec.load_family(cell.cfg, cell.base), name))
        assert callable(
            spec.load_reference(cell.cfg, cell.base).reference_logits)
    # the toy cell of the tests holds the same numbers as the real cells
    toy = spec.load_cell(
        "toy-gqa.toy", os.path.join(os.path.dirname(__file__), "toy", "BENCHMARK.json"))
    assert set(toy.cell["check"]) == CHECK_KEYS
    chat = spec.load_cell("mistral-7b-v0.3-l16.chat")
    assert "token_gap_p99_ms" not in {m["name"] for m in chat.end_to_end}
    assert "decode_step_roofline.sat" not in {m["name"] for m in chat.per_layer}


def test_a_cell_is_added_with_files_alone(tmp_path):
    """A configuration, a mix, a cell and a per-layer metric of a later PR:
    new files and one entry each, no edit to a file that is there."""
    base = tmp_path / "extra"
    for d in ("configs", "traffic", "workloads", "layer_metrics"):
        (base / d).mkdir(parents=True)
    toy = os.path.join(os.path.dirname(__file__), "toy")
    cfg = json.load(open(os.path.join(toy, "configs", "toy-gqa.json")))
    (base / "configs" / "later.json").write_text(json.dumps(dict(cfg, name="later")))
    mix = json.load(open(os.path.join(toy, "traffic", "toy.json")))
    (base / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (base / "workloads" / "later.bursty.json").write_text(json.dumps(
        {"config": "later", "traffic": "bursty", "why": "x",
         "check": {"tail_over": 0.1, "tail_share": 0.01, "mean_gap": 0.01,
                   "sample_min_tokens": 100, "sample_max_requests": 4}}))
    (base / "layer_metrics" / "queue_wait_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    (base / "layer_metrics" / "queue_wait_ms.alias.json").write_text(
        '{"same_as": "queue_wait_ms"}')
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "later", "source": "x", "reduced": [], "why": "x",
        "file": os.path.relpath(base / "configs" / "later.json", ROOT)})
    bench["workloads"].append({
        "name": "later.bursty", "config": "later", "traffic": "bursty",
        "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "queue_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "engine scheduler step/_admit",
        "moves": "tokens_per_s", "workloads": ["later.bursty"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = spec.load_cell("later.bursty", str(path))
    assert cell.cfg["name"] == "later" and cell.mix["what"]
    names = [m["name"] for m in cell.per_layer]
    assert "queue_wait_ms" in names and "generator_late_p95_ms" in names
    assert "ttft_p90_ms" not in names  # lists its own cells
    assert spec.load_reader("queue_wait_ms", cell.base)(None) == 1.5
    assert spec.load_reader("queue_wait_ms.alias", cell.base)(None) == 1.5
