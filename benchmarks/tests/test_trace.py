import os

import pytest

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "toy_v5e.xplane.pb.gz")


def test_union_and_self_times():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    # a while of 10 holds two fusions of 3 and 4; a copy of 2 follows
    ev = [(0, 10, "while"), (1, 4, "fusion.1"), (5, 9, "fusion.2"),
          (10, 12, "copy")]
    selfs = trace._self_times(ev)
    assert selfs == {"while": 3, "fusion.1": 3, "fusion.2": 4, "copy": 2}


def test_idle_gaps_go_to_the_innermost_engine_span():
    spans = [
        (0, 100, "bench.engine.step"),
        (10, 40, "bench.engine.admit"),
        (20, 30, "bench.engine.prefill"),
        (0, 200, "bench.router.stream"),
    ]
    segs = trace._flatten_spans(spans)
    assert segs == [
        (0, 10, "bench.engine.step"), (10, 20, "bench.engine.admit"),
        (20, 30, "bench.engine.prefill"), (30, 40, "bench.engine.admit"),
        (40, 100, "bench.engine.step"), (100, 200, "bench.router.stream"),
    ]
    gaps = [(5, 15), (25, 26), (90, 110), (190, 210)]
    by = trace._attribute(gaps, segs)
    assert by["bench.engine.step"] == 5 + 10
    assert by["bench.engine.admit"] == 5
    assert by["bench.engine.prefill"] == 1
    assert by["bench.router.stream"] == 10 + 10
    assert by["none"] == 10


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_reduction_of_the_recorded_trace(tmp_path):
    """A trace of the toy cell (0.6 s) recorded on one TPU v5e, PR 25."""
    import gzip
    import shutil

    raw = tmp_path / "toy.xplane.pb"
    with gzip.open(RECORDED) as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = trace.reduce_xplane(str(raw))
    assert s.n_devices == 1
    # 23 ms of a quiet stretch: eight decode steps, no prefill
    assert s.window_s == pytest.approx(0.023152049)
    assert s.busy_s == pytest.approx(0.000782656)
    secs, runs = s.program("decode_step")
    assert runs == 8 and secs == pytest.approx(0.000776957)
    assert s.program("prefill") == (0.0, 0)
    # programs lie inside the busy time
    assert sum(v[0] for v in s.programs.values()) <= s.busy_s * 1.02
    assert s.device_ops[0][0] == "copy.19 bf16[4,4,64,16,32]"
    assert s.device_ops[0][1] >= s.device_ops[-1][1]
    assert s.host_spans["bench.engine.step"][1] == 8
    assert s.host_spans["bench.engine.decode"][1] == 8
    gaps = dict(s.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert max(gaps, key=gaps.get) == "bench.engine.step"
