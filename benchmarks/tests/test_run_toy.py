"""The whole run at a toy size on the CPU, the look for a chip skipped:
the rehearsal of ``benchmarks/run.py``, the control, and the timed path
broken underneath (a dropped token, an answer that ends short by itself;
the altered token is ``test_fault_toy``'s). Each drives ``serve.run`` in
this process. The toy cell holds the same numbers as the real cells
(``test_spec`` checks that), with limits of its own, set as theirs are from
twelve toy seeds (1101-1112, 8 s, 504 tokens a run, CPU): the program's
mean gap 0.0001-0.0003 and share over 0.03 0-0.4 %, int8's 0.0024-0.0058
and 3.6-6.3 %; limits 0.0009 and 1.2 %."""
import json
import os

import pytest

import run as bench_run
from harness import check, peaks, probes, spec, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy", "BENCHMARK.json")


def toy_run(seed, trace=False, control=False, seconds=3.0, mix=None):
    import jax

    cell = spec.load_cell("toy-gqa.toy", TOY)
    if mix is not None:
        cell.mix = mix(cell.mix)
    return bench_run.run_cell(
        cell, seed, seconds, trace, jax.devices()[:1],
        peaks.PEAKS["TPU v5 lite"], control=control,
    )


def test_toy_cell_end_to_end_and_result_keys():
    res = toy_run(3_000_000_007)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    mix = spec.load_cell("toy-gqa.toy", TOY).mix
    assert res["attempted"] == len(traffic.schedule(mix, 3.0))
    assert set(res["metrics"]) == {
        "tokens_per_s", "token_gap_mean_ms", "token_gap_p99_ms", "setup_s"
    }
    c = res["compared"]
    assert c["window_compiles"] == {"value": 0, "limit": 0}
    assert c["cut_unexplained"] == {"value": 0, "limit": 0}
    assert c["gross_gaps"]["value"] == 0 and c["gross_gaps"]["limit"] == 0
    assert c["tail_share"]["value"] <= c["tail_share"]["limit"]
    json.dumps(res)


def test_traced_run_reports_what_its_readers_find():
    res = toy_run(11, trace=True)
    assert res["correct"] is True
    # no device plane on a CPU: every device metric stays out of the line,
    # none reads 0
    assert "decode_step_roofline" not in res["metrics"]
    assert "device_idle_pct" not in res["metrics"]
    assert res["metrics"]["decode_batch_mean"]["value"] >= 1
    assert res["metrics"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_comes_out_not_correct(seed):
    """The program passes the toy cell's limits; the control, put in its
    place at the same positions and through the same limits, does not: too
    many of the tokens that the lower precision puts first lie further
    below the float32 reference's best than ``tail_over``."""
    res = toy_run(seed, control=True, seconds=8.0)
    c = res["compared"]
    assert res["correct"] is True
    assert c["compared_tokens"]["value"] > 300
    assert c["control_correct"]["value"] is False
    assert c["control_tail_share"]["value"] > c["tail_share"]["limit"]
    assert c["control_mean_gap"]["value"] > c["mean_gap"]["limit"]


def test_dropped_token_comes_out_not_correct(monkeypatch):
    """An answer cut short where it is produced: wrong_length catches it."""
    from ray_tpu.llm.continuous import ContinuousBatchingEngine

    real = ContinuousBatchingEngine.stream_rid

    def short(self, rid):
        for i, tok in enumerate(real(self, rid)):
            if i != 3:
                yield tok

    monkeypatch.setattr(ContinuousBatchingEngine, "stream_rid", short)
    res = toy_run(41)
    assert res["correct"] is False
    assert res["compared"]["wrong_length"]["value"] > 0


def test_an_answer_that_ends_short_unevicted_comes_out_not_correct(monkeypatch):
    """After the close only the eviction may end an answer short: one that
    ends short by itself is not classed as cut and let through."""
    real = probes.EngineProbes.end_live_answers

    def unseen(self):
        real(self)
        return 0

    monkeypatch.setattr(probes.EngineProbes, "end_live_answers", unseen)
    # long answers at three times the rate, so that some are live at the close
    busy = lambda m: dict(
        m, arrivals=dict(m["arrivals"], rate_per_s=12.0),
        output_tokens={"dist": "uniform", "min": 50, "max": 60})
    res = toy_run(51, mix=busy)
    assert res["compared"]["cut_unexplained"]["value"] > 0
    assert res["correct"] is False


def test_probes_name_what_the_program_lacks():
    class Renamed:
        def step(self): ...
        def _admit(self): ...
        def _build_fns(self): ...

    with pytest.raises(RuntimeError, match="_prefix_insert.*_force_evict_active"):
        probes.EngineProbes().install(Renamed)


def test_verdict_rules():
    ok = {"a": {"value": 0.1, "limit": 0.2}, "n": {"value": 5, "at_least": 1},
          "free": {"value": 99}}
    assert check.verdict(ok)
    assert not check.verdict({**ok, "a": {"value": 0.3, "limit": 0.2}})
    assert not check.verdict({**ok, "n": {"value": 0, "at_least": 1}})
    assert not check.verdict({"a": {"value": float("nan"), "limit": 1}})
    assert not check.verdict({"a": {"value": None, "limit": 1}})


def test_gap_numbers_on_hand_made_gaps():
    gaps = [0.0] * 95 + [0.05, 0.1, 0.2, 0.3, 4.0]
    n = check.gap_numbers(gaps, tail_over=0.15)
    assert n["tail_share"] == pytest.approx(0.03)
    assert n["gross_gaps"] == 1 and n["off_argmax"] == 5
    assert n["mean_gap"] == pytest.approx(4.65 / 100)
    assert n["max_gap"] == 4.0
    assert check.gap_numbers([], 0.15)["tail_share"] is None
