"""The family ``moe_conv_gqa`` (``lfm2-8b-a1b-l14``): its counts against a
hand count, the ``turns`` mix as the issue gives it and replayed through 64
slots, the readers this family brought on a hand-made ring, and a toy of
the family (``toy_conv/``: eight layers at toy widths, float32) through the
whole run: the control comes out not correct, and so does one altered
token. ``decode_step`` of a model with state by slot returns ``((tokens,
counts), pool_k, pool_v, state)``."""
import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import check, engine_spans, metrics, peaks, spec, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TOY = os.path.join(HERE, "toy_conv", "BENCHMARK.json")
CELL = "lfm2-8b-a1b-l14.turns"


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


@pytest.fixture(scope="module")
def family(cell):
    return spec.load_family(cell.cfg, cell.base)


# -- the counts, by hand -----------------------------------------------------------


def test_counts_against_a_hand_count(cell, family):
    cfg = cell.cfg
    d = 2048
    conv = d * 3 * d + d * d + 3 * d            # W_in, W_out, three taps
    attn = 2 * d * d + 2 * d * 512 + 2 * 64     # Wq Wo, Wk Wv, the two head norms
    dense, expert, router = 3 * d * 7168, 3 * d * 1792, d * 32
    assert (conv, attn, dense, expert) == (16_783_360, 10_485_888, 44_040_192, 11_010_048)
    assert family.operator_params(cfg, "conv") == conv
    assert family.operator_params(cfg, "full") == attn
    assert family.expert_params(cfg) == expert
    assert [family.conv_layers(cfg), family.attention_layers(cfg),
            family.expert_layers(cfg), family.experts_held(cfg)] == [11, 3, 12, 32]
    always = 11 * conv + 3 * attn + 2 * dense + 12 * router + 65536 * d
    assert family.always_read_params(cfg) == always
    whole = always + 12 * 32 * expert  # norms' vectors apart: 4.67 B
    assert whole == pytest.approx(4.667e9, rel=1e-3)
    assert whole * 2 / 2**30 == pytest.approx(8.69, abs=0.01)
    # K and V: 3 layers x 8 heads x (64 + 64) x 2 B; state: 11 x 2 x 2048 x 2 B
    assert family.kv_bytes_per_token(cfg) == {"full": 6144}
    assert family.state_bytes_per_slot(cfg) == 90_112
    assert family.held_share(cfg) == 4.0
    assert family.experts_hit(cfg, 1) == pytest.approx(4.0)
    assert family.experts_hit(cfg, 64) == pytest.approx(32 * (1 - (28 / 32) ** 64))
    ctxs = [500] * 64
    flops, nbytes = family.decode_step_work(cfg, ctxs)
    hit = 32 * (1 - 0.875 ** 64)
    want_bytes = (
        (always + 12 * hit * expert + 64 * d) * 2
        + 64 * 501 * 6144 + 2 * 64 * 90_112
    )
    assert nbytes == pytest.approx(want_bytes)
    per_token = 2 * (always + 12 * 4 * expert)
    assert flops == pytest.approx(64 * (per_token + 3 * 2 * 32 * 128 * 500))
    assert family.decode_token_flops(cfg, 500) == pytest.approx(flops / 64)
    # a full batch reads every expert: 8.45 GB of experts, 0.88 GB of the rest
    assert 12 * hit * expert * 2 == pytest.approx(8.45e9, rel=0.01)
    assert always * 2 == pytest.approx(0.88e9, rel=0.01)
    # bytes bound the step at these batches
    assert nbytes / 819e9 > flops / 197e12
    t = 1000
    assert family.prefill_flops(cfg, t) == pytest.approx(
        (per_token - 2 * 65536 * d) * t
        + 3 * 2 * 32 * 128 * t * (t + 1) / 2 + 2 * 65536 * d
    )


def test_the_configuration_is_the_catalogs_row_but_for_its_depth(cell):
    cfg = cell.cfg
    assert cfg["num_hidden_layers"] == 14 == len(cfg["layer_types"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cfg["reduced"]["num_hidden_layers"]["source"] == 24
    assert cfg["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 3
    for key, value in {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "moe_intermediate_size": 1792,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe",
    }.items():
        assert cfg[key] == value, key
    dep = cfg["deployment"]
    assert (dep["slots"], dep["page_size"], dep["pool_pages"],
            dep["max_context_tokens"]) == (64, 16, 8192, 4096)
    assert dep["engine_kwargs"] == {"prefix_cache": False}


def test_the_family_builds_the_programs_configuration(cell, family):
    m = family.model_config(cell.cfg)
    assert [r.key for r in m.layer_runs()] == [
        key for key, _, _ in family.layer_runs(cell.cfg)]
    assert (m.head_dim, m.v_head_dim, m.rotary_dim) == (64, 64, 64)
    assert (m.qk_norm, m.tie_embeddings, m.conv_kernel) == (True, True, 3)
    assert (m.n_routed_experts, m.experts_per_token, m.experts_held) == (32, 4, (0, 32))
    assert (m.router_norm_eps, m.routed_scaling, m.rms_eps) == (1e-6, 1.0, 1e-5)
    assert m.state_layers == 11 and list(m.kv_classes()) == ["full"]
    with pytest.raises(ValueError, match="conv_bias"):
        family.model_config(dict(cell.cfg, conv_bias=True))


# -- the mix ----------------------------------------------------------------------

# measured (my chip runs, PR 38, the traced run of the reloaded cell): a
# decode step's interval is 43.2 ms on the device and 6.3 on the host, 46-47
# of the 64 slots are live a step (a freed slot waits turns at the lock
# before its next occupant is in), and a prefill costs the slots 136 ms per
# 1,000 prompt tokens (prefill_device_ms_per_ktok.state). With those the
# replay reads 810 tokens/s, 143 finished and 221 in flight at the close;
# the six runs read 793-836, 141-151 and 213-223. Then PR 37's speed
# (ledger, PR 37: 24.1 ms and 6.0, prefill 74.8 ms per 1,000).
TURNS_STEP_S, TURNS_SLOTS_FULL = 0.0495, 47
TURNS_PREFILL_S_PER_KTOK = 0.136
TURNS_PR37 = (0.030, 0.075)
# PR 36's reading of the interval between two steps, admissions in it
# (50 s in 870-882 steps): what its order_seed rule replayed at, and PR 38's
TURNS_INTERVAL_S = 0.057


def test_turns_is_the_issues_mix(cell):
    """Lengths as PR 36's issue gives them; ten padded prompt lengths, none
    longer than one prefill program; 64 at once when the ramp-in starts and
    then k + 2.75 = 5.0 a second (k = 2.25, PR 36's sweep), which PR 38's
    rule kept: at most 236 requests in flight at the close in each of six
    runs (213-223 read)."""
    a = cell.mix["arrivals"]
    s = traffic.schedule(cell.mix, 50)
    steady = (len(s) - a["initial_burst"]) / (a["ramp_in_s"] + 50)
    assert steady == pytest.approx(2.25 + 2.75)
    assert sum(1 for r in s if r.due < -a["ramp_in_s"] + 0.064) == 64
    p, o = [r.prompt_len for r in s], [r.max_new for r in s]
    assert min(p) >= 48 - 16 and max(p) <= 2048
    assert len({-(-x // 16) for x in p}) <= 10
    assert stats.percentile(p, 50) == pytest.approx(320, rel=0.25)
    assert np.mean(p) == pytest.approx(420, rel=0.01)
    assert min(o) >= 96 and max(o) <= 768
    assert stats.percentile(o, 50) == pytest.approx(256, rel=0.05)
    assert (len(s), sum(o)) == (364, 104_915)
    assert max(a + b for a, b in zip(p, o)) <= (
        cell.cfg["deployment"]["max_context_tokens"])


def test_turns_schedule_outlasts_the_engine(cell):
    """What the mix's ``what`` says. Through the 47 slots the engine keeps
    full, from the measured step interval and cost of a prefill down to
    PR 37's (30 ms, 75 ms per 1,000 prompt tokens), the tokens in the window
    never fall and requests are queued at the close at every speed; at the
    slowest the requests in flight stay under the router's
    ``serve_admission_max_inflight`` by the rule's 20. The schedule it
    replaced (267 requests, 3.375 a second) reads the same down to 35 ms,
    has none queued at PR 37's speed and reads a seventh less there (1,149
    tokens/s in the replay, 1,119 in the ledger: the schedule's number).
    Through 47 slots the new one is spent near 22.5 ms and 55 ms per 1,000;
    through 64 full slots (the hand-over mended, Speed 2) it holds down to
    35 ms and is spent at PR 37's speed: Speed 2 laid over Speed 1 c spends
    it, and the cell is reloaded again before both are in."""
    from ray_tpu.config import cfg
    from test_traffic import replay_slots

    s = traffic.schedule(cell.mix, 50)
    speeds = [(TURNS_STEP_S, TURNS_PREFILL_S_PER_KTOK), (0.045, 0.120),
              (0.040, 0.105), (0.035, 0.090), TURNS_PR37]
    as_it_is = [replay_slots(s, TURNS_SLOTS_FULL, x, 50, pf) for x, pf in speeds]
    counts = [r["tokens"] for r in as_it_is]
    assert counts == sorted(counts) and counts[-1] > 1.6 * counts[0]
    assert all(r["queued"] > 0.15 * len(s) for r in as_it_is)
    assert as_it_is[0]["queued"] > 0.45 * len(s)
    assert as_it_is[0]["in_flight"] <= cfg.serve_admission_max_inflight - 20
    assert replay_slots(s, TURNS_SLOTS_FULL, 0.0225, 50, 0.055)["queued"] == 0
    m = cell.mix
    old = traffic.schedule(
        dict(m, arrivals=dict(m["arrivals"], rate_per_s=4.4417, order_seed=23)), 50)
    assert len(old) == 267
    was = replay_slots(old, TURNS_SLOTS_FULL, TURNS_PR37[0], 50, TURNS_PR37[1])
    assert was["queued"] == 0 and was["tokens"] < 0.87 * counts[-1]
    full = [replay_slots(s, 64, x, 50, pf) for x, pf in speeds]
    assert all(r["queued"] > 0 for r in full[:4])
    assert [r["tokens"] for r in full] == sorted(r["tokens"] for r in full)
    assert full[4]["queued"] == 0  # spent by here: 64 full slots at 30 ms
    assert full[4]["tokens"] > 1.2 * counts[-1]


def test_turns_order_seed_is_the_median_order_at_the_measured_interval(cell):
    """``order_seed`` by the README's rule, as PR 36 applied it: of the
    orders 0..39 the one whose tokens inside the window are the median,
    replayed through the cell's 64 slots at the interval PR 36 measured
    between two steps. The forty orders lie within 5 % of each other; at
    the step PR 38 measured, through 47 slots, order 22 reads half a
    percent over their median."""
    from test_traffic import rank_orders

    ranked, counts = rank_orders(
        cell.mix, 64, TURNS_INTERVAL_S, TURNS_PREFILL_S_PER_KTOK)
    assert ranked.index(cell.mix["arrivals"]["order_seed"]) in (19, 20, 21, 22)
    assert counts[ranked[-1]] < 1.06 * counts[ranked[0]]


# -- the readers, on a hand-made ring ------------------------------------------------


def _run(cell, spans, monkeypatch):
    ring = engine_spans.EngineSpans(spans, 0.0, 50e6)
    monkeypatch.setattr(engine_spans, "load", lambda run: ring)
    return metrics.Run(
        cfg=cell.cfg, mix=cell.mix, base=cell.base, peaks={}, t_open=0.0,
        t_close=50.0, setup_s=1.0, clients=[], decode_log=[], prefill_log=[],
        window_compiles=0, memory_peak_bytes=None)


def _span(name, ts, **args):
    return {"name": name, "ts": ts, "dur": 10.0, "args": args}


def test_the_new_readers_on_a_hand_made_ring(cell, monkeypatch):
    spans = [
        _span("engine.admit", 1e6, admitted=3, pool_stall=0),
        _span("engine.admit", 2e6, admitted=2, pool_stall=0),
        _span("engine.admit", 60e6, admitted=9, pool_stall=0),  # after the close
        # 60 live slots of 11 layers; 2,400 live pages
        _span("engine.decode", 3e6, live=60, state_layers=11,
              state_slots_written=660, full_pages=2400,
              moe_pairs_held=60 * 4 * 12, moe_experts_hit=12 * 32),
        _span("engine.decode", 4e6, live=30, state_layers=11,
              state_slots_written=330, full_pages=2400,
              moe_pairs_held=30 * 4 * 12, moe_experts_hit=12 * 30),
    ]
    run = _run(cell, spans, monkeypatch)

    def read(name):
        return spec.load_reader(name, cell.base)(run)

    assert read("slot_turnover_per_s") == pytest.approx(5 / 50)
    assert read("expert_tokens_mean.state") == pytest.approx((7.5 + 3.75) / 2)
    assert read("experts_hit_pct.state") == pytest.approx(100 * (32 + 30) / 64)
    pages = 2400 * 16 * 6144
    shares = [100 * s / (s + pages) for s in (2 * 60 * 90_112, 2 * 30 * 90_112)]
    assert read("state_cache_share_pct") == pytest.approx(sum(shares) / 2)
    assert 4 < shares[0] < 5


def test_the_new_readers_are_silent_on_the_parents_spans(cell, monkeypatch):
    """The parent's ``engine.decode`` carries no state counts and, for a
    model it can run, no expert counts: ``None``, never 0."""
    spans = [_span("engine.decode", 3e6, live=4, pages_written=9)]
    run = _run(cell, spans, monkeypatch)
    for name in ("state_cache_share_pct", "expert_tokens_mean.state",
                 "experts_hit_pct.state"):
        assert spec.load_reader(name, cell.base)(run) is None
    assert spec.load_reader("slot_turnover_per_s", cell.base)(run) == 0.0
    monkeypatch.setattr(engine_spans, "load", lambda run: None)
    for name in ("slot_turnover_per_s", "state_cache_share_pct"):
        assert spec.load_reader(name, cell.base)(run) is None


def test_every_new_entry_has_a_file_and_lists_the_cell_alone(cell):
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ours = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(ours) == 18 and bench["per_layer"][-18:] == ours
    for m in ours:
        assert m["moves"] == "tokens_per_s"
        assert callable(spec.load_reader(m["name"], cell.base))
    assert {m["name"] for m in cell.per_layer} >= {m["name"] for m in ours}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}


# -- a toy of the family through the whole run ----------------------------------------


def toy_run(seed, seconds, control=False, trace=False):
    import jax

    cell = spec.load_cell("toy-moe-conv.toy", TOY)
    return bench_run.run_cell(
        cell, seed, seconds, trace, jax.devices()[:1],
        peaks.PEAKS["TPU v5 lite"], control=control,
    )


def test_the_toy_passes_and_its_control_does_not():
    res = toy_run(1105, 8.0, control=True)
    c = res["compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert c["compared_tokens"]["value"] > 300
    assert c["window_compiles"] == {"value": 0, "limit": 0}
    assert c["control_correct"]["value"] is False
    assert c["control_tail_share"]["value"] > c["tail_share"]["limit"]
    assert c["control_mean_gap"]["value"] > c["mean_gap"]["limit"]


def test_a_traced_toy_run_reports_the_new_metrics():
    got = toy_run(3_000_000_007, 4.0, trace=True)["metrics"]
    assert got["slot_turnover_per_s"]["value"] > 0
    assert 0 < got["state_cache_share_pct"]["value"] < 100
    batch = got["decode_batch_mean"]["value"]
    assert got["expert_tokens_mean.state"]["value"] == pytest.approx(
        batch * 4 / 32, rel=0.05)
    assert 0 < got["experts_hit_pct.state"]["value"] <= 100
    assert "decode_step_roofline" not in got  # no device plane on a CPU


def test_one_altered_token_comes_out_not_correct(monkeypatch):
    """The timed path broken underneath: one token of the whole run, a live
    slot's at the twentieth decode step or the first after it that has
    one, is altered where it is produced."""
    from ray_tpu.llm.continuous import ContinuousBatchingEngine

    real = ContinuousBatchingEngine._build_fns
    calls = {"n": 0}

    def broken_build(engine):
        real(engine)
        decode = engine._decode_step

        def altered(*a, **kw):
            (nxt, counts), k, v, state = decode(*a, **kw)
            calls["n"] += 1
            live = [i for i, s in enumerate(engine.slots) if s.active]
            if calls["n"] >= 20 and live and not calls.get("altered"):
                i = live[0]
                nxt = nxt.at[i].set((nxt[i] + 1) % engine.cfg.vocab_size)
                calls["altered"] = True
            return (nxt, counts), k, v, state

        engine._decode_step = altered

    monkeypatch.setattr(ContinuousBatchingEngine, "_build_fns", broken_build)
    res = toy_run(31, 4.0)
    assert calls["altered"]
    assert res["correct"] is False and res["failed"] == 0
    c = res["compared"]
    assert c["gross_gaps"] == {"value": 1, "limit": 0, "over": check.GROSS_OVER}
