"""The family ``parallel_ssm_gqa`` (``falcon-h1-34b-l6``): its counts
against a hand count, the configuration against the catalog's row, the
``think`` mix as specified and replayed through its slots, the
reader this family brought on a hand-made ring, and a toy of the family
(``toy_ssm/``: three parallel layers at toy widths, groups of 5, float32)
through the whole run: the control comes out not correct, and so does one
altered token. ``decode_step`` of a model with state by slot returns
``((tokens, counts), pool_k, pool_v, state)``."""
import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import check, engine_spans, metrics, peaks, spec, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy_ssm", "BENCHMARK.json")
CELL = "falcon-h1-34b-l6.think"
SOURCE_URL = "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json"
# the source's config.json, its keys that say something of the shape, as
# published (72 layers)
SOURCE = {
    "attention_bias": False,
    "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 5120,
    "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128,
    "mamba_conv_bias": True,
    "mamba_d_conv": 4,
    "mamba_d_head": 128,
    "mamba_d_ssm": 4096,
    "mamba_d_state": 256,
    "mamba_expand": 2,
    "mamba_n_groups": 2,
    "mamba_n_heads": 32,
    "mamba_norm_before_gate": False,
    "mamba_proj_bias": False,
    "mamba_rms_norm": True,
    "mamba_use_mlp": True,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [
        0.1767766952966369,
        0.011160714285714284
    ],
    "model_type": "falcon_h1",
    "num_attention_heads": 20,
    "num_hidden_layers": 72,
    "num_key_value_heads": 4,
    "num_logits_to_keep": 1,
    "projectors_bias": False,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [
        0.3535533905932738,
        0.25,
        0.1767766952966369,
        0.5,
        0.3535533905932738
    ],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False,
    "vocab_size": 261120
}


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


@pytest.fixture(scope="module")
def family(cell):
    return spec.load_family(cell.cfg, cell.base)


# -- the counts, by hand -----------------------------------------------------------


def test_counts_against_a_hand_count(cell, family):
    cfg = cell.cfg
    d, ff, vocab = 5120, 21504, 261120
    # wq (5,120 x 2,560) and wo, wk and wv (5,120 x 512 each)
    attention = 2 * d * 2560 + 2 * d * 512
    # in_proj (5,120 x [4,096 z + 4,096 x + 512 B + 512 C + 32 dt]), out_proj
    # (4,096 x 5,120), four taps and a bias over 5,120 channels, A_log,
    # dt_bias and D a head, the gated norm's scale of 4,096
    mixer = d * 9248 + 4096 * d + 5 * 5120 + 3 * 32 + 4096
    mlp = 3 * d * ff
    assert (attention, mixer, mlp) == (31_457_280, 68_351_072, 330_301_440)
    assert family.attention_params(cfg) == attention
    assert family.mixer_params(cfg) == mixer
    layer = attention + mixer + mlp + 2 * d
    assert family.layer_params(cfg) == layer == 430_120_032  # 430.12 M
    always = 6 * layer + d + d * vocab
    assert family.always_read_params(cfg) == always
    whole = always + vocab * d              # the embedding: rows read, not the matrix
    assert whole == pytest.approx(5.255e9, rel=1e-3)
    assert whole * 2 / 2**30 == pytest.approx(9.79, abs=0.01)
    # the whole model: 72 layers and the vocabulary twice, 62.7 GiB
    assert (72 * layer + 2 * vocab * d) * 2 / 2**30 == pytest.approx(62.7, abs=0.05)
    # K and V: 6 layers x 4 heads x (128 + 128) x 2 B = 12 KiB a token
    assert family.kv_bytes_per_token(cfg) == {"full": 12_288}
    # state: 6 x (32 x 256 x 128 x 4 B + 3 columns x 5,120 x 2 B) = 24.18 MiB
    a_slot = 6 * (32 * 256 * 128 * 4 + 3 * 5120 * 2)
    assert family.state_bytes_per_slot(cfg) == a_slot == 25_350_144
    assert 64 * a_slot / 2**30 == pytest.approx(1.51, abs=0.01)
    assert family.ssm_state_bytes(cfg, 64) == 2 * 64 * a_slot
    recur = 6 * 32 * 2 * 256 * 128 * 3
    assert family.recurrence_flops(cfg) == recur
    per_token = 2 * always + recur
    ctxs = [2048] * 64
    flops, nbytes = family.decode_step_work(cfg, ctxs)
    assert nbytes == (always + 64 * d) * 2 + 64 * 2049 * 12_288 + 2 * 64 * a_slot
    assert flops == 64 * (per_token + 6 * 2 * 20 * 2 * 128 * 2048)
    assert family.decode_token_flops(cfg, 2048) == flops // 64
    # the reckoning of a step at 64 live slots of 2k: 7.8 GB of
    # weights (2.7 of them the head), 3.2 of state, 1.6 of K and V: 15.4 ms
    assert always * 2 == pytest.approx(7.84e9, rel=0.01)
    assert d * vocab * 2 == pytest.approx(2.67e9, rel=0.01)
    assert 2 * 64 * a_slot == pytest.approx(3.24e9, rel=0.01)
    assert 64 * 2049 * 12_288 == pytest.approx(1.61e9, rel=0.01)
    assert nbytes / 819e9 == pytest.approx(0.0155, rel=0.01)
    assert nbytes / 819e9 > flops / 197e12  # bytes bound the step
    t = 724
    assert family.prefill_flops(cfg, t) == (
        (per_token - 2 * d * vocab) * t
        + 6 * 2 * 20 * 2 * 128 * t * (t + 1) // 2 + 2 * d * vocab
    )
    assert family.prefill_flops(cfg, t) == pytest.approx(3.7e12, rel=0.05)


def test_the_configuration_is_the_catalogs_row_but_for_its_depth(cell):
    cfg = cell.cfg
    assert cfg["source"] == SOURCE_URL
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    for key, value in SOURCE.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["reduced"]["num_hidden_layers"]["source"] == 72
    assert {"1_ssm_multipliers", "2_gated_norm", "3_key_multiplier",
            "4_mlp_multipliers", "5_d_skip", "state", "weights", "torch_dtype",
            "tokenizer", "unused_keys"} <= set(cfg["assumed"])
    dep = cfg["deployment"]
    assert (dep["slots"], dep["replica_concurrency"], dep["page_size"],
            dep["pool_pages"], dep["max_context_tokens"]) == (64, 64, 16, 12288, 4096)
    assert dep["engine_kwargs"] == {"prefix_cache": False}
    assert dep["chips_sharing_a_layer"] == 1


def test_the_family_builds_the_programs_configuration(cell, family):
    m = family.model_config(cell.cfg)
    assert [r.key for r in m.layer_runs()] == [family.RUN]
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.rope_theta) == (20, 4, 128, 1e11)
    assert (m.ssm_heads, m.ssm_head_dim, m.ssm_state, m.ssm_groups,
            m.conv_kernel) == (32, 128, 256, 2, 4)
    assert (m.ssm_inner, m.ssm_width) == (4096, 5120) and m.rms_eps == 1e-5
    assert m.state_kinds() == {"ssm": 6} and m.kv_classes()["full"][0] == 6
    assert m.ssm_multipliers == tuple(cell.cfg["ssm_multipliers"])
    assert m.lm_head_multiplier == 0.0078125 and not m.tie_embeddings
    assert m.max_seq_len == 4096
    for key, value in (("attention_bias", True), ("mamba_norm_before_gate", True),
                       ("attn_layer_indices", [0, 3])):
        with pytest.raises(ValueError, match=key):
            family.model_config(dict(cell.cfg, **{key: value}))


def test_the_seeded_weights_have_unit_gain_and_the_decay_spreads(family):
    """On the toy of the family: a branch's matrix times its multiplier is
    a fan-in draw (deviation ``fan_in ** -0.5``), the head's logits spread
    with a deviation of 3 over unit rows, and with a stream near zero the
    decay ``exp(-A softplus(dt_bias))`` of a token lies in (0.2, 1)."""
    toy = spec.load_cell("toy-ssm.toy", TOY).cfg
    w = family.make_weights(toy, 3)
    p = w["blocks"][family.RUN]
    d = toy["hidden_size"]

    def gain(name, m, fan_in=d):
        return float(np.asarray(p[name], np.float64).std() * m * fan_in ** 0.5)

    assert gain("wk", toy["key_multiplier"]) == pytest.approx(1, rel=0.1)
    assert gain("wo", toy["attention_out_multiplier"], 160) == pytest.approx(1, rel=0.1)
    assert gain("ssm_out", toy["ssm_out_multiplier"], 64) == pytest.approx(1, rel=0.1)
    assert gain("w_down", toy["mlp_multipliers"][1], 96) == pytest.approx(1, rel=0.1)
    z = np.asarray(p["ssm_in"], np.float64)[..., :64]  # the z columns
    assert z.std() * toy["ssm_in_multiplier"] * toy["ssm_multipliers"][0] * d ** 0.5 == (
        pytest.approx(1, rel=0.1))
    head = np.asarray(w["head"], np.float64) * toy["lm_head_multiplier"]
    assert head.std() * d ** 0.5 == pytest.approx(3, rel=0.1)
    emb = np.asarray(w["embed"], np.float64) * toy["embedding_multiplier"]
    assert emb.std() == pytest.approx(1, rel=0.1)
    a = np.exp(np.asarray(p["ssm_a_log"], np.float64))
    step = np.log1p(np.exp(np.asarray(p["ssm_dt_bias"], np.float64)))
    assert 1 <= a.min() and a.max() <= 16
    assert 0.001 * 0.99 < step.min() and step.max() < 0.1 * 1.01
    decay = np.exp(-a * step)
    assert 0.2 < decay.min() and decay.max() < 1.0


# -- the mix ----------------------------------------------------------------------

THINK_LENGTHS = [256, 304, 368, 448, 544, 656, 800, 960, 1168, 1408, 1696, 2048]


def test_think_mix_is_as_specified(cell):
    """Prompts log-uniform 256-2,048 (median 724) under twelve padded
    lengths spread evenly in the logarithm, each a multiple of 16, none
    chunked; answers log-uniform 512-2,048 (median 1,024, mean 1,108); 64
    at once (the slots) when the 10 s ramp-in starts; contexts 768-4,096."""
    mix, a = cell.mix, cell.mix["arrivals"]
    assert mix["prompt_tokens"]["round_to"] == THINK_LENGTHS
    assert THINK_LENGTHS == [
        int(round(256 * 8 ** (i / 11) / 16)) * 16 for i in range(12)]
    assert (a["initial_burst"], a["ramp_in_s"], a["interarrival_cv"]) == (64, 10.0, 1.0)
    assert mix["temperature"] == 0.0
    s = traffic.schedule(mix, 50)
    # the burst: 64 requests a millisecond apart at the ramp-in's start
    assert [r.due for r in s[:64]] == pytest.approx(
        [-a["ramp_in_s"] + 0.001 * i for i in range(64)])
    p, o = [r.prompt_len for r in s], [r.max_new for r in s]
    assert min(p) >= 256 - 16 and max(p) <= 2048
    assert {-(-x // 16) * 16 for x in p} <= set(THINK_LENGTHS)
    # the median, 724 before rounding, falls on 656 or 800
    assert 656 - 16 <= stats.percentile(p, 50) <= 800
    assert min(o) >= 512 and max(o) <= 2048
    assert stats.percentile(o, 50) == pytest.approx(1024, rel=0.02)
    assert np.mean(o) == pytest.approx(1108, rel=0.02)
    assert max(x + y for x, y in zip(p, o)) <= cell.cfg["deployment"]["max_context_tokens"]
    assert min(x + y for x, y in zip(p, o)) >= 768 - 16
    # no prompt is chunked: 2,048 < the 3,648 one program takes at 20 heads
    assert max(p) < 3648


# measured on one TPU v5e (the traced run at 4.0 arrivals a second after the
# 64): 369 decode steps in the 10 s capture, 25.3 ms from step to step
# with the prefills' 0.69 s taken out (21.2 of them the decode program), a
# batch of 57.9 of 64 (a freed slot waits for its thread's turns at the
# lock), prefill 40 ms per 1,000 tokens on the device and about 45 with the
# host's part. With those the replay reads what the chip read: at 1.5 a
# second 8 queued at the close (the sweep's run: 12) and 2,144 tokens/s
# (2,164); at 4.0 220 in flight at most (the traced run's router: 227)
THINK_SLOTS, THINK_STEP_S, THINK_PREFILL_S_PER_KTOK = 58, 0.0253, 0.045


def _replay(cell, steady=None, order=None, speed=1.0, slots=THINK_SLOTS):
    from test_traffic import replay_slots

    a = dict(cell.mix["arrivals"])
    if steady is not None:
        a["rate_per_s"] = (steady * 60 + a["initial_burst"]) / 60
    if order is not None:
        a["order_seed"] = order
    s = traffic.schedule(dict(cell.mix, arrivals=a), 50)
    return replay_slots(s, slots, THINK_STEP_S * speed, 50,
                        THINK_PREFILL_S_PER_KTOK * speed)


def test_the_replay_reads_what_the_chip_read(cell):
    low, high = _replay(cell, 1.5, order=0), _replay(cell, 4.0, order=0)
    assert low["queued"] == 8 and low["tokens"] / 50 == pytest.approx(2144, abs=1)
    assert high["in_flight"] == 220


def test_think_schedule_outlasts_the_engine(cell):
    """What the mix's ``what`` says: 64 at once, then 2.5 k = 2.5 arrivals a
    second (k = 1.0, the sweep): 214 requests. At the measured step and
    cost of a prefill the replay has 73 queued and 131 in flight at the
    close, as six runs on the chip had (71-73 and 132-134). Down to two
    thirds of both the tokens in the window grow and requests are queued
    at the close at every speed; it is spent near 0.6 of both through the
    58 slots the engine keeps full, at two thirds through all 64. The
    requests in flight stay under the rule's 236."""
    a = cell.mix["arrivals"]
    s = traffic.schedule(cell.mix, 50)
    assert (len(s) - a["initial_burst"]) / 60 == pytest.approx(2.5 * 1.0, rel=0.01)
    assert (len(s), sum(r.max_new for r in s)) == (214, 237_111)
    now = _replay(cell)
    assert (now["queued"], now["in_flight"]) == (73, 131)
    runs = [_replay(cell, speed=x) for x in (1.0, 0.9, 0.8, 2 / 3)]
    counts = [r["tokens"] for r in runs]
    assert counts == sorted(counts) and counts[-1] > 1.45 * counts[0]
    assert all(r["queued"] > 0.05 * len(s) for r in runs)
    assert all(r["in_flight"] <= 236 - 100 for r in runs)
    assert _replay(cell, speed=0.6)["queued"] == 0
    assert _replay(cell, speed=2 / 3, slots=64)["queued"] == 0


def test_a_k_under_the_one_swept_would_spend_the_schedule(cell):
    """Why k is the highest rate with at most one request without its first
    token at the close, not none: at 1.0 and 1.25 a second fewer requests
    were in flight at the close than the 64 slots (35 and 53), so the one
    and two were being admitted. A k under 1.0 would set the rate at 2.25
    a second or less, and the replay is then spent at two thirds of the
    measured step, where the cell has to keep a queue."""
    assert _replay(cell, 2.5, speed=2 / 3)["queued"] > 0
    for steady in (2.25, 2.0):
        assert _replay(cell, steady, speed=2 / 3)["queued"] == 0


def test_think_order_seed_is_the_median_order_of_a_replay(cell):
    counts = {o: _replay(cell, order=o)["tokens"] for o in range(40)}
    ranked = sorted(counts, key=lambda o: (counts[o], o))
    assert ranked.index(cell.mix["arrivals"]["order_seed"]) in (19, 20)
    assert counts[ranked[-1]] < 1.05 * counts[ranked[0]]  # the order weighs little


# -- the reader, on a hand-made ring ------------------------------------------------------


def _run(cell, spans, monkeypatch):
    ring = engine_spans.EngineSpans(spans, 0.0, 50e6)
    monkeypatch.setattr(engine_spans, "load", lambda run: ring)
    return metrics.Run(
        cfg=cell.cfg, mix=cell.mix, base=cell.base,
        peaks=peaks.PEAKS["TPU v5 lite"], t_open=0.0, t_close=50.0,
        setup_s=1.0, clients=[], decode_log=[], prefill_log=[],
        window_compiles=0, memory_peak_bytes=None)


def _span(name, ts, **args):
    return {"name": name, "ts": ts, "dur": 10.0, "args": args}


def test_the_new_reader_on_a_hand_made_ring(cell, family, monkeypatch):
    a_slot = 25_350_144
    spans = [
        _span("engine.decode", 1e6, live=64, ctx=64 * 2048,
              ssm_state_bytes=2 * 64 * a_slot),
        _span("engine.decode", 2e6, live=10, ctx=10 * 700 + 3,
              ssm_state_bytes=2 * 10 * a_slot),
        _span("engine.decode", 60e6, live=1, ctx=5, ssm_state_bytes=1),  # after
        _span("engine.decode", 3e6, live=4, ctx=99),  # no such count: passed over
    ]
    run = _run(cell, spans, monkeypatch)
    got = spec.load_reader("ssm_state_step_share_pct", cell.base)(run)
    full = family.decode_step_work(cell.cfg, [2048] * 64)[1]
    part = family.decode_step_work(cell.cfg, [700] * 7 + [701] * 3)[1]
    assert got == pytest.approx(100 * 2 * 74 * a_slot / (full + part))
    # a full batch at 2k: a quarter of the step is the mixer's state
    one = spec.load_reader("ssm_state_step_share_pct", cell.base)(
        _run(cell, spans[:1], monkeypatch))
    assert one == pytest.approx(100 * 2 * 64 * a_slot / full)
    assert 24 < one < 27
    # the parent's spans carry no such count, and no step is no share
    for ring in ([spans[3]], []):
        assert spec.load_reader("ssm_state_step_share_pct", cell.base)(
            _run(cell, ring, monkeypatch)) is None
    monkeypatch.setattr(engine_spans, "load", lambda run: None)
    assert spec.load_reader("ssm_state_step_share_pct", cell.base)(run) is None


def test_every_new_entry_has_a_file_and_lists_the_cell_alone(cell):
    """By name, not by place: a later PR's entries may follow them."""
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ours = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(ours) == 19
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in ours:
        assert m["moves"] == "tokens_per_s"
        assert callable(spec.load_reader(m["name"], cell.base))
        if m["name"].endswith(".ssm"):  # an alias keeps its original's entry
            plain = by_name[m["name"][: -len(".ssm")]]
            assert [m[k] for k in ("unit", "better", "source", "layer")] == [
                plain[k] for k in ("unit", "better", "source", "layer")]
    assert {m["name"] for m in cell.per_layer} >= {m["name"] for m in ours}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "falcon-h1-34b-l6"
    conf = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b-l6")
    assert conf["reduced"] == ["num_hidden_layers"]


def test_the_reference_and_its_control_fit_beside_the_weights(cell, family):
    """The reference at the cell's longest sample (4,096 tokens, 2,048 rows
    of logits), compiled for a described v5e: the output check holds the
    plain logits while the control's program runs, so the chip holds the
    weights, two buffers of logits and one program's temporaries at once.
    With the head's blocks written into a buffer of their own layout and
    copied out, the temporaries were a third buffer of logits (1.99 GiB)
    and a traced run's control ran out of memory."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e topology can be described here: {e}")
    one = SingleDeviceSharding(desc.devices[0])
    ref = spec.load_reference(cell.cfg, cell.base)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda: family.make_weights(cell.cfg, 0)),
    )
    tokens = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one)
    gib = 2 ** 30
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for quant in (None, check.CONTROL_QUANT):
            mem = ref.logits_at.lower(
                params, tokens, rows, shape=ref._shape(cell.cfg), quant=quant
            ).compile().memory_analysis()
            logits = 2048 * cell.cfg["vocab_size"] * 4
            assert mem.output_size_in_bytes == logits
            assert mem.temp_size_in_bytes < 0.5 * gib
            held = mem.argument_size_in_bytes + 2 * logits + mem.temp_size_in_bytes
            assert held < 14.5 * gib  # with room below the chip's 16 GiB
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


# -- a toy of the family through the whole run ----------------------------------------


def toy_run(seed, seconds, control=False, trace=False):
    import jax

    cell = spec.load_cell("toy-ssm.toy", TOY)
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
    assert 0 < got["ssm_state_step_share_pct"]["value"] < 100
    assert 0 < got["state_cache_share_pct.ssm"]["value"] < 100
    assert got["schedule_unspent_pct.ssm"]["value"] >= 0
    assert got["engine_decode_batch_mean.ssm"]["value"] >= 1
    assert got["slot_turnover_per_s.ssm"]["value"] > 0
    assert got["prefill_span_ms_per_ktok.ssm"]["value"] > 0
    # no device plane on a CPU: every device metric stays out of the line
    for name in ("decode_step_roofline.ssm", "decode_step_device_ms.ssm",
                 "prefill_busy_share_pct.ssm", "device_idle_pct.ssm"):
        assert name not in got


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
