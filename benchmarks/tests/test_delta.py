"""The family ``gated_delta_mha`` (``olmo-hybrid-7b-l16``): its counts
against a hand count, the configuration against the catalog's row, the
``longdoc`` mix as the issue gives it and replayed through its pool, the
readers this family brought on a hand-made ring and a hand-made trace, and
a toy of the family (``toy_delta/``: eight layers at toy widths, float32)
through the whole run: the control comes out not correct, and so does one
altered token. ``decode_step`` of a model with state by slot returns
``((tokens, counts), pool_k, pool_v, state)``."""
import json
import os

import numpy as np
import pytest

import run as bench_run
from harness import check, engine_spans, metrics, peaks, spec, stats, traffic
from harness import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TOY = os.path.join(HERE, "toy_delta", "BENCHMARK.json")
CELL = "olmo-hybrid-7b-l16.longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


@pytest.fixture(scope="module")
def family(cell):
    return spec.load_family(cell.cfg, cell.base)


# -- the counts, by hand -----------------------------------------------------------


def test_counts_against_a_hand_count(cell, family):
    cfg = cell.cfg
    d, ff, vocab = 3840, 11008, 100352
    # q k (3840 x 2880 each), v and the output gate (3840 x 5760 each), the
    # output (5760 x 3840), two per-head scalars' projections, the four
    # taps over q k v, A_log and dt_bias, o_norm's one scale of 192
    linear = (
        2 * d * 2880 + 2 * d * 5760 + 5760 * d + 2 * d * 30
        + 4 * 11520 + 2 * 30 + 192
    )
    full = 4 * d * d + 2 * d               # q k v o, the two norms of 3,840
    mlp = 3 * d * ff
    assert (linear, full, mlp) == (88_750_332, 58_990_080, 126_812_160)
    assert family.operator_params(cfg, "delta") == linear
    assert family.operator_params(cfg, "full") == full
    assert family.layer_params(cfg, "delta") == linear + mlp + 2 * d
    # the issue's 215.57 M and 185.81 M a layer, 770.7 M in embedding and head
    assert (linear + mlp + 2 * d) / 1e6 == pytest.approx(215.57, abs=0.01)
    assert (full + mlp + 2 * d) / 1e6 == pytest.approx(185.81, abs=0.01)
    assert [family.linear_layers(cfg), family.attention_layers(cfg)] == [12, 4]
    always = 12 * (linear + mlp + 2 * d) + 4 * (full + mlp + 2 * d) + d + d * vocab
    assert family.always_read_params(cfg) == always
    whole = always + vocab * d             # the embedding: rows read, not the matrix
    assert whole == pytest.approx(4.101e9, rel=1e-3)
    assert whole * 2 / 2**30 == pytest.approx(7.64, abs=0.01)
    # the whole model, as the catalog's row describes it: 7.43 B
    assert 24 * 215.57e6 + 8 * 185.81e6 + 770.7e6 == pytest.approx(7.431e9, rel=1e-3)
    # K and V: 4 layers x 30 heads x (128 + 128) x 2 B = 60 KiB a token
    assert family.kv_bytes_per_token(cfg) == {"full": 61_440}
    # state: 12 x (30 x 96 x 192 x 4 B + 3 columns x 11,520 x 2 B) = 26.1 MiB
    a_slot = 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert family.state_bytes_per_slot(cfg) == a_slot == 27_371_520
    assert a_slot / 2**20 == pytest.approx(26.1, abs=0.05)
    assert a_slot / 61_440 == pytest.approx(445, abs=1)  # tokens of KV
    recur = 12 * 30 * 2 * 96 * 192 * 3
    assert family.recurrence_flops(cfg) == recur
    per_token = 2 * always + recur
    ctxs = [7000] * 9
    flops, nbytes = family.decode_step_work(cfg, ctxs)
    assert nbytes == (always + 9 * d) * 2 + 9 * 7001 * 61_440 + 2 * 9 * a_slot
    assert flops == 9 * (per_token + 4 * 2 * 30 * 2 * 128 * 7000)
    assert family.decode_token_flops(cfg, 7000) == flops // 9
    # 6.7 GFLOP a token through the layers and 0.77 through the head; a
    # step reads 7.4 GB of weights, 3.9 of K and V, 0.5 of state
    assert per_token - 2 * d * vocab == pytest.approx(6.70e9, rel=0.01)
    assert always * 2 == pytest.approx(7.43e9, rel=0.01)
    assert 2 * 9 * a_slot == pytest.approx(0.49e9, rel=0.01)
    assert 9 * 7001 * 61_440 == pytest.approx(3.87e9, rel=0.01)
    assert nbytes / 819e9 > flops / 197e12  # bytes bound the step
    t = 1000
    assert family.prefill_flops(cfg, t) == (
        (per_token - 2 * d * vocab) * t
        + 4 * 2 * 30 * 2 * 128 * t * (t + 1) // 2 + 2 * d * vocab
    )
    # one chunk of 736 tokens behind 5,000: compute bounds it
    flops, nbytes = family.prefill_chunk_work(cfg, 736, 5000)
    keys = 736 * 5000 + 736 * 737 // 2
    assert flops == (
        (per_token - 2 * d * vocab) * 736 + 4 * 2 * 30 * 2 * 128 * keys
        + 2 * d * vocab
    )
    assert nbytes == (always + 736 * d) * 2 + 5736 * 61_440 + 2 * a_slot
    assert flops / 197e12 > nbytes / 819e9
    assert flops / 197e12 == pytest.approx(0.0263, rel=0.02)


def test_the_configuration_is_the_catalogs_row_but_for_its_depth(cell):
    cfg = cell.cfg
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == row["source_url"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 16 == len(cfg["layer_types"])
    assert cfg["reduced"]["num_hidden_layers"]["source"] == 32
    assert cfg["layer_types"] == row["config"]["layer_types"][:16] == (
        ["linear_attention"] * 3 + ["full_attention"]) * 4
    assert {"1_rotary", "2_block", "3_qk_norm", "4_linear_qk", "5_beta",
            "6_o_norm", "state", "weights", "torch_dtype"} <= set(cfg["assumed"])
    dep = cfg["deployment"]
    assert (dep["slots"], dep["replica_concurrency"], dep["page_size"],
            dep["pool_pages"], dep["max_context_tokens"]) == (16, 16, 16, 4096, 16896)
    assert dep["engine_kwargs"] == {"prefix_cache": False}
    assert dep["chips_sharing_a_layer"] == 1


def test_the_family_builds_the_programs_configuration(cell, family):
    m = family.model_config(cell.cfg)
    assert [r.key for r in m.layer_runs()] == [
        key for key, _, _ in family.layer_runs(cell.cfg)]
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.v_head_dim) == (30, 30, 128, 128)
    assert (m.qk_norm, m.qk_norm_whole, m.post_norm, m.rope_theta) == (
        True, True, True, 0.0)
    assert (m.delta_heads, m.delta_key_dim, m.delta_value_dim, m.conv_kernel,
            m.delta_neg_eigval) == (30, 96, 192, 4, True)
    assert m.delta_width == 11520 and m.rms_eps == 1e-6
    assert m.state_kinds() == {"delta": 12} and list(m.kv_classes()) == ["full"]
    assert not m.tie_embeddings and m.max_seq_len == 16896
    with pytest.raises(ValueError, match="attention_bias"):
        family.model_config(dict(cell.cfg, attention_bias=True))
    with pytest.raises(ValueError, match="rope_theta"):
        family.model_config(dict(cell.cfg, rope_parameters={"rope_theta": 5e5}))


def test_the_seeded_gate_spreads(family):
    """``a_log`` and ``dt_bias`` as drawn: with a stream near zero the gate
    ``exp(-A softplus(dt_bias))`` of a token lies in (0.2, 1)."""
    import jax

    toy = spec.load_cell("toy-delta.toy", TOY).cfg
    p = family.make_weights(toy, 3)["blocks"]["delta.dense"]
    a = np.exp(np.asarray(p["a_log"], np.float64))
    step = np.log1p(np.exp(np.asarray(p["dt_bias"], np.float64)))
    assert 0 < a.min() and a.max() < 16
    assert 0.001 * 0.99 < step.min() and step.max() < 0.1 * 1.01
    gate = np.exp(-a * step)
    assert 0.2 < gate.min() and gate.max() < 1.0
    assert {x.dtype for x in jax.tree.leaves((p["a_log"], p["dt_bias"]))} == {
        np.dtype("float32")}


# -- the mix ----------------------------------------------------------------------

LONGDOC_LENGTHS = [2048, 2480, 2992, 3616, 4368, 5264, 6368, 7696, 9296,
                   11232, 13568, 16384]


def test_longdoc_is_the_issues_mix(cell):
    """Prompts log-uniform 2,048-16,384 under twelve padded lengths spread
    evenly in the logarithm, each a multiple of 16; answers log-uniform
    128-512; 16 at once (the slots) when the 10 s ramp-in starts; every
    prompt over 2,976 tokens is chunked, up to 19 runs of the suffix
    program."""
    mix, a = cell.mix, cell.mix["arrivals"]
    assert mix["prompt_tokens"]["round_to"] == LONGDOC_LENGTHS
    assert LONGDOC_LENGTHS == [
        int(round(2048 * 8 ** (i / 11) / 16)) * 16 for i in range(12)]
    assert (a["initial_burst"], a["ramp_in_s"], a["interarrival_cv"]) == (16, 10.0, 1.0)
    s = traffic.schedule(mix, 50)
    assert sum(1 for r in s if r.due < -a["ramp_in_s"] + 0.016) == 16
    p, o = [r.prompt_len for r in s], [r.max_new for r in s]
    assert min(p) >= 2048 - 16 and max(p) <= 16384
    assert {-(-x // 16) * 16 for x in p} <= set(LONGDOC_LENGTHS)
    # the median falls on one of the two padded lengths around 5,793
    assert 5264 - 16 <= stats.percentile(p, 50) <= 6368
    assert np.mean(p) == pytest.approx(6894, rel=0.03)
    assert min(o) >= 128 and max(o) <= 512
    assert stats.percentile(o, 50) == pytest.approx(256, rel=0.05)
    assert max(x + y for x, y in zip(p, o)) <= (
        cell.cfg["deployment"]["max_context_tokens"])

    def suffix_runs(t):
        return max(0, -(-(-(-t // 16) * 16 - 2976) // 736))

    runs = sorted({suffix_runs(x) for x in p})
    assert runs[0] == 0 and runs[-1] == 19
    assert sum(1 for x in p if suffix_runs(x)) > 0.75 * len(p)


# measured (my chip runs, PR 40, the sweep's traced run): a decode step is
# 20.5 ms on the device and the interval between two steps 25 ms with no
# admission in it; prefill takes 60 % of the busy time at 0.72 admissions a
# second of 6,884 tokens, 118 ms per 1,000 prompt tokens (a 736-token chunk
# is 90.9 ms). With those the replay below reads what the sweep read at
# 0.5 / 0.8 / 1.1 arrivals a second: 178 / 197 / 171 tokens/s (183.0 / - /
# 179.6 measured), 5 / 22 / 44 queued at the close (5 / 21 / 45), 14 / 30 /
# 51 in flight (14 / 29 / 53), 32 / 34 / 30 finished (32 / 35 / 29).
LONGDOC_STEP_S, LONGDOC_PREFILL_S_PER_KTOK = 0.025, 0.118


def replay_pool(schedule, slots, pages, page, step_s, seconds, prefill_s_per_ktok):
    """``test_traffic.replay_slots`` with the pool as the capacity: a free
    slot takes the oldest request that is due only if the pool has the
    pages of its prompt and its whole answer (the engine reserves them at
    admission, and the queue's head waits for them: ``_admit_queued``). A
    replay, not a measurement."""
    reqs = sorted(schedule, key=lambda r: r.due)
    t, due, queue, live, free = reqs[0].due, 0, [], [], pages
    tokens = finished = 0
    while t < seconds:
        while due < len(reqs) and reqs[due].due <= t:
            queue.append(reqs[due])
            due += 1
        while queue and len(live) < slots:
            need = -(-(queue[0].prompt_len + queue[0].max_new) // page)
            if need > free:
                break
            r = queue.pop(0)
            free -= need
            t += prefill_s_per_ktok * r.prompt_len / 1000.0
            tokens += 0 <= t < seconds
            live.append([r.max_new - 1, need])
        tokens += len(live) * (0 <= t < seconds)
        for left in live:
            left[0] -= 1
        free += sum(need for left, need in live if left <= 0)
        finished += sum(1 for left, _ in live if left <= 0)
        live = [x for x in live if x[0] > 0]
        t += step_s
    return {"tokens": tokens, "queued": len(queue), "in_flight": due - finished,
            "finished": finished}


def _replay(cell, steady=None, order=None, step=LONGDOC_STEP_S,
            prefill=LONGDOC_PREFILL_S_PER_KTOK):
    m, dep = cell.mix, cell.cfg["deployment"]
    a = dict(m["arrivals"])
    if steady is not None:
        a["rate_per_s"] = (steady * 60 + a["initial_burst"]) / 60
    if order is not None:
        a["order_seed"] = order
    s = traffic.schedule(dict(m, arrivals=a), 50)
    return replay_pool(
        s, dep["slots"], dep["pool_pages"] - 1, dep["page_size"], step, 50, prefill)


def test_the_replay_reads_what_the_sweep_read(cell):
    got = [_replay(cell, steady, order=0) for steady in (0.5, 0.8, 1.1)]
    assert [r["queued"] for r in got] == [5, 22, 44]
    assert [r["in_flight"] for r in got] == [14, 30, 51]
    assert [r["tokens"] / 50 for r in got] == pytest.approx([178, 197, 171], abs=1)


def test_longdoc_schedule_outlasts_the_engine(cell):
    """What the mix's ``what`` says: 16 at once, then 2.5 k = 1.05 arrivals a
    second (k = 0.42, the sweep): 79 requests, 21,882 answer tokens, 438
    tokens/s offered against some 172 delivered. From the measured step
    interval and cost of a prefill down to two thirds of both, the tokens
    in the window never fall and requests are queued at the close at every
    speed; the schedule is spent only near half of both (12.5 ms, 59 ms per
    1,000). The requests in flight at the close stay far under the rule's
    236: what binds here is the drain, a prefill for each queued prompt."""
    a = cell.mix["arrivals"]
    s = traffic.schedule(cell.mix, 50)
    assert (len(s) - a["initial_burst"]) / 60 == pytest.approx(2.5 * 0.42)
    assert (len(s), sum(r.max_new for r in s)) == (79, 21_882)
    speeds = [(1.0, 1.0), (0.9, 0.9), (0.8, 0.8), (2 / 3, 2 / 3)]
    runs = [
        _replay(cell, step=LONGDOC_STEP_S * x, prefill=LONGDOC_PREFILL_S_PER_KTOK * y)
        for x, y in speeds
    ]
    counts = [r["tokens"] for r in runs]
    assert counts == sorted(counts) and counts[-1] > 1.45 * counts[0]
    assert all(r["queued"] >= 0.15 * len(s) for r in runs)
    assert runs[0]["queued"] > 0.45 * len(s)
    assert runs[0]["in_flight"] <= 236 - 150
    # the drain: each queued prompt is prefilled before it can be ended
    assert runs[0]["queued"] * 6.884 * LONGDOC_PREFILL_S_PER_KTOK < 45
    assert _replay(cell, step=LONGDOC_STEP_S / 2,
                   prefill=LONGDOC_PREFILL_S_PER_KTOK / 2)["queued"] <= 1


def test_longdoc_order_seed_is_the_median_order_of_a_replay(cell):
    """``order_seed`` by the README's rule: of the orders 0..39 the one
    whose tokens inside the window are the median, replayed through the
    cell's slots and pool at the measured speed. The order matters more
    here than in any other cell (6,813-9,973 tokens, 35 answers finish in a
    window and which prompts fall into it decides a fifth of the time)."""
    counts = {o: _replay(cell, order=o)["tokens"] for o in range(40)}
    ranked = sorted(counts, key=lambda o: (counts[o], o))
    assert ranked.index(cell.mix["arrivals"]["order_seed"]) in (19, 20)
    assert counts[ranked[-1]] < 1.5 * counts[ranked[0]]


# -- the readers, on a hand-made ring and a hand-made trace ------------------------------


def _run(cell, spans, monkeypatch, programs=None, capture=None, busy=0.0):
    ring = engine_spans.EngineSpans(spans, 0.0, 50e6)
    monkeypatch.setattr(engine_spans, "load", lambda run: ring)
    tr = None
    if programs is not None:
        tr = trace_mod.TraceSummary(window_s=10.0, busy_s=busy, programs=programs)
    return metrics.Run(
        cfg=cell.cfg, mix=cell.mix, base=cell.base,
        peaks=peaks.PEAKS["TPU v5 lite"], t_open=0.0, t_close=50.0,
        setup_s=1.0, clients=[], decode_log=[], prefill_log=[],
        window_compiles=0, memory_peak_bytes=None, capture=capture, trace=tr)


def _span(name, ts, dur=10.0, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


def test_the_new_readers_on_a_hand_made_ring_and_trace(cell, family, monkeypatch):
    spans = [
        # 5,264 padded: 2,320 in the first program, then four chunks of 736
        _span("engine.prefill", 6e6, dur=600e3, t_pad=5264, true_len=5260,
              chunks=5, head=2320, hit_tokens=0),
        _span("engine.prefill", 8e6, dur=200e3, t_pad=2048, true_len=2040,
              chunks=1, head=2048, hit_tokens=0),
        # outside the capture, inside the window
        _span("engine.prefill", 30e6, dur=400e3, t_pad=3616, true_len=3610,
              chunks=2, head=2880, hit_tokens=0),
        _span("engine.prefill", 70e6, dur=9e6, t_pad=16384, true_len=16380,
              chunks=20, head=2400, hit_tokens=0),  # after the close
        _span("engine.first_token", 6.7e6, dur=300e3),
        _span("engine.admit", 1e6, admitted=3, pool_stall=1),
        _span("engine.decode", 3e6, live=9, state_layers=12,
              state_slots_written=108, full_pages=4000),
    ]
    programs = {
        "jit_prefill": (0.5, 2), "jit_prefill_suffix": (0.32, 4),
        "jit_decode_step": (3.0, 100),
    }
    run = _run(cell, spans, monkeypatch, programs, capture=(5.0, 15.0), busy=8.0)

    def read(name):
        return spec.load_reader(name, cell.base)(run)

    # us a token = ms per 1,000 tokens, over the window's three prompts and
    # the wait for a prompt's last logits
    assert read("prefill_span_ms_per_ktok") == pytest.approx(
        1.5e6 / (5260 + 2040 + 3610))
    assert read("prefill_chunk_device_ms") == pytest.approx(80.0)
    assert read("prefill_busy_share_pct") == pytest.approx(100 * 0.82 / 8.0)
    # the capture's four chunks of 736 lie behind 2,320, 3,056, 3,792, 4,528
    least = peaks.least_seconds(
        *family.prefill_chunk_work(cell.cfg, 736, 3424), run.peaks)
    assert read("prefill_chunk_roofline") == pytest.approx(100 * least / 0.08)
    assert 20 < read("prefill_chunk_roofline") < 35
    assert read("slot_turnover_per_s.delta") == pytest.approx(3 / 50)
    assert read("admit_pool_stalls.delta") == 1
    state = 2 * 9 * 27_371_520
    assert read("state_cache_share_pct.delta") == pytest.approx(
        100 * state / (state + 4000 * 16 * 61_440))


def test_the_new_readers_are_silent_on_the_parents_spans(cell, monkeypatch):
    """The parent's ``engine.prefill`` carries no ``head``, and a run with
    no chunk or no trace gives nothing to read: ``None``, never 0."""
    spans = [_span("engine.prefill", 6e6, t_pad=5264, true_len=5260, chunks=5)]
    programs = {"jit_prefill_suffix": (0.32, 4), "jit_prefill": (0.5, 2)}
    run = _run(cell, spans, monkeypatch, programs, capture=(5.0, 15.0), busy=8.0)
    assert spec.load_reader("prefill_chunk_roofline", cell.base)(run) is None
    assert spec.load_reader("prefill_span_ms_per_ktok", cell.base)(run) > 0
    run = _run(cell, [_span("engine.prefill", 6e6, t_pad=64, chunks=1)],
               monkeypatch, {"jit_prefill": (0.5, 2)}, capture=(5.0, 15.0), busy=8.0)
    for name in ("prefill_span_ms_per_ktok", "prefill_chunk_device_ms",
                 "prefill_chunk_roofline"):
        assert spec.load_reader(name, cell.base)(run) is None
    run = _run(cell, [], monkeypatch)  # an untraced run
    for name in ("prefill_chunk_device_ms", "prefill_chunk_roofline",
                 "prefill_busy_share_pct"):
        assert spec.load_reader(name, cell.base)(run) is None
    monkeypatch.setattr(engine_spans, "load", lambda run: None)
    assert spec.load_reader("prefill_span_ms_per_ktok", cell.base)(run) is None


def test_every_new_entry_has_a_file_and_lists_the_cell_alone(cell):
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ours = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(ours) == 19 and bench["per_layer"][-19:] == ours
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in ours:
        assert m["moves"] == "tokens_per_s"
        assert callable(spec.load_reader(m["name"], cell.base))
        if m["name"].endswith(".delta"):  # an alias keeps its original's entry
            plain = by_name[m["name"][: -len(".delta")]]
            assert [m[k] for k in ("unit", "better", "source", "layer")] == [
                plain[k] for k in ("unit", "better", "source", "layer")]
    assert {m["name"] for m in cell.per_layer} >= {m["name"] for m in ours}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers", "layer_types"]


# -- a toy of the family through the whole run ----------------------------------------


def toy_run(seed, seconds, control=False, trace=False):
    import jax

    cell = spec.load_cell("toy-delta.toy", TOY)
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
    assert got["prefill_span_ms_per_ktok"]["value"] > 0
    assert got["schedule_unspent_pct.delta"]["value"] >= 0
    # no device plane on a CPU: every device metric stays out of the line
    for name in ("decode_step_roofline", "prefill_chunk_device_ms",
                 "prefill_chunk_roofline", "prefill_busy_share_pct"):
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
