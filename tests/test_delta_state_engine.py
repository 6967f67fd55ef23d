"""A stack three of whose four layers are the gated delta rule, through the
paged engine at toy size on the CPU: linear-attention layers whose float32
matrix of state a head, and the last columns of q, k and v before their
short convolution, live by slot beside the paged KV; full multi-head
attention with a norm over the whole of q and of k and no rotary; the
block's two norms after the operator and after the feed-forward; against
the benchmark's plain reference (``benchmarks/references/gated_delta_mha.py``),
which keeps no state from call to call and runs the recurrence one token
after another. float32, seeded weights, the key names of
``olmo-hybrid-7b-l16``.

What these tests were seen to catch, each by an edit of the program made
once by hand and taken back (PR 40): the state taken at the padded end of
a prompt (``scan_sequence`` without its mask, or ``shift_sequence`` slicing
at ``t``): the decoded tokens of every prompt whose length is no whole page
fail the reference; a prefill that starts from the slot's rows and not
from zeros: the second request through a slot fails ``test_a_recycled_slot
...``; the suffix program starting from zeros: every chunked prompt fails;
``S`` rounded to bfloat16 wherever it is kept: ``test_a_bfloat16_state_
fails...`` holds that one as a test; an inactive slot's ``S`` written:
``test_an_idle_slot_keeps_its_state...`` fails and nothing else, by design
(its rows are read by no one before a prefill overwrites them)."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

from ray_tpu.llm import continuous  # noqa: E402
from ray_tpu.llm.continuous import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.llm.engine import GenerationConfig  # noqa: E402
from ray_tpu.models import transformer as tfm  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402

# two periods of the cell's pattern at toy widths: heads of 8 x 16 in the
# linear layers (3 of them), 4 heads of 16 on 4 KV heads in the full ones
TOY = {
    "name": "toy-delta", "family": "gated_delta_mha",
    "reference": "gated_delta_mha", "model_type": "olmo_hybrid",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "hidden_act": "silu", "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "num_hidden_layers": 8,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "vocab_size": 512,
    "embedding_std": 0.125, "torch_dtype": "float32",
    "deployment": {"max_context_tokens": 128},
}
PAGE = 4
# float32 against float32 at `highest`: what the order of the sums leaves.
# The program sums a block of 8 tokens of the recurrence as matrix
# products and solves a triangular system where the reference goes token
# by token; over 60 tokens and six such layers the logits (deviation 1)
# differ by under 1e-4. A state kept in bfloat16 (8 bits) moves them by
# 1e-2, a hundred times this.
TOL = 2e-4
RUNS = ["delta.dense", "full.dense", "delta.dense.1", "full.dense.1"]


@pytest.fixture(scope="module")
def toy():
    family = spec.load_family(TOY, BENCH)
    reference = spec.load_reference(TOY, BENCH)
    return family.model_config(TOY), family.make_weights(TOY, 5), reference


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """One prefill program takes 16 tokens at the toy's 4 heads, the rest of
    a prompt goes through the suffix program in chunks of 4, which has to
    carry the state; the scan's block is 8 tokens, so the 16-token program
    runs two blocks and a chunk of 4 a ragged one."""
    monkeypatch.setattr(continuous, "PREFILL_SCORES_BYTES", 4 * 4 * 16 * 16)
    monkeypatch.setattr(tfm, "DELTA_BLOCK", 8)


def make_engine(toy, on_tpu=False, **kw):
    kw = {"max_batch": 3, "page_size": PAGE, "n_pages": 64, **kw}
    with pytest.MonkeyPatch.context() as m:
        if on_tpu:  # the pool's rows as the chip stores them
            m.setattr(jax, "default_backend", lambda: "tpu")
        eng = ContinuousBatchingEngine(toy[0], toy[1], **kw)
    if on_tpu:
        eng._attn_kernel = "interpret"
    assert (eng.max_prefill_tokens, eng.prefill_chunk) == (16, 4)
    return eng


def reference_logits(toy, tokens, quant=None, cfg=TOY):
    """Reference logits at every position of ``tokens``."""
    t = len(tokens)
    padded = np.zeros(80, np.int32)  # one length: one compile
    padded[:t] = tokens
    return np.asarray(toy[2].reference_logits(
        toy[1], cfg, jnp.asarray(padded), jnp.arange(80), quant=quant
    ))[:t]


def capture_prefill_logits(eng):
    """What each run of the two prefill programs returns, in order: the
    position of its last real token in the prompt, and that token's
    logits (the one row the host reads)."""
    seen = []
    for name in ("_prefill", "_prefill_suffix"):
        program = getattr(eng, name)

        def spied(*a, _program=program, _suffix=name == "_prefill_suffix", **kw):
            out = _program(*a, **kw)
            at = int(a[-1]) - 1 + (int(a[5]) if _suffix else 0)
            seen.append((at, np.asarray(out[0][0])))
            return out

        setattr(eng, name, spied)
    return seen


def served(eng, prompt, new):
    """(the positions the prefill runs returned a row for, those rows, the
    tokens) of one request run alone through ``eng``."""
    seen = capture_prefill_logits(eng)
    (out,) = eng.generate_ids([prompt], GenerationConfig(max_new_tokens=new))
    return [p for p, _ in seen], np.stack([r for _, r in seen]), out


def gaps(toy, prompt, out, cfg=TOY):
    """The reference's best logit less its logit of each served token."""
    want = reference_logits(toy, prompt + out, cfg=cfg)[len(prompt) - 1 : -1]
    return want.max(-1) - want[np.arange(len(out)), out]


def test_the_stack_is_cut_into_four_runs_and_keeps_two_kinds_of_state(toy):
    cfg, params, _ = toy
    runs = cfg.layer_runs()
    assert [r.key for r in runs] == RUNS and set(params["blocks"]) == set(RUNS)
    assert [r.count for r in runs] == [3, 1, 3, 1]
    assert [r.cache_start for r in runs] == [0, 0, 3, 1]
    assert list(cfg.kv_classes()) == ["full"]
    assert cfg.state_kinds() == {"delta": 6} and cfg.state_layers == 6
    assert (cfg.post_norm, cfg.qk_norm, cfg.qk_norm_whole) == (True, True, True)
    assert cfg.rope_theta == 0.0 and cfg.delta_width == 3 * (8 + 8 + 16)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params["blocks"])
    assert shapes == jax.tree.map(
        lambda a: (a.shape, a.dtype),
        tfm.init_params(cfg, jax.random.PRNGKey(0))["blocks"],
    )
    state = make_engine(toy).pool.state
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "delta_taps": ((6, 3, 3, 96), jnp.float32),
        "delta_s": ((6, 3, 3, 8, 16), jnp.float32),
    }
    bf16 = ContinuousBatchingEngine(
        dataclasses.replace(cfg, dtype=jnp.bfloat16),
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        max_batch=2, page_size=PAGE, n_pages=64,
    ).pool
    assert bf16.state["delta_taps"].dtype == jnp.bfloat16
    assert bf16.state["delta_s"].dtype == jnp.float32
    assert bf16.state_bytes_per_slot == 6 * (3 * 8 * 16 * 4 + 3 * 96 * 2)


# -- (a) the chunked scan against the recurrence, one token after another ------


def _recurrence(state, q, k, v, g, beta):
    def one(s, x):
        o, s = tfm.delta_step(s, *x)
        return s, o

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    return o, state


@pytest.mark.parametrize("from_zero", [True, False], ids=["zero", "carried"])
@pytest.mark.parametrize(
    "t, block", [(64, 16), (37, 8), (5, 8), (130, 64), (46, 16), (8, 8)]
)
def test_the_scan_over_blocks_is_the_recurrence(t, block, from_zero):
    """Block lengths that do and do not divide T (a ragged last block, a
    block longer than the sequence), from a zero and from a non-zero state;
    gates over (0.2, 1), beta over (0, 2), unit keys, as the layer makes
    them."""
    heads, dk, dv = 3, 12, 20
    ks = jax.random.split(jax.random.PRNGKey(t * 100 + block), 6)
    q, k = (jax.random.normal(x, (t, heads, dk)) for x in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (t, heads, dv))
    g = -1.6 * jax.random.uniform(ks[3], (t, heads))
    beta = 2.0 * jax.random.uniform(ks[4], (t, heads))
    state = jnp.zeros((heads, dk, dv)) if from_zero else (
        jax.random.normal(ks[5], (heads, dk, dv)))
    want_o, want_s = _recurrence(state, q, k, v, g, beta)
    got_o, got_s = tfm.delta_scan(state, q, k, v, g, beta, block)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=0)
    # a token with gate 1 and beta 0 changes nothing: the state after the
    # first 3 tokens is the state of a block whose rest is so masked
    real = (jnp.arange(t) < 3)[:, None]
    _, cut = tfm.delta_scan(
        state, q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
        block,
    )
    _, first = _recurrence(state, q[:3], k[:3], v[:3], g[:3], beta[:3])
    np.testing.assert_allclose(cut, first, atol=2e-5, rtol=0)


def test_a_block_of_the_scan_holds_a_power_of_two():
    x = jnp.zeros((5, 1, 2))
    with pytest.raises(ValueError, match="power of two"):
        tfm.delta_scan(jnp.zeros((1, 2, 2)), x, x, x, x[..., 0], x[..., 0], 6)


# -- (b) prefill, then decode through cache and state, against the reference --


@pytest.mark.parametrize("on_tpu", [False, True], ids=["gather", "kernel_whole_tiles"])
def test_prefill_then_decode_agrees_with_the_reference(toy, on_tpu):
    """Prompts whose true length is not their padded length (37, 5, 23;
    16 is whole pages), and prompts longer than one prefill program: 37 is
    16 tokens and then six runs of the suffix program, 23 two, the state
    carried from run to run in the slot's rows. Logits, not tokens: the row
    each prefill run returns (its last real token's) against the
    reference's at that position; a decoded token by the reference's
    logit of it against the reference's best at that position. With
    ``on_tpu`` the pool is built as on the chip, rows of 16 stored in whole
    tiles of 128, and the full layers go through the Pallas kernel in
    groups of one, interpreted."""
    eng = make_engine(toy, on_tpu)
    assert (eng.pool.k_dim, eng.pool.v_dim) == ((128, 128) if on_tpu else (16, 16))
    rng = np.random.default_rng(0)
    new = 24
    for n in (37, 5, 16, 23):  # one at a time: the captures are this prompt's
        prompt = rng.integers(0, 512, n).tolist()
        at, got, out = served(eng, prompt, new)
        assert len(out) == new and at[-1] == n - 1
        want = reference_logits(toy, prompt)
        np.testing.assert_allclose(got, want[at], atol=TOL, rtol=0)
        assert gaps(toy, prompt, out).max() <= TOL
        # an altered token must fail: the reference does not put it first
        wrong = list(out)
        wrong[7] = (wrong[7] + 1) % 512
        assert gaps(toy, prompt, wrong)[7] > 100 * TOL
    assert eng.pool.free_pages == eng.pool.usable_pages


def test_a_batch_of_mixed_lengths_agrees_with_the_reference(toy):
    """Short and long contexts in one decode batch, admitted as others
    finish into slots that others held: each sequence's state is its
    own."""
    eng = make_engine(toy)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 41, 18, 9, 30, 2, 21)]
    outs = eng.generate_ids(prompts, GenerationConfig(max_new_tokens=20))
    for prompt, out in zip(prompts, outs):
        assert gaps(toy, prompt, out).max() <= TOL


def test_a_bfloat16_state_fails_the_comparison(toy, monkeypatch):
    """``S`` rounded to bfloat16 wherever a program leaves it (after each
    decoded token, at the end of each run of a prefill program): the
    prefill's logits and the decoded tokens' leave the tolerance by far."""
    def rounded(fn, at):
        def wrapper(*a, **kw):
            out = list(fn(*a, **kw))
            out[at] = out[at].astype(jnp.bfloat16).astype(jnp.float32)
            return tuple(out)

        return wrapper

    monkeypatch.setattr(tfm, "delta_step", rounded(tfm.delta_step, 1))
    monkeypatch.setattr(tfm, "delta_scan", rounded(tfm.delta_scan, 1))
    eng = make_engine(toy, max_batch=1)
    prompt = np.random.default_rng(0).integers(0, 512, 37).tolist()
    at, got, out = served(eng, prompt, 24)
    assert np.abs(got - reference_logits(toy, prompt)[at]).max() > 10 * TOL
    want = reference_logits(toy, prompt + out)[36:-1]
    teacher = np.abs(want.max(-1) - want[np.arange(24), out]).max()
    logits_off = np.abs(got[-1] - want[0]).max()
    assert max(teacher, logits_off) > 10 * TOL


def test_the_int8_control_fails_the_comparison(toy):
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 512, 48).tolist()
    want = reference_logits(toy, tokens)
    low = reference_logits(toy, tokens, quant="int8")
    assert np.abs(low - want).max() > 10 * TOL
    first = low.argmax(-1)
    assert (want.max(-1) - want[np.arange(48), first]).max() > 10 * TOL


# -- (c) the family's small switches: each, set wrong, fails the reference ------


def _qk_norm_a_head(cfg, params):
    blocks = {
        key: {**p, **{
            n: jnp.ones((p[n].shape[0], cfg.head_dim), p[n].dtype)
            for n in ("q_norm", "k_norm") if n in p
        }}
        for key, p in params["blocks"].items()
    }
    return dataclasses.replace(cfg, qk_norm_whole=False), {**params, "blocks": blocks}


@pytest.mark.parametrize(
    "wrong",
    [
        lambda cfg, p: (dataclasses.replace(cfg, delta_neg_eigval=False), p),
        lambda cfg, p: (dataclasses.replace(cfg, rope_theta=1e4), p),
        _qk_norm_a_head,
        lambda cfg, p: (dataclasses.replace(cfg, post_norm=False), p),
    ],
    ids=["beta_without_its_2", "a_rotary", "qk_norm_a_head", "norm_before"],
)
def test_a_switch_set_wrong_fails_the_reference(toy, wrong):
    cfg, params = wrong(toy[0], toy[1])
    eng = make_engine((cfg, params, toy[2]), max_batch=1)
    prompt = np.random.default_rng(5).integers(0, 512, 21).tolist()
    at, got, _ = served(eng, prompt, 2)
    assert np.abs(got - reference_logits(toy, prompt)[at]).max() > 100 * TOL


# -- (d) a slot that changes hands ----------------------------------------------


def alone(toy, prompt, new):
    return served(make_engine(toy, max_batch=1), prompt, new)


def assert_same(a, b):
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])
    assert a[2] == b[2]


def test_a_recycled_slot_reads_nothing_of_its_former_occupant(toy):
    """Two requests through one slot in turn: the second's logits and
    tokens are those it gives alone in a fresh engine, to the bit. Lengths
    that leave the first occupant's state, and its padding's, in the
    rows."""
    rng = np.random.default_rng(3)
    first, second = (rng.integers(0, 512, n).tolist() for n in (21, 6))
    eng = make_engine(toy, max_batch=1)
    served(eng, first, 9)
    assert_same(served(eng, second, 12), alone(toy, second, 12))


@pytest.mark.parametrize("how", ["evicted", "cancelled"])
def test_an_answer_ended_from_outside_leaves_no_state_behind(toy, how):
    rng = np.random.default_rng(4)
    first, second = (rng.integers(0, 512, n).tolist() for n in (10, 19))
    eng = make_engine(toy, max_batch=1)
    if how == "evicted":
        rid = eng.submit(first, GenerationConfig(max_new_tokens=30))
        for _ in range(5):
            eng.step()
        eng._force_evict_active()
        assert len(eng.results.pop(rid)) == 6  # the prefill's and five steps'
    else:
        stream = eng.stream_ids(first, GenerationConfig(max_new_tokens=30))
        assert len([next(stream) for _ in range(5)]) == 5
        stream.close()  # the consumer goes away mid-stream
    assert not any(s.active for s in eng.slots)
    assert eng.pool.free_pages == eng.pool.usable_pages
    for name in ("delta_taps", "delta_s"):
        assert np.abs(np.asarray(eng.pool.state[name])).max() > 0
    assert_same(served(eng, second, 8), alone(toy, second, 8))


def test_an_idle_slot_keeps_its_state_and_a_live_one_moves_on(toy):
    eng = make_engine(toy, max_batch=3)
    rid = eng.submit([5, 6, 7, 8, 9], GenerationConfig(max_new_tokens=6))
    eng.step()
    before = {k: np.asarray(v) for k, v in eng.pool.state.items()}
    eng.step()
    after = {k: np.asarray(v) for k, v in eng.pool.state.items()}
    taps = "delta_taps"
    np.testing.assert_array_equal(after[taps][:, 0, 0], before[taps][:, 1, 0])
    assert np.abs(after[taps][:, 2, 0] - before[taps][:, 2, 0]).max() > 0
    assert np.abs(after["delta_s"][:, 0] - before["delta_s"][:, 0]).max() > 0
    np.testing.assert_array_equal(after[taps][:, :, 1:], before[taps][:, :, 1:])
    np.testing.assert_array_equal(after["delta_s"][:, 1:], before["delta_s"][:, 1:])
    while rid not in eng.results:
        eng.step()


# -- (e) spans, and what the system cannot do for such a model yet ------------------


def test_spans_carry_the_state_and_scan_counts(toy):
    tracing.SPANS.clear()
    eng = make_engine(toy)
    eng.generate_ids([list(range(1, 30)), [7, 8]], GenerationConfig(max_new_tokens=10))
    spans = tracing.SPANS.slices(cat="engine")
    prefills = [s["args"] for s in spans if s["name"] == "engine.prefill"]
    # 29 tokens: 32 padded, 16 in the prefill program and four chunks of 4
    assert [(p["t_pad"], p["true_len"], p["chunks"], p["head"]) for p in prefills] == [
        (32, 29, 5, 16), (4, 2, 1, 4)]
    assert [p["state_written"] for p in prefills] == [6 * 5, 6]
    # blocks of 8 tokens: two in the 16-token program, one a chunk of 4
    assert [p["scan_blocks"] for p in prefills] == [6 * (2 + 4), 6]
    decodes = [s["args"] for s in spans if s["name"] == "engine.decode"]
    assert decodes and max(d["live"] for d in decodes) == 2
    a_slot = 6 * (3 * 8 * 16 * 4 + 3 * 96 * 4)  # float32 toy: 4-byte columns
    assert eng.pool.state_bytes_per_slot == a_slot
    for d in decodes:
        assert d["state_layers"] == 6
        assert d["state_slots_written"] == 6 * d["live"]
        assert d["state_bytes"] == 2 * d["live"] * a_slot
        assert d["full_pages"] == d["pages_written"]
        assert d["attn_full_layers"] == 2 and "window_pages" not in d


class _Cache:
    page = PAGE


@pytest.mark.parametrize(
    "call",
    [
        lambda toy: make_engine(toy, prefix_cache=_Cache()),
        lambda toy: make_engine(toy).prefill_extract(
            [1, 2, 3], GenerationConfig(max_new_tokens=2)),
        lambda toy: make_engine(toy).adopt_pages({}, None, None),
        lambda toy: make_engine(toy).swap_params(toy[1]),
        lambda toy: tfm.forward(toy[1], jnp.zeros((1, 4), jnp.int32), toy[0]),
        lambda toy: tfm.make_train_step(toy[0], None),
    ],
    ids=["prefix_cache", "prefill_extract", "adopt_pages", "swap_params",
         "forward", "train_step"],
)
def test_a_path_that_lacks_the_feature_raises_a_typed_error(toy, call):
    with pytest.raises(tfm.UnsupportedModelFeature, match="delta|attn_pattern"):
        call(toy)


def test_the_small_switches_are_refused_where_they_are_not_implemented():
    for field, value in (("post_norm", True), ("rope_theta", 0.0)):
        with pytest.raises(tfm.UnsupportedModelFeature, match=field):
            tfm.ModelConfig(**{field: value}).require_uniform_dense("forward")
    with pytest.raises(ValueError, match="qk_norm_whole"):
        tfm.ModelConfig(qk_norm_whole=True)
    with pytest.raises(ValueError, match="delta_heads"):
        tfm.ModelConfig(n_layers=1, attn_pattern=("delta",),
                        ffn_pattern=("dense",), conv_kernel=4)
    with pytest.raises(ValueError, match="conv_kernel"):
        tfm.ModelConfig(n_layers=1, attn_pattern=("delta",),
                        ffn_pattern=("dense",), delta_heads=1,
                        delta_key_dim=2, delta_value_dim=2)


# -- (f) the configurations the benchmark had: the parent's, to rounding --------

PARENT = os.path.join(HERE, "data", "parent_outputs.json")
OLD_TOYS = {
    "dense": "toy/configs/toy-gqa.json",
    "mimo": "toy_moe/configs/toy-moe-window.json",
    "lfm2": "toy_conv/configs/toy-moe-conv.json",
    "olmo": "toy_delta/configs/toy-delta.json",
}


def old_toy_outputs(name, bench=BENCH):
    """Two requests through one slot of a toy of a family the benchmark
    had (the second after the first, so a slot changes hands), the first
    longer than one prefill program: the decoded tokens and the row of
    logits each prefill run gives the host (its last real token's), as the
    first eight logits and the row's sum and sum of magnitudes in float64.
    ``python tests/test_delta_state_engine.py <checkout>`` writes the file
    these are compared with, from that checkout's program (whose prefill
    programs returned every row of logits: the one read is taken)."""
    with open(os.path.join(bench, "tests", OLD_TOYS[name])) as f:
        cfg = json.load(f)
    family = spec.load_family(cfg, bench)
    page = cfg["deployment"]["page_size"]
    saved = continuous.PREFILL_SCORES_BYTES, tfm.DELTA_BLOCK
    continuous.PREFILL_SCORES_BYTES = 4 * cfg["num_attention_heads"] * 32 * 32
    tfm.DELTA_BLOCK = 8  # the Olmo toy's scan: several blocks a run
    try:
        eng = ContinuousBatchingEngine(
            family.model_config(cfg), family.make_weights(cfg, 7),
            max_batch=1, page_size=page, n_pages=64,
        )
        rng = np.random.default_rng(11)
        at, rows, tokens = [], [], []
        for n in (53, 18):
            prompt = rng.integers(0, cfg["vocab_size"], n).tolist()
            where, got, out = served(eng, prompt, 12)
            at.append(where)
            rows.append(np.asarray(got, np.float32))
            tokens.append(out)
    finally:
        continuous.PREFILL_SCORES_BYTES, tfm.DELTA_BLOCK = saved
    rows = np.concatenate(rows)
    assert len(at[0]) > 1  # the first prompt ran in chunks
    return {
        "dtype": cfg["torch_dtype"],
        "tokens": tokens,
        "positions": at,
        "rows_head": rows[:, :8].tolist(),
        "rows_moments": [
            [float(r.sum(dtype=np.float64)), float(np.abs(r).sum(dtype=np.float64))]
            for r in rows
        ],
    }


@pytest.mark.parametrize("name", sorted(OLD_TOYS))
def test_the_families_the_benchmark_had_give_the_parents_outputs(name):
    """A dense, a MiMo, an LFM2 and an Olmo toy take none of the parallel
    layer's branches: their tokens are what the parent commit's program
    gave on this machine, and the row of logits each prefill run gives the
    host is the parent's to rounding: the head now runs over that row
    alone where it ran over every row of the run, a product of another
    shape, which sums in another order on the CPU."""
    with open(PARENT) as f:
        want = json.load(f)[name]
    got = old_toy_outputs(name)
    for key in ("dtype", "tokens", "positions"):
        assert got[key] == want[key]
    # float32 logits move by a few 1e-7 and a row's sums (of 512 or 4,096
    # logits) by 1e-4; the dense toy's head is a bfloat16 product, whose
    # last bit is 0.4 % of a logit: 0.03, and 2 in a row's sum of 4,096
    row, moments = {"float32": (1e-5, 1e-3), "bfloat16": (0.05, 5.0)}[got["dtype"]]
    np.testing.assert_allclose(got["rows_head"], want["rows_head"], rtol=0, atol=row)
    np.testing.assert_allclose(
        got["rows_moments"], want["rows_moments"], rtol=0, atol=moments
    )


def _every_row_program(eng):
    """``capture_prefill_logits`` for a program whose prefill runs return
    every row of logits: the row the host read of each."""
    seen = []
    for name in ("_prefill", "_prefill_suffix"):
        program = getattr(eng, name)

        def spied(*a, _program=program, _suffix=name == "_prefill_suffix", **kw):
            out = _program(*a, **kw)
            at = int(a[-1]) - 1 + (int(a[5]) if _suffix else 0)
            row = np.asarray(out[0][0])
            seen.append((at, row if row.ndim == 1 else row[int(a[-1]) - 1]))
            return out

        setattr(eng, name, spied)
    return seen


if __name__ == "__main__":  # python tests/test_delta_state_engine.py <checkout>
    bench = os.path.join(os.path.abspath(sys.argv[1]), "benchmarks")
    assert tfm.__file__.startswith(os.path.abspath(sys.argv[1])), tfm.__file__
    capture_prefill_logits = _every_row_program
    with open(PARENT, "w") as f:
        json.dump({n: old_toy_outputs(n, bench) for n in sorted(OLD_TOYS)}, f, indent=1)
        f.write("\n")
