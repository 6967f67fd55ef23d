"""A stack of parallel hybrid blocks through the paged engine at toy size
on the CPU: in every layer grouped-query attention (groups of 5 query heads
on a KV head, as in the cell) and a Mamba-2 mixer over the same normed
input, their scaled outputs summed into one residual add; the attention's
K and V in pages of the ``full`` class and the mixer's float32 matrix a
head and the last columns of x, B and C before its short convolution by
slot, written by the same programs; the muP multipliers of Falcon-H1.
Against the benchmark's plain reference
(``benchmarks/references/parallel_ssm_gqa.py``), which keeps no state from
call to call and runs the recurrence one token after another. float32,
weights seeded as the cell's are (unit gain through each multiplier), the
key names of ``falcon-h1-34b-l6``.

What these tests were seen to catch, each by an edit of the program made
once by hand and taken back: the SSM's state taken at a padded prompt's
end (``scan_sequence`` without its mask on ``v``): every prompt whose
length is no whole page fails ``test_prefill_then_decode...``; the suffix
program starting from zeros: every chunked prompt fails it; a parallel
layer's row of state counted as its row of pages (``run_stack``'s
``in_state`` without ``state_start``, the same row in the cell, where
every layer is alike): ``test_a_mixed_stack...`` fails; ``S`` rounded to
bfloat16: ``test_a_bfloat16_state_fails...`` holds that one as a test.
"""
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

with open(os.path.join(BENCH, "configs", "falcon-h1-34b-l6.json")) as _f:
    CELL = json.load(_f)
# the cell's file at toy widths: 10 heads of 16 on 2 KV heads (groups of 5),
# a mixer of 8 heads of 8 with a state of 16 in 2 groups, every multiplier
# and switch the cell's
TOY = dict(
    CELL, name="toy-ssm", hidden_size=64, num_attention_heads=10,
    num_key_value_heads=2, head_dim=16, intermediate_size=96,
    num_hidden_layers=3, vocab_size=512, mamba_d_ssm=64, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2, rope_theta=1e4,
    torch_dtype="float32", deployment={"max_context_tokens": 128},
)
PAGE = 4
# float32 against float32 at `highest`: what the order of the sums leaves.
# The program sums a block of 8 tokens of the recurrence as matrix products
# where the reference goes token by token; over 60 tokens and three layers
# the logits (deviation 3) differ by under 1e-4. A state kept in bfloat16
# (8 bits) moves them by 1e-2, a hundred times this.
TOL = 2e-4


@pytest.fixture(scope="module")
def toy():
    family = spec.load_family(TOY, BENCH)
    reference = spec.load_reference(TOY, BENCH)
    return family.model_config(TOY), family.make_weights(TOY, 5), reference


@pytest.fixture(autouse=True)
def small_programs(monkeypatch):
    """One prefill program takes 16 tokens at the toy's 10 heads, the rest
    of a prompt goes through the suffix program in chunks of 4, which has
    to carry the state; the scan's block is 8 tokens, so the 16-token
    program runs two blocks and a chunk of 4 a ragged one."""
    monkeypatch.setattr(continuous, "PREFILL_SCORES_BYTES", 4 * 10 * 16 * 16)
    monkeypatch.setattr(tfm, "DELTA_BLOCK", 8)


def make_engine(toy, on_tpu=False, **kw):
    kw = {"max_batch": 3, "page_size": PAGE, "n_pages": 64, **kw}
    with pytest.MonkeyPatch.context() as m:
        if on_tpu:  # the pool's rows as the chip stores them
            m.setattr(jax, "default_backend", lambda: "tpu")
        eng = ContinuousBatchingEngine(toy[0], toy[1], **kw)
    if on_tpu:
        eng._attn_kernel = eng._state_kernel = "interpret"
    assert (eng.max_prefill_tokens, eng.prefill_chunk) == (16, 4)
    return eng


def reference_logits(toy, tokens, quant=None):
    """Reference logits at every position of ``tokens``."""
    t = len(tokens)
    padded = np.zeros(80, np.int32)  # one length: one compile
    padded[:t] = tokens
    return np.asarray(toy[2].reference_logits(
        toy[1], TOY, jnp.asarray(padded), jnp.arange(80), quant=quant
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


def gaps(toy, prompt, out):
    """The reference's best logit less its logit of each served token."""
    want = reference_logits(toy, prompt + out)[len(prompt) - 1 : -1]
    return want.max(-1) - want[np.arange(len(out)), out]


def prefill_off(toy, eng, prompt):
    """How far the prefill's rows lie from the reference's, at most."""
    at, got, _ = served(eng, prompt, 2)
    return np.abs(got - reference_logits(toy, prompt)[at]).max()


def test_one_run_of_layers_keeps_pages_and_state(toy):
    cfg, params, _ = toy
    runs = cfg.layer_runs()
    assert [r.key for r in runs] == ["parallel.dense"]
    assert set(params["blocks"]) == {"parallel.dense"}
    assert (runs[0].count, runs[0].cache_start, runs[0].state_start) == (3, 0, 0)
    kind = runs[0].attn
    assert (kind.name, kind.state, kind.kv_heads, kind.rope_theta) == (
        "full", "ssm", 2, 1e4)
    assert list(cfg.kv_classes()) == ["full"] and cfg.kv_classes()["full"][0] == 3
    assert cfg.state_kinds() == {"ssm": 3} and cfg.state_layers == 3
    assert cfg.state_patterns() == ("parallel",)
    assert cfg.n_heads // cfg.n_kv_heads == 5 and cfg.head_dim == 16
    assert (cfg.ssm_inner, cfg.ssm_width) == (64, 64 + 2 * 2 * 16)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params["blocks"])
    assert shapes == jax.tree.map(
        lambda a: (a.shape, a.dtype),
        tfm.init_params(cfg, jax.random.PRNGKey(0))["blocks"],
    )
    pool = make_engine(toy).pool
    assert {k: (v.shape, v.dtype) for k, v in pool.state.items()} == {
        "ssm_taps": ((3, 3, 3, 128), jnp.float32),
        "ssm_s": ((3, 3, 8, 16, 8), jnp.float32),
    }
    assert pool.k["full"].shape == (3, 2, 64, PAGE, 16)
    bf16 = ContinuousBatchingEngine(
        dataclasses.replace(cfg, dtype=jnp.bfloat16),
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        max_batch=2, page_size=PAGE, n_pages=64,
    ).pool
    assert bf16.state["ssm_taps"].dtype == jnp.bfloat16
    assert bf16.state["ssm_s"].dtype == jnp.float32
    assert bf16.state_bytes_per_slot == 3 * (8 * 16 * 8 * 4 + 3 * 128 * 2)


# -- (a) the chunked scan against the recurrence, one token after another ------


def _recurrence(state, q, k, v, g):
    def one(s, x):
        o, s = tfm.delta_step(s, *x)
        return s, o

    state, o = jax.lax.scan(one, state, (q, k, v, g))
    return o, state


def _by_hand(state, c, b, v, g):
    """The SSD recurrence written out: S = exp(g) S + b v^T; y = S^T c."""
    out = []
    for t in range(g.shape[0]):
        state = (jnp.exp(g[t])[:, None, None] * state
                 + b[t][:, :, None] * v[t][:, None, :])
        out.append(jnp.sum(state * c[t][:, :, None], axis=1))
    return jnp.stack(out), state


@pytest.mark.parametrize("from_zero", [True, False], ids=["zero", "carried"])
@pytest.mark.parametrize(
    "t, block", [(64, 16), (37, 8), (5, 8), (130, 64), (46, 16), (8, 8)]
)
def test_the_scan_over_blocks_is_the_ssd_recurrence(t, block, from_zero):
    """The diagonal transition (``beta`` None): block lengths that do and
    do not divide T (a ragged last block, a block longer than the
    sequence), from a zero and from a non-zero state; decays over (0.2, 1)
    and B, C, dt x of the sizes the layer makes, one head's B and C its
    group's."""
    heads, groups, n, size = 4, 2, 12, 20
    ks = jax.random.split(jax.random.PRNGKey(t * 100 + block), 6)
    c = jax.random.normal(ks[0], (t, groups, n))
    b = jax.random.normal(ks[1], (t, groups, n))
    c, b = (jnp.repeat(y, heads // groups, axis=1) for y in (c, b))
    dt = jax.random.uniform(ks[2], (t, heads), minval=0.01, maxval=0.2)
    v = dt[..., None] * jax.random.normal(ks[3], (t, heads, size))
    g = -dt * jax.random.uniform(ks[4], (heads,), minval=1.0, maxval=8.0)
    state = jnp.zeros((heads, n, size)) if from_zero else (
        jax.random.normal(ks[5], (heads, n, size)))
    want_o, want_s = _by_hand(state, c, b, v, g)
    step_o, step_s = _recurrence(state, c, b, v, g)
    np.testing.assert_allclose(step_o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(step_s, want_s, atol=1e-5, rtol=1e-5)
    got_o, got_s = tfm.delta_scan(state, c, b, v, g, None, block)
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=5e-5, rtol=1e-5)
    # a token with g = 0 and v = 0 changes nothing: the state after the
    # first 3 tokens is the state of a block whose rest is so masked
    real = (jnp.arange(t) < 3)[:, None]
    _, cut = tfm.delta_scan(
        state, c, b, jnp.where(real[..., None], v, 0.0), jnp.where(real, g, 0.0),
        None, block,
    )
    _, first = _by_hand(state, c[:3], b[:3], v[:3], g[:3])
    np.testing.assert_allclose(cut, first, atol=5e-5, rtol=1e-5)


# -- (b) prefill, then decode through pages and state, against the reference ---


@pytest.mark.parametrize("on_tpu", [False, True], ids=["gather", "kernel_groups_of_5"])
def test_prefill_then_decode_agrees_with_the_reference(toy, on_tpu):
    """Prompts whose true length is not their padded length (37, 5, 23;
    16 is whole pages), and prompts longer than one prefill program: 37 is
    16 tokens and then six runs of the suffix program, 23 two, pages and
    state carried from run to run. Logits, not tokens: the row each
    prefill run returns (its last real token's) against the reference's at
    that position; a decoded token by the reference's logit of it against
    the reference's best at that position. With ``on_tpu`` the pool is
    built as on the chip, rows of 16 stored in whole tiles of 128, the
    attention goes through the Pallas kernel in groups of 5 and the
    Mamba-2 state through the state kernel, both interpreted."""
    eng = make_engine(toy, on_tpu)
    assert (eng.pool.k_dim, eng.pool.v_dim) == ((128, 128) if on_tpu else (16, 16))
    rng = np.random.default_rng(0)
    new = 24
    for n in (37, 5, 16, 23):  # one at a time: the captures are this prompt's
        prompt = rng.integers(0, 512, n).tolist()
        at, got, out = served(eng, prompt, new)
        assert len(out) == new and at[-1] == n - 1
        assert len(at) == 1 + max(0, -(-(-(-n // PAGE) * PAGE - 16) // 4))
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
    finish into slots that others held: each sequence's pages and state
    are its own."""
    eng = make_engine(toy)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 41, 18, 9, 30, 2, 21)]
    outs = eng.generate_ids(prompts, GenerationConfig(max_new_tokens=20))
    for prompt, out in zip(prompts, outs):
        assert gaps(toy, prompt, out).max() <= TOL


def test_a_bfloat16_state_fails_the_comparison(toy, monkeypatch):
    """``S`` rounded to bfloat16 wherever a program leaves it (after each
    decoded token, at the end of each run of a prefill program): the
    prefill's rows and the decoded tokens' leave the tolerance by far."""
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
    off = np.abs(got - reference_logits(toy, prompt)[at]).max()
    assert max(off, gaps(toy, prompt, out).max()) > 10 * TOL


def test_the_int8_control_fails_the_comparison(toy):
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 512, 48).tolist()
    want = reference_logits(toy, tokens)
    low = reference_logits(toy, tokens, quant="int8")
    assert np.abs(low - want).max() > 10 * TOL
    first = low.argmax(-1)
    assert (want.max(-1) - want[np.arange(48), first]).max() > 10 * TOL


def test_the_seeded_logits_spread_as_a_trained_models(toy):
    """The head is drawn so that the logits' deviation is about 3 through
    ``lm_head_multiplier``, and every branch has unit gain through its own:
    at plain fan-in scale the 0.0078 on the logits would leave them at a
    deviation near 0.01, and every gap of the output check with them."""
    want = reference_logits(toy, list(range(1, 49)))
    assert 2.0 < want.std() < 4.0


# -- (c) each part of the layer, left out or set wrong, fails the reference -----

SCALARS = [m for m in tfm.MULTIPLIERS if CELL[m] != 1]  # attention_in is 1


def _without(field, index=None):
    def change(cfg):
        if index is None:
            return dataclasses.replace(cfg, **{field: 1.0})
        values = list(getattr(cfg, field))
        values[index] = 1.0
        return dataclasses.replace(cfg, **{field: tuple(values)})

    return change


@pytest.mark.parametrize(
    "change",
    [lambda cfg: dataclasses.replace(cfg, attention_out_multiplier=0.0),
     lambda cfg: dataclasses.replace(cfg, ssm_out_multiplier=0.0)]
    + [_without(m) for m in SCALARS]
    + [_without("ssm_multipliers", i) for i in range(5)]
    + [_without("mlp_multipliers", i) for i in range(2)],
    ids=["no_attention", "no_ssm"] + [f"no_{m}" for m in SCALARS]
    + [f"no_ssm_multiplier_{s}" for s in "zxBCd"]
    + ["no_mlp_gate_multiplier", "no_mlp_down_multiplier"],
)
def test_a_branch_or_a_multiplier_left_out_fails_the_reference(toy, change):
    """Either branch of the block dropped (its output multiplied by 0), and
    each multiplier that is not 1 dropped in turn (``attention_in`` is 1 in
    the source): the prefill's rows leave the tolerance by a hundred times."""
    cfg = change(toy[0])
    eng = make_engine((cfg, toy[1], toy[2]), max_batch=1)
    prompt = np.random.default_rng(5).integers(0, 512, 21).tolist()
    assert prefill_off(toy, eng, prompt) > 100 * TOL


def _gate_after_the_norm(cfg, y, z, scale):
    f32 = jnp.float32
    normed = tfm.rms_norm(
        y.reshape(*y.shape[:-1], cfg.ssm_groups, -1),
        scale.astype(f32).reshape(cfg.ssm_groups, -1), cfg.rms_eps,
    ).reshape(y.shape)
    return normed * jax.nn.silu(z.astype(f32))


def _one_group(cfg, y, z, scale):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    return tfm.rms_norm(y, scale.astype(jnp.float32), cfg.rms_eps)


@pytest.mark.parametrize(
    "norm", [_gate_after_the_norm, _one_group],
    ids=["gate_after_the_norm", "norm_over_all_channels"],
)
def test_the_gated_norm_done_otherwise_fails_the_reference(toy, norm, monkeypatch):
    monkeypatch.setattr(tfm, "gated_group_norm", norm)
    eng = make_engine(toy, max_batch=1)
    prompt = np.random.default_rng(5).integers(0, 512, 21).tolist()
    assert prefill_off(toy, eng, prompt) > 100 * TOL


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
    for name in ("ssm_taps", "ssm_s"):
        assert np.abs(np.asarray(eng.pool.state[name])).max() > 0
    assert_same(served(eng, second, 8), alone(toy, second, 8))


def test_an_idle_slot_keeps_its_state_and_a_live_one_moves_on(toy):
    eng = make_engine(toy, max_batch=3)
    rid = eng.submit([5, 6, 7, 8, 9], GenerationConfig(max_new_tokens=6))
    eng.step()
    before = {k: np.asarray(v) for k, v in eng.pool.state.items()}
    eng.step()
    after = {k: np.asarray(v) for k, v in eng.pool.state.items()}
    taps = "ssm_taps"
    np.testing.assert_array_equal(after[taps][:, 0, 0], before[taps][:, 1, 0])
    assert np.abs(after[taps][:, 2, 0] - before[taps][:, 2, 0]).max() > 0
    assert np.abs(after["ssm_s"][:, 0] - before["ssm_s"][:, 0]).max() > 0
    np.testing.assert_array_equal(after[taps][:, :, 1:], before[taps][:, :, 1:])
    np.testing.assert_array_equal(after["ssm_s"][:, 1:], before["ssm_s"][:, 1:])
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
    assert [p["state_written"] for p in prefills] == [3 * 5, 3]
    # blocks of 8 tokens: two in the 16-token program, one a chunk of 4
    assert [p["scan_blocks"] for p in prefills] == [3 * (2 + 4), 3]
    decodes = [s["args"] for s in spans if s["name"] == "engine.decode"]
    assert decodes and max(d["live"] for d in decodes) == 2
    a_slot = 3 * (8 * 16 * 8 * 4 + 3 * 128 * 4)  # float32 toy: 4-byte columns
    assert eng.pool.state_bytes_per_slot == a_slot
    for d in decodes:
        assert d["state_layers"] == 3
        assert d["state_slots_written"] == 3 * d["live"]
        assert d["ssm_state_bytes"] == d["state_bytes"] == 2 * d["live"] * a_slot
        assert d["full_pages"] == d["pages_written"]
        assert d["attn_full_layers"] == 3 and "window_pages" not in d


def test_the_state_kernel_serves_the_jnp_paths_tokens(toy):
    """``decode_step``'s Mamba-2 state through the state kernel, interpreted (as
    the engine runs it compiled on a TPU), serves the tokens the jnp path
    serves, slot for slot, with slots that idle and fill beside live ones;
    ``engine.decode`` ``state_kernel_layers`` counts the 3 parallel layers a
    step in the kernel, 0 on the jnp path."""
    prompts = [list(range(1, 30)), [7, 8], [9, 10, 11, 12, 13], [300, 301]]
    outs = {}
    for kernel in (None, "interpret"):
        tracing.SPANS.clear()
        eng = make_engine(toy)
        assert eng._state_kernel is None  # no TPU here
        eng._state_kernel = kernel
        outs[kernel] = eng.generate_ids(
            prompts, GenerationConfig(max_new_tokens=12)
        )
        decodes = [s["args"] for s in tracing.SPANS.slices(cat="engine")
                   if s["name"] == "engine.decode"]
        assert decodes and max(d["live"] for d in decodes) == 3
        assert {d["state_kernel_layers"] for d in decodes} == {
            3 if kernel else 0}
    assert outs["interpret"] == outs[None]


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
    with pytest.raises(tfm.UnsupportedModelFeature, match="parallel|attn_pattern"):
        call(toy)


def test_the_new_fields_are_checked_and_refused_where_not_implemented():
    for field, value in (("key_multiplier", 0.5), ("lm_head_multiplier", 2.0),
                         ("ssm_multipliers", (1.0,) * 5),
                         ("mlp_multipliers", (0.5, 1.0))):
        with pytest.raises(tfm.UnsupportedModelFeature, match=field):
            tfm.ModelConfig(**{field: value}).require_uniform_dense("forward")
    with pytest.raises(ValueError, match="ssm_heads"):
        tfm.ModelConfig(n_layers=1, attn_pattern=("parallel",),
                        ffn_pattern=("dense",), conv_kernel=4)
    with pytest.raises(ValueError, match="groups dividing"):
        tfm.ModelConfig(n_layers=1, attn_pattern=("parallel",),
                        ffn_pattern=("dense",), conv_kernel=4, ssm_heads=3,
                        ssm_head_dim=2, ssm_state=2, ssm_groups=2)
    with pytest.raises(ValueError, match="conv_kernel"):
        tfm.ModelConfig(n_layers=1, attn_pattern=("parallel",),
                        ffn_pattern=("dense",), ssm_heads=2, ssm_head_dim=2,
                        ssm_state=2, ssm_groups=1)
    with pytest.raises(ValueError, match="z, x, B, C and dt"):
        tfm.ModelConfig(ssm_multipliers=(1.0, 2.0))


def test_a_mixed_stack_reads_each_layers_own_row_of_state(toy):
    """Parallel layers beside a plain attention layer (``parallel full
    parallel parallel``): a parallel layer's row of K and V counts among
    all the ``full`` class's layers, its row of state among the parallel
    ones (``LayerRun.state_start``). Against the same stack computed layer
    by layer with ``decoder_block`` and no cache, the whole sequence at
    once; no multiplier, fan-in weights."""
    cfg = dataclasses.replace(
        toy[0], n_layers=4, attn_pattern=("parallel", "full", "parallel",
                                          "parallel"),
        ffn_pattern=("dense",) * 4, ssm_multipliers=(),
        mlp_multipliers=(1.0, 1.0), **{m: 1.0 for m in tfm.MULTIPLIERS},
    )
    runs = cfg.layer_runs()
    assert [(r.key, r.count, r.cache_start, r.state_start) for r in runs] == [
        ("parallel.dense", 1, 0, 0), ("full.dense", 1, 1, 0),
        ("parallel.dense.1", 2, 2, 1)]
    assert cfg.state_kinds() == {"ssm": 3} and cfg.kv_classes()["full"][0] == 4
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    eng = make_engine((cfg, params, None), max_batch=1)
    prompt = np.random.default_rng(6).integers(0, 512, 13).tolist()
    at, got, out = served(eng, prompt, 6)

    def whole(tokens):
        """Every layer over the whole sequence, causal, no cache."""
        t = len(tokens)
        h = tfm.embed(cfg, params, jnp.asarray(tokens))
        mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        for run in runs:
            for i in range(run.count):
                p = jax.tree.map(lambda a: a[i], params["blocks"][run.key])
                ang = tfm.rope_freqs(cfg.rotary_dim, t, cfg.rope_theta)

                def attend(q, k, v, sink):
                    g = cfg.n_heads // cfg.n_kv_heads
                    qh = q.reshape(t, cfg.n_kv_heads, g, -1)
                    s = jnp.einsum("tkgd,skd->kgts", qh, k) / jnp.sqrt(cfg.head_dim)
                    s = jnp.where(mask[None, None], s, -1e30)
                    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
                    return o.reshape(t, -1), None

                def shift(s, cache):
                    taps = cfg.conv_kernel - 1
                    ext = jnp.concatenate([jnp.zeros((taps, s.shape[-1])), s])
                    return tuple(ext[j : j + t] for j in range(taps)), None

                def recur(q, k, v, g, beta, cache):
                    state = jnp.zeros(q.shape[1:] + (v.shape[-1],))
                    return tfm.delta_scan(state, q, k, v, g, beta)[0], None

                mix = (attend, shift, recur) if run.attn.state else attend
                h, _, _ = tfm.decoder_block(cfg, run, p, h, ang, mix)
        return np.asarray(tfm.head_logits(cfg, params, h))

    np.testing.assert_allclose(got, whole(prompt)[at], atol=TOL, rtol=0)
    want = whole(prompt + out)[len(prompt) - 1 : -1]
    assert (want.max(-1) - want[np.arange(6), out]).max() <= TOL
