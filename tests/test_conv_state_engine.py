"""A stack most of whose layers are no attention, through the paged engine
at toy size on the CPU: gated short convolutions whose state lives by slot
beside the paged KV, full attention with a norm over each head of q and k,
two dense layers and then experts held whole with a bias that chooses and
does not weigh, the head tied to the embedding; against the benchmark's
plain reference (``benchmarks/references/moe_conv_gqa.py``), which keeps
no state at all. float32, seeded weights, the key names of
``lfm2-8b-a1b-l14``.

What these tests were seen to catch, each by an edit of the program made
once by hand and taken back (PR 36): the state taken at the padded end of
a prompt and not at its true end (``shift_sequence`` slicing at ``t``):
the decoded tokens of every prompt whose length is no whole page fail the
reference; a prefill that starts from the slot's row and not from zeros:
the second request through a slot fails ``test_a_recycled_slot...``; the
suffix program starting from zeros: every chunked prompt fails; an
inactive slot's write let through: nothing fails, by design (its row is
read by no one before a prefill overwrites it)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

from ray_tpu.llm import continuous  # noqa: E402
from ray_tpu.llm.continuous import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.llm.engine import GenerationConfig  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models import transformer as tfm  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402

# the cell's 14 layers at toy widths: conv conv | full conv conv conv x 3,
# two dense layers, 32 experts held whole, top-4
TOY = {
    "name": "toy-moe-conv", "family": "moe_conv_gqa",
    "reference": "moe_conv_gqa", "model_type": "lfm2_moe",
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "num_hidden_layers": 14, "num_dense_layers": 2,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3,
    "num_experts": 32, "num_experts_per_tok": 4, "experts_held": [0, 32],
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "router_norm_eps": 1e-6,
    "expert_bias_std": 0.05, "rope_theta": 1e6, "tie_word_embeddings": True,
    "vocab_size": 512, "torch_dtype": "float32",
    "deployment": {"max_context_tokens": 128},
}
PAGE = 4
# float32 against float32 at `highest`: what the order of the sums leaves
# (the same reason and the same number as test_moe_window_engine's)
TOL = 2e-4
RUNS = ["conv.dense", "full.experts", "conv.experts", "full.experts.1",
        "conv.experts.1", "full.experts.2", "conv.experts.2"]


@pytest.fixture(scope="module")
def toy():
    family = spec.load_family(TOY, BENCH)
    reference = spec.load_reference(TOY, BENCH)
    return family.model_config(TOY), family.make_weights(TOY, 5), reference


@pytest.fixture(autouse=True)
def small_prefill_programs(monkeypatch):
    """One prefill program takes 16 tokens at the toy's 8 heads, and the
    rest of a prompt goes through the suffix program in chunks of 4, which
    has to carry the state."""
    monkeypatch.setattr(continuous, "PREFILL_SCORES_BYTES", 4 * 8 * 16 * 16)


def make_engine(toy, on_tpu=False, **kw):
    kw = {"max_batch": 3, "page_size": PAGE, "n_pages": 64, **kw}
    with pytest.MonkeyPatch.context() as m:
        if on_tpu:  # the pool's rows as the chip stores them
            m.setattr(jax, "default_backend", lambda: "tpu")
        eng = ContinuousBatchingEngine(toy[0], toy[1], **kw)
    if on_tpu:  # both of decode's kernels, interpreted
        eng._attn_kernel = eng._moe_kernel = "interpret"
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


def test_the_stack_is_cut_into_seven_runs_each_with_its_own_weights(toy):
    cfg, params, _ = toy
    runs = cfg.layer_runs()
    assert [r.key for r in runs] == RUNS and set(params["blocks"]) == set(RUNS)
    assert [r.count for r in runs] == [2, 1, 3, 1, 3, 1, 3]
    assert [r.cache_start for r in runs] == [0, 0, 2, 1, 5, 2, 8]
    assert list(cfg.kv_classes()) == ["full"] and cfg.state_layers == 11
    assert "head" not in params
    assert set(tfm.init_params(cfg, jax.random.PRNGKey(0))["blocks"]) == set(RUNS)
    shapes = jax.tree.map(lambda a: a.shape, params["blocks"])
    assert shapes == jax.tree.map(
        lambda a: a.shape, tfm.init_params(cfg, jax.random.PRNGKey(0))["blocks"]
    )


# -- (a) prefill, then decode through cache and state, against the reference --


@pytest.mark.parametrize("on_tpu", [False, True], ids=["gather", "kernel_whole_tiles"])
def test_prefill_then_decode_agrees_with_the_reference(toy, on_tpu):
    """Prompts whose true length is not their padded length (37, 5, 23;
    16 is whole pages), and prompts longer than one prefill program (37,
    23: 16 tokens and then chunks of 4, the state carried from chunk to
    chunk in the slot's row). Logits, not tokens: the row each prefill
    run returns (its last real token's) against the reference's at that
    position; a decoded token by the reference's logit of it against
    the reference's best at that position. With ``on_tpu`` the pool is
    built as on the chip, rows of 8 stored in whole tiles of 128, the full
    layers go through the Pallas kernel and the expert layers' decode
    products through Megablox ``gmm``, both interpreted."""
    eng = make_engine(toy, on_tpu)
    assert (eng.pool.k_dim, eng.pool.v_dim) == ((128, 128) if on_tpu else (8, 8))
    assert eng.pool.state["conv"].shape == (11, 2, 3, 64)
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


def test_the_int8_control_fails_the_comparison(toy):
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 512, 48).tolist()
    want = reference_logits(toy, tokens)
    low = reference_logits(toy, tokens, quant="int8")
    assert np.abs(low - want).max() > 10 * TOL
    first = low.argmax(-1)
    assert (want.max(-1) - want[np.arange(48), first]).max() > 10 * TOL


# -- (b) a slot that changes hands ----------------------------------------------


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
    row."""
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
    assert np.abs(np.asarray(eng.pool.state["conv"])).max() > 0
    assert_same(served(eng, second, 8), alone(toy, second, 8))


def test_an_idle_slot_keeps_its_row_and_a_live_one_moves_on(toy):
    eng = make_engine(toy, max_batch=3)
    rid = eng.submit([5, 6, 7, 8, 9], GenerationConfig(max_new_tokens=6))
    eng.step()
    before = np.asarray(eng.pool.state["conv"])
    eng.step()
    after = np.asarray(eng.pool.state["conv"])
    np.testing.assert_array_equal(after[:, 0, 0], before[:, 1, 0])
    assert np.abs(after[:, 1, 0] - before[:, 1, 0]).max() > 0
    np.testing.assert_array_equal(after[:, :, 1:], before[:, :, 1:])
    while rid not in eng.results:
        eng.step()


# -- (c) the router: a bias that chooses and does not weigh -----------------------


def _expert_layer(held, bias=0.3):
    """Weights of one toy expert layer holding ``held`` of the 32 experts
    (cut out of one seeded whole layer), with a bias large enough to change
    who is chosen, and 50 tokens."""
    whole = moe.init_experts(32, 32, 64, 32, 1, jax.random.PRNGKey(3), jnp.float32)
    whole = jax.tree.map(lambda a: a[0], whole)
    whole["router_bias"] = bias * jax.random.normal(
        jax.random.PRNGKey(4), (32,), jnp.float32)
    first, count = held
    cut = {
        k: v[first : first + count] if k.startswith("w_") else v
        for k, v in whole.items()
    }
    y = jax.random.normal(jax.random.PRNGKey(5), (50, 64), jnp.float32)
    return cut, y


ROUTER = dict(top_k=4, norm_eps=1e-6, scale=1.0)


def _reference_layer(toy, y, p, held):
    return toy[2]._experts(y, p, 4, held, True, 1e-6, 1.0, None)


def test_the_bias_chooses_and_a_bias_that_weighs_fails_the_reference(
    toy, monkeypatch
):
    p, y = _expert_layer((0, 32))
    want = np.asarray(_reference_layer(toy, y, p, (0, 32)))
    got, pairs, hit = moe.experts_apply(p, y, held=(0, 32), **ROUTER)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    plain, _ = moe.route({**p, "router_bias": jnp.zeros(32)}, y, 4)
    chosen, weights = moe.route(p, y, 4, 1e-6, 1.0)
    assert int(pairs) == 50 * 4
    assert int(hit) == len(set(np.asarray(chosen).ravel().tolist()))
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()  # it does choose
    np.testing.assert_allclose(
        np.asarray(weights.sum(-1)), 1.0, atol=1e-5)  # 1e-6 under a sum near 2

    def weighing(p, x, top_k, norm_eps=0.0, scale=1.0):
        scores = jax.nn.sigmoid(x @ p["router"]) + p["router_bias"]
        w, chosen = jax.lax.top_k(scores, top_k)
        return chosen, w / (w.sum(-1, keepdims=True) + norm_eps) * scale

    monkeypatch.setattr(moe, "route", weighing)
    wrong, _, _ = moe.experts_apply(p, y, held=(0, 32), **ROUTER)
    assert np.abs(np.asarray(wrong) - want).max() > 100 * 1e-5


def test_a_zero_bias_could_not_tell_the_two_apart(toy):
    """Why the configuration draws its bias: at 0 weighing by score + bias
    is weighing by the score."""
    p, y = _expert_layer((0, 32), bias=0.0)
    got, _, _ = moe.experts_apply(p, y, held=(0, 32), **ROUTER)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_reference_layer(toy, y, p, (0, 32))),
        atol=1e-5)


SHARES = [(0, 8), (8, 8), (16, 8), (24, 8)]


def test_the_parts_of_all_32_experts_add_up_to_the_uncut_layer(toy):
    """What each of four holders of 8 experts computes of one expert layer
    adds up to what ``held`` (0, 32), the branch the cell runs (its row
    budget is every pair), and the uncut reference give."""
    whole, y = _expert_layer((0, 32))
    want = np.asarray(_reference_layer(toy, y, whole, (0, 32)))
    uncut, pairs, _ = moe.experts_apply(whole, y, held=(0, 32), **ROUTER)
    assert int(pairs) == 50 * 4
    parts = [
        moe.experts_apply(_expert_layer(held)[0], y, held=held, **ROUTER)
        for held in SHARES
    ]
    total = sum(np.asarray(out, np.float64) for out, _, _ in parts)
    np.testing.assert_allclose(total, want, atol=1e-5)
    np.testing.assert_allclose(total, np.asarray(uncut), atol=1e-5)
    assert sum(int(n) for _, n, _ in parts) == 50 * 4
    for held, (out, _, _) in zip(SHARES, parts):
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(_reference_layer(toy, y, _expert_layer(held)[0], held)),
            atol=1e-5)


# -- (d) spans, and what the system cannot do for such a model yet ------------------


def test_spans_carry_the_state_counts(toy):
    tracing.SPANS.clear()
    eng = make_engine(toy)
    eng.generate_ids([list(range(1, 30)), [7, 8]], GenerationConfig(max_new_tokens=10))
    spans = tracing.SPANS.slices(cat="engine")
    prefills = [s["args"] for s in spans if s["name"] == "engine.prefill"]
    # 29 tokens: 32 padded, 16 in the prefill program and four chunks of 4
    assert [(p["t_pad"], p["true_len"], p["chunks"]) for p in prefills] == [
        (32, 29, 5), (4, 2, 1)]
    assert [p["state_written"] for p in prefills] == [11 * 5, 11]
    assert all(p["moe_pairs_held"] == p["t_pad"] * 4 * 12 for p in prefills)
    decodes = [s["args"] for s in spans if s["name"] == "engine.decode"]
    assert decodes and max(d["live"] for d in decodes) == 2
    for d in decodes:
        assert d["state_layers"] == 11
        assert d["state_slots_written"] == 11 * d["live"]
        assert d["full_pages"] == d["pages_written"]
        assert d["attn_full_layers"] == 3 and "window_pages" not in d
        assert d["moe_pairs_held"] == d["live"] * 4 * 12


@pytest.mark.parametrize("kernel", [None, "interpret"], ids=["cpu", "interpret"])
def test_the_decode_span_counts_the_expert_layers_in_the_kernel(toy, kernel):
    """``engine.decode`` ``moe_kernel_layers``: of the 12 expert layers,
    those whose grouped matmuls ran in Megablox ``gmm``: all of them where
    the engine runs the kernel (compiled on a TPU, interpreted here), none
    on the CPU's ``lax.ragged_dot``. The tokens are the same either way."""
    tracing.SPANS.clear()
    eng = make_engine(toy)
    assert eng._moe_kernel is None  # no TPU here
    eng._moe_kernel = kernel
    prompts = [list(range(1, 12)), [7, 8, 9]]
    outs = eng.generate_ids(prompts, GenerationConfig(max_new_tokens=8))
    decodes = [s["args"] for s in tracing.SPANS.slices(cat="engine")
               if s["name"] == "engine.decode"]
    assert decodes
    assert {d["moe_kernel_layers"] for d in decodes} == {12 if kernel else 0}
    for prompt, out in zip(prompts, outs):
        assert gaps(toy, prompt, out).max() <= TOL


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
    with pytest.raises(tfm.UnsupportedModelFeature, match="conv|attn_pattern"):
        call(toy)


def test_a_uniform_stack_with_the_small_switches_is_refused_by_the_train_step():
    for field in ("qk_norm", "tie_embeddings"):
        with pytest.raises(tfm.UnsupportedModelFeature, match=field):
            tfm.ModelConfig(**{field: True}).require_uniform_dense("forward")
    with pytest.raises(ValueError, match="conv_kernel"):
        tfm.ModelConfig(n_layers=1, attn_pattern=("conv",), ffn_pattern=("dense",))
