"""A stack by position through the paged engine, at toy size on the CPU:
windowed and full attention mixed (two classes of KV page), a dense layer
and then dropless sigmoid-routed experts of which this holder has some,
against the benchmark's plain reference (``benchmarks/references/
moe_window_gqa.py``); and the dense configurations through the same block,
to the bit."""
import os
import sys
import zlib

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

# two periods of the pattern after the dense layer, 32 experts of which 8
# are held, top-4, a window of 8 over pages of 4: the source's key names
TOY = {
    "name": "toy-moe-window", "family": "moe_window_gqa",
    "reference": "moe_window_gqa",
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "partial_rotary_factor": 0.334, "rope_theta": 1e7, "swa_rope_theta": 1e4,
    "attention_value_scale": 0.707, "sliding_window": 8,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "layernorm_epsilon": 1e-5, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 13,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
    "moe_layer_freq": [0] + [1] * 12,
    "n_routed_experts": 8, "router_width": 32, "experts_held": [8, 8],
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "vocab_size": 512, "torch_dtype": "float32",
    "deployment": {"max_context_tokens": 128},
}
PAGE = 4
# float32 against float32 at `highest`: what the order of the sums leaves
TOL = 2e-4


def by_run(cfg, params):
    """The family stacks the layers of a kind together, and its reference
    reads them so; the engine takes a stack of its own for each run of
    consecutive layers (``ModelConfig.layer_runs``). The toy has kinds
    with several runs, which the cell's seven layers have not."""
    blocks, taken = {}, {}
    for run in cfg.layer_runs():
        kind = ".".join(run.key.split(".")[:2])
        at = taken.get(kind, 0)
        taken[kind] = at + run.count
        blocks[run.key] = jax.tree.map(
            lambda a: a[at : at + run.count], params["blocks"][kind]
        )
    return {**params, "blocks": blocks}


@pytest.fixture(scope="module")
def toy():
    """(the program's configuration, the weights as the reference reads
    them, the reference, the weights as the engine takes them)."""
    family = spec.load_family(TOY, BENCH)
    reference = spec.load_reference(TOY, BENCH)
    cfg, weights = family.model_config(TOY), family.make_weights(TOY, 5)
    return cfg, weights, reference, by_run(cfg, weights)


@pytest.fixture(autouse=True)
def small_prefill_programs(monkeypatch):
    """One prefill program takes 16 tokens at the toy's 8 heads, and the
    rest of a prompt goes in chunks of 4."""
    monkeypatch.setattr(continuous, "PREFILL_SCORES_BYTES", 4 * 8 * 16 * 16)


def make_engine(toy, **kw):
    kw = {"max_batch": 3, "page_size": PAGE, "n_pages": 64, **kw}
    eng = ContinuousBatchingEngine(toy[0], toy[3], **kw)
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


# -- (a) prefill, then decode through the paged cache, against the reference --


@pytest.mark.parametrize(
    "lanes", [continuous.LANES, 16],
    ids=["keys_as_wide_as_the_head", "keys_stored_in_whole_tiles"],
)
def test_prefill_then_decode_agrees_with_the_reference(toy, monkeypatch, lanes):
    """Prompts that span several chunks (one program takes 16 tokens, the
    rest goes in chunks of 4) and contexts that wrap a slot's ring of
    3 pages x 4 tokens several times. Logits, not tokens: the row each
    prefill run returns (its last real token's) against the reference's at
    that position; a decoded token by the reference's logit of it
    against the reference's best at that position."""
    monkeypatch.setattr(continuous, "LANES", lanes)
    eng = make_engine(toy)
    assert eng.pool.k_dim == (24 if lanes > 24 else 32)
    assert eng.pool.ring_pages == 3
    seen = capture_prefill_logits(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 5, 16, 23)]
    new = 40
    for prompt in prompts:  # one at a time: the captures are this prompt's
        del seen[:]
        (out,) = eng.generate_ids([prompt], GenerationConfig(max_new_tokens=new))
        assert len(out) == new
        want = reference_logits(toy, prompt + out)
        at = [p for p, _ in seen]
        got = np.stack([r for _, r in seen])
        padded = -(-len(prompt) // PAGE) * PAGE
        assert len(seen) == 1 + max(0, -(-(padded - 16) // 4))
        assert at[-1] == len(prompt) - 1
        np.testing.assert_allclose(got, want[at], atol=TOL, rtol=0)
        at = want[len(prompt) - 1 : len(prompt) + new - 1]
        gaps = at.max(-1) - at[np.arange(new), out]
        assert gaps.max() <= TOL
    assert eng.pool.free_pages == eng.pool.usable_pages


def test_a_batch_of_mixed_lengths_agrees_with_the_reference(toy):
    """Short and long contexts in one decode batch, admitted as others
    finish: each sequence's ring and table are its own."""
    eng = make_engine(toy)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 41, 18, 9, 30)]
    outs = eng.generate_ids(prompts, GenerationConfig(max_new_tokens=25))
    for prompt, out in zip(prompts, outs):
        want = reference_logits(toy, prompt + out)[len(prompt) - 1 : -1]
        assert (want.max(-1) - want[np.arange(25), out]).max() <= TOL


# -- (b) the share test ---------------------------------------------------------


def _expert_layer(toy, held):
    """Weights of one toy expert layer holding ``held`` of the 32 experts
    (cut out of one seeded whole layer), and 50 tokens."""
    whole = moe.init_experts(32, 32, 64, 32, 1, jax.random.PRNGKey(3), jnp.float32)
    whole = jax.tree.map(lambda a: a[0], whole)
    whole["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (32,), jnp.float32)
    first, count = held
    cut = {
        k: v[first : first + count] if k.startswith("w_") else v
        for k, v in whole.items()
    }
    y = jax.random.normal(jax.random.PRNGKey(5), (50, 64), jnp.float32)
    return cut, y


SHARES = [(0, 8), (8, 8), (16, 8), (24, 8)]


@pytest.mark.parametrize("held", SHARES + [(0, 32)], ids=str)
def test_program_and_reference_leave_out_the_same_experts(toy, held):
    p, y = _expert_layer(toy, held)
    got, pairs, hit = moe.experts_apply(p, y, top_k=4, held=held)
    want = toy[2]._experts(y, p, 4, held, True, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    chosen, _ = moe.route(p, y, 4)
    here = (np.asarray(chosen) >= held[0]) & (np.asarray(chosen) < sum(held))
    assert int(pairs) == here.sum()
    assert int(hit) == len(set(np.asarray(chosen)[here].tolist()))


def test_the_parts_of_all_shares_add_up_to_the_uncut_layer(toy):
    """What each of four holders of 8 experts computes of one expert layer,
    routed over all 32 and normalised over all 4 chosen, adds up to what
    the uncut reference gives: nothing stands in for the absent holders."""
    whole, y = _expert_layer(toy, (0, 32))
    want = toy[2]._experts(y, whole, 4, (0, 32), True, None)
    parts = [
        moe.experts_apply(_expert_layer(toy, held)[0], y, top_k=4, held=held)
        for held in SHARES
    ]
    total = sum(np.asarray(out, np.float64) for out, _, _ in parts)
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5)
    assert sum(int(pairs) for _, pairs, _ in parts) == 50 * 4
    assert all(np.abs(np.asarray(out)).max() > 0 for out, _, _ in parts)


def test_more_pairs_than_the_usual_rows_take_the_whole_budget(toy):
    """A router that sends every token to the held experts: more pairs
    than twice a uniform router's, so the branch of N * k rows runs, and no
    token is dropped."""
    p, y = _expert_layer(toy, (8, 8))
    p["router_bias"] = jnp.where(
        (jnp.arange(32) >= 8) & (jnp.arange(32) < 16), 10.0, 0.0)
    got, pairs, hit = moe.experts_apply(p, y, top_k=4, held=(8, 8))
    assert int(pairs) == 50 * 4 and int(hit) == 8
    want = toy[2]._experts(y, p, 4, (8, 8), True, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- (b2) a run's stack of experts read in place -----------------------------------


def _expert_run(held, n_layers, every=False):
    """Weights of a run of ``n_layers`` toy expert layers holding ``held``
    of 32 experts, each layer with a router and a bias of its own, and 50
    tokens of which six do not count. ``every``: a bias that sends every
    token's four choices to the held experts, so that the pairs outgrow
    the usual rows."""
    run = moe.init_experts(
        32, held[1], 64, 32, n_layers, jax.random.PRNGKey(7), jnp.float32)
    run["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(8), (n_layers, 32), jnp.float32)
    if every:
        ids = jnp.arange(32)
        run["router_bias"] += jnp.where(
            (ids >= held[0]) & (ids < sum(held)), 10.0, 0.0)
    y = jax.random.normal(jax.random.PRNGKey(9), (50, 64), jnp.float32)
    return run, y, jnp.arange(50) % 8 != 3


@pytest.mark.parametrize("every", [False, True], ids=["usual", "every"])
@pytest.mark.parametrize("held", [(8, 8), (0, 32)], ids=str)
@pytest.mark.parametrize(
    "n_layers, layer", [(1, 0), (3, 0), (3, 1), (3, 2)],
    ids=["1of1", "1of3", "2of3", "3of3"],
)
def test_a_layer_read_in_its_runs_stack_is_the_layer_alone(
    n_layers, layer, held, every
):
    """``experts_apply`` handed a run's ``[L, held, D, F]`` stacks and the
    layer's index (traced, as under ``run_stack``'s scan) gives what it
    gives handed that layer's own ``[held, D, F]``: the output, and both
    counts to the unit, in both branches of the row budget, with tokens
    that do not count and with a holder of a part of the router's width."""
    run, y, live = _expert_run(held, n_layers, every)
    kw = dict(top_k=4, held=held, live=live)
    alone = [
        moe.experts_apply(jax.tree.map(lambda a, i=i: a[i], run), y, **kw)
        for i in range(n_layers)
    ]
    want, pairs, hit = alone[layer]
    assert (int(pairs) == 44 * 4) == (every or held == (0, 32))
    in_place = jax.jit(lambda p, y, i: moe.experts_apply(p, y, layer=i, **kw))
    p = {**jax.tree.map(lambda a: a[layer], run),
         **{k: run[k] for k in moe.EXPERT_WEIGHTS}}
    got = in_place(p, y, jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=1e-5)
    assert (int(got[1]), int(got[2])) == (int(pairs), int(hit))
    assert np.abs(np.asarray(want)).max() > 0.1
    for i, (other, _, _) in enumerate(alone):  # and no neighbour's weights
        if i != layer:
            assert np.abs(np.asarray(got[0]) - np.asarray(other)).max() > 0.1


@pytest.mark.parametrize("every", [False, True], ids=["usual", "every"])
@pytest.mark.parametrize("held", [(8, 8), (0, 32)], ids=str)
@pytest.mark.parametrize(
    "n_layers, layer", [(1, 0), (3, 0), (3, 1), (3, 2)],
    ids=["1of1", "1of3", "2of3", "3of3"],
)
def test_the_grouped_matmul_kernel_gives_what_ragged_dot_gives(
    n_layers, layer, held, every
):
    """Megablox ``gmm`` (interpreted; ``kernel``), the decode step's
    grouped matmul on a TPU, over a run's stack with the layer's index
    traced: the output ``lax.ragged_dot`` gives over the same rows, groups
    and stack within float32's order of summation, and both counts to the
    unit, in both branches of the row budget, with tokens that do not count
    and with a holder of a part of the router's width."""
    run, y, live = _expert_run(held, n_layers, every)
    kw = dict(top_k=4, held=held, live=live)
    p = {**jax.tree.map(lambda a: a[layer], run),
         **{k: run[k] for k in moe.EXPERT_WEIGHTS}}
    got, want = (
        jax.jit(lambda p, y, i, kernel=kernel: moe.experts_apply(
            p, y, layer=i, kernel=kernel, **kw))(p, y, jnp.int32(layer))
        for kernel in ("interpret", None)
    )
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), atol=1e-5, rtol=0)
    assert np.abs(np.asarray(want[0])).max() > 0.1
    assert (int(got[1]), int(got[2])) == (int(want[1]), int(want[2]))
    assert (int(got[1]) == 44 * 4) == (every or held == (0, 32))


@pytest.mark.parametrize(
    "shape, tiles",
    [((256, 2048, 1792), (128, 2048, 896)), ((256, 1792, 2048), (128, 1792, 1024)),
     ((64, 4096, 2048), (64, 2048, 1024)), ((512, 2048, 4096), (128, 2048, 1024)),
     ((200, 64, 32), (40, 64, 32)), ((15, 96, 200), (15, 96, 200))],
    ids=["turns-in", "turns-out", "mixed-64", "mixed-512", "toy", "ragged"],
)
def test_the_kernels_tiles_come_from_the_products_shape(shape, tiles):
    """Rows in tiles that divide them and are no longer than 128, the
    contraction in pieces of up to 2,048, the columns of up to 1,024; what
    no such piece divides is one tile."""
    assert moe.gmm_tiles(*shape) == tiles


# -- (c) the control fails the same comparison -------------------------------------


def test_the_int8_control_fails_the_comparison(toy):
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 512, 48).tolist()
    want = reference_logits(toy, tokens)
    low = reference_logits(toy, tokens, quant="int8")
    assert np.abs(low - want).max() > 10 * TOL
    first = low.argmax(-1)
    gaps = want.max(-1) - want[np.arange(48), first]
    assert gaps.max() > 10 * TOL  # some token int8 puts first is not the best


# -- (d) dense configurations through the new block, to the bit --------------------

# The served tokens of the parent commit's engine (6598cc2, its three
# inlined copies of the block) on this drive, and, of each prefill /
# prefill_suffix run, the row of logits the host reads (its last real
# token's) as the parent commit 1b925e3 gave it, computed by that
# parent itself: in float32 the CRC32, to the bit; in bfloat16 the row's
# (sum, sum of magnitudes) in float64, to rounding: the head is now a
# product of one row, whose bfloat16 result rounds otherwise on the CPU
PARENT = {"float32": [4000668772, 437513611, 1547471121, 3715026781]}
PARENT_BF16_MOMENTS = [(-9.76585, 69.55078), (7.0463, 89.88766),
                       (-14.37257, 71.235), (-2.20629, 68.70417)]
PARENT_TOKENS = [
    [41] * 12,
    [37, 33, 32, 66, 28, 28, 57, 28, 66, 66, 66, 33],
    [41, 41, 41, 41, 41, 41, 41, 89, 24, 21, 41, 0],
    [70, 41, 41, 41, 41, 66, 41, 41, 56, 66, 41, 66],
]


class _Hit:
    def __init__(self, tokens, k, v):
        self.tokens, self.k, self.v = tokens, k, v

    def release(self):
        pass


class _ListPrefixCache:
    def __init__(self, page):
        self.page, self.entries, self.hits = page, [], 0

    def insert(self, tokens, k, v):
        self.entries.append((list(tokens), np.asarray(k), np.asarray(v)))

    def lookup(self, prompt, max_tokens):
        best = None
        for tokens, k, v in self.entries:
            n = 0
            while n < min(len(tokens), max_tokens) and tokens[n] == prompt[n]:
                n += 1
            n -= n % self.page
            if n and (best is None or n > best.tokens):
                best = _Hit(n, k[:, :, : n // self.page], v[:, :, : n // self.page])
        self.hits += best is not None
        return best

    def stats(self):
        return {}


def dense_drive(dtype):
    """A dense configuration through the engine with a prefix cache, whole
    prompts in one prefill program as the dense cells run them (the caller
    sets ``PREFILL_SCORES_BYTES``): (the row of logits each prefill run
    returns, as float32; the served tokens; the engine)."""
    cfg = tfm.ModelConfig(
        vocab_size=97, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=48, max_seq_len=64, dtype=jnp.dtype(dtype),
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32,
        prefix_cache=_ListPrefixCache(8),
    )
    seen = capture_prefill_logits(eng)
    long = [3, 5, 7, 9, 11, 2, 4, 6, 8, 1, 3, 5, 7, 2, 9, 4, 6, 1]
    gen = GenerationConfig(max_new_tokens=12)
    outs = eng.generate_ids([long, [4, 8], long[:9]], gen)
    outs += eng.generate_ids([long[:16] + [9, 9, 9]], gen)  # a prefix hit
    return [np.asarray(r, np.float32) for _, r in seen], outs, eng


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_dense_configuration_gives_the_parents_logits_to_the_bit(
    dtype, monkeypatch
):
    monkeypatch.setattr(continuous, "PREFILL_SCORES_BYTES", 2**30)
    seen, outs, eng = dense_drive(dtype)
    assert eng.prefix_cache.hits == 2
    assert outs == PARENT_TOKENS
    if dtype == "float32":
        assert [zlib.crc32(x.tobytes()) for x in seen] == PARENT[dtype]
    else:
        moments = [
            (x.sum(dtype=np.float64), np.abs(x).sum(dtype=np.float64))
            for x in seen
        ]
        np.testing.assert_allclose(moments, PARENT_BF16_MOMENTS, rtol=0, atol=0.05)


# What the parent commit's engine (4ec9a34: `decoder_block` before it knew a
# mixer that is no attention, stacks by kind sliced a run) gave on this
# file's toy (13 layers, two page classes, 8 of 32 experts held): the CRC32
# of the served tokens; and of each prefill / prefill_suffix run the row of
# logits the host reads, as the parent commit 1b925e3 gave it: in
# float32 its CRC32, in bfloat16 its (sum, sum of magnitudes) in float64
# (the head is now a product of one row, whose bfloat16 result
# rounds otherwise on the CPU)
PARENT_TOY = {
    "float32": ([2622514700, 3517881183, 2162343575, 4049468345, 3093657870,
                 1525900907, 3785984651, 750154741, 711009151, 2225842359,
                 2140864943, 3202436064], 3355322928),
    "bfloat16": ([
        (40.9505, 395.5886), (12.0921, 397.9979), (11.2609, 411.9698),
        (24.3021, 395.1198), (29.7828, 402.0468), (16.7107, 411.5064),
        (5.2977, 419.7569), (34.1218, 403.3266), (29.9677, 386.7218),
        (6.3073, 413.1787), (19.7319, 398.4499), (0.4809, 403.5144),
    ], 3932849118),
}


def windowed_drive(dtype, bench=BENCH):
    """This file's toy through the engine, prompts in chunks (the caller
    sets the small programs): (the row of logits each prefill run returns,
    as float32; the served tokens)."""
    cfg = dict(TOY, torch_dtype=dtype)
    family = spec.load_family(cfg, bench)
    model, weights = family.model_config(cfg), family.make_weights(cfg, 5)
    eng = make_engine((model, weights, None, by_run(model, weights)))
    seen = capture_prefill_logits(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 5, 16, 23)]
    outs = eng.generate_ids(prompts, GenerationConfig(max_new_tokens=20))
    return [np.asarray(r, np.float32) for _, r in seen], outs


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_windowed_expert_toy_gives_the_parents_outputs_to_the_bit(dtype):
    """A stack by position that has no convolution layer takes none of the
    branches a model with state by slot takes (state by slot, QK-norm, a tied head, the router's
    epsilon), a stack of its own for each run holds the rows the slice of a
    kind's stack held, and an expert run's weights read in place by the
    grouped matmul are the weights the scan sliced: the tokens to
    the bit, the rows of logits the host reads to the bit in float32 and
    to rounding in bfloat16."""
    seen, outs = windowed_drive(dtype)
    logits, tokens = PARENT_TOY[dtype]
    assert zlib.crc32(np.asarray(outs, np.int32).tobytes()) == tokens
    if dtype == "float32":
        assert [zlib.crc32(x.tobytes()) for x in seen] == logits
    else:
        moments = [
            (x.sum(dtype=np.float64), np.abs(x).sum(dtype=np.float64))
            for x in seen
        ]
        np.testing.assert_allclose(moments, logits, rtol=0, atol=0.05)


def test_rms_eps_is_the_configurations(toy):
    """The norm's epsilon follows ``ModelConfig.rms_eps``: the default is
    the 1e-6 every dense configuration has run with."""
    assert tfm.ModelConfig().rms_eps == 1e-6

    def first_logits(eps):
        cfg = tfm.ModelConfig(
            vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=48, max_seq_len=64, dtype=jnp.float32, rms_eps=eps,
        )
        params = tfm.init_params(cfg, jax.random.PRNGKey(3))
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=1, page_size=8, n_pages=16)
        seen = capture_prefill_logits(eng)
        eng.generate_ids([[1, 2, 3]], GenerationConfig(max_new_tokens=1))
        logits = tfm.forward(params, jnp.asarray([[1, 2, 3]]), cfg)
        ((at, row),) = seen
        assert at == 2
        np.testing.assert_allclose(row, logits[0, 2], atol=1e-5)
        return row

    assert np.abs(first_logits(1e-6) - first_logits(1e-2)).max() > 1e-3


# -- (e) the window class: rings reused in place, stalls named ----------------------


def test_a_ring_is_reused_however_long_the_context(toy):
    eng = make_engine(toy, max_batch=2, n_pages=40)
    window = eng.pool.classes["window"]
    assert window.usable_pages == 2 * 3
    gen = GenerationConfig(max_new_tokens=60)
    eng.submit([1, 2, 3], gen)
    eng.step()
    held, full_held = window.free_pages, eng.pool.classes["full"].free_pages
    assert held == window.usable_pages - 3
    assert full_held == eng.pool.classes["full"].usable_pages - 16
    while eng.pending():
        assert window.free_pages == held
        eng.step()
    assert eng.pool.free_pages == eng.pool.usable_pages
    pages = window.alloc(3)
    window.free(pages)
    with pytest.raises(ValueError, match="double free: window page"):
        window.free(pages)
    with pytest.raises(ValueError, match="invalid window page"):
        window.free([0])


@pytest.mark.parametrize("short", ["full", "window"])
def test_a_stalled_admit_names_the_class_that_was_short(toy, short):
    tracing.SPANS.clear()
    eng = make_engine(toy, max_batch=2, n_pages=12 if short == "full" else 64)
    if short == "window":  # one ring left for two slots
        taken = eng.pool.classes["window"].alloc(3)
    gen = GenerationConfig(max_new_tokens=20)
    eng.generate_ids([[5, 6, 7], [5, 6, 8]], gen)
    admits = [s["args"] for s in tracing.SPANS.slices(cat="engine")
              if s["name"] == "engine.admit"]
    stalls = [a for a in admits if a["pool_stall"]]
    assert stalls and all(a["pool_stall_class"] == short for a in stalls)
    assert all("pool_stall_class" not in a for a in admits if not a["pool_stall"])
    assert eng.stats()["admit_pool_stalls"] == len(stalls)
    if short == "window":
        eng.pool.classes["window"].free(taken)
    assert eng.pool.free_pages == eng.pool.usable_pages


def test_spans_carry_the_expert_counts_and_the_pages_by_class(toy):
    tracing.SPANS.clear()
    eng = make_engine(toy)
    prompt = list(range(1, 30))
    eng.generate_ids([prompt, [7, 8]], GenerationConfig(max_new_tokens=30))
    spans = tracing.SPANS.slices(cat="engine")
    prefills = [s["args"] for s in spans if s["name"] == "engine.prefill"]
    # 29 tokens: 32 padded, 16 in the prefill program and four chunks of 4
    assert [p["chunks"] for p in prefills] == [5, 1]
    assert all(0 < p["moe_pairs_held"] <= p["t_pad"] * 4 * 12 for p in prefills)
    decodes = [s["args"] for s in spans if s["name"] == "engine.decode"]
    assert decodes
    for d in decodes:
        assert 0 < d["moe_pairs_held"] <= d["live"] * 4 * 12
        assert 0 < d["moe_experts_hit"] <= min(8 * 12, d["moe_pairs_held"])
        assert d["full_pages"] == d["pages_written"]
        assert 0 < d["window_pages"] <= 3 * d["live"]
    assert max(d["window_pages"] for d in decodes) == 3 * 2
    assert max(d["full_pages"] for d in decodes) > 3 * 2


@pytest.mark.parametrize("kernel", [None, "interpret"], ids=["gather", "kernel"])
def test_full_layers_take_the_kernel_and_rings_their_own_path(toy, kernel):
    """Separate paths by layer kind: with the Pallas decode kernel
    (interpreted) the three full layers read live pages (keys of 24 beside
    values of 16, four KV heads' groups of four), the ten windowed layers
    keep their ring gather, and the tokens are the XLA formulation's slot
    for slot; the step counts which layers ran where."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, n).tolist() for n in (3, 41, 18, 9)]
    gen = GenerationConfig(max_new_tokens=12)
    want = make_engine(toy).generate_ids(prompts, gen)
    eng = make_engine(toy)
    assert eng._attn_kernel is None
    eng._attn_kernel = kernel
    tracing.SPANS.clear()
    assert eng.generate_ids(prompts, gen) == want
    decodes = [s["args"] for s in tracing.SPANS.slices(cat="engine")
               if s["name"] == "engine.decode"]
    assert decodes
    entries = 3 * 3 * eng.max_pages_per_seq  # full layers x slots x table
    for d in decodes:
        assert d["attn_full_layers"] == 3
        assert d["attn_table_entries"] == entries
        assert d["attn_kernel_layers"] == (3 if kernel else 0)
        assert d["attn_pages_walked"] == (3 * d["full_pages"] if kernel else 0)


# -- what the system cannot do for such a model yet, and says so -------------------


@pytest.mark.parametrize(
    "call",
    [
        lambda toy: make_engine(toy, prefix_cache=_ListPrefixCache(PAGE)),
        lambda toy: make_engine(toy).prefill_extract(
            [1, 2, 3], GenerationConfig(max_new_tokens=2)),
        lambda toy: make_engine(toy).adopt_pages({}, None, None),
        lambda toy: make_engine(toy).swap_params(toy[1]),
        lambda toy: tfm.forward(toy[1], jnp.zeros((1, 4), jnp.int32), toy[0]),
        lambda toy: tfm.make_train_step(toy[0], None),
        lambda toy: ContinuousBatchingEngine(
            tfm.ModelConfig(n_experts=4, n_layers=1)),
    ],
    ids=["prefix_cache", "prefill_extract", "adopt_pages", "swap_params",
         "forward", "train_step", "switch_experts"],
)
def test_a_path_that_lacks_the_feature_raises_a_typed_error(toy, call):
    with pytest.raises(tfm.UnsupportedModelFeature):
        call(toy)


def test_weights_stacked_by_kind_are_refused_by_name(toy):
    """The program reads one stack of weights a run. The family's own
    layout (one a kind, which is the cell's too while each of its kinds
    has one run) is refused by name where a kind has several runs, as the
    engine is built and as ``run_stack`` is traced: no ``KeyError``, no
    scan over the wrong layers."""
    cfg, by_kind = toy[0], toy[1]
    assert len(cfg.layer_runs()) > len(by_kind["blocks"])
    with pytest.raises(tfm.StackLayoutError, match="one stack of weights"):
        ContinuousBatchingEngine(
            cfg, by_kind, max_batch=1, page_size=PAGE, n_pages=16)
    with pytest.raises(tfm.StackLayoutError):
        tfm.run_stack(cfg, by_kind["blocks"], None, None, None, None)
    cfg.require_blocks_by_run(toy[3]["blocks"])


def test_params_sig_tells_two_sets_of_weights_apart():
    """Two deployments of one shape with other weights must not share
    prefix-cache KV: the first leaf alone is a norm's vector of ones."""
    from ray_tpu.llm.serving import _params_sig

    cfg = tfm.ModelConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=48, dtype=jnp.float32)
    a = tfm.init_params(cfg, jax.random.PRNGKey(1))
    b = tfm.init_params(cfg, jax.random.PRNGKey(2))
    first = jax.tree_util.tree_leaves(a)[0]
    assert np.array_equal(first, jax.tree_util.tree_leaves(b)[0])
    assert _params_sig(cfg, a, "llm") != _params_sig(cfg, b, "llm")
    assert _params_sig(cfg, a, "llm") == _params_sig(
        cfg, jax.tree.map(jnp.array, a), "llm")
    assert _params_sig(cfg, a, "llm") != _params_sig(cfg, a, "other")
