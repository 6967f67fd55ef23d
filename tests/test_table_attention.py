"""The history-plus-suffix program's attention of the ``full`` class
(``continuous._table_attention``): a walk over the slot's pages block by
block with a running softmax, against the form it replaced, which gathered
the slot's whole table and took one softmax over all of it. CPU, float32,
toy widths."""
import json
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

PAGE = 4
T = 8  # the chunk: two pages
LAYERS, LAYER = 2, 1


def whole_table(q, pool_k, pool_v, layer, table, pos, head_dim):
    """The replaced lines: every entry of the table gathered, float32
    scores over all of its keys, one softmax, one product with V."""
    kh = pool_k.shape[1]
    ks = pool_k[layer][:, table].reshape(kh, -1, pool_k.shape[-1])
    vs = pool_v[layer][:, table].reshape(kh, -1, pool_v.shape[-1])
    scores = jnp.einsum(
        "tkgd,ksd->tkgs", q.astype(jnp.float32), ks.astype(jnp.float32),
        precision="highest",
    ) / jnp.sqrt(head_dim)
    causal = jnp.arange(ks.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(causal[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "tkgs,ksd->tkgd", probs, vs.astype(jnp.float32), precision="highest"
    )


def case(seed, hist_len, table_len, groups, head_dim, k_dim, t=T, kh=2,
         scale=1.0):
    """A pool whose pages are all noise (the scratch page and what other
    slots hold too), a table in shuffled order with scratch entries behind
    the slot's own pages, and a chunk of queries after ``hist_len``."""
    rng = np.random.default_rng(seed)
    n_pages = table_len + 3
    shape = (LAYERS, kh, n_pages, PAGE)
    pool_k = rng.normal(size=shape + (k_dim,)).astype(np.float32) * scale
    pool_k[..., head_dim:] = 0.0
    pool_v = rng.normal(size=shape + (k_dim,)).astype(np.float32)
    held = (hist_len + t) // PAGE
    table = np.zeros(table_len, np.int32)
    table[:held] = rng.permutation(np.arange(1, n_pages))[:held]
    q = rng.normal(size=(t, kh, groups, k_dim)).astype(np.float32) * scale
    q[..., head_dim:] = 0.0
    pos = hist_len + np.arange(t, dtype=np.int32)
    return tuple(map(jnp.asarray, (q, pool_k, pool_v))), jnp.asarray(table), \
        jnp.asarray(pos)


def both(monkeypatch, block_pages, hist_len, table_len, groups=1,
         head_dim=16, k_dim=16, **kw):
    # a trip's scores [T, heads, block_pages * PAGE] float32, to the byte
    monkeypatch.setattr(
        continuous, "ATTN_BLOCK_BYTES",
        4 * kw.get("t", T) * kw.get("kh", 2) * groups * PAGE * block_pages,
    )
    (q, pk, pv), table, pos = case(
        hist_len + 7 * groups, hist_len, table_len, groups, head_dim, k_dim,
        **kw,
    )
    with jax.default_matmul_precision("highest"):
        got = jax.jit(continuous._table_attention, static_argnums=(6,))(
            q, pk, pv, LAYER, table, pos, head_dim
        )
    return got, whole_table(q, pk, pv, LAYER, table, pos, head_dim)


BLOCK_PAGES = 4  # a block of 16 keys, two chunks
TABLE = 12  # three blocks


@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize(
    "hist_len",
    [0, PAGE, 16 - T, 16 - T + PAGE, 16, 16 + PAGE, TABLE * PAGE - T],
    ids=["none", "one-page", "to-a-blocks-edge", "a-page-over-the-edge",
         "a-whole-block", "a-block-and-a-page", "a-full-table"],
)
def test_a_walk_over_the_pages_is_the_softmax_over_the_whole_table(
    monkeypatch, hist_len, groups
):
    got, want = both(monkeypatch, BLOCK_PAGES, hist_len, TABLE, groups)
    assert got.shape == want.shape == (T, 2, groups, 16)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("groups", [1, 4])
def test_a_stored_width_wider_than_the_head(monkeypatch, groups):
    """Keys of 12 stored 16 wide: zeros behind the head's own dims, the
    scale the head's."""
    got, want = both(
        monkeypatch, BLOCK_PAGES, 20, TABLE, groups, head_dim=12, k_dim=16
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("block_pages", [1, 2, 5, TABLE, 64])
def test_any_block_of_whole_pages_the_table_need_not_be_a_multiple(
    monkeypatch, block_pages
):
    """Five pages a block over a table of twelve: the last block reaches
    past the table's end and reads the scratch page there; a block larger
    than the table is the table."""
    got, want = both(monkeypatch, block_pages, TABLE * PAGE - T, TABLE)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert continuous._attention_block_pages(T, 2, PAGE, TABLE) == min(
        block_pages, TABLE
    )


def test_a_row_whose_later_blocks_are_wholly_masked_keeps_its_values(
    monkeypatch,
):
    """A chunk of 16 queries over blocks of one page: the first row sees
    one block of the four the walk makes, and scores a hundred times the
    usual size would show a masked block that leaked (exp(-1e30 - m) is 0
    only if the running maximum is a real score)."""
    got, want = both(
        monkeypatch, 1, 0, TABLE, t=16, scale=10.0
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_the_walk_ends_where_the_keys_do(monkeypatch):
    """Pages past the last query's position are not read: NaN there
    changes nothing (the whole-table form would have read them)."""
    monkeypatch.setattr(
        continuous, "ATTN_BLOCK_BYTES", 4 * T * 2 * PAGE * BLOCK_PAGES
    )
    (q, pk, pv), table, pos = case(3, 4, TABLE, 1, 16, 16)
    # history 4 + chunk 8 = 12 keys: one block of 16; entries 4.. unread
    unheld = max(set(range(1, pk.shape[2])) - set(np.asarray(table)[:3]))
    poisoned = np.asarray(table).copy()
    poisoned[4:] = unheld
    bad_k = pk.at[:, :, unheld].set(jnp.nan)
    bad_v = pv.at[:, :, unheld].set(jnp.nan)
    got = continuous._table_attention(
        q, bad_k, bad_v, LAYER, jnp.asarray(poisoned), pos, 16
    )
    want = continuous._table_attention(q, pk, pv, LAYER, table, pos, 16)
    np.testing.assert_array_equal(got, want)


# -- through the engine: a prompt in chunks against the same prompt whole --------

# the benchmark's toy of each family: dense GQA; windowed layers, a sink and
# held experts; gated short convolutions; the gated delta rule; attention
# and a Mamba-2 mixer side by side
TOYS = {
    "dense": "toy/configs/toy-gqa.json",
    "windowed-and-expert": "toy_moe/configs/toy-moe-window.json",
    "convolution": "toy_conv/configs/toy-moe-conv.json",
    "delta": "toy_delta/configs/toy-delta.json",
    "parallel": "toy_ssm/configs/toy-ssm.json",
}
# what the engines' chunk tests allow a chunked prompt's logits; the delta
# toy's differ by 5.4e-4 at the parent too, in rows of the first program
# among them: the scan's blocks of 64 tokens fall elsewhere in a run of 16
TOL = {name: 2e-4 for name in TOYS} | {"delta": 1e-3}


@pytest.mark.parametrize("name", sorted(TOYS))
def test_a_prompt_in_chunks_gives_the_logits_of_the_prompt_prefilled_whole(
    name, monkeypatch
):
    """53 tokens through one prefill program, then the same tokens as 16
    through it and the rest in chunks of a page through the suffix program,
    each chunk walking the slot's table in blocks of two pages (up to
    seven trips a layer of the ``full`` class; the dense toy's pages are 16
    tokens, its three chunks walk one to two blocks): float32 logits of the
    row each run returns (its last real token's), the first chunk's, a
    middle one's and the last's, against the same prompt cut there and
    prefilled whole."""
    with open(os.path.join(BENCH, "tests", TOYS[name])) as f:
        cfg = {**json.load(f), "torch_dtype": "float32"}
    family = spec.load_family(cfg, BENCH)
    model, weights = family.model_config(cfg), family.make_weights(cfg, 7)
    page, heads = cfg["deployment"]["page_size"], cfg["num_attention_heads"]
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 53).tolist()

    def logits(longest, block_pages, n=len(prompt)):
        """(the positions of the rows the runs returned, the rows, the
        engine) of ``prompt[:n]``."""
        monkeypatch.setattr(
            continuous, "PREFILL_SCORES_BYTES", 4 * heads * longest * longest
        )
        eng = ContinuousBatchingEngine(
            model, weights, max_batch=1, page_size=page, n_pages=64
        )
        assert eng.max_prefill_tokens == longest
        monkeypatch.setattr(
            continuous, "ATTN_BLOCK_BYTES",
            4 * eng.prefill_chunk * heads * page * block_pages,
        )
        seen = []
        for program in ("_prefill", "_prefill_suffix"):
            def spied(*a, _program=getattr(eng, program),
                      _suffix=program == "_prefill_suffix", **kw):
                out = _program(*a, **kw)
                at = int(a[-1]) - 1 + (int(a[5]) if _suffix else 0)
                seen.append((at, np.asarray(out[0][0])))
                return out

            setattr(eng, program, spied)
        eng.generate_ids([prompt[:n]], GenerationConfig(max_new_tokens=1))
        return [p for p, _ in seen], np.stack([r for _, r in seen]), eng

    at, chunked, eng = logits(16, 2)
    padded = -(-len(prompt) // page) * page
    assert eng.prefill_chunk == page and len(at) == 1 + (padded - 16) // page
    assert at[-1] == len(prompt) - 1
    assert continuous._attention_block_pages(
        page, heads, page, eng.max_pages_per_seq
    ) == 2 < eng.max_pages_per_seq
    for i in (1, len(at) // 2, len(at) - 1):
        whole_at, whole, _ = logits(64, 64, at[i] + 1)
        assert whole_at == [at[i]]
        np.testing.assert_allclose(chunked[i], whole[0], rtol=0, atol=TOL[name])
