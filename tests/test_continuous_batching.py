"""Continuous batching + paged KV cache engine.

Correctness bar: every greedy token is the first choice of the benchmark's
plain reference (``benchmarks/references/dense_gqa.py``: float32, no cache,
nothing of the program) over the served sequence, compared as
``benchmarks/harness/check.py`` compares. Plus: staggered admission,
page-pool backpressure, and page reuse across more requests than the
pool holds at once.
"""
import inspect
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import check, spec  # noqa: E402

from ray_tpu.llm import continuous  # noqa: E402
from ray_tpu.llm.continuous import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.llm.engine import GenerationConfig  # noqa: E402
from ray_tpu.models import transformer as tfm  # noqa: E402

# float32 against float32 at `highest`: what the order of the sums leaves
TOL = 2e-4


@pytest.fixture(scope="module")
def small():
    cfg = tfm.ModelConfig(
        vocab_size=96,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        dtype=jnp.float32,  # the reference's own type
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def assert_reference_first_choices(small, prompts, served):
    """Teacher-forced over each served sequence (``check.output_gaps``): at
    every served token the reference's best logit less its logit of that
    token is under ``TOL``."""
    cfg, params = small
    src = {
        "name": "small", "reference": "dense_gqa",
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
    }
    sample = [{"prompt": p, "ids": ids} for p, ids in zip(prompts, served)]
    gaps, _ = check.output_gaps(
        src, params, sample, spec.load_reference(src, BENCH), control=False
    )
    assert len(gaps) == sum(len(ids) for ids in served)
    assert max(gaps) <= TOL, (prompts, served, gaps)


def test_greedy_tokens_are_the_plain_references_first_choices(small):
    cfg, params = small
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=4, page_size=8, n_pages=64
    )
    prompts = [
        [1, 5, 9, 2],
        [3, 3, 7],
        [11, 12, 13, 14, 15, 16, 17],
        [2],
    ]
    gen = GenerationConfig(max_new_tokens=12, temperature=0.0)
    got = paged.generate_ids(prompts, gen)
    assert [len(o) for o in got] == [12] * 4
    assert_reference_first_choices(small, prompts, got)


def test_continuous_admission_interleaves(small):
    """More requests than slots: later requests join as earlier finish —
    and the interleaving does not change any request's output."""
    cfg, params = small
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32
    )
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0)
    got = paged.generate_ids(prompts, gen)
    assert [len(o) for o in got] == [8] * 6
    assert_reference_first_choices(small, prompts, got)
    # pool fully reclaimed
    assert paged.pool.free_pages == paged.pool.usable_pages
    assert paged.stats()["active_slots"] == 0


def test_page_pool_backpressure(small):
    """A pool too small for all requests at once still completes them
    (admission waits for pages instead of failing)."""
    cfg, params = small
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=4, page_size=8, n_pages=6
    )
    # each request needs ceil((3+16)/8)=3 pages; 5 usable pages (one is
    # scratch) -> only 1 fits at a time
    prompts = [[5, 6, 7] for _ in range(5)]
    gen = GenerationConfig(max_new_tokens=16, temperature=0.0)
    out = paged.generate_ids(prompts, gen)
    assert len(out) == 5
    assert all(len(o) == 16 for o in out)
    assert out[0] == out[1] == out[4]  # same prompt, same greedy tokens
    assert paged.pool.free_pages == paged.pool.usable_pages


def test_eos_stops_early(small):
    cfg, params = small
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32
    )
    gen0 = GenerationConfig(max_new_tokens=10, temperature=0.0)
    first = paged.generate_ids([[4, 8]], gen0)[0]
    eos = first[3]  # pretend the 4th generated token is EOS
    gen = GenerationConfig(max_new_tokens=10, temperature=0.0, eos_token=eos)
    out = paged.generate_ids([[4, 8]], gen)[0]
    assert out == first[:3]
    assert paged.pool.free_pages == paged.pool.usable_pages


def test_long_prompt_multiple_pages(small):
    cfg, params = small
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=64
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 90, size=37).tolist()]
    gen = GenerationConfig(max_new_tokens=6, temperature=0.0)
    got = paged.generate_ids(prompts, gen)
    assert len(got[0]) == 6
    assert_reference_first_choices(small, prompts, got)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_decode_kernel_matches_gather_path(small, temperature):
    """The Pallas paged-attention decode (interpret mode) gives the XLA
    gather path's tokens slot for slot, with slots that idle, fill and end
    at different steps; and the step counts what it walked."""
    from ray_tpu.util import tracing

    cfg, params = small
    prompts = [[2, 4, 6, 8], [1, 3, 5], [7], list(range(1, 40)), [9, 9]]
    gen = GenerationConfig(max_new_tokens=10, temperature=temperature, seed=3)

    def run(kernel):
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=3, page_size=8, n_pages=48
        )
        assert eng._attn_kernel is None  # no TPU here: the XLA formulation
        eng._attn_kernel = kernel
        tracing.SPANS.clear()
        out = eng.generate_ids(prompts, gen)
        steps = [
            s["args"] for s in tracing.SPANS.slices(cat="engine")
            if s["name"] == "engine.decode"
        ]
        return out, steps, eng

    want, gathered, _ = run(None)
    got, walked, eng = run("interpret")
    assert got == want
    table = 3 * eng.max_pages_per_seq  # entries a layer's gather reads
    for a in gathered:
        assert (a["attn_kernel_layers"], a["attn_full_layers"]) == (0, 2)
        assert (a["attn_pages_walked"], a["attn_table_entries"]) == (0, 2 * table)
    assert walked
    for a in walked:
        assert a["attn_kernel_layers"] == a["attn_full_layers"] == cfg.n_layers
        assert a["attn_pages_walked"] == cfg.n_layers * a["pages_written"]
        assert a["attn_table_entries"] == cfg.n_layers * table
        assert a["attn_pages_walked"] < a["attn_table_entries"]


def test_no_caller_chooses_the_attention_path(small, monkeypatch):
    """The platform chooses: the kernel on a TPU, the XLA formulation
    elsewhere, for the decode attention and for the expert layers' grouped
    matmuls alike; the constructor has no argument for either."""
    import inspect

    cfg, params = small
    names = inspect.signature(ContinuousBatchingEngine.__init__).parameters
    assert not [n for n in names if "pallas" in n or "kernel" in n or "attention" in n]
    with pytest.raises(TypeError):
        ContinuousBatchingEngine(cfg, params, use_pallas_attention=True)
    eng = ContinuousBatchingEngine(cfg, params)
    assert (eng._attn_kernel, eng._moe_kernel) == (None, None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ContinuousBatchingEngine(cfg, params)
    assert (eng._attn_kernel, eng._moe_kernel) == ("compiled", "compiled")


def test_concurrent_callers_share_one_engine(small):
    """A serve replica runs several requests at once, each thread driving
    step() until its own answer is there. More threads than slots (and
    than cores), a shortened switch interval: two threads inside _admit at
    once would hand one slot to two requests and lose one of them, and its
    caller would never return."""
    import sys
    import threading
    import time

    cfg, params = small
    gen = GenerationConfig(max_new_tokens=10, temperature=0.0)
    prompts = [[1 + (i * 7 + j) % 90 for j in range(3 + i)] for i in range(12)]
    want = ContinuousBatchingEngine(
        cfg, params, max_batch=3, page_size=8, n_pages=64
    ).generate_ids(prompts, gen)
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=3, page_size=8, n_pages=64
    )
    got = [None] * len(prompts)

    def call(i):
        got[i] = eng.generate_ids([prompts[i]], gen)[0]

    threads = [
        threading.Thread(target=call, args=(i,), daemon=True)
        for i in range(len(prompts))
    ]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads), "a caller never returned"
    assert got == want
    assert eng.pool.free_pages == eng.pool.usable_pages


# ---------------------------------------------------------------------------
# the pool is only ever updated in place: every writer takes it donated
# ---------------------------------------------------------------------------


class _Hit:
    def __init__(self, tokens, k, v):
        self.tokens, self.k, self.v = tokens, k, v

    def release(self):
        pass


class _ListPrefixCache:
    """The engine's side of ``serve.prefix_cache`` over a list: the longest
    page-aligned prefix any inserted prompt shares with the one asked for."""

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
                pages = n // self.page
                best = _Hit(n, k[:, :, :pages], v[:, :, :pages])
        self.hits += best is not None
        return best

    def stats(self):
        return {"hits": self.hits}


_LONG = [3, 5, 7, 9, 11, 2, 4, 6, 8, 1, 3, 5, 7, 2, 9, 4, 6, 1]  # 2 pages + 2


def _engine(small, **kw):
    cfg, params = small
    return ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32, **kw
    )


def _spy(monkeypatch, owner, name, seen):
    """Record, for each call of the jitted program ``owner.name``, the
    pool it was handed and the compiled program's text."""
    program = getattr(owner, name)

    def spied(*args, **kw):
        names = list(inspect.signature(program).parameters)
        at = names.index("pool_k")
        before = len(jax.tree.leaves(args[:at]))
        text = program.lower(*args, **kw).compile().as_text()
        # the pool's K and V: a dict of arrays by class of page each
        seen.append((
            jax.tree.leaves(args[at]), jax.tree.leaves(args[at + 1]),
            before, text,
        ))
        return program(*args, **kw)

    monkeypatch.setattr(owner, name, spied)


def _drive_step(name):
    def drive(eng, _small, monkeypatch, seen):
        _spy(monkeypatch, eng, name, seen)
        eng.submit([1, 2, 3], GenerationConfig(max_new_tokens=4))
        eng.step()  # one prefill, then one decode step

    return drive


def _drive_prefix_hit(name):
    def drive(eng, _small, monkeypatch, seen):
        gen = GenerationConfig(max_new_tokens=4)
        eng.generate_ids([_LONG], gen)  # publishes the prompt's two pages
        owner = eng if name == "_prefill_suffix" else continuous
        _spy(monkeypatch, owner, name, seen)
        eng.generate_ids([_LONG[:16] + [9, 9, 9]], gen)
        assert eng.prefix_cache.hits == 1

    return drive


def _drive_adopt(eng, small, monkeypatch, seen):
    gen = GenerationConfig(max_new_tokens=4)
    manifest, k, v = _engine(small).prefill_extract(_LONG, gen)
    _spy(monkeypatch, continuous, "_scatter_pages", seen)
    assert eng.adopt_pages(manifest, k, v) is not None


@pytest.mark.parametrize(
    "drive, with_cache",
    [
        (_drive_step("_decode_step"), False),
        (_drive_step("_prefill"), False),
        (_drive_prefix_hit("_prefill_suffix"), True),
        (_drive_prefix_hit("_scatter_pages"), True),
        (_drive_adopt, False),
    ],
    ids=["decode_step", "prefill", "prefill_suffix", "prefix_hit_restore",
         "adopt_pages"],
)
def test_every_pool_writer_updates_the_pool_in_place(
    small, monkeypatch, drive, with_cache
):
    """The arrays that were the pool before a writer ran are gone after it
    (donated), the engine holds live ones, and the compiled program aliases
    its last two results to the two pool operands: nothing of the pool's
    size is copied."""
    eng = _engine(
        small, prefix_cache=_ListPrefixCache(8) if with_cache else None
    )
    seen = []
    drive(eng, small, monkeypatch, seen)
    assert len(seen) == 1
    old_k, old_v, before, text = seen[0]
    assert all(a.is_deleted() for a in old_k + old_v)
    live = jax.tree.leaves((eng.pool.k, eng.pool.v))
    assert not any(a.is_deleted() for a in live)
    assert all(np.isfinite(np.asarray(a)).all() for a in live)
    header = text.split("\n", 1)[0]
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    n = len(old_k + old_v)
    assert [int(p) for _, p in aliased] == list(range(before, before + n)), header
    outs = [int(o) for o, _ in aliased]
    # every class's K and V: the program's last results, in their order
    assert outs == list(range(outs[0], outs[0] + n))


def _undonated(eng, monkeypatch):
    """The same engine with the parent commit's programs: the same Python
    bodies, jitted without donation."""
    eng._decode_step = jax.jit(eng._decode_step.__wrapped__)
    for name in ("_prefill", "_prefill_suffix"):
        setattr(
            eng, name,
            jax.jit(getattr(eng, name).__wrapped__, static_argnums=(4,)),
        )
    monkeypatch.setattr(
        continuous, "_scatter_pages",
        jax.jit(continuous._scatter_pages.__wrapped__),
    )
    return eng


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_donation_changes_no_token(small, monkeypatch, temperature):
    """Five requests over two slots, so they admit as others finish, then
    four that hit the prefix cache the first round filled: token for token
    what the undonated programs give."""
    gen = GenerationConfig(
        max_new_tokens=9, temperature=temperature, seed=1234
    )
    first = [_LONG, [4, 8], _LONG[:9], [2] * 17, [7, 1, 5]]
    second = [_LONG[:16] + [9, 9], _LONG, [2] * 17 + [3], [6]]

    def run(eng):
        out = eng.generate_ids(first, gen) + eng.generate_ids(second, gen)
        assert eng.prefix_cache.hits >= 3
        assert eng.pool.free_pages == eng.pool.usable_pages
        return out

    got = run(_engine(small, prefix_cache=_ListPrefixCache(8)))
    with monkeypatch.context() as m:
        want = run(
            _undonated(_engine(small, prefix_cache=_ListPrefixCache(8)), m)
        )
    assert got == want
    assert all(len(o) == 9 for o in got)


@pytest.mark.parametrize("when", ["after_donation", "before_donation"])
def test_a_failed_pool_writer_leaves_a_typed_error(small, when):
    """A writer that fails once the pool is donated has taken the pool with
    it: that step and every later one raise ``KVPoolLost``, never a bare
    deleted-array error. One that fails before (a trace error, say) leaves
    the pool whole and the engine serving."""
    eng = _engine(small)
    gen = GenerationConfig(max_new_tokens=6)
    eng.submit([1, 2, 3], gen)
    real = eng._decode_step

    def failing(*args, **kw):
        if when == "after_donation":
            real(*args, **kw)
        raise ValueError("boom")

    eng._decode_step = failing
    if when == "before_donation":
        with pytest.raises(ValueError, match="boom"):
            eng.step()
        eng._decode_step = real
        assert len(eng.generate_ids([[4, 5]], gen)[0]) == 6
        return
    with pytest.raises(continuous.KVPoolLost, match="ValueError: boom"):
        eng.step()
    eng._decode_step = real
    for call in (eng.step, lambda: eng.generate_ids([[4, 5]], gen)):
        with pytest.raises(continuous.KVPoolLost, match="KV pool"):
            call()
