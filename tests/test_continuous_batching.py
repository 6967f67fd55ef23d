"""Continuous batching + paged KV cache engine.

Correctness bar: greedy outputs must MATCH the dense-cache LLMEngine
token-for-token (same params, same prompts) — the paged layout is a
memory-management change, not a math change. Plus: staggered admission,
page-pool backpressure, and page reuse across more requests than the
pool holds at once.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.continuous import ContinuousBatchingEngine
from ray_tpu.llm.engine import GenerationConfig, LLMEngine
from ray_tpu.models import transformer as tfm


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
        dtype=jnp.float32,  # exact parity with the dense engine
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def test_matches_dense_engine_greedy(small):
    cfg, params = small
    dense = LLMEngine(cfg, params, max_len=96)
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
    want = dense.generate_ids(prompts, gen)
    got = paged.generate_ids(prompts, gen)
    assert got == want


def test_continuous_admission_interleaves(small):
    """More requests than slots: later requests join as earlier finish —
    and the interleaving does not change any request's output."""
    cfg, params = small
    dense = LLMEngine(cfg, params, max_len=96)
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=32
    )
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0)
    want = dense.generate_ids(prompts, gen)
    got = paged.generate_ids(prompts, gen)
    assert got == want
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
    dense = LLMEngine(cfg, params, max_len=128)
    paged = ContinuousBatchingEngine(
        cfg, params, max_batch=2, page_size=8, n_pages=64
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 90, size=37).tolist()]
    gen = GenerationConfig(max_new_tokens=6, temperature=0.0)
    assert paged.generate_ids(prompts, gen) == dense.generate_ids(
        prompts, gen
    )


def test_pallas_attention_matches_gather_path(small):
    """The Pallas paged-attention decode (interpret mode) is a drop-in for
    the XLA gather path: identical greedy tokens."""
    cfg, params = small
    base = ContinuousBatchingEngine(
        cfg, params, max_batch=3, page_size=8, n_pages=48
    )
    pallas = ContinuousBatchingEngine(
        cfg,
        params,
        max_batch=3,
        page_size=8,
        n_pages=48,
        use_pallas_attention=True,
        pallas_interpret=True,
    )
    prompts = [[2, 4, 6, 8], [1, 3, 5], [7]]
    gen = GenerationConfig(max_new_tokens=10, temperature=0.0)
    assert pallas.generate_ids(prompts, gen) == base.generate_ids(
        prompts, gen
    )


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


def test_pallas_pool_beyond_vmem_raises_at_construction(small):
    """The paged-decode kernel stages one head's whole pool slice in VMEM:
    a pool past that is refused where it is asked for, with the sizes,
    not by the compiler at the first decode step."""
    cfg = tfm.ModelConfig(
        vocab_size=96, d_model=2048, n_layers=1, n_heads=16, n_kv_heads=16,
        d_ff=64, max_seq_len=128,
    )  # head_dim 128, bf16
    with pytest.raises(ValueError, match=r"64\.0 MiB for n_pages=4096.*16 MiB"):
        ContinuousBatchingEngine(
            cfg, params={}, n_pages=4096, use_pallas_attention=True
        )
