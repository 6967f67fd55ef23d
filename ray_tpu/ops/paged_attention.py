"""Paged-attention decode as a Pallas TPU kernel.

The decode-step attention of the continuous-batching engine
(ray_tpu/llm/continuous.py): each slot's single query token attends over
its paged KV cache via a block table. The XLA formulation gathers every
slot's pages into a contiguous [S_max] view (one big materialized gather
per layer); this kernel instead walks the block table INSIDE the kernel —
pages stream out of the per-head pool and scores/weights never leave
VMEM, with an online-softmax accumulator across pages (the
JetStream/PagedAttention structure).

Grid: (batch_slot, kv_head). Per program: q [G, D] resident; fori_loop
over the slot's table entries; each iteration dynamically indexes one
[page, D] K/V tile from the head's pool slice and folds it into the
running max/sum/output.

VMEM note: the BlockSpec stages one HEAD's pool slice
(n_pages·page·head_dim elements) per program, for K and for V, each
double-buffered by the pipeline — with the engine defaults (256 pages ×
16 × 64 × bf16 ≈ 512 KB a slice) this fits comfortably; ``staged_vmem_bytes``
is that sum and ``SCOPED_VMEM_BYTES`` what Mosaic allows a kernel by
default (the engine checks one against the other at construction). Larger
pools need the HBM-resident variant with explicit page DMA
(make_async_copy); the call signature is layout-compatible.

Numerics are validated against the XLA reference in interpret mode
(tests/test_paged_attention.py) and slot-for-slot against the engine's
gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Mosaic's default scoped-VMEM allowance for one kernel. Compiling for a
#: described v5e refuses this kernel exactly when ``staged_vmem_bytes``
#: exceeds it at head_dim 128 ("Scoped allocation with size ... and limit
#: 16.00M", tests/test_chip_compile.py pins the refusal).
SCOPED_VMEM_BYTES = 16 * 2**20


def staged_vmem_bytes(n_pages: int, page_size: int, head_dim: int, dtype) -> int:
    """VMEM the kernel's BlockSpecs stage: one head's whole pool slice for
    K and for V, two pipeline buffers each."""
    return 4 * n_pages * page_size * head_dim * jnp.dtype(dtype).itemsize


def _paged_kernel(
    tbl_ref,  # [B, P_max] int32 in SMEM — all block tables (scalar loads)
    len_ref,  # [B] int32 in SMEM — valid positions (q_pos + 1) per slot
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, N, page, D] — this kv head's pool slice
    v_ref,  # [1, N, page, D]
    o_ref,  # [1, 1, G, D]
    *,
    page: int,
    p_max: int,
    scale: float,
):
    g, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0] * scale  # [G, D]
    slot = pl.program_id(0)
    length = len_ref[slot]

    m0 = jnp.full((g,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    o0 = jnp.zeros((g, d), jnp.float32)

    def body(j, carry):
        m, l, o = carry
        pid = tbl_ref[slot, j]
        k_pg = k_ref[0, pid]  # [page, D] — dynamic page index into the pool
        v_pg = v_ref[0, pid]
        scores = jnp.dot(
            q, k_pg.T, preferred_element_type=jnp.float32
        )  # [G, page]
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        scores = jnp.where(pos < length, scores, -1e30)
        m_blk = jnp.max(scores, axis=1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(scores - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        o_new = o * alpha[:, None] + jnp.dot(
            p.astype(v_pg.dtype), v_pg, preferred_element_type=jnp.float32
        )
        return m_new, l_new, o_new

    # only pages that hold valid positions contribute; masked pages beyond
    # the sequence are skipped entirely (live = ceil(length / page))
    live = jnp.minimum(p_max, (length + page - 1) // page)
    m, l, o = jax.lax.fori_loop(0, live, body, (m0, l0, o0))
    o_ref[0, 0] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_attention_decode(
    q: jax.Array,  # [B, KH, G, D] one query token per slot, grouped heads
    k_pages: jax.Array,  # [KH, N_pages, page, D] head-major pool
    v_pages: jax.Array,  # [KH, N_pages, page, D]
    block_tables: jax.Array,  # [B, P_max] int32
    lengths: jax.Array,  # [B] int32 valid positions per slot
    *,
    page_size: int,
    interpret: bool = False,
) -> jax.Array:  # [B, KH, G, D]
    b, kh, g, d = q.shape
    p_max = block_tables.shape[1]
    scale = 1.0 / (d**0.5)
    kernel = functools.partial(
        _paged_kernel, page=page_size, p_max=p_max, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=(b, kh),
        in_specs=[
            # block table + lengths are scalar control data: whole arrays
            # in SMEM (the Mosaic lowering rejects (1, P) VMEM windows on
            # int32 tables, and page ids drive addresses, not vectors)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec(
                (1, k_pages.shape[1], page_size, d), lambda i, h: (h, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, v_pages.shape[1], page_size, d), lambda i, h: (h, 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, h: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)


def paged_attention_reference(
    q, k_pages, v_pages, block_tables, lengths, *, page_size
):
    """XLA gather formulation (the engine's default path) — the golden
    model the kernel is tested against."""
    b, kh, g, d = q.shape
    p_max = block_tables.shape[1]
    s_max = p_max * page_size
    # [B, P, page, KH→, D] per-slot gather, head-major pool in
    ks = jnp.transpose(k_pages, (1, 2, 0, 3))[  # [N, page, KH, D]
        block_tables
    ].reshape(b, s_max, kh, d)
    vs = jnp.transpose(v_pages, (1, 2, 0, 3))[block_tables].reshape(
        b, s_max, kh, d
    )
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", q.astype(jnp.float32), ks.astype(jnp.float32)
    ) / (d**0.5)
    valid = jnp.arange(s_max)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhgs,bshd->bhgd", probs, vs.astype(jnp.float32)
    ).astype(q.dtype)
