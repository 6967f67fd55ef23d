"""Paged-attention decode as a Pallas TPU kernel over a pool that stays in HBM.

The decode-step attention of the continuous-batching engine
(ray_tpu/llm/continuous.py) for layers of the ``full`` class of KV page:
each slot's one query token attends over its context through its block
table. The XLA formulation (``paged_attention_reference``, and the
engine's own path where there is no TPU) gathers every slot's whole table,
live or empty, into a contiguous view for every layer; this kernel is
handed the pool where it lies and reads only the pages that hold live
positions.

Grid: one program a slot, in order. The pool ``[layers, KV heads, pages,
page, size]`` is an operand in ``pl.ANY`` (HBM) and so is never staged,
sliced or copied by the compiler; the layer is a scalar operand, not a slice
taken outside. A program walks ``ceil(length / page)`` entries of its
slot's row of the table (none for an inactive slot, whose length is 0) in
chunks of ``pages_per_chunk``: one DMA a page brings all KV heads of that
page (``pool[layer, :, page id]``; a head-major page alone is 4 KiB) into
one of two VMEM buffers while the chunk before it is computed, so a chunk's
pages are all in flight at once; a slot's last chunk is computed while the
next slot's first is under way. Online softmax over chunks: scores and the
running max, sum and output in float32; K and V are read as the pool's
type (bfloat16 in every deployment) and the products are accumulated in
float32, the probabilities rounded to the pool's type for the second
product, which is what the XLA formulation's einsum does on a TPU.

Scalar memory: the lengths (``4 B`` bytes) and the layer are prefetched
whole; of the table only the running slot's row and the next slot's
(``4 P`` bytes each, 2 KiB at 512 entries) are in SMEM at a time, brought by
the pipeline as blocks.

K and V may differ in width (keys stored 256 wide for a head of 192,
values 128), and the scale is the caller's, the head's own and not the
stored width's.

Numerics are validated against the XLA reference in interpret mode
(tests/test_paged_attention.py), slot for slot against the engine's gather
path (tests/test_continuous_batching.py, tests/test_moe_window_engine.py),
and the kernel is compiled for a described v5e at the deployments' real
geometries (tests/test_chip_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: pages a buffer holds, all in flight at once and computed as one block.
#: Measured on a v5e at the deployments' geometries (PERF.md, PR 32): a
#: block costs 0.7 us however small, so larger is faster (1,024 keys: 617
#: GB/s of K and V over 8,000-token contexts, 540 over 700-token ones; 256
#: keys: 440 and 380) until the unfilled tail of a slot's last block, which
#: is computed though not read, outweighs it. 64 pages of 16 tokens at 8
#: KV heads x 128 are 2 MiB of K and 2 MiB of V a buffer, two buffers each.
PAGES_PER_CHUNK = 64
#: what the four buffers may take of the 16 MiB of VMEM a kernel is allowed
#: by default; wider pages (more KV heads, wider heads) get fewer a chunk
BUFFER_BYTES = 8 * 2**20


def _decode_kernel(
    layer_ref,  # int32[1] in SMEM (prefetched): the layer within the pool
    len_ref,  # int32[B] in SMEM (prefetched): live positions a slot, 0 = idle
    tbl_ref,  # int32[1, 1, P] in SMEM: this slot's row of the block table
    nxt_ref,  # int32[1, 1, P] in SMEM: the next slot's row
    q_ref,  # [1, KH, G, Dk]
    k_hbm,  # [L, KH, N, page, Dk] where it lies
    v_hbm,  # [L, KH, N, page, Dv]
    o_ref,  # float32[1, KH, G, Dv]
    k_buf,  # [2, KH, chunk * page, Dk] VMEM
    v_buf,  # [2, KH, chunk * page, Dv] VMEM
    sem,  # DMA semaphores [2 (K, V), 2 (buffer)]
    first_ref,  # int32[1] in SMEM: the buffer this slot's first chunk is in
    *,
    page: int,
    chunk: int,  # pages a buffer holds
    scale: float,
):
    kh, g = q_ref.shape[1], q_ref.shape[2]
    dv = v_buf.shape[-1]
    keys = chunk * page
    slot, slots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    length = len_ref[slot]
    live = (length + page - 1) // page  # table entries that hold a position
    n_chunks = (live + chunk - 1) // chunk

    def copies(page_id, buf, i):
        at = pl.ds(i * page, page)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, :, page_id], k_buf.at[buf, :, at], sem.at[0, buf]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, :, page_id], v_buf.at[buf, :, at], sem.at[1, buf]
            ),
        )

    def start(table, pages, c, buf):
        """Chunk ``c`` of a slot that holds ``pages`` live pages: every
        live page of it in flight, into buffer ``buf``."""

        def one(i, _):
            for cp in copies(table[0, 0, c * chunk + i], buf, i):
                cp.start()
            return 0

        jax.lax.fori_loop(0, jnp.clip(pages - c * chunk, 0, chunk), one, 0)

    def start_next_slot(buf):
        """The first chunk of the slot after this one, so that it is under
        way while this slot's last is computed (none past the last slot,
        none for an idle one)."""
        nxt = jnp.minimum(slot + 1, slots - 1)
        pages = jnp.where(
            slot + 1 < slots, (len_ref[nxt] + page - 1) // page, 0
        )
        start(nxt_ref, pages, 0, buf)

    @pl.when(slot == 0)
    def _():
        # what a chunk's unfilled tail holds is masked out of the scores but
        # multiplied (by an exact 0) in the second product: it must be
        # finite, so the buffers start as zeros and hold real pages after
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        first_ref[0] = 0
        start(tbl_ref, live, 0, 0)  # no slot before this one began it

    first = first_ref[0]

    def chunk_step(c, carry):
        buf = (first + c) % 2
        # the buffer the chunk before this one was computed out of
        @pl.when(c + 1 < n_chunks)
        def _():
            start(tbl_ref, live, c + 1, 1 - buf)

        @pl.when(c + 1 == n_chunks)
        def _():
            start_next_slot(1 - buf)

        def arrived(i, _):
            # a wait is by the copy's size and semaphore, not its source
            for cp in copies(0, buf, i):
                cp.wait()
            return 0

        jax.lax.fori_loop(0, jnp.minimum(chunk, live - c * chunk), arrived, 0)
        pos = c * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        valid = pos < length
        out = []
        for h in range(kh):
            m, l, acc = carry[h]
            k = k_buf[buf, h]  # [keys, Dk]
            v = v_buf[buf, h]  # [keys, Dv]
            scores = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, keys]
            scores = jnp.where(valid, scores, -1e30)
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple(
        (
            jnp.full((g, 1), -jnp.inf, jnp.float32),
            jnp.zeros((g, 1), jnp.float32),
            jnp.zeros((g, dv), jnp.float32),
        )
        for _ in range(kh)
    )
    done = jax.lax.fori_loop(0, n_chunks, chunk_step, init)

    @pl.when(n_chunks == 0)
    def _():
        start_next_slot(first)  # an idle slot hands the turn on

    first_ref[0] = (first + n_chunks) % 2
    for h, (_, l, acc) in enumerate(done):
        # an idle slot walked nothing: its sums are 0 and so is its output
        o_ref[0, h] = acc / jnp.maximum(l, 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "pages_per_chunk", "interpret"),
)
def paged_attention_decode(
    q: jax.Array,  # [B, KH, G, Dk] one query token a slot, grouped heads
    k_pool: jax.Array,  # [L, KH, N_pages, page, Dk] head-major, all layers
    v_pool: jax.Array,  # [L, KH, N_pages, page, Dv]
    layer: jax.Array,  # int32 scalar: the layer of the pool to read
    block_tables: jax.Array,  # [B, P_max] int32
    lengths: jax.Array,  # [B] int32 live positions a slot; 0: an idle slot
    *,
    scale: float,
    pages_per_chunk: int = PAGES_PER_CHUNK,
    interpret: bool = False,
) -> jax.Array:  # float32 [B, KH, G, Dv]
    b, kh, g, dk = q.shape
    page, dv = k_pool.shape[3], v_pool.shape[4]
    p_max = block_tables.shape[1]
    one_page = kh * page * (dk + dv) * k_pool.dtype.itemsize  # K and V
    chunk = max(1, min(pages_per_chunk, p_max, BUFFER_BYTES // (2 * one_page)))
    kernel = functools.partial(
        _decode_kernel, page=page, chunk=chunk, scale=scale
    )
    tables = block_tables.reshape(b, 1, p_max)

    def row(of):
        # one slot's row of the table at a time: 4 * P_max bytes of SMEM
        return pl.BlockSpec(
            (1, 1, p_max), lambda i, *_: (of(i), 0, 0),
            memory_space=pltpu.SMEM,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            row(lambda i: i),
            row(lambda i: jnp.minimum(i + 1, b - 1)),
            pl.BlockSpec((1, kh, g, dk), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kh, g, dv), lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kh, chunk * page, dk), k_pool.dtype),
            pltpu.VMEM((2, kh, chunk * page, dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dv), jnp.float32),
        # slots in order: each begins the next one's first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name="paged_attention_decode",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        lengths.astype(jnp.int32),
        tables,
        tables,
        q.astype(k_pool.dtype),
        k_pool,
        v_pool,
    )


def paged_attention_reference(
    q, k_pool, v_pool, layer, block_tables, lengths, *, scale
):
    """XLA gather formulation (the engine's ``_attention_pages`` where
    there is no TPU is the same computation): the golden model the kernel is
    tested against. Same operands and result as ``paged_attention_decode``;
    an idle slot's row is zeros."""
    b, kh, g, _ = q.shape
    page = k_pool.shape[3]
    s_max = block_tables.shape[1] * page
    # [KH, B, P, page, size] per-slot gather out of the head-major pool
    ks = k_pool[layer][:, block_tables].reshape(kh, b, s_max, -1)
    vs = v_pool[layer][:, block_tables].reshape(kh, b, s_max, -1)
    scores = jnp.einsum(
        "bhgd,hbsd->bhgs", q.astype(jnp.float32), ks.astype(jnp.float32)
    ) * scale
    valid = jnp.arange(s_max)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,hbsd->bhgd", probs, vs.astype(jnp.float32))
    return jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
