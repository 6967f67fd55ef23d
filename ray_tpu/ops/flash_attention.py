"""Flash attention as Pallas TPU kernels — forward AND backward.

Blockwise attention with an online-softmax accumulator: Q stays resident in
VMEM per grid step while K/V blocks stream HBM→VMEM; scores never
materialize in HBM (the memory win), and the causal grid skips fully-masked
K blocks (the compute win). Grid: (batch·kv_heads·groups, q_blocks).

The backward pass is the FlashAttention-2 recipe: the forward saves only
the per-row logsumexp L; the backward recomputes score blocks on the fly
and accumulates dQ (grid over Q blocks) and dK/dV (grid over K blocks)
without ever materializing the [T, S] probability matrix. This is what
makes the flagship model's training step runnable on the TPU — without a
custom VJP, autodiff cannot see through pallas_call.

Single-chip counterpart of ops/ring_attention.py (which handles the
sequence-sharded case over ICI); together they are the long-context story
the reference lacks natively (SURVEY §2.3).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .layers import attention_reference

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, block_k: int,
                causal: bool, scale: float):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, S, d]; o_ref: [1, block_q, d]
    # l_ref: [1, 1, block_q] — per-row logsumexp saved for the backward
    # pass. lse/delta ride as [bh, 1, t] (not [bh, t]) so their block
    # specs' trailing dims are (1, block) with 1 == the full array dim —
    # the Mosaic TPU lowering rejects a (1, block) window on a 2-D array
    # whose sublane dim is larger.
    _, block_q, d = q_ref.shape
    s = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0] * scale

    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o0 = jnp.zeros((block_q, d), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(kb, carry):
        m, l, o = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        scores = jnp.dot(
            q, k_blk.T, preferred_element_type=jnp.float32
        )  # [bq, bk]
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            scores = jnp.where(k_pos <= q_pos, scores, -1e30)
        m_blk = jnp.max(scores, axis=1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(scores - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        o_new = o * alpha[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, o_new

    num_kb = s // block_k
    if causal:
        # K blocks strictly above this Q block's diagonal are fully masked.
        num_kb_live = jnp.minimum(
            num_kb, (qi + 1) * block_q // block_k + 1
        )
    else:
        num_kb_live = num_kb
    m, l, o = jax.lax.fori_loop(0, num_kb_live, body, (m0, l0, o0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe[:, None]).astype(o_ref.dtype)
    l_ref[0, 0] = m + jnp.log(l_safe)  # logsumexp per row


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_k: int, causal: bool, scale: float):
    # per program: one Q block against all K blocks (same live set as fwd)
    _, block_q, d = q_ref.shape
    s = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0] * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        scores = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            scores = jnp.where(k_pos <= q_pos, scores, -1e30)
        p = jnp.exp(scores - lse[:, None])  # masked entries underflow to 0
        dp = jnp.dot(do, v_blk.T.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jnp.dot(
            ds.astype(k_blk.dtype), k_blk,
            preferred_element_type=jnp.float32,
        )

    num_kb = s // block_k
    if causal:
        num_kb_live = jnp.minimum(num_kb, (qi + 1) * block_q // block_k + 1)
    else:
        num_kb_live = num_kb
    dq = jax.lax.fori_loop(
        0, num_kb_live, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float):
    # per program: one K block against the Q blocks that can see it
    _, block_k, d = k_ref.shape
    t = q_ref.shape[1]
    ki = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :] * scale
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(
            jnp.float32
        )
        lse_blk = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        scores = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            scores = jnp.where(k_pos <= q_pos, scores, -1e30)
        p = jnp.exp(scores - lse_blk[:, None])  # [bq, bk]
        dv = dv + jnp.dot(
            p.T.astype(do_blk.dtype), do_blk,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.dot(do_blk, v.T.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None])
        dk = dk + jnp.dot(
            ds.T.astype(q_blk.dtype), q_blk,
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    num_qb = t // block_q
    if causal:
        qb_start = ki * block_k // block_q  # earlier Q blocks see nothing
    else:
        qb_start = 0
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb_start, num_qb, body, (dk0, dv0))
    # q_blk carried the scale into ds already — no second factor here
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _out_like(x, shape=None, dtype=None):
    """pallas_call out_shape for an output that varies over the same
    manual mesh axes as ``x`` (shard_map's check_vma needs it spelled out;
    outside shard_map the set is empty)."""
    return jax.ShapeDtypeStruct(
        x.shape if shape is None else shape,
        x.dtype if dtype is None else dtype,
        vma=jax.typeof(x).vma,
    )


def _to_bh(x):
    """[B, T, H, D] -> [B·H, T, D]: (batch, head) is the grid's first axis."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b):
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def pad_len(t: int, s: int, causal: bool, block_q: int, block_k: int):
    """Rows of zero padding that bring a ragged causal self-attention to
    the block multiple, 0 when the shapes already tile, None when they
    cannot be padded. Exact for causal t == s: padded keys sit at
    positions >= t, strictly in every real query's masked future, and
    padded query rows are sliced off (their cotangents are zero). Keeps
    the O(T) flash memory profile on ragged lengths (e.g. the T-1
    next-token training slice), where the reference would materialize
    [T, S] per layer."""
    if not (t % block_q or s % block_k):
        return 0
    if causal and t == s:
        return -t % (block_q * block_k // math.gcd(block_q, block_k))
    return None


def pad_rows(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x


def flash_fwd(q, k, v, *, causal=True, block_q=DEFAULT_BLOCK_Q,
              block_k=DEFAULT_BLOCK_K, interpret=False):
    """Forward kernel only: ``(out [B,T,H,D], lse [B,H,T_pad])``. With
    ``flash_bwd`` this is the pair ``flash_attention`` differentiates
    through; a caller that has to carry the residuals across a boundary
    autodiff cannot cross (models/transformer.py, attention under a mesh)
    uses the pair directly. Shapes must tile or be paddable (``pad_len``)."""
    b, t, h, d = q.shape
    groups = h // k.shape[2]
    pad = pad_len(t, k.shape[1], causal, block_q, block_k)
    q, k, v = (pad_rows(x, pad) for x in (q, k, v))
    # GQA: each K/V head serves `groups` consecutive Q heads
    qg = _to_bh(q)
    kg = _to_bh(jnp.repeat(k, groups, axis=2))
    vg = _to_bh(jnp.repeat(v, groups, axis=2))
    bh, tp, _ = qg.shape
    s = kg.shape[1]
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_k=block_k, causal=causal, scale=1.0 / d**0.5
        ),
        grid=(bh, tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, s, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, qi: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
        ],
        out_shape=[_out_like(qg), _out_like(qg, (bh, 1, tp), jnp.float32)],
        interpret=interpret,
    )(qg, kg, vg)
    return _from_bh(out, b)[:, :t], lse.reshape(b, h, tp)


def flash_bwd(q, k, v, out, lse, do, *, causal=True,
              block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
              interpret=False):
    """Backward kernels: ``(dq, dk, dv)`` from the forward's inputs, its
    output and its logsumexp, and the output cotangent."""
    b, t, h, d = q.shape
    s0, hkv = k.shape[1], k.shape[2]
    groups = h // hkv
    pad = lse.shape[2] - t
    q, k, v, out, do = (pad_rows(x, pad) for x in (q, k, v, out, do))
    qg, og, dog = _to_bh(q), _to_bh(out), _to_bh(do)
    kg = _to_bh(jnp.repeat(k, groups, axis=2))
    vg = _to_bh(jnp.repeat(v, groups, axis=2))
    bh, tp, _ = qg.shape
    s = kg.shape[1]
    scale = 1.0 / d**0.5
    lse = lse.reshape(bh, 1, tp)
    # delta_i = rowsum(dO ⊙ O): the softmax-jacobian correction term, in
    # the same [bh, 1, t] layout as lse (see _fwd_kernel)
    delta = jnp.sum(
        dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1
    )[:, None, :]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_k=block_k, causal=causal, scale=scale
        ),
        grid=(bh, tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, s, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, qi: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
        out_shape=_out_like(qg),
        interpret=interpret,
    )(qg, kg, vg, dog, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, causal=causal, scale=scale
        ),
        grid=(bh, s // block_k),
        in_specs=[
            pl.BlockSpec((1, tp, d), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, tp, d), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda b, ki: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
        ],
        out_shape=[_out_like(kg), _out_like(vg)],
        interpret=interpret,
    )(qg, kg, vg, dog, lse, delta)

    def kv_grad(g):
        # sum over the `groups` query heads that shared each K/V head
        g = _from_bh(g, b)[:, :s0].reshape(b, s0, hkv, groups, d)
        return g.astype(jnp.float32).sum(axis=3).astype(g.dtype)

    return _from_bh(dq, b)[:, :t], kv_grad(dk), kv_grad(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = flash_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    return flash_bwd(
        *res, do, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    if pad_len(q.shape[1], k.shape[1], causal, block_q, block_k) is None:
        # ragged cross/non-causal tails fall back to the fused-XLA path
        return attention_reference(q, k, v, causal=causal)
    return _flash(q, k, v, causal, block_q, block_k, interpret)
