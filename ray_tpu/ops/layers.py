"""Elementary model ops: RMSNorm, RoPE, SwiGLU, attention (jnp reference).

Pure-functional building blocks, written for XLA fusion: everything is
jnp-level so the compiler fuses the elementwise chains into the surrounding
matmuls (HBM-bandwidth discipline); the Pallas flash-attention kernel in
``flash_attention.py`` replaces ``attention_reference`` on TPU for long
sequences.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def rope_freqs(head_dim: int, max_len: int, theta: float = 10000.0) -> jax.Array:
    """[max_len, head_dim//2] complex rotation angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    return jnp.outer(t, inv)  # [T, D/2]


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: [..., T, H, D]; angles: [T, D/2] (already offset for this shard)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
           gate_scale: float = 1.0, down_scale: float = 1.0):
    """SwiGLU; ``gate_scale`` scales the gate's projection before its SiLU
    and ``down_scale`` the result (Falcon-H1's ``mlp_multipliers``)."""
    g = jax.nn.silu((x @ w_gate) * gate_scale)
    return ((g * (x @ w_up)) @ w_down) * down_scale


def attention_reference(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    causal: bool = True,
    q_offset: int = 0,
) -> jax.Array:
    """Plain softmax attention with GQA head-group broadcast. Numerics
    reference for the flash/ring kernels."""
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    groups = h // hkv
    qh = q.reshape(b, t, hkv, groups, d)
    scores = jnp.einsum("bthgd,bshd->bhgts", qh, k) / jnp.sqrt(d).astype(q.dtype)
    scores = scores.astype(jnp.float32)
    if causal:
        q_pos = jnp.arange(t) + q_offset
        k_pos = jnp.arange(s)
        mask = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v)
    return out.reshape(b, t, h, d)
