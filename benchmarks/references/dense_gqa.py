"""Plain reference of a dense grouped-query-attention decoder
(Mistral-7B-v0.3, InternLM2): RMSNorm, rotary embedding in the rotate-half
convention, causal softmax attention with KV heads shared by groups of
query heads, SwiGLU, untied head. Straight ``jax.numpy`` in float32 with
matrix products at ``highest`` precision; no cache, no paging, no batching,
no kernels. It imports nothing of the program.

Weights arrive in the type they are served in and are widened one layer at
a time, so the float32 copy of a 7 GB stack never exists.

``quant="int8"`` is the control: the same forward pass with the operands of
every matrix product in the precision below the stated bfloat16, symmetric
int8 (127 levels a side), weights scaled per output channel and
activations per row: the W8A8 step a later change would be tempted to take
on a chip whose int8 peak is twice its bfloat16 peak.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x: [T, H, hd]; pos: [T]. Rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fake_int8(x, axis):
    """Symmetric int8 along ``axis``, returned as the float32 it stands for."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _matmul(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "int8":
        x = _fake_int8(x, axis=-1)  # one scale for each row of activations
        w = _fake_int8(w, axis=0)   # one scale for each output channel
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


@functools.partial(
    jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps", "quant")
)
def logits_at(
    params, tokens, rows, *, heads, kv_heads, theta, eps, quant=None
):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence
    ``tokens`` [T] (right-padded; causal, so padding cannot reach back)."""
    t = tokens.shape[0]
    pos = jnp.arange(t)
    h = params["embed"][tokens].astype(jnp.float32)
    groups = heads // kv_heads
    causal = pos[None, :] <= pos[:, None]

    def block(h, p):
        x = _rms_norm(h, p["ln1"].astype(jnp.float32), eps)
        q = _matmul(x, p["wq"], quant).reshape(t, heads, -1)
        k = _matmul(x, p["wk"], quant).reshape(t, kv_heads, -1)
        v = _matmul(x, p["wv"], quant).reshape(t, kv_heads, -1)
        hd = q.shape[-1]
        q = _rope(q, pos, theta).reshape(t, kv_heads, groups, hd)
        k = _rope(k, pos, theta)
        scores = jnp.einsum(
            "tkgd,skd->kgts", q, k, precision=jax.lax.Precision.HIGHEST
        ) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum(
            "kgts,skd->tkgd", probs, v, precision=jax.lax.Precision.HIGHEST
        ).reshape(t, heads * hd)
        h = h + _matmul(attn, p["wo"], quant)
        x = _rms_norm(h, p["ln2"].astype(jnp.float32), eps)
        gate = jax.nn.silu(_matmul(x, p["w_gate"], quant))
        up = _matmul(x, p["w_up"], quant)
        return h + _matmul(gate * up, p["w_down"], quant), None

    h, _ = jax.lax.scan(block, h, params["blocks"])
    h = _rms_norm(h[rows], params["ln_f"].astype(jnp.float32), eps)
    return _matmul(h, params["head"], quant)


def reference_logits(params, cfg: dict, tokens, rows, quant=None):
    return logits_at(
        params, tokens, rows,
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        quant=quant,
    )
