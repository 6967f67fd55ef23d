"""Plain reference of a decoder most of whose layers are no attention
(LFM2-8B-A1B, ``lfm2_moe``): gated short convolutions and, every fourth
layer or so, full grouped-query attention with a norm over each head of q
and k; two dense SwiGLU layers and then sigmoid-routed experts with a bias
that chooses and does not weigh; the head is the embedding. Straight
``jax.numpy`` in float32 with matrix products at ``highest`` precision; no
cache, no state carried from step to step, no paging, no batching, no
kernels. It imports nothing but JAX.

The layer, as ``configs/lfm2-8b-a1b-l14.json`` reads the source's
``config`` (each point it had to infer is under ``assumed`` there). For a
layer with input ``x`` of ``T`` tokens, ``d = hidden_size``:

- ``u = RMSNorm(x, operator_norm)`` (``ln1``; ``norm_eps``).
- ``layer_types`` "conv": ``[B, C, z] = split3(u W_in)`` (``W_in``: d x
  3d, no bias, the parts in that order); ``s_t = B_t * z_t``; ``c_t = k_0
  * s_{t-2} + k_1 * s_{t-1} + k_2 * s_t`` (depthwise, causal, ``conv_L_cache``
  = 3 taps as three shifted products, ``s`` before the sequence 0, no
  bias); the operator gives ``(C * c) W_out``.
- "full_attention": ``q = u Wq`` as ``num_attention_heads`` heads of d /
  heads, ``k = u Wk`` and ``v = u Wv`` as ``num_key_value_heads`` heads;
  q and k each through an RMS norm over a head's dims with a learned
  scale (``q_norm``, ``k_norm``; ``norm_eps``); rotary, rotate-half over
  the whole head, base ``rope_theta``; causal softmax of ``q k /
  sqrt(head size)`` over every earlier key; ``Wo``.
- ``x = x + operator``; ``f = RMSNorm(x, ffn_norm)`` (``ln2``).
- the first ``num_dense_layers`` layers: ``x + (silu(f W_1) * (f W_3))
  W_2`` (``w_gate``, ``w_up``, ``w_down``; width ``intermediate_size``).
- every later layer: ``p = sigmoid(f W_r)`` over ``num_experts``; chosen
  = the ``num_experts_per_tok`` largest of ``p + b`` (``use_expert_bias``:
  ``b`` chooses and does not weigh); ``w = p[chosen] / (sum p[chosen] +
  router_norm_eps)`` (``norm_topk_prob``) times ``routed_scaling_factor``;
  ``x + sum_e w_e SwiGLU_e(f)`` over the chosen experts that are HELD
  HERE (``experts_held = [first, count]``; all of them in the cell), width
  ``moe_intermediate_size``; no shared expert.
- after the last layer ``RMSNorm(x, embedding_norm)`` (``ln_f``); logits
  ``= h E^T`` with the embedding itself (``tie_word_embeddings``).

Weights arrive in the type they are served in, one stack for each run of
consecutive layers of one kind (``params["blocks"]["<conv|full>.<dense|
experts>[.<n>]"]``: a kind's first run, then its n-th later one), and are
widened one matrix at a time; a run is one ``lax.scan``. Attention runs
over blocks of ``Q_BLOCK`` queries.

``quant="int8"`` is the control: the same pass with the operands of every
matrix product (the router's and the head's too) in symmetric int8,
weights scaled per output channel and activations per row. The
convolution's three products are element-wise and stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x: [T, H, hd]; pos: [T]. Rotate-half over the whole head."""
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
        w = _fake_int8(w, axis=-2)  # one scale for each output channel
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _attention(q, k, v):
    """q: [T, KH, G, hd]; k, v: [T, KH, hd]. Blocks of queries against
    every key, causal."""
    t, hd = q.shape[0], q.shape[-1]
    block = min(Q_BLOCK, t)
    assert t % block == 0
    k_pos = jnp.arange(t)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        q_pos = start + jnp.arange(block)
        s = jnp.einsum("tkgd,skd->kgts", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(hd))
        s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)

    return jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, -1)


def _gated_conv(u, p, quant):
    """The operator of a "conv" layer. u: [T, D]. ``p["conv"]``: [taps, D],
    the oldest tap first."""
    gate_in, gate_out, z = jnp.split(_matmul(u, p["w_in"], quant), 3, axis=-1)
    s = gate_in * z
    taps = p["conv"].astype(jnp.float32)
    n = taps.shape[0]
    c = jnp.zeros_like(s)
    for j in range(n):  # tap j weighs s_{t - (n - 1 - j)}
        back = n - 1 - j
        shifted = jnp.pad(s, ((back, 0), (0, 0)))[: s.shape[0]]
        c = c + taps[j] * shifted
    return _matmul(gate_out * c, p["w_out"], quant)


def _route(y, p, top_k, norm_topk, norm_eps, scaling, quant):
    """Chosen experts [T, k] and their weights [T, k]."""
    sigma = jax.nn.sigmoid(_matmul(y, p["router"], quant))
    _, chosen = jax.lax.top_k(sigma + p["router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sigma, chosen, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return chosen, w * scaling


def _experts(y, p, top_k, held, norm_topk, norm_eps, scaling, quant):
    """This holder's part of the expert layer. y: [T, D]."""
    first, count = held
    chosen, w = _route(y, p, top_k, norm_topk, norm_eps, scaling, quant)
    # weight of each held expert for each token: 0 where it was not chosen
    ids = first + jnp.arange(count)
    w_held = jnp.sum(
        jnp.where(chosen[:, :, None] == ids[None, None, :], w[:, :, None], 0.0),
        axis=1,
    )  # [T, count]

    def one(out, e):
        gate = jax.nn.silu(_matmul(y, p["w_gate"][e], quant))
        up = _matmul(y, p["w_up"][e], quant)
        return out + w_held[:, e, None] * _matmul(
            gate * up, p["w_down"][e], quant
        ), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), jnp.arange(count))
    return out


@functools.partial(jax.jit, static_argnames=("shape", "quant"))
def logits_at(params, tokens, rows, *, shape, quant=None):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence
    ``tokens`` [T] (right-padded; causal, so padding cannot reach back).
    ``shape``: the hashable tuple ``_shape(cfg)`` makes."""
    (heads, kv_heads, theta, eps, pattern, top_k, held, norm_topk, norm_eps,
     scaling) = shape
    pos = jnp.arange(tokens.shape[0])
    h = params["embed"][tokens].astype(jnp.float32)
    t = h.shape[0]

    def block(h, p, conv, experts):
        u = _rms_norm(h, p["ln1"].astype(jnp.float32), eps)
        if conv:
            h = h + _gated_conv(u, p, quant)
        else:
            groups = heads // kv_heads
            q = _matmul(u, p["wq"], quant).reshape(t, heads, -1)
            k = _matmul(u, p["wk"], quant).reshape(t, kv_heads, -1)
            v = _matmul(u, p["wv"], quant).reshape(t, kv_heads, -1)
            q = _rms_norm(q, p["q_norm"].astype(jnp.float32), eps)
            k = _rms_norm(k, p["k_norm"].astype(jnp.float32), eps)
            q = _rope(q, pos, theta).reshape(t, kv_heads, groups, -1)
            h = h + _matmul(_attention(q, _rope(k, pos, theta), v), p["wo"], quant)
        y = _rms_norm(h, p["ln2"].astype(jnp.float32), eps)
        if experts:
            return h + _experts(
                y, p["moe"], top_k, held, norm_topk, norm_eps, scaling, quant
            )
        gate = jax.nn.silu(_matmul(y, p["w_gate"], quant))
        up = _matmul(y, p["w_up"], quant)
        return h + _matmul(gate * up, p["w_down"], quant)

    # layers in the pattern's order; a run of layers of one kind is one scan
    # over the run's own stack (the same block, compiled once a kind)
    runs, at = {}, 0
    while at < len(pattern):
        kind = pattern[at]
        n = 1
        while at + n < len(pattern) and pattern[at + n] == kind:
            n += 1
        nth = runs.get(kind, 0)
        runs[kind] = nth + 1
        key = ".".join(kind) + (f".{nth}" if nth else "")
        h, _ = jax.lax.scan(
            lambda h, p, kind=kind: (
                block(h, p, kind[0] == "conv", kind[1] == "experts"), None
            ),
            h, params["blocks"][key],
        )
        at += n
    h = _rms_norm(h[rows], params["ln_f"].astype(jnp.float32), eps)
    return _matmul(h, params["embed"].T, quant)


def layer_kinds(cfg: dict):
    """(operator, feed-forward) of each layer, by the source's keys."""
    return tuple(
        ("conv" if kind == "conv" else "full",
         "dense" if i < cfg["num_dense_layers"] else "experts")
        for i, kind in enumerate(cfg["layer_types"])
    )


def _shape(cfg: dict):
    """What ``logits_at`` needs of a configuration's file, hashable."""
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("the reference computes no convolution bias and "
                         "always the expert bias")
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("the reference's head is the embedding")
    return (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        float(cfg["rope_theta"]), float(cfg["norm_eps"]), layer_kinds(cfg),
        cfg["num_experts_per_tok"],
        tuple(cfg.get("experts_held", (0, cfg["num_experts"]))),
        bool(cfg["norm_topk_prob"]), float(cfg["router_norm_eps"]),
        float(cfg["routed_scaling_factor"]),
    )


def reference_logits(params, cfg: dict, tokens, rows, quant=None):
    return logits_at(params, tokens, rows, shape=_shape(cfg), quant=quant)
