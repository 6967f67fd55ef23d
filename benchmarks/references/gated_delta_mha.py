"""Plain reference of a decoder three of whose four layers are the gated
delta rule, a linear attention with a matrix of state a head, and the
fourth full multi-head attention (Olmo-Hybrid-7B, ``olmo_hybrid``).
Straight ``jax.numpy`` in float32 with matrix products at ``highest``
precision; no cache, no state carried from call to call, no paging, no
batching, no kernels, no chunked form: **the recurrence runs as written,
one token after another** (``lax.scan`` over T), so the program's scan
over blocks of tokens is held to a formulation it does not share. It
imports nothing but JAX.

The layer, as ``configs/olmo-hybrid-7b-l16.json`` reads the source's
``config`` (each point it had to infer is under ``assumed`` there, and the
six a reader with the source's modelling code should check first are
numbered). For a layer with input ``x`` of ``T`` tokens, ``d =
hidden_size``, eps ``rms_norm_eps``:

- The block, (2) the family's reordered norm as in ``olmo2``/``olmo3``:
  ``h = x + RMSNorm(op(x), post_attention_layernorm)`` (``ln1``); ``out = h
  + RMSNorm(mlp(h), post_feedforward_layernorm)`` (``ln2``); ``mlp(h) =
  (silu(h W_gate) * (h W_up)) W_down``. Nothing is normed before either.
  After the last layer ``RMSNorm(., norm)`` (``ln_f``) and an untied
  ``head``.
- ``layer_types`` "linear_attention", H = ``linear_num_value_heads`` heads
  (as many key heads), dk = ``linear_key_head_dim``, dv =
  ``linear_value_head_dim``: ``[q~, k~, v~] = x W_qkv`` (d -> H dk, H dk, H
  dv, in that order, no bias); (4) each channel through a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps (``conv``:
  [taps, channels], the oldest tap first; no bias; zeros before the
  sequence), **then** SiLU, **then** per head ``q = q~ / (|q~|_2 + 1e-6) *
  dk^-1/2`` and ``k = k~ / (|k~|_2 + 1e-6)``: the scale acts on q alone;
  (5) ``beta = sigmoid(x W_b)`` per head, times 2 with
  ``linear_allow_neg_eigval``; ``g = -exp(A_log) * softplus(x W_a +
  dt_bias)`` per head; with ``S_0 = 0`` a dk x dv matrix a head: ``S' =
  exp(g_t) S_{t-1}``; ``u_t = beta_t (v_t - S'^T k_t)``; ``S_t = S' + k_t
  u_t^T``; ``o_t = S_t^T q_t``; (6) ``y_t = RMSNorm(o_t, o_norm)`` over the
  dv dims of a head, **one** learned scale of dv shared by the heads,
  ``* silu(x W_g)``; ``op = concat(y) W_o``.
- "full_attention": ``q = x Wq``, ``k = x Wk``, ``v = x Wv`` (no bias); (3)
  q and k each through an RMS norm **over the whole projection** (all
  heads' dims as one row) with a learned scale of that width (``q_norm``,
  ``k_norm``); then ``num_attention_heads`` heads on ``num_key_value_heads``
  KV heads; (1) **no rotary** (the source's ``rope_parameters.rope_theta``
  is null: the attention layers carry no position, the recurrent layers
  before them do); causal softmax of ``q k / sqrt(head size)`` over every
  earlier key; ``Wo``.

``quant="int8"`` is the control of the output check: the operands of every
matrix product through symmetric int8 (one scale for each row of
activations, one for each output channel of a weight) and back: the
precision below the configuration's bfloat16. The recurrence's own
products are sums over one axis and stay float32 there too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # queries a block of the full attention: [H, 512, T] scores


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


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


def _short_conv(s, taps):
    """Causal depthwise convolution. s: [T, C]; taps: [n, C], the oldest
    first: tap j weighs ``s_{t - (n - 1 - j)}``; zeros before the sequence."""
    taps = taps.astype(jnp.float32)
    n = taps.shape[0]
    out = jnp.zeros_like(s)
    for j in range(n):
        back = n - 1 - j
        out = out + taps[j] * jnp.pad(s, ((back, 0), (0, 0)))[: s.shape[0]]
    return out


def _delta_rule(q, k, v, g, beta):
    """The recurrence as written, one token after another. q, k: [T, H,
    dk]; v: [T, H, dv]; g, beta: [T, H]. Returns o: [T, H, dv]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def one(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = jnp.exp(g_t)[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.sum(decayed * k_t[:, :, None], axis=1))
        state = decayed + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(
        one, jnp.zeros((heads, dk, dv), jnp.float32), (q, k, v, g, beta)
    )
    return o


def _gated_delta(x, p, dims, neg_eigval, eps, quant):
    """The operator of a "linear_attention" layer. x: [T, D]."""
    heads, dk, dv = dims
    t = x.shape[0]
    mixed = jax.nn.silu(_short_conv(_matmul(x, p["w_qkv"], quant), p["conv"]))
    q, k, v = jnp.split(mixed, [heads * dk, 2 * heads * dk], axis=-1)
    q, k = q.reshape(t, heads, dk), k.reshape(t, heads, dk)
    q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
    k = k / (jnp.linalg.norm(k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(_matmul(x, p["w_b"], quant))
    if neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        _matmul(x, p["w_a"], quant) + p["dt_bias"].astype(jnp.float32)
    )
    o = _delta_rule(q, k, v.reshape(t, heads, dv), g, beta)
    y = _rms_norm(o, p["o_norm"], eps).reshape(t, -1)
    return _matmul(y * jax.nn.silu(_matmul(x, p["w_g"], quant)), p["wo"], quant)


@functools.partial(jax.jit, static_argnames=("shape", "quant"))
def logits_at(params, tokens, rows, *, shape, quant=None):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence
    ``tokens`` [T] (right-padded; causal, so padding cannot reach back).
    ``shape``: the hashable tuple ``_shape(cfg)`` makes."""
    heads, kv_heads, eps, pattern, delta_dims, neg_eigval = shape
    h = params["embed"][tokens].astype(jnp.float32)
    t = h.shape[0]

    def block(h, p, linear):
        if linear:
            op = _gated_delta(h, p, delta_dims, neg_eigval, eps, quant)
        else:
            q = _rms_norm(_matmul(h, p["wq"], quant), p["q_norm"], eps)
            k = _rms_norm(_matmul(h, p["wk"], quant), p["k_norm"], eps)
            v = _matmul(h, p["wv"], quant).reshape(t, kv_heads, -1)
            q = q.reshape(t, kv_heads, heads // kv_heads, -1)
            op = _matmul(
                _attention(q, k.reshape(t, kv_heads, -1), v), p["wo"], quant
            )
        h = h + _rms_norm(op, p["ln1"], eps)
        gate = jax.nn.silu(_matmul(h, p["w_gate"], quant))
        up = _matmul(h, p["w_up"], quant)
        return h + _rms_norm(_matmul(gate * up, p["w_down"], quant), p["ln2"], eps)

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
        key = f"{kind}.dense" + (f".{nth}" if nth else "")
        h, _ = jax.lax.scan(
            lambda h, p, kind=kind: (block(h, p, kind == "delta"), None),
            h, params["blocks"][key],
        )
        at += n
    h = _rms_norm(h[rows], params["ln_f"], eps)
    return _matmul(h, params["head"], quant)


def layer_kinds(cfg: dict):
    """The operator of each layer, by the source's key."""
    return tuple(
        "delta" if kind == "linear_attention" else "full"
        for kind in cfg["layer_types"]
    )


def _shape(cfg: dict):
    """What ``logits_at`` needs of a configuration's file, hashable."""
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings"):
        raise ValueError("the reference computes no attention bias and an "
                         "untied head")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("the reference's attention layers do not rotate")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("the reference gives each value head its own key head")
    return (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        float(cfg["rms_norm_eps"]), layer_kinds(cfg),
        (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
         cfg["linear_value_head_dim"]),
        bool(cfg["linear_allow_neg_eigval"]),
    )


def reference_logits(params, cfg: dict, tokens, rows, quant=None):
    return logits_at(params, tokens, rows, shape=_shape(cfg), quant=quant)
