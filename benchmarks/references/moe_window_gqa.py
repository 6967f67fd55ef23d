"""Plain reference of a decoder whose layers differ by position
(MiMo-V2.5): full and windowed grouped-query attention mixed, a value
head narrower than the query head, rotary embedding on part of a head, a
learned sink in windowed layers, one dense SwiGLU layer and then
sigmoid-routed experts, of which this holder has some. Straight
``jax.numpy`` in float32 with matrix products at ``highest`` precision;
no cache, no paging, no batching, no kernels. It imports nothing but JAX.

The layer, as ``configs/mimo-v2.5-l7-ep16.json`` reads the source's
``config`` (each point it had to infer is under ``assumed`` there):

- ``x = RMSNorm(h, eps)``; ``q = x Wq -> [H, hd]``, ``k = x Wk -> [KH,
  hd]``, ``v = value_scale * (x Wv) -> [KH, vd]``; KH, the rope base and
  the sink depend on the layer's kind.
- rotary on the first ``rotary_dim`` dims of each head, rotate-half
  within them; the other dims pass.
- ``s_ij = q_i k_j / sqrt(hd)``, causal; in a windowed layer also
  ``j > i - window``, and ``p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m) +
  exp(sink_h - m))``: a column that takes mass and gives no value.
- after a second RMSNorm a SwiGLU (``ffn`` "dense") or experts:
  ``sigma = sigmoid(y Wr)``; the ``top_k`` largest of ``sigma + bias`` are
  chosen; ``w_e = sigma_e / sum over the chosen``; the result is ``sum of
  w_e SwiGLU_e(y)`` over the chosen experts that are HELD HERE
  (``experts_held = [first, count]``). What the absent experts would add
  is left out, as in the program; with ``[0, router_width]`` it is the
  whole layer.

Weights arrive in the type they are served in, grouped by kind of layer
(``params["blocks"]["<attention>.<ffn>"]``, stacked), and are widened one
matrix at a time; consecutive layers of one kind are one ``lax.scan``. Attention runs over blocks of ``Q_BLOCK`` queries, so the
scores of an 8,192-token context are ``[H, 512, 8192]`` and fit.

``quant="int8"`` is the control: the same pass with the operands of every
matrix product (the router's too) in symmetric int8, weights scaled per
output channel and activations per row.
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


def _rope(x, pos, theta, rotary_dim):
    """x: [T, H, hd]; pos: [T]. Rotate-half within the first
    ``rotary_dim`` dims."""
    inv = 1.0 / (
        theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    )
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :rotary_dim], 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rotary_dim:]], -1
    )


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


def _attention(q, k, v, window, sink):
    """q: [T, KH, G, hd]; k: [T, KH, hd]; v: [T, KH, vd]; sink: [KH, G] or
    None. Blocks of queries against every key."""
    t, hd = q.shape[0], q.shape[-1]
    block = min(Q_BLOCK, t)
    assert t % block == 0
    k_pos = jnp.arange(t)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        q_pos = start + jnp.arange(block)
        s = jnp.einsum("tkgd,skd->kgts", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(hd))
        seen = k_pos[None, :] <= q_pos[:, None]
        if window:
            seen &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        if sink is not None:
            m = jnp.maximum(m, sink[:, :, None])
        e = jnp.exp(s - m[..., None])
        denom = jnp.sum(e, axis=-1)
        if sink is not None:
            denom = denom + jnp.exp(sink[:, :, None] - m)
        p = e / denom[..., None]
        return jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(0, t, block))
    return out.reshape(t, -1)


def _experts(y, p, top_k, held, norm_topk, quant):
    """This holder's part of the expert layer. y: [T, D]."""
    first, count = held
    sigma = jax.nn.sigmoid(_matmul(y, p["router"], quant))
    _, chosen = jax.lax.top_k(sigma + p["router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sigma, chosen, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
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
    (heads, hd, vd, rotary_dim, value_scale, eps, kinds, pattern, top_k, held,
     norm_topk) = shape
    kinds = dict(kinds)
    pos = jnp.arange(tokens.shape[0])
    h = params["embed"][tokens].astype(jnp.float32)
    t = h.shape[0]

    def block(h, p, kv_heads, theta, window, has_sink, experts):
        x = _rms_norm(h, p["ln1"].astype(jnp.float32), eps)
        q = _matmul(x, p["wq"], quant).reshape(t, heads, hd)
        k = _matmul(x, p["wk"], quant).reshape(t, kv_heads, hd)
        v = value_scale * _matmul(x, p["wv"], quant).reshape(t, kv_heads, vd)
        groups = heads // kv_heads
        q = _rope(q, pos, theta, rotary_dim).reshape(t, kv_heads, groups, hd)
        k = _rope(k, pos, theta, rotary_dim)
        sink = (
            p["sink"].astype(jnp.float32).reshape(kv_heads, groups)
            if has_sink else None
        )
        h = h + _matmul(_attention(q, k, v, window, sink), p["wo"], quant)
        y = _rms_norm(h, p["ln2"].astype(jnp.float32), eps)
        if experts:
            return h + _experts(y, p["moe"], top_k, held, norm_topk, quant)
        gate = jax.nn.silu(_matmul(y, p["w_gate"], quant))
        up = _matmul(y, p["w_up"], quant)
        return h + _matmul(gate * up, p["w_down"], quant)

    # layers in the pattern's order; a run of layers of one kind is one
    # scan over that kind's stacked weights (the same block, compiled once)
    taken, at = {}, 0
    while at < len(pattern):
        attn, ffn = pattern[at]
        n = 1
        while at + n < len(pattern) and pattern[at + n] == (attn, ffn):
            n += 1
        key = f"{attn}.{ffn}"
        first = taken.get(key, 0)
        taken[key] = first + n
        stack = jax.tree.map(
            lambda a: a[first : first + n], params["blocks"][key]
        )
        h, _ = jax.lax.scan(
            lambda h, p: (block(h, p, *kinds[attn], ffn == "experts"), None),
            h, stack,
        )
        at += n
    h = _rms_norm(h[rows], params["ln_f"].astype(jnp.float32), eps)
    return _matmul(h, params["head"], quant)


def _shape(cfg: dict):
    """What ``logits_at`` needs of a configuration's file, hashable."""
    hd = cfg["head_dim"]
    rotary = int(cfg["partial_rotary_factor"] * hd) // 2 * 2
    attn = {0: "full", 1: "window"}
    ffn = {0: "dense", 1: "experts"}
    kinds = (
        ("full", (cfg["num_key_value_heads"], float(cfg["rope_theta"]), 0,
                  bool(cfg["add_full_attention_sink_bias"]))),
        ("window", (cfg["swa_num_key_value_heads"],
                    float(cfg["swa_rope_theta"]), int(cfg["sliding_window"]),
                    bool(cfg["add_swa_attention_sink_bias"]))),
    )
    pattern = tuple(
        (attn[a], ffn[f])
        for a, f in zip(cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"])
    )
    return (
        cfg["num_attention_heads"], hd, cfg["v_head_dim"], rotary,
        float(cfg["attention_value_scale"]), float(cfg["layernorm_epsilon"]),
        kinds, pattern, cfg["num_experts_per_tok"],
        tuple(cfg["experts_held"]), bool(cfg["norm_topk_prob"]),
    )


def reference_logits(params, cfg: dict, tokens, rows, quant=None):
    return logits_at(params, tokens, rows, shape=_shape(cfg), quant=quant)
