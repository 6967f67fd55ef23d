"""Plain reference of a decoder whose every layer is a parallel hybrid
block: grouped-query attention and a Mamba-2 mixer side by side over one
normed input (Falcon-H1-34B, ``falcon_h1``). Straight ``jax.numpy`` in
float32 with matrix products at ``highest`` precision; no cache, no state
carried from call to call, no paging, no batching, no kernels, no chunked
form: **the recurrence runs as written, one token after another**
(``lax.scan`` over T), so the program's scan over blocks of tokens is held
to a formulation it does not share. It imports nothing but JAX.

The layer, as ``configs/falcon-h1-34b-l6.json`` reads the source's
``config`` (each point it had to infer is under ``assumed`` there; the five
a reader with the source's modelling code should check first are numbered).
For hidden size ``d``, eps ``rms_norm_eps``:

- ``h_0 = E[ids] * embedding_multiplier``.
- The block, with ``x = RMSNorm(h, input_layernorm)`` (``ln1``): ``h' = h
  + attn(x * attention_in_multiplier) * attention_out_multiplier +
  mamba(x * ssm_in_multiplier) * ssm_out_multiplier``; ``out = h' +
  mlp(RMSNorm(h', pre_ff_layernorm))`` (``ln2``); (4) ``mlp(y) =
  (silu((y W_gate) * mlp_multipliers[0]) * (y W_up)) W_down *
  mlp_multipliers[1]``. No biases.
- After the last layer ``logits = (RMSNorm(h, final_layernorm) W_head) *
  lm_head_multiplier``; the head is untied.
- ``attn``: ``q = x Wq`` in heads of ``head_dim``; (3) ``k = (x Wk) *
  key_multiplier``, before the rotary; ``v = x Wv``; K and V in
  ``num_key_value_heads`` heads, each read by a group of heads; the rotary
  over all of a head's dims at base ``rope_theta`` (rotate-half); causal
  softmax of ``q k / sqrt(head_dim)``; ``Wo``.
- ``mamba`` (Mamba-2, SSD), H = ``mamba_n_heads`` heads of P =
  ``mamba_d_head``, N = ``mamba_d_state``, G = ``mamba_n_groups``: (1)
  ``[z, x~, B~, C~, dt~] = (x W_in) * mu``, ``mu`` constant on each of the
  five segments (widths H P, H P, G N, G N, H), ``ssm_multipliers`` in that
  order; ``[x, B, C] = silu(conv(x~ B~ C~))``, a causal depthwise
  convolution of ``mamba_d_conv`` taps with a bias over the ``H P + 2 G N``
  channels (zeros before the sequence); ``dt = softplus(dt~ + dt_bias)``
  and ``A = -exp(A_log)`` a head; head ``i`` reads group ``i // (H / G)``
  of B and C; with ``S_0 = 0`` an N x P matrix a head: ``S_t = exp(dt_t A)
  S_{t-1} + B_t (dt_t x_t)^T``; (5) ``y_t = S_t^T C_t + D x_t`` (x the
  convolved one); (2) ``o = RMSNorm_by_group(y * silu(z))``: the gate
  first (``mamba_norm_before_gate`` false), the RMS over each of the G
  groups of ``H P / G`` channels, one scale of ``H P``; ``o W_out``.

Built to fit beside the served bfloat16 weights on one chip: each matrix
raised to float32 only inside the product that reads it (layer by layer;
a float32 head of 261,120 x 5,120 alone would be 5.35 GB), attention in
blocks of queries, and the logits written once, into the result. At the
cell's longest sample (4,096 tokens, 2,048 rows) the program's
temporaries are 0.40 GiB beside its 1.99 GiB of logits: the output
check's control holds the plain logits while it runs, and the two fit
beside the weights.

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
Q_BLOCK = 512        # queries a block of the attention: [H, 512, T] scores


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


def _rotate(x, theta):
    """Rotate-half over all of a head's dims. x: [T, heads, hd]."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def _short_conv(s, taps, bias):
    """Causal depthwise convolution with a bias. s: [T, C]; taps: [n, C],
    the oldest first: tap j weighs ``s_{t - (n - 1 - j)}``; zeros before
    the sequence."""
    taps = taps.astype(jnp.float32)
    n = taps.shape[0]
    out = jnp.zeros_like(s) + bias.astype(jnp.float32)
    for j in range(n):
        back = n - 1 - j
        out = out + taps[j] * jnp.pad(s, ((back, 0), (0, 0)))[: s.shape[0]]
    return out


def _ssd(x, b, c, dt, a):
    """The recurrence as written, one token after another. x: [T, H, P];
    b, c: [T, H, N] (each head's group); dt: [T, H]; a: [H]. Returns
    ``S_t^T C_t``: [T, H, P]."""
    heads, size, n = x.shape[1], x.shape[2], b.shape[2]

    def one(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        return state, jnp.sum(state * c_t[:, :, None], axis=1)

    _, y = jax.lax.scan(
        one, jnp.zeros((heads, n, size), jnp.float32), (x, b, c, dt)
    )
    return y


def _mamba(x, p, dims, mults, eps, quant):
    """The Mamba-2 mixer. x: [T, D], already times ``ssm_in_multiplier``."""
    heads, size, n, groups = dims
    t, inner = x.shape[0], heads * size
    widths = (inner, inner, groups * n, groups * n, heads)
    mu = jnp.concatenate([jnp.full((w,), m, jnp.float32)
                          for w, m in zip(widths, mults)])
    proj = _matmul(x, p["ssm_in"], quant) * mu
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * groups * n], axis=-1)
    xbc = jax.nn.silu(_short_conv(xbc, p["ssm_conv"], p["ssm_conv_bias"]))
    xs, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    xs = xs.reshape(t, heads, size)
    per = heads // groups
    b = jnp.repeat(b.reshape(t, groups, n), per, axis=1)
    c = jnp.repeat(c.reshape(t, groups, n), per, axis=1)
    dt = jax.nn.softplus(dt + p["ssm_dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["ssm_a_log"].astype(jnp.float32))
    y = _ssd(xs, b, c, dt, a) + p["ssm_d"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(t, inner) * jax.nn.silu(z)
    y = _rms_norm(
        y.reshape(t, groups, inner // groups),
        p["ssm_norm"].reshape(groups, inner // groups), eps,
    ).reshape(t, inner)
    return _matmul(y, p["ssm_out"], quant)


def _head(h, w, scale, quant):
    """``(h @ w) * scale``: [rows, vocab] in float32. The weight is raised
    to float32 inside the product (compiled for a v5e, the program holds
    no float32 copy of the head, and no buffer of the logits but the
    result: ``benchmarks/tests/test_parallel_ssm.py``)."""
    return _matmul(h, w, quant) * scale


@functools.partial(jax.jit, static_argnames=("shape", "quant"))
def logits_at(params, tokens, rows, *, shape, quant=None):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence
    ``tokens`` [T] (right-padded; causal, so padding cannot reach back).
    ``shape``: the hashable tuple ``_shape(cfg)`` makes."""
    (heads, kv_heads, eps, theta, dims, mults, attn_in, attn_out, key_m,
     ssm_in, ssm_out, mlp_m, emb_m, head_m) = shape
    h = params["embed"][tokens].astype(jnp.float32) * emb_m
    t = h.shape[0]

    def block(h, p):
        x = _rms_norm(h, p["ln1"], eps)
        xa = x * attn_in
        q = _matmul(xa, p["wq"], quant).reshape(t, heads, -1)
        k = (_matmul(xa, p["wk"], quant) * key_m).reshape(t, kv_heads, -1)
        v = _matmul(xa, p["wv"], quant).reshape(t, kv_heads, -1)
        q, k = _rotate(q, theta), _rotate(k, theta)
        q = q.reshape(t, kv_heads, heads // kv_heads, -1)
        attn = _matmul(_attention(q, k, v), p["wo"], quant)
        ssm = _mamba(x * ssm_in, p, dims, mults, eps, quant)
        h = h + attn * attn_out + ssm * ssm_out
        y = _rms_norm(h, p["ln2"], eps)
        gate = jax.nn.silu(_matmul(y, p["w_gate"], quant) * mlp_m[0])
        up = _matmul(y, p["w_up"], quant)
        return h + _matmul(gate * up, p["w_down"], quant) * mlp_m[1]

    h, _ = jax.lax.scan(
        lambda h, p: (block(h, p), None), h, params["blocks"]["parallel.dense"]
    )
    h = _rms_norm(h[rows], params["ln_f"], eps)
    return _head(h, params["head"], head_m, quant)


def _shape(cfg: dict):
    """What ``logits_at`` needs of a configuration's file, hashable."""
    for key in ("attention_bias", "mlp_bias", "projectors_bias",
                "mamba_proj_bias", "tie_word_embeddings",
                "mamba_norm_before_gate"):
        if cfg.get(key):
            raise ValueError(f"the reference does not compute `{key}`")
    if not (cfg.get("mamba_rms_norm") and cfg.get("mamba_conv_bias")):
        raise ValueError("the reference's mixer has its gated norm and its "
                         "convolution's bias")
    if cfg.get("attn_layer_indices") is not None:
        raise ValueError("the reference runs attention in every layer")
    return (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
         cfg["mamba_n_groups"]),
        tuple(float(m) for m in cfg["ssm_multipliers"]),
        float(cfg["attention_in_multiplier"]),
        float(cfg["attention_out_multiplier"]),
        float(cfg["key_multiplier"]), float(cfg["ssm_in_multiplier"]),
        float(cfg["ssm_out_multiplier"]),
        tuple(float(m) for m in cfg["mlp_multipliers"]),
        float(cfg["embedding_multiplier"]), float(cfg["lm_head_multiplier"]),
    )


def reference_logits(params, cfg: dict, tokens, rows, quant=None):
    return logits_at(params, tokens, rows, shape=_shape(cfg), quant=quant)
