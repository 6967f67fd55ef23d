"""A decoder whose every layer is a parallel hybrid block (Falcon-H1-34B,
``falcon_h1``): grouped-query attention (heads of ``head_dim`` on
``num_key_value_heads`` KV heads, rotary at ``rope_theta``) and a Mamba-2
mixer (SSD: a scalar decay a head, B and C shared by the heads of a group,
a D skip, a gated RMS norm by group) side by side over one normed input,
their scaled outputs summed into one residual add, then a dense SwiGLU; the
muP multipliers of the source on the embedding, both branches, the keys,
the mixer's five input segments, the MLP and the logits; an untied head.
Everything the benchmark knows of the architecture apart from its plain
reference (``references/parallel_ssm_gqa.py``, whose docstring has the
equations and the five points a reader with the source's modelling code
should check first). ``cfg`` is a configuration file's dict: the source's
key names.

- ``make_weights``: seeded weights, made on the device in one jitted call,
  in the type they are served in and in the pytree the program takes:
  ``embed``, ``ln_f`` (the source's ``final_layernorm``), ``head``
  (``lm_head``), and ``blocks["parallel.dense"]``, one stack of all the
  layers: ``ln1`` (``input_layernorm``), ``ln2`` (``pre_ff_layernorm``),
  ``wq wk wv wo``, ``ssm_in`` (``in_proj``: d x [z, x, B, C, dt]),
  ``ssm_conv`` ([taps, x B C], the oldest tap first) ``ssm_conv_bias``,
  ``ssm_a_log ssm_dt_bias ssm_d`` (float32, a head), ``ssm_norm`` (one scale
  of ``mamba_d_ssm``), ``ssm_out`` (``out_proj``), ``w_gate w_up w_down``.
  The reference reads the same arrays. **Drawn so that every branch has
  unit gain through its multiplier**: a matrix whose output is multiplied
  by ``m`` is normal with a deviation of ``fan_in ** -0.5 / m`` (``wk``
  through ``key_multiplier``, ``wo`` through ``attention_out_multiplier``,
  the columns of ``ssm_in`` through ``ssm_in_multiplier`` times their
  segment's ``ssm_multipliers``, ``ssm_out`` through
  ``ssm_out_multiplier``, ``w_gate`` and ``w_down`` through the two
  ``mlp_multipliers``), the embedding ``1 / embedding_multiplier`` (rows of
  unit size after it), and the head ``3 d ** -0.5 / lm_head_multiplier``, so
  that the logits spread with a deviation of about 3 as a trained model's
  do. At plain fan-in scale the small multipliers would shrink every branch
  and the logits (0.0078 on the logits alone), and the output check would
  lose its teeth. ``ssm_a_log`` is the log of a uniform draw in [1, 16] and
  ``ssm_dt_bias`` the inverse softplus of a log-uniform draw in [0.001,
  0.1], Mamba-2's initialisation, so that a token's decay ``exp(dt A)``
  spreads over (0.2, 1); the convolution's taps normal at ``taps ** -0.5``
  and its bias uniform in (-taps ** -0.5, taps ** -0.5), as PyTorch's
  ``Conv1d`` draws them; ``ssm_d`` and every norm 1.
- ``model_config``: the program's own configuration object. The one place
  here that imports the program.
- The counts: operations and bytes the ALGORITHM needs, from shapes and
  live context lengths alone, blind to how the program runs a step. Every
  matrix of the layers and the head is read once a step; K and V over each
  live context; each layer's ``S`` and convolution columns read once and
  written once a live slot; the recurrence is ``2 N P 3`` FLOPs a head a
  token (the decay and the rank-one update of ``S``; ``S^T C``) beside the
  projections'. ``kv_bytes_per_token``, ``state_bytes_per_slot`` and
  ``ssm_state_bytes`` are for the per-layer readers.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from harness.peaks import dtype_bytes

STATE_BYTES = 4  # S is float32 whatever the weights' type
RUN = "parallel.dense"  # every layer is alike: one run, one stack


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def ssm_dims(cfg: dict) -> Tuple[int, int, int, int]:
    """(heads, a head's size, the state's size, groups) of the mixer."""
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"])


def conv_width(cfg: dict) -> int:
    """Channels of the mixer's short convolution: x, B, C."""
    heads, size, n, groups = ssm_dims(cfg)
    return heads * size + 2 * groups * n


def in_width(cfg: dict) -> int:
    """Columns of the mixer's input projection: z, x, B, C, dt."""
    heads, size, _, _ = ssm_dims(cfg)
    return heads * size + conv_width(cfg) + heads


def segment_multipliers(cfg: dict):
    """(columns, multiplier) of each segment of the input projection."""
    heads, size, n, groups = ssm_dims(cfg)
    widths = (heads * size, heads * size, groups * n, groups * n, heads)
    return list(zip(widths, cfg["ssm_multipliers"]))


def make_weights(cfg: dict, seed: int):
    d, h, kh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    heads, size, _, _ = ssm_dims(cfg)
    inner, taps = heads * size, cfg["mamba_d_conv"]
    ff, vocab, n = cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    gate_m, down_m = cfg["mlp_multipliers"]
    dt = jnp.dtype(cfg["torch_dtype"])

    def dense(key, rows, cols, scale, parts=16):
        # a part of the rows at a time: a float32 copy of the embedding
        # (261,120 x 5,120) would be 5.35 GB beside the weights
        parts = parts if rows % parts == 0 else 1
        return jax.lax.map(
            lambda k: (
                jax.random.normal(k, (rows // parts, cols), jnp.float32) * scale
            ).astype(dt),
            jax.random.split(key, parts),
        ).reshape(rows, cols)

    def stacked(key, rows, cols, gain=1.0):
        # one matrix at a time, so that no float32 copy of a stack exists;
        # ``gain``: one number, or one a column
        return jax.lax.map(
            lambda k: (
                jax.random.normal(k, (rows, cols), jnp.float32)
                * (rows ** -0.5 * gain)
            ).astype(dt),
            jax.random.split(key, n),
        )

    @jax.jit
    def build(key):
        k = jax.random.split(jax.random.fold_in(key, 0), 16)
        in_gain = jnp.concatenate([
            jnp.full((w,), 1.0 / (cfg["ssm_in_multiplier"] * m), jnp.float32)
            for w, m in segment_multipliers(cfg)
        ])
        step = jnp.exp(jax.random.uniform(
            k[8], (n, heads), jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
        bound = taps ** -0.5
        p = {
            "ln1": jnp.ones((n, d), dt), "ln2": jnp.ones((n, d), dt),
            "wq": stacked(k[0], d, h * hd, 1.0 / cfg["attention_in_multiplier"]),
            "wk": stacked(k[1], d, kh * hd, 1.0 / (
                cfg["attention_in_multiplier"] * cfg["key_multiplier"])),
            "wv": stacked(k[2], d, kh * hd, 1.0 / cfg["attention_in_multiplier"]),
            "wo": stacked(k[3], h * hd, d, 1.0 / cfg["attention_out_multiplier"]),
            "ssm_in": stacked(k[4], d, in_width(cfg), in_gain),
            "ssm_conv": stacked(k[5], taps, conv_width(cfg)),
            "ssm_conv_bias": jax.random.uniform(
                k[6], (n, conv_width(cfg)), jnp.float32, -bound, bound
            ).astype(dt),
            "ssm_a_log": jnp.log(jax.random.uniform(
                k[7], (n, heads), jnp.float32, 1.0, 16.0)),
            "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssm_d": jnp.ones((n, heads), jnp.float32),
            "ssm_norm": jnp.ones((n, inner), dt),
            "ssm_out": stacked(k[9], inner, d, 1.0 / cfg["ssm_out_multiplier"]),
            "w_gate": stacked(k[10], d, ff, 1.0 / gate_m),
            "w_up": stacked(k[11], d, ff),
            "w_down": stacked(k[12], ff, d, 1.0 / down_m),
        }
        return {
            "embed": dense(k[13], vocab, d, 1.0 / cfg["embedding_multiplier"]),
            "blocks": {RUN: p},
            "ln_f": jnp.ones((d,), dt),
            "head": dense(k[14], d, vocab, 3 * d ** -0.5 / cfg["lm_head_multiplier"]),
        }

    params = build(seed_key(seed))
    jax.block_until_ready(params)
    return params


def model_config(cfg: dict):
    from ray_tpu.models import transformer as tfm

    for key, want in (
        ("attention_bias", False), ("mlp_bias", False),
        ("projectors_bias", False), ("mamba_proj_bias", False),
        ("tie_word_embeddings", False), ("hidden_act", "silu"),
        ("model_type", "falcon_h1"), ("mamba_rms_norm", True),
        ("mamba_norm_before_gate", False), ("mamba_use_mlp", True),
        ("attn_layer_indices", None), ("rope_scaling", None),
    ):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"`{key}`={cfg[key]!r}: the program has no such option "
                f"(it computes {want!r})"
            )
    heads, size, n, groups = ssm_dims(cfg)
    if heads * size != cfg["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
    if not cfg.get("mamba_conv_bias"):
        raise ValueError("the program's parallel layer convolves with a bias")
    layers = cfg["num_hidden_layers"]
    model = tfm.ModelConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=layers, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["deployment"]["max_context_tokens"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        attn_pattern=("parallel",) * layers, ffn_pattern=("dense",) * layers,
        conv_kernel=cfg["mamba_d_conv"],
        ssm_heads=heads, ssm_head_dim=size, ssm_state=n, ssm_groups=groups,
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        lm_head_multiplier=float(cfg["lm_head_multiplier"]),
        attention_in_multiplier=float(cfg["attention_in_multiplier"]),
        attention_out_multiplier=float(cfg["attention_out_multiplier"]),
        key_multiplier=float(cfg["key_multiplier"]),
        ssm_in_multiplier=float(cfg["ssm_in_multiplier"]),
        ssm_out_multiplier=float(cfg["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]),
    )
    if [run.key for run in model.layer_runs()] != [RUN]:
        raise ValueError(f"the program stacks its runs as "
                         f"{[run.key for run in model.layer_runs()]}")
    if model.ssm_width != conv_width(cfg):
        raise ValueError("the program convolves another width than x, B, C")
    return model


# -- the algorithm's counts ---------------------------------------------------


def attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return 2 * d * q + 2 * d * kv                       # q o, k v


def mixer_params(cfg: dict) -> int:
    """The Mamba-2 mixer: both projections, the taps and their bias, the
    per-head scalars and the gated norm's scale."""
    heads, size, _, _ = ssm_dims(cfg)
    inner = heads * size
    return (
        cfg["hidden_size"] * in_width(cfg) + inner * cfg["hidden_size"]
        + (cfg["mamba_d_conv"] + 1) * conv_width(cfg) + 3 * heads + inner
    )


def layer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (attention_params(cfg) + mixer_params(cfg)
            + 3 * d * cfg["intermediate_size"] + 2 * d)


def always_read_params(cfg: dict) -> int:
    """Parameters a step reads: every layer, the last norm, the head."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) + d + d * cfg["vocab_size"]


def kv_bytes_per_token(cfg: dict) -> Dict[str, int]:
    """Bytes of K and V one token holds, by class of page: 6 layers x 2 x 4
    KV heads x 128 x 2 B = 12 KiB in the cell."""
    row = 2 * cfg["head_dim"] * dtype_bytes(cfg)
    return {"full": cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * row}


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of state a sequence carries from step to step: a layer's ``S``
    (heads x N x P in float32) and the ``taps - 1`` columns of x, B, C
    before the convolution (in the served type): 6 x (32 x 256 x 128 x 4 +
    3 x 5,120 x 2) B = 24.18 MiB in the cell."""
    heads, size, n, _ = ssm_dims(cfg)
    columns = (cfg["mamba_d_conv"] - 1) * conv_width(cfg)
    return cfg["num_hidden_layers"] * (
        heads * n * size * STATE_BYTES + columns * dtype_bytes(cfg)
    )


def ssm_state_bytes(cfg: dict, live: int) -> int:
    """The mixer's state a decode step over ``live`` sequences reads and
    writes: each live slot's, once each way."""
    return 2 * live * state_bytes_per_slot(cfg)


def recurrence_flops(cfg: dict) -> int:
    """The SSD recurrence for one token, all layers: 2 N P 3 a head."""
    heads, size, n, _ = ssm_dims(cfg)
    return cfg["num_hidden_layers"] * heads * 2 * n * size * 3


def attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV of one query over the keys it sees, every layer."""
    per_key = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    return cfg["num_hidden_layers"] * per_key * context


def token_matrix_flops(cfg: dict) -> int:
    """One token through every matrix applied to it (taps, scalars and norm
    scales among the parameters: a multiply-add each) and the recurrence."""
    return 2 * always_read_params(cfg) + recurrence_flops(cfg)


def decode_token_flops(cfg: dict, context: int) -> int:
    return token_matrix_flops(cfg) + attention_flops(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """All blocks over the prompt, causal attention, the head once."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (
        (token_matrix_flops(cfg) - 2 * head) * prompt_len
        + attention_flops(cfg, 1) * prompt_len * (prompt_len + 1) // 2
        + 2 * head
    )


def decode_step_work(cfg: dict, contexts: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of one decode step that advances one token in each
    live sequence: every matrix of the layers and the head read once; the
    batch's embedding rows; each live context's K and V read once and one
    new K, V written; each layer's state read once and written once a live
    slot."""
    batch, nb = len(contexts), dtype_bytes(cfg)
    weights = (always_read_params(cfg) + batch * cfg["hidden_size"]) * nb
    kv = sum(c + 1 for c in contexts) * kv_bytes_per_token(cfg)["full"]
    flops = batch * token_matrix_flops(cfg) + sum(
        attention_flops(cfg, c) for c in contexts
    )
    return flops, weights + kv + ssm_state_bytes(cfg, batch)
