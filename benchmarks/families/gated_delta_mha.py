"""A decoder three of whose four layers are the gated delta rule
(Olmo-Hybrid-7B, ``olmo_hybrid``): linear-attention layers that keep a
float32 matrix of state a head and the last columns of q, k and v before
their short convolution for each sequence, and no K and V; every fourth
layer full multi-head attention with as many KV heads as heads, a norm over
the whole of q and of k, and no rotary; a dense SwiGLU in every layer; the
block's two norms after the operator and after the feed-forward, inside
the residual; an untied head. Everything the benchmark knows of the
architecture apart from its plain reference
(``references/gated_delta_mha.py``, whose docstring has the equations and
the six points a reader with the source's modelling code should check
first: the rotary, the norm placement, QK-norm's span, the order
convolution -> SiLU -> L2 norm and where ``dk^-1/2`` acts, the factor 2 on
beta, whether ``o_norm``'s scale is a head's or the layer's). ``cfg`` is a
configuration file's dict: the source's key names.

- ``make_weights``: seeded weights, made on the device in one jitted call,
  in the type they are served in and in the pytree the program takes for a
  stack by position: ``embed``, ``ln_f`` (the source's ``norm``), ``head``,
  and ``blocks[<run>]``, one stack for each run of consecutive layers of
  one kind (``delta.dense``, ``full.dense``, ``delta.dense.1``, ...). A
  layer holds ``ln1`` (``post_attention_layernorm``), ``ln2``
  (``post_feedforward_layernorm``), ``w_gate w_up w_down``, and ``w_qkv``
  (d x [H dk, H dk, H dv], q k v side by side) ``conv`` ([taps, that
  width], the oldest tap first) ``w_a w_b`` (d x H) ``a_log dt_bias``
  (float32[H]) ``w_g o_norm wo`` or ``wq wk wv wo q_norm k_norm``. The
  reference reads the same arrays. Every matrix is normal with a deviation
  of fan_in ** -0.5 (the four taps too), the embedding 0.02, norms 1;
  ``a_log`` is the log of a uniform draw in (0, 16) and ``dt_bias`` the
  inverse softplus of a log-uniform draw in [0.001, 0.1], Mamba-2's and
  the published layer's initialisation: with a stream near zero the gate
  ``exp(g)`` spreads over (0.2, 1), so a state that is wrong shows in the
  logits for many tokens.
- ``model_config``: the program's own configuration object. The one place
  here that imports the program.
- The counts: operations and bytes the ALGORITHM needs, from shapes and
  live context lengths alone, blind to how the program runs a step. Every
  matrix of the layers and the head is read once a step; K and V of the
  full layers over each live context; each linear layer's ``S`` and
  columns read once and written once a live slot; the recurrence is ``2 dk
  dv 3`` FLOPs a head a token (decay and ``S^T k``; the rank-one update;
  ``S^T q``) beside the projections'. ``kv_bytes_per_token``,
  ``state_bytes_per_slot`` and ``prefill_chunk_work`` are for the
  per-layer readers.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from harness.peaks import dtype_bytes

STATE_BYTES = 4  # S is float32 whatever the weights' type


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layer_kinds(cfg: dict) -> List[str]:
    """The operator of each layer: ``delta`` or ``full``."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or set(types) - {
        "linear_attention", "full_attention"
    }:
        raise ValueError("`layer_types` names linear_attention or "
                         "full_attention for every layer once")
    return ["delta" if t == "linear_attention" else "full" for t in types]


def layer_runs(cfg: dict) -> List[Tuple[str, str, int]]:
    """(key in ``blocks``, kind, layers) of each run of consecutive layers
    of one kind, in order."""
    runs: List[Tuple[str, str, int]] = []
    seen: Dict[str, int] = {}
    for kind in layer_kinds(cfg):
        if runs and runs[-1][1] == kind:
            runs[-1] = (runs[-1][0], kind, runs[-1][2] + 1)
            continue
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        runs.append((f"{kind}.dense" + (f".{nth}" if nth else ""), kind, 1))
    return runs


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def delta_dims(cfg: dict) -> Tuple[int, int, int]:
    """(heads, key size, value size) of a linear layer."""
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def delta_width(cfg: dict) -> int:
    """Channels of a linear layer's short convolution: q, k, v."""
    heads, dk, dv = delta_dims(cfg)
    return heads * (2 * dk + dv)


def make_weights(cfg: dict, seed: int):
    d, h, kh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, taps = head_dim(cfg), cfg["linear_conv_kernel_dim"]
    heads, dk, dv = delta_dims(cfg)
    ff, vocab = cfg["intermediate_size"], cfg["vocab_size"]
    dt = jnp.dtype(cfg["torch_dtype"])

    def dense(key, rows, cols, scale):
        return (
            jax.random.normal(key, (rows, cols), jnp.float32) * scale
        ).astype(dt)

    def stacked(key, n, rows, cols):
        # one matrix at a time, so that no float32 copy of a stack exists
        return jax.lax.map(
            lambda k: dense(k, rows, cols, rows ** -0.5),
            jax.random.split(key, n),
        )

    @jax.jit
    def build(key):
        blocks = {}
        for i, (name, kind, n) in enumerate(layer_runs(cfg)):
            k = jax.random.split(jax.random.fold_in(key, i), 12)
            p = {"ln1": jnp.ones((n, d), dt), "ln2": jnp.ones((n, d), dt)}
            if kind == "delta":
                p["w_qkv"] = stacked(k[0], n, d, delta_width(cfg))
                p["conv"] = stacked(k[1], n, taps, delta_width(cfg))
                p["w_a"] = stacked(k[2], n, d, heads)
                p["w_b"] = stacked(k[3], n, d, heads)
                p["a_log"] = jnp.log(jax.random.uniform(
                    k[4], (n, heads), jnp.float32, 1e-4, 16.0))
                step = jnp.exp(jax.random.uniform(
                    k[5], (n, heads), jnp.float32,
                    jnp.log(1e-3), jnp.log(0.1)))
                p["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
                p["w_g"] = stacked(k[6], n, d, heads * dv)
                p["o_norm"] = jnp.ones((n, dv), dt)
                p["wo"] = stacked(k[7], n, heads * dv, d)
            else:
                p["wq"] = stacked(k[0], n, d, h * hd)
                p["wk"] = stacked(k[1], n, d, kh * hd)
                p["wv"] = stacked(k[2], n, d, kh * hd)
                p["wo"] = stacked(k[3], n, h * hd, d)
                p["q_norm"] = jnp.ones((n, h * hd), dt)
                p["k_norm"] = jnp.ones((n, kh * hd), dt)
            p["w_gate"] = stacked(k[8], n, d, ff)
            p["w_up"] = stacked(k[9], n, d, ff)
            p["w_down"] = stacked(k[10], n, ff, d)
            blocks[name] = p
        k = jax.random.split(jax.random.fold_in(key, len(blocks)), 2)
        return {
            "embed": dense(k[0], vocab, d, float(cfg.get("embedding_std", 0.02))),
            "blocks": blocks,
            "ln_f": jnp.ones((d,), dt),
            "head": dense(k[1], d, vocab, d ** -0.5),
        }

    params = build(seed_key(seed))
    jax.block_until_ready(params)
    return params


def model_config(cfg: dict):
    from ray_tpu.models import transformer as tfm

    for key, want in (
        ("attention_bias", False), ("tie_word_embeddings", False),
        ("hidden_act", "silu"), ("model_type", "olmo_hybrid"),
    ):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"`{key}`={cfg[key]!r}: the program has no such option "
                f"(it computes {want!r})"
            )
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError(
            "`rope_parameters.rope_theta` is null in the source; a base "
            "would be one more key here and `rope_theta` of the program"
        )
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("the head's size is hidden_size / num_attention_heads")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("the program gives each value head its own key head")
    heads, dk, dv = delta_dims(cfg)
    model = tfm.ModelConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["deployment"]["max_context_tokens"],
        rope_theta=0.0,
        dtype=jnp.dtype(cfg["torch_dtype"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        attn_pattern=tuple(layer_kinds(cfg)),
        ffn_pattern=("dense",) * cfg["num_hidden_layers"],
        qk_norm=True, qk_norm_whole=True, post_norm=True,
        conv_kernel=cfg["linear_conv_kernel_dim"],
        delta_heads=heads, delta_key_dim=dk, delta_value_dim=dv,
        delta_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
    )
    ours = [key for key, _, _ in layer_runs(cfg)]
    theirs = [run.key for run in model.layer_runs()]
    if ours != theirs:
        raise ValueError(f"the program stacks its runs as {theirs}, "
                         f"the weights here as {ours}")
    return model


# -- the algorithm's counts ---------------------------------------------------


def linear_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("delta")


def attention_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("full")


def operator_params(cfg: dict, kind: str) -> int:
    """One layer's operator: the matrices, the taps, the per-head scalars
    and the operator's own norm scales."""
    d = cfg["hidden_size"]
    if kind == "delta":
        heads, dk, dv = delta_dims(cfg)
        return (
            d * delta_width(cfg)                         # q, k, v
            + cfg["linear_conv_kernel_dim"] * delta_width(cfg)
            + 2 * d * heads + 2 * heads                   # a, b; A_log, dt_bias
            + d * heads * dv + dv + heads * dv * d        # gate, o_norm, out
        )
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * d * d + 2 * d * kv + d + kv               # q o, k v, the norms


def layer_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    return operator_params(cfg, kind) + 3 * d * cfg["intermediate_size"] + 2 * d


def always_read_params(cfg: dict) -> int:
    """Parameters a step reads: every layer, the last norm, the head."""
    d = cfg["hidden_size"]
    return sum(layer_params(cfg, k) for k in layer_kinds(cfg)) + d + (
        d * cfg["vocab_size"]
    )


def kv_bytes_per_token(cfg: dict) -> Dict[str, int]:
    """Bytes of K and V one token holds, by class of page: the full
    layers' alone, 4 x 2 x 30 x 128 x 2 B = 60 KiB in the cell."""
    row = 2 * head_dim(cfg) * dtype_bytes(cfg)
    return {"full": attention_layers(cfg) * cfg["num_key_value_heads"] * row}


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of state a sequence carries from step to step: a linear
    layer's ``S`` (H x dk x dv in float32) and the ``taps - 1`` columns of
    q, k, v before the convolution that are read (in the served type): 12 x
    (30 x 96 x 192 x 4 + 3 x 11,520 x 2) B = 26.1 MiB in the cell, as the
    algorithm needs them (the chip lays a row of 192 out in 256 lanes)."""
    heads, dk, dv = delta_dims(cfg)
    columns = (cfg["linear_conv_kernel_dim"] - 1) * delta_width(cfg)
    return linear_layers(cfg) * (
        heads * dk * dv * STATE_BYTES + columns * dtype_bytes(cfg)
    )


def recurrence_flops(cfg: dict) -> int:
    """The delta rule for one token, all linear layers: 2 dk dv 3 a head."""
    heads, dk, dv = delta_dims(cfg)
    return linear_layers(cfg) * heads * 2 * dk * dv * 3


def attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV of one query over the keys it sees, full layers."""
    per_key = 2 * cfg["num_attention_heads"] * 2 * head_dim(cfg)
    return attention_layers(cfg) * per_key * context


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
    new K, V written; each linear layer's state read once and written once
    a live slot."""
    batch, nb = len(contexts), dtype_bytes(cfg)
    weights = (always_read_params(cfg) + batch * cfg["hidden_size"]) * nb
    kv = sum(c + 1 for c in contexts) * kv_bytes_per_token(cfg)["full"]
    state = 2 * batch * state_bytes_per_slot(cfg)
    flops = batch * token_matrix_flops(cfg) + sum(
        attention_flops(cfg, c) for c in contexts
    )
    return flops, weights + kv + state


def prefill_chunk_work(cfg: dict, tokens: int, history: int) -> Tuple[int, int]:
    """(flops, bytes) of ``tokens`` more tokens of one sequence whose
    first ``history`` tokens' K, V and state are kept already (one run of
    a history-plus-suffix program): the blocks over the new tokens, each
    attending over the history and the new tokens before it, the head
    once; every weight read once, the history's K and V read once and the
    new tokens' written, the sequence's state read once and written once."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    keys = tokens * history + tokens * (tokens + 1) // 2
    flops = (
        (token_matrix_flops(cfg) - 2 * head) * tokens
        + attention_flops(cfg, 1) * keys + 2 * head
    )
    nbytes = (
        (always_read_params(cfg) + tokens * cfg["hidden_size"]) * dtype_bytes(cfg)
        + (history + tokens) * kv_bytes_per_token(cfg)["full"]
        + 2 * state_bytes_per_slot(cfg)
    )
    return flops, nbytes
