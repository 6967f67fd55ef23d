"""A decoder most of whose layers are no attention (LFM2-8B-A1B,
``lfm2_moe``): gated short convolutions that keep a few columns of state
for each sequence and no K and V, full grouped-query attention with a norm
over each head of q and k every fourth layer or so, two dense SwiGLU layers
and then sigmoid-routed experts held whole, the head tied to the embedding.
Everything the benchmark knows of the architecture apart from its plain
reference (``references/moe_conv_gqa.py``). ``cfg`` is a configuration
file's dict: the source's key names, with ``experts_held``,
``router_norm_eps``, ``tie_word_embeddings`` and ``expert_bias_std``
beside them (and ``embedding_std``, 0.02 where the file has none: the toy
of ``tests/toy_conv`` is too narrow for logits of a tied head at 0.02 to
lie a whole unit apart).

- ``make_weights``: seeded weights, made on the device in one jitted call,
  in the type they are served in and in the pytree the program takes for a
  stack by position: ``embed``, ``ln_f`` (the source's ``embedding_norm``),
  no ``head``, and ``blocks[<run>]``, one stack for each run of consecutive
  layers of one kind (``conv.dense``, ``full.experts``, ``conv.experts``,
  ``full.experts.1``, ...: a kind's first run, then its n-th later one).
  A layer holds ``ln1`` (``operator_norm``), ``ln2`` (``ffn_norm``); ``w_in
  conv w_out`` (convolution: d x 3d, taps x d with the oldest tap first, d x
  d) or ``wq wk wv wo q_norm k_norm``; ``w_gate w_up w_down`` (the source's
  ``w1 w3 w2``) or ``moe``: ``router router_bias w_gate w_up w_down``. The
  reference reads the same arrays. The expert bias is drawn, not zero: a
  bias of zero cannot tell a program that weighs by score + bias from one
  that weighs by the score. Every matrix is normal with a deviation of
  fan_in ** -0.5 but the experts' ``w_down``, which has that over the root
  of the number of expert layers (the depth-scaled output projection of
  GPT-2's and Megatron's initialisation, applied to the routed layers).
  Why: a seeded router's 4th and 5th scores of 32 lie closer than
  bfloat16's rounding of the stream in about 3 % of (token, layer) pairs,
  so a bfloat16 program chooses another expert than the float32 reference
  somewhere in 12 layers for some 30 % of the tokens, and with twelve
  layers of unit weight each such flip moved the logits by a tenth to a
  whole deviation: on the chip the mean gap read 0.064 and 4 of 1,528
  tokens lay over the check's 1.0, which no rounding is to give (PR 36;
  with every expert chosen, or four of four, the same toy reads 0.0005:
  the flips are the whole of it). Scaled so, the twelve layers together
  weigh what one operator does and a flip is a near-tie like any other;
  choice, shapes and work are what they were.
- ``model_config``: the program's own configuration object. The one place
  here that imports the program.
- The counts: operations and bytes the ALGORITHM needs, from shapes and
  live context lengths alone, blind to how the program runs a step. Every
  operator, router, dense and head matrix is read once a step; of a layer's
  experts those that the step's tokens are expected to hit under a uniform
  router, once each; K and V of the attention layers over each live
  context; each convolution layer's state read once and written once a
  live slot. ``kv_bytes_per_token``, ``state_bytes_per_slot``,
  ``expert_layers`` and ``experts_held`` are for the per-layer readers.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from harness.peaks import dtype_bytes


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layer_kinds(cfg: dict) -> List[Tuple[str, str]]:
    """(operator, feed-forward) of each layer."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or set(types) - {
        "conv", "full_attention"
    }:
        raise ValueError("`layer_types` names conv or full_attention for "
                         "every layer once")
    return [
        ("conv" if kind == "conv" else "full",
         "dense" if i < cfg["num_dense_layers"] else "experts")
        for i, kind in enumerate(types)
    ]


def layer_runs(cfg: dict) -> List[Tuple[str, Tuple[str, str], int]]:
    """(key in ``blocks``, kind, layers) of each run of consecutive layers
    of one kind, in order."""
    runs: List[Tuple[str, Tuple[str, str], int]] = []
    seen: Dict[Tuple[str, str], int] = {}
    for kind in layer_kinds(cfg):
        if runs and runs[-1][1] == kind:
            runs[-1] = (runs[-1][0], kind, runs[-1][2] + 1)
            continue
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        runs.append((".".join(kind) + (f".{nth}" if nth else ""), kind, 1))
    return runs


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def experts_held(cfg: dict) -> int:
    return cfg["experts_held"][1]


def make_weights(cfg: dict, seed: int):
    d, h, kh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, taps = head_dim(cfg), cfg["conv_L_cache"]
    held, width = experts_held(cfg), cfg["num_experts"]
    ff, eff = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    vocab = cfg["vocab_size"]
    dt = jnp.dtype(cfg["torch_dtype"])
    bias_std = float(cfg["expert_bias_std"])

    def dense(key, rows, cols, scale):
        return (
            jax.random.normal(key, (rows, cols), jnp.float32) * scale
        ).astype(dt)

    def stacked(key, lead, rows, cols, scale=1.0):
        # one matrix at a time, so that no float32 copy of a stack exists
        n = 1
        for x in lead:
            n *= x
        flat = jax.lax.map(
            lambda k: dense(k, rows, cols, scale * rows ** -0.5),
            jax.random.split(key, n),
        )
        return flat.reshape(*lead, rows, cols)

    # see the module's docstring: one flipped choice of expert must not
    # move the logits by more than rounding does
    expert_out = max(1, expert_layers(cfg)) ** -0.5

    @jax.jit
    def build(key):
        blocks = {}
        for i, (name, (op, ffn), n) in enumerate(layer_runs(cfg)):
            k = jax.random.split(jax.random.fold_in(key, i), 12)
            p = {"ln1": jnp.ones((n, d), dt), "ln2": jnp.ones((n, d), dt)}
            if op == "conv":
                p["w_in"] = stacked(k[0], (n,), d, 3 * d)
                p["conv"] = stacked(k[1], (n,), taps, d)
                p["w_out"] = stacked(k[2], (n,), d, d)
            else:
                p["wq"] = stacked(k[0], (n,), d, h * hd)
                p["wk"] = stacked(k[1], (n,), d, kh * hd)
                p["wv"] = stacked(k[2], (n,), d, kh * hd)
                p["wo"] = stacked(k[3], (n,), h * hd, d)
                p["q_norm"] = jnp.ones((n, hd), dt)
                p["k_norm"] = jnp.ones((n, hd), dt)
            if ffn == "experts":
                p["moe"] = {
                    "router": stacked(k[5], (n,), d, width),
                    "router_bias": bias_std * jax.random.normal(
                        k[4], (n, width), jnp.float32),
                    "w_gate": stacked(k[6], (n, held), d, eff),
                    "w_up": stacked(k[7], (n, held), d, eff),
                    "w_down": stacked(k[8], (n, held), eff, d, expert_out),
                }
            else:
                p["w_gate"] = stacked(k[9], (n,), d, ff)
                p["w_up"] = stacked(k[10], (n,), d, ff)
                p["w_down"] = stacked(k[11], (n,), ff, d)
            blocks[name] = p
        k = jax.random.fold_in(key, len(blocks))
        return {
            "embed": dense(k, vocab, d, float(cfg.get("embedding_std", 0.02))),
            "blocks": blocks,
            "ln_f": jnp.ones((d,), dt),
        }

    params = build(seed_key(seed))
    jax.block_until_ready(params)
    return params


def model_config(cfg: dict):
    from ray_tpu.models import transformer as tfm

    for key, want in (
        ("conv_bias", False), ("use_expert_bias", True),
        ("norm_topk_prob", True), ("tie_word_embeddings", True),
        ("model_type", "lfm2_moe"),
    ):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"`{key}`={cfg[key]!r}: the program has no such option "
                f"(it computes {want!r})"
            )
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("the head's size is hidden_size / num_attention_heads")
    if list(cfg["experts_held"])[0] + experts_held(cfg) > cfg["num_experts"]:
        raise ValueError("`experts_held` lies inside `num_experts`")
    kinds = layer_kinds(cfg)
    model = tfm.ModelConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["deployment"]["max_context_tokens"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
        rms_eps=float(cfg["norm_eps"]),
        attn_pattern=tuple(op for op, _ in kinds),
        ffn_pattern=tuple(ffn for _, ffn in kinds),
        qk_norm=True, conv_kernel=cfg["conv_L_cache"], tie_embeddings=True,
        d_ff_expert=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
        router_norm_eps=float(cfg["router_norm_eps"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
    )
    ours = [key for key, _, _ in layer_runs(cfg)]
    theirs = [run.key for run in model.layer_runs()]
    if ours != theirs:
        raise ValueError(f"the program stacks its runs as {theirs}, "
                         f"the weights here as {ours}")
    return model


# -- the algorithm's counts ---------------------------------------------------


def operator_params(cfg: dict, op: str) -> int:
    d = cfg["hidden_size"]
    if op == "conv":
        return d * 3 * d + d * d + cfg["conv_L_cache"] * d
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * d * d + 2 * d * kv + 2 * head_dim(cfg)


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_read_params(cfg: dict) -> int:
    """Parameters a step reads whatever its tokens choose: operators,
    routers, the dense feed-forward layers, the head (the embedding, read
    once as the head's matrix)."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for op, ffn in layer_kinds(cfg):
        total += operator_params(cfg, op)
        total += (
            d * cfg["num_experts"] if ffn == "experts"
            else 3 * d * cfg["intermediate_size"]
        )
    return total


def expert_layers(cfg: dict) -> int:
    return sum(1 for _, ffn in layer_kinds(cfg) if ffn == "experts")


def attention_layers(cfg: dict) -> int:
    return sum(1 for op, _ in layer_kinds(cfg) if op == "full")


def conv_layers(cfg: dict) -> int:
    return sum(1 for op, _ in layer_kinds(cfg) if op == "conv")


def held_share(cfg: dict) -> float:
    """Experts of a token's ``num_experts_per_tok`` that are held here,
    expected under a uniform router: all 4 where all 32 are held."""
    return cfg["num_experts_per_tok"] * experts_held(cfg) / cfg["num_experts"]


def experts_hit(cfg: dict, tokens: int) -> float:
    """Held experts of one layer that ``tokens`` tokens are expected to hit
    under a uniform router: ``held * (1 - (1 - k / width) ** tokens)``,
    32 * (1 - (28/32)^n) here."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_experts"]
    return experts_held(cfg) * (1.0 - miss ** tokens)


def kv_bytes_per_token(cfg: dict) -> Dict[str, int]:
    """Bytes of K and V one token holds, by class of page: the attention
    layers' alone, 3 x 2 x 8 x 64 x 2 B = 6 KiB in the cell (as the
    algorithm needs them; the chip stores a row of 64 in 128 lanes)."""
    row = 2 * head_dim(cfg) * dtype_bytes(cfg)
    return {"full": attention_layers(cfg) * cfg["num_key_value_heads"] * row}


def state_bytes_per_slot(cfg: dict) -> int:
    """Bytes of convolution state a sequence carries from step to step:
    the ``conv_L_cache - 1`` columns that are read, a layer (the source
    keeps ``conv_L_cache``): 11 x 2 x 2048 x 2 B = 88 KiB in the cell."""
    return (
        conv_layers(cfg) * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]
        * dtype_bytes(cfg)
    )


def attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV of one query over the keys it sees, attention layers."""
    per_key = 2 * cfg["num_attention_heads"] * 2 * head_dim(cfg)
    return attention_layers(cfg) * per_key * context


def token_matrix_flops(cfg: dict) -> float:
    """One token through every matrix applied to it (the convolution's
    taps among the operators' parameters: a multiply-add each)."""
    return 2 * (
        always_read_params(cfg)
        + expert_layers(cfg) * held_share(cfg) * expert_params(cfg)
    )


def decode_token_flops(cfg: dict, context: int) -> float:
    return token_matrix_flops(cfg) + attention_flops(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """All blocks over the prompt, causal attention, the head once."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (
        (token_matrix_flops(cfg) - 2 * head) * prompt_len
        + attention_flops(cfg, 1) * prompt_len * (prompt_len + 1) // 2
        + 2 * head
    )


def decode_step_work(cfg: dict, contexts: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) of one decode step that advances one token in each
    live sequence: every operator, router, dense and head matrix read once;
    in each expert layer the held experts the batch is expected to hit,
    once each; the batch's embedding rows; each live context's K and V read
    once and one new K, V written; each convolution layer's state read once
    and written once a live slot."""
    batch, nb = len(contexts), dtype_bytes(cfg)
    weights = (
        always_read_params(cfg)
        + expert_layers(cfg) * experts_hit(cfg, batch) * expert_params(cfg)
        + batch * cfg["hidden_size"]
    ) * nb
    kv = sum(c + 1 for c in contexts) * kv_bytes_per_token(cfg)["full"]
    state = 2 * batch * state_bytes_per_slot(cfg)
    flops = batch * token_matrix_flops(cfg) + sum(
        attention_flops(cfg, c) for c in contexts
    )
    return flops, weights + kv + state
