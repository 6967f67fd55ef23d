"""A decoder whose layers differ by position (MiMo-V2.5): windowed and
full grouped-query attention mixed, one dense feed-forward layer and then
sigmoid-routed experts of which this chip holds some. Everything the
benchmark knows of the architecture apart from its plain reference
(``references/moe_window_gqa.py``). ``cfg`` is a configuration file's dict
(the source's key names, with ``router_width`` and ``experts_held`` beside
``n_routed_experts``, the count held here).

- ``make_weights``: seeded weights, made on the device in one jitted call,
  in the type they are served in and in the pytree the program takes for a
  stack by position: ``embed``, ``ln_f``, ``head`` and
  ``blocks["<attention>.<feed-forward>"]``, one stack for each kind of
  layer (``ln1 ln2 wq wk wv wo``; ``sink`` in a windowed layer;
  ``w_gate w_up w_down`` or ``moe``: ``router router_bias w_gate w_up
  w_down``). The reference reads the same arrays.
- ``model_config``: the program's own configuration object. The one place
  here that imports the program.
- The counts: operations and bytes the ALGORITHM needs on THIS chip, from
  shapes and live context lengths alone, blind to how the program runs a
  step. A windowed layer reads at most ``sliding_window`` keys of a
  context; of the held experts a step reads those that its tokens are
  expected to hit under a uniform router, once each.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from harness.peaks import dtype_bytes

ATTN = {0: "full", 1: "window"}
FFN = {0: "dense", 1: "experts"}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layer_kinds(cfg: dict) -> List[Tuple[str, str]]:
    kinds = [
        (ATTN[a], FFN[f])
        for a, f in zip(cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"])
    ]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("the two patterns name every layer once")
    return kinds


def kv_heads(cfg: dict, attn: str) -> int:
    return cfg["swa_num_key_value_heads" if attn == "window"
               else "num_key_value_heads"]


def rotary_dim(cfg: dict) -> int:
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def make_weights(cfg: dict, seed: int):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, vd = cfg["head_dim"], cfg["v_head_dim"]
    held, width = cfg["n_routed_experts"], cfg["router_width"]
    ff, eff = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    vocab = cfg["vocab_size"]
    dt = jnp.dtype(cfg["torch_dtype"])
    counts: Dict[Tuple[str, str], int] = {}
    for kind in layer_kinds(cfg):
        counts[kind] = counts.get(kind, 0) + 1

    def dense(key, rows, cols, scale):
        return (
            jax.random.normal(key, (rows, cols), jnp.float32) * scale
        ).astype(dt)

    def stacked(key, lead, rows, cols):
        # one matrix at a time, so that no float32 copy of a stack exists
        n = 1
        for x in lead:
            n *= x
        flat = jax.lax.map(
            lambda k: dense(k, rows, cols, rows ** -0.5),
            jax.random.split(key, n),
        )
        return flat.reshape(*lead, rows, cols)

    @jax.jit
    def build(key):
        blocks = {}
        for i, ((attn, ffn), n) in enumerate(sorted(counts.items())):
            k = jax.random.split(jax.random.fold_in(key, i), 12)
            kh = kv_heads(cfg, attn)
            p = {
                "ln1": jnp.ones((n, d), dt),
                "ln2": jnp.ones((n, d), dt),
                "wq": stacked(k[0], (n,), d, h * hd),
                "wk": stacked(k[1], (n,), d, kh * hd),
                "wv": stacked(k[2], (n,), d, kh * vd),
                "wo": stacked(k[3], (n,), h * vd, d),
            }
            if attn == "window" and cfg["add_swa_attention_sink_bias"]:
                p["sink"] = jax.random.normal(k[4], (n, h), jnp.float32)
            if ffn == "experts":
                p["moe"] = {
                    "router": stacked(k[5], (n,), d, width),
                    "router_bias": jnp.zeros((n, width), jnp.float32),
                    "w_gate": stacked(k[6], (n, held), d, eff),
                    "w_up": stacked(k[7], (n, held), d, eff),
                    "w_down": stacked(k[8], (n, held), eff, d),
                }
            else:
                p["w_gate"] = stacked(k[9], (n,), d, ff)
                p["w_up"] = stacked(k[10], (n,), d, ff)
                p["w_down"] = stacked(k[11], (n,), ff, d)
            blocks[f"{attn}.{ffn}"] = p
        k = jax.random.split(jax.random.fold_in(key, len(counts)), 2)
        return {
            "embed": dense(k[0], vocab, d, 0.02),
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
        ("swa_head_dim", cfg["head_dim"]), ("swa_v_head_dim", cfg["v_head_dim"]),
        ("swa_num_attention_heads", cfg["num_attention_heads"]),
        ("sliding_window_size", cfg["sliding_window"]),
        ("add_full_attention_sink_bias", False), ("n_group", 1),
        ("topk_group", 1), ("n_shared_experts", None),
        ("routed_scaling_factor", None), ("scoring_func", "sigmoid"),
        ("norm_topk_prob", True),
        ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
        ("attention_bias", False), ("tie_word_embeddings", False),
    ):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"`{key}`={cfg[key]!r}: the program has no such option "
                f"(it computes {want!r})"
            )
    if list(cfg["experts_held"]) != [cfg["experts_held"][0],
                                     cfg["n_routed_experts"]]:
        raise ValueError("`n_routed_experts` counts the experts held here")
    kinds = layer_kinds(cfg)
    return tfm.ModelConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["deployment"]["max_context_tokens"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
        rms_eps=float(cfg["layernorm_epsilon"]),
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        rotary_dim=rotary_dim(cfg),
        value_scale=float(cfg["attention_value_scale"]),
        attn_pattern=tuple(a for a, _ in kinds),
        ffn_pattern=tuple(f for _, f in kinds),
        window=cfg["sliding_window"],
        window_kv_heads=cfg["swa_num_key_value_heads"],
        window_rope_theta=float(cfg["swa_rope_theta"]),
        window_sink=bool(cfg["add_swa_attention_sink_bias"]),
        d_ff_expert=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
    )


# -- the algorithm's counts ---------------------------------------------------


def attention_params(cfg: dict, attn: str) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, vd, kh = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, attn)
    return d * h * hd + d * kh * hd + d * kh * vd + h * vd * d


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_read_params(cfg: dict) -> int:
    """Matrix parameters a step reads whatever its tokens choose:
    attention, routers, the dense feed-forward, the head."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for attn, ffn in layer_kinds(cfg):
        total += attention_params(cfg, attn)
        total += (
            d * cfg["router_width"] if ffn == "experts"
            else 3 * d * cfg["intermediate_size"]
        )
    return total


def expert_layers(cfg: dict) -> int:
    return sum(1 for _, ffn in layer_kinds(cfg) if ffn == "experts")


def held_share(cfg: dict) -> float:
    """Experts of a token's ``num_experts_per_tok`` that are held here,
    expected under a uniform router: 8 * 16 / 256 = 0.5."""
    return (
        cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
        / cfg["router_width"]
    )


def experts_hit(cfg: dict, tokens: int) -> float:
    """Held experts of one layer that ``tokens`` tokens are expected to
    hit under a uniform router: ``held * (1 - (1 - k / width) ** tokens)``."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["router_width"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token(cfg: dict) -> Dict[str, int]:
    """Bytes of K and V one token holds, by class of page."""
    out: Dict[str, int] = {}
    row = (cfg["head_dim"] + cfg["v_head_dim"]) * dtype_bytes(cfg)
    for attn, _ in layer_kinds(cfg):
        out[attn] = out.get(attn, 0) + kv_heads(cfg, attn) * row
    return out


def keys_seen(cfg: dict, attn: str, context: int) -> int:
    return min(context, cfg["sliding_window"]) if attn == "window" else context


def attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV of one query over the keys it sees, all layers."""
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["head_dim"] + cfg["v_head_dim"]
    )
    return sum(
        per_key * keys_seen(cfg, attn, context) for attn, _ in layer_kinds(cfg)
    )


def token_matrix_flops(cfg: dict) -> float:
    """One token through every matrix this chip applies to it."""
    return 2 * (
        always_read_params(cfg)
        + expert_layers(cfg) * held_share(cfg) * expert_params(cfg)
    )


def decode_token_flops(cfg: dict, context: int) -> float:
    return token_matrix_flops(cfg) + attention_flops(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """All blocks over the prompt, causal attention (a windowed layer's
    query i sees min(i + 1, window) keys), the head once."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    per_key = 2 * cfg["num_attention_heads"] * (
        cfg["head_dim"] + cfg["v_head_dim"]
    )
    keys = 0
    for attn, _ in layer_kinds(cfg):
        if attn == "window":
            w = min(prompt_len, cfg["sliding_window"])
            keys += w * (w + 1) // 2 + (prompt_len - w) * w
        else:
            keys += prompt_len * (prompt_len + 1) // 2
    return (
        (token_matrix_flops(cfg) - 2 * head) * prompt_len
        + per_key * keys + 2 * head
    )


def decode_step_work(cfg: dict, contexts: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) of one decode step that advances one token in each
    live sequence: every attention, router, dense and head matrix read
    once; in each expert layer the held experts the batch is expected to
    hit, once each; the batch's embedding rows; each live context's K and V
    read once (at most ``sliding_window`` keys in a windowed layer) and one
    new K, V written."""
    batch, nb = len(contexts), dtype_bytes(cfg)
    weights = (
        always_read_params(cfg)
        + expert_layers(cfg) * experts_hit(cfg, batch) * expert_params(cfg)
        + batch * cfg["hidden_size"]
    ) * nb
    per_token = kv_bytes_per_token(cfg)
    kv = sum(
        (keys_seen(cfg, attn, c) + 1) * per_token[attn]
        for c in contexts for attn in per_token
    )
    flops = batch * token_matrix_flops(cfg) + sum(
        attention_flops(cfg, c) for c in contexts
    )
    return flops, weights + kv
