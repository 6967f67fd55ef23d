"""The dense grouped-query-attention decoder (Mistral-7B-v0.3, InternLM2):
everything the benchmark knows of the architecture apart from its plain
reference (``references/dense_gqa.py``). ``cfg`` is a configuration file's
dict (the source's key names).

- ``make_weights``: seeded weights, made on the device in one jitted call,
  in the type they are served in and in the pytree
  ``ray_tpu.models.transformer`` takes (``embed``, ``blocks`` stacked over
  layers, ``ln_f``, ``head``). The reference reads the same arrays; the
  program makes none of them.
- ``model_config``: the program's own configuration object. The one place
  here that imports the program.
- The counts: operations and bytes the ALGORITHM needs, from shapes and
  live context lengths alone. Nothing here looks at how the program
  implements a step: not its gathers, not its copies, not its padding. A
  later PR that replaces the decode program is judged by the same counts.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from harness.peaks import dtype_bytes


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_weights(cfg: dict, seed: int):
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff, layers, vocab = (
        cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    )
    dt = jnp.dtype(cfg["torch_dtype"])

    def dense(key, rows, cols, scale):
        return (
            jax.random.normal(key, (rows, cols), jnp.float32) * scale
        ).astype(dt)

    def stacked(key, rows, cols):
        # one layer at a time, so that no float32 copy of a whole stack
        # ever exists
        return jax.lax.map(
            lambda k: dense(k, rows, cols, rows ** -0.5),
            jax.random.split(key, layers),
        )

    @jax.jit
    def build(key):
        k = jax.random.split(key, 9)
        blocks = {
            "ln1": jnp.ones((layers, d), dt),
            "ln2": jnp.ones((layers, d), dt),
            "wq": stacked(k[0], d, h * hd),
            "wk": stacked(k[1], d, kh * hd),
            "wv": stacked(k[2], d, kh * hd),
            "wo": stacked(k[3], h * hd, d),
            "w_gate": stacked(k[4], d, ff),
            "w_up": stacked(k[5], d, ff),
            "w_down": stacked(k[6], ff, d),
        }
        return {
            "embed": dense(k[7], vocab, d, 0.02),
            "blocks": blocks,
            "ln_f": jnp.ones((d,), dt),
            "head": dense(k[8], d, vocab, d ** -0.5),
        }

    params = build(seed_key(seed))
    jax.block_until_ready(params)
    return params


def model_config(cfg: dict):
    from ray_tpu.models import transformer as tfm

    model = tfm.ModelConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["deployment"]["max_context_tokens"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
    )
    if model.head_dim != cfg.get("head_dim", model.head_dim):
        raise ValueError("the program derives another head size")
    return model


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return (
        d, hd, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["intermediate_size"], cfg["num_hidden_layers"],
        cfg["vocab_size"],
    )


def block_params(cfg: dict) -> int:
    """Matrix parameters of all blocks (norm vectors are not multiplied)."""
    d, hd, h, kh, ff, layers, _ = _dims(cfg)
    per_layer = d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * ff
    return layers * per_layer


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Everything held: blocks, norms, embedding and head."""
    d, _, _, _, _, layers, vocab = _dims(cfg)
    tied = bool(cfg.get("tie_word_embeddings"))
    return (
        block_params(cfg) + (2 * layers + 1) * d
        + vocab * d * (1 if tied else 2)
    )


def kv_bytes_per_token(cfg: dict) -> int:
    _, hd, _, kh, _, layers, _ = _dims(cfg)
    return 2 * layers * kh * hd * dtype_bytes(cfg)


def attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV for one query over ``context`` keys, all layers."""
    _, hd, h, _, _, layers, _ = _dims(cfg)
    return 4 * layers * h * hd * context


def decode_token_flops(cfg: dict, context: int) -> int:
    return 2 * (block_params(cfg) + head_params(cfg)) + attention_flops(
        cfg, context
    )


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """All blocks over the prompt, causal attention, the head once."""
    _, hd, h, _, _, layers, _ = _dims(cfg)
    causal = 4 * layers * h * hd * prompt_len * (prompt_len + 1) // 2
    return 2 * block_params(cfg) * prompt_len + causal + 2 * head_params(cfg)


def decode_step_work(cfg: dict, contexts: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of one decode step that advances one token in each
    live sequence, ``contexts`` being their context lengths (only their
    count and sum matter to this family): every weight matrix read once,
    the batch's embedding rows, each live context's K and V read once and
    one new K, V written."""
    batch, context_sum = len(contexts), sum(contexts)
    nb = dtype_bytes(cfg)
    d = cfg["hidden_size"]
    weights = (block_params(cfg) + head_params(cfg) + batch * d) * nb
    kv = (context_sum + batch) * kv_bytes_per_token(cfg)
    flops = (
        2 * (block_params(cfg) + head_params(cfg)) * batch
        + attention_flops(cfg, context_sum)
    )
    return flops, weights + kv
