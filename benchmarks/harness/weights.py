"""Seeded weights, made on the device in one jitted call, in the type they
are served in and in the pytree ``ray_tpu.models.transformer`` takes
(``embed``, ``blocks`` stacked over layers, ``ln_f``, ``head``). The
reference reads the same arrays; the program makes none of them."""
from __future__ import annotations

import jax
import jax.numpy as jnp


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
