"""Native LLM engine: jitted continuous prefill+decode with KV cache."""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import transformer as tfm


@dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0            # 0 = no top-k filter
    seed: int = 0
    eos_token: Optional[int] = None


class ByteTokenizer:
    """Self-contained byte-level tokenizer (no external vocab files needed;
    swap in a transformers tokenizer for real checkpoints)."""

    vocab_size = 256 + 2
    bos = 256
    eos = 257

    def encode(self, text: str) -> List[int]:
        return [self.bos] + list(text.encode("utf-8"))

    def decode(self, ids: List[int]) -> str:
        return bytes(i for i in ids if i < 256).decode("utf-8", "replace")


class LLMEngine:
    """Batched generation over the flagship model.

    One jitted prefill (full prompt) + one jitted decode step re-used for
    every generated token; the KV cache buffer is donated between steps so
    decoding is in-place on device (HBM-friendly).
    """

    def __init__(
        self,
        cfg: tfm.ModelConfig,
        params: Optional[Any] = None,
        *,
        max_len: int = 256,
        tokenizer: Optional[Any] = None,
    ):
        cfg.require_uniform_dense("LLMEngine")
        self.cfg = cfg
        self.max_len = min(max_len, cfg.max_seq_len)
        self.tokenizer = tokenizer or ByteTokenizer()
        self.params = (
            params
            if params is not None
            else tfm.init_params(cfg, jax.random.PRNGKey(0))
        )

        @jax.jit
        def _prefill(params, tokens, lengths, cache):
            b, t = tokens.shape
            positions = jnp.arange(t)[None, :].repeat(b, 0)
            seq_mask = jnp.arange(cache["k"].shape[2])[None, :] < lengths[:, None]
            logits, cache = tfm.forward_with_cache(
                params, tokens, positions, cache, seq_mask, cfg
            )
            # logits at each sequence's last real token
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1
            )[:, 0]
            return last, cache

        @functools.partial(
            jax.jit, donate_argnums=(3,), static_argnums=(5, 6)
        )
        def _decode(params, token, pos, cache, key, temperature, top_k):
            b = token.shape[0]
            positions = pos[:, None]
            seq_mask = (
                jnp.arange(cache["k"].shape[2])[None, :] <= pos[:, None]
            )
            logits, cache = tfm.forward_with_cache(
                params, token[:, None], positions, cache, seq_mask, cfg
            )
            logits = logits[:, 0]
            nxt = _sample(logits, key, temperature, top_k)
            return nxt, cache

        def _sample(logits, key, temperature, top_k):
            def greedy():
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def sampled():
                scaled = logits / jnp.maximum(temperature, 1e-6)
                if self_top_k := int(top_k):
                    kth = jnp.sort(scaled, axis=-1)[:, -self_top_k][:, None]
                    scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
                return jax.random.categorical(key, scaled).astype(jnp.int32)

            # temperature is a python float captured at trace time
            return greedy() if temperature == 0.0 else sampled()

        self._prefill = _prefill
        self._decode = _decode

    def generate_ids(
        self,
        prompts: List[List[int]],
        gen: GenerationConfig = GenerationConfig(),
    ) -> List[List[int]]:
        b = len(prompts)
        lengths = np.array([len(p) for p in prompts], dtype=np.int32)
        t = int(lengths.max())
        tokens = np.zeros((b, t), dtype=np.int32)
        for i, p in enumerate(prompts):
            tokens[i, : len(p)] = p
        cache = tfm.init_kv_cache(self.cfg, b, self.max_len)
        last_logits, cache = self._prefill(
            self.params, jnp.asarray(tokens), jnp.asarray(lengths), cache
        )
        key = jax.random.PRNGKey(gen.seed)
        nxt = (
            jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            if gen.temperature == 0.0
            else jax.random.categorical(
                key, last_logits / max(gen.temperature, 1e-6)
            ).astype(jnp.int32)
        )
        pos = jnp.asarray(lengths)
        out = [nxt]
        steps = min(gen.max_new_tokens - 1, self.max_len - t - 1)
        for i in range(max(0, steps)):
            key = jax.random.fold_in(key, i)
            nxt, cache = self._decode(
                self.params, nxt, pos, cache, key,
                gen.temperature, gen.top_k,
            )
            pos = pos + 1
            out.append(nxt)
        gen_tokens = np.stack([np.asarray(x) for x in out], axis=1)
        results = []
        for i in range(b):
            ids = gen_tokens[i].tolist()
            if gen.eos_token is not None and gen.eos_token in ids:
                ids = ids[: ids.index(gen.eos_token)]
            results.append(ids)
        return results

    def generate(
        self, prompts: List[str], gen: GenerationConfig = GenerationConfig()
    ) -> List[str]:
        enc = [self.tokenizer.encode(p) for p in prompts]
        cfg = gen if gen.eos_token is not None else GenerationConfig(
            max_new_tokens=gen.max_new_tokens,
            temperature=gen.temperature,
            top_k=gen.top_k,
            seed=gen.seed,
            eos_token=getattr(self.tokenizer, "eos", None),
        )
        out_ids = self.generate_ids(enc, cfg)
        return [self.tokenizer.decode(ids) for ids in out_ids]
