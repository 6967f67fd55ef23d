"""Operations and bytes the ALGORITHM needs, from shapes and live context
lengths alone. Nothing here looks at how the program implements a step:
not its gathers, not its copies, not its padding. A later PR that
replaces the decode program is judged by the same counts.

``cfg`` is a configuration file's dict (the source's key names).
"""
from __future__ import annotations

from typing import Tuple


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return (
        d, hd, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["intermediate_size"], cfg["num_hidden_layers"],
        cfg["vocab_size"],
    )


def dtype_bytes(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


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


def decode_step_work(cfg: dict, batch: int, context_sum: int) -> Tuple[int, int]:
    """(flops, bytes) of one decode step that advances one token in each
    of ``batch`` live sequences whose context lengths sum to
    ``context_sum`` (all the work depends on): every weight matrix read
    once, the batch's embedding rows, each live context's K and V read
    once and one new K, V written."""
    nb = dtype_bytes(cfg)
    d = cfg["hidden_size"]
    weights = (block_params(cfg) + head_params(cfg) + batch * d) * nb
    kv = (context_sum + batch) * kv_bytes_per_token(cfg)
    flops = (
        2 * (block_params(cfg) + head_params(cfg)) * batch
        + attention_flops(cfg, context_sum)
    )
    return flops, weights + kv


def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
