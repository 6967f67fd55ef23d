"""What a request says of its generation, and the byte-level tokenizer.
The engine that serves them is ``continuous.ContinuousBatchingEngine``."""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional


@dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
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
