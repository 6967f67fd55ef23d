"""Batch inference processor: datasets through engine actors.

Analog of the reference's vLLM batch stage (/root/reference/python/ray/llm/
_internal/batch/stages/vllm_engine_stage.py): rows with a "prompt" column
flow through a pool of engine-holding actors via Dataset.map_batches.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np

import ray_tpu
from .continuous import ContinuousBatchingEngine
from .engine import GenerationConfig


@dataclass
class LLMProcessor:
    model_config: Any                       # tfm.ModelConfig
    params: Optional[Any] = None
    generation: GenerationConfig = field(default_factory=GenerationConfig)
    batch_size: int = 16
    max_len: int = 256

    def process(self, dataset):
        """dataset rows: {"prompt": str, ...} -> adds "generated_text"."""
        cfg = self.model_config
        params = self.params
        gen = self.generation
        # a pool for batch_size contexts of max_len tokens (prompt and
        # answer), and the scratch page
        page = 16
        ctx_pages = -(-min(self.max_len, cfg.max_seq_len) // page)
        engine_kw = dict(
            max_batch=self.batch_size, page_size=page,
            n_pages=self.batch_size * ctx_pages + 1,
            max_pages_per_seq=ctx_pages,
        )
        engine_holder: Dict[str, ContinuousBatchingEngine] = {}

        def infer(batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
            # engine is constructed once per worker and reused across blocks
            if "engine" not in engine_holder:
                engine_holder["engine"] = ContinuousBatchingEngine(
                    cfg, params, **engine_kw
                )
            engine = engine_holder["engine"]
            prompts = [str(p) for p in batch["prompt"]]
            outputs = engine.generate(prompts, gen)
            out = dict(batch)
            out["generated_text"] = np.array(outputs, dtype=object)
            return out

        return dataset.map_batches(infer, batch_size=self.batch_size)
