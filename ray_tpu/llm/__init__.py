"""ray_tpu.llm — LLM batch inference and serving on the native engine.

The reference's ray.llm is config passthrough to vLLM/SGLang
(/root/reference/python/ray/llm/_internal/). Here the engine is native:
``ContinuousBatchingEngine`` (continuous.py), continuous batching over a
paged KV pool with three jitted programs (prefill, prefill of a suffix,
one decode step) on the flagship model (ray_tpu.models.transformer). Batch
inference is a Data pipeline stage over it (vllm_engine_proc analog) and
serving a Serve deployment of it.
"""
from .continuous import ContinuousBatchingEngine, PagedKVPool  # noqa: F401
from .engine import GenerationConfig  # noqa: F401
from .processor import LLMProcessor  # noqa: F401
from .serving import build_llm_deployment  # noqa: F401
