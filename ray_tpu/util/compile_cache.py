"""Where JAX's persistent compilation cache lives.

One rule for every program this repo puts on a device (scheduler kernels,
serving engine, train step): ``JAX_COMPILATION_CACHE_DIR`` decides. JAX
reads that variable itself, so when it is set nothing here names a
directory. When it is not, the cache goes to one fixed directory inside
the checkout — the path is part of the cache key, so it must never carry
a pid, a timestamp or a temporary name.
"""
from __future__ import annotations

import os
import threading

#: the fallback location: ``<checkout>/.jax_compile_cache`` (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)

_lock = threading.Lock()
_configured = False


def configure_compile_cache() -> None:
    """Idempotent; call before the first compile of a device program."""
    global _configured
    with _lock:
        if _configured:
            return
        _configured = True
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            os.makedirs(DEFAULT_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # the scheduler's round kernels compile in well under a second
        # each but there are dozens of bucket shapes: cache all of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
