"""One persistent XLA compile cache, placed from outside.

A served 8B program menu or an unrolled train step is minutes of compile
on a cold TPU process and seconds from the cache, so the serving and
training entry points (serving/llm_runtime.LLMModel.load,
training/job.train_target) call `ensure_compile_cache()` before they
compile anything. The rule:

  - `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing here
    touches the configuration. This is how an operator (or a test loop
    that wants a warm cache) places the cache.
  - unset, on a TPU backend: one fixed, git-ignored directory inside the
    checkout. The path is part of the cache key's environment, so it never
    carries a pid or a timestamp; every process of the checkout shares it.
  - unset, on any other backend: JAX's default (no persistent cache). On
    this jaxlib's XLA:CPU a process mixing fresh and deserialized
    executables has returned wrong tokens (tests/conftest.py), so CPU runs
    opt in from outside or not at all.
"""

from __future__ import annotations

import os
import threading

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_lock = threading.Lock()
_placed = False


def ensure_compile_cache() -> str | None:
    """Apply the rule above; returns the cache directory in effect, or
    None when there is none. Idempotent and thread-safe (thread-backend
    jobs and model loads share the process's single JAX cache)."""
    global _placed
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    if jax.default_backend() != "tpu":
        return None
    with _lock:
        if not _placed:
            from jax.experimental.compilation_cache import compilation_cache

            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
            # jax binds its cache object lazily to the directory in effect
            # at the first compile; reset so a process that already
            # compiled something still starts writing here
            compilation_cache.reset_cache()
            _placed = True
    return CACHE_DIR
