"""Persistent XLA compile cache — one policy for every entry point that
compiles (chip_smoke.py, bench.py, tests_tpu/, tools/serving_benchmark.py,
inference/replica_worker.py).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``, computed
from this file's location (the copy on a chip machine is not a git
repository). The directory is part of the cache key, so it is never a temp
name, a pid or a time. A process pinned to the CPU platform gets no cache:
the cache exists for chip compile time, XLA:CPU programs are cheap to
rebuild, and their AOT reload warns about host-feature mismatches.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

__all__ = ["enable_compile_cache", "cache_stats"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_stats: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(name: str, **kwargs) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _stats["hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        _stats["misses"] += 1


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on (idempotent) and return the
    directory in effect — None in a CPU-pinned process. Call before the
    first compile."""
    global _listening
    import jax

    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses since :func:`enable_compile_cache`
    (programs under JAX's minimum compile time are not cached and count as
    neither)."""
    return dict(_stats)
