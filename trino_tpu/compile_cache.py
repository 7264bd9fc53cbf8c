"""The one rule for JAX's persistent compilation cache.

Process entry points (``coordinator.main``, ``worker.main``,
``dispatch.executor_process_main``, ``microbench/join_kernels.py``,
``chip_smoke.py``, ``benchmark/run.py``) call
:func:`configure_compile_cache` once, before their first compile. Nothing
calls it at import or from a constructor, so library users and the tests
keep whatever cache configuration their process already has.

The rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, the environment owns
the cache and this module sets NOTHING (JAX reads that variable, and the
``JAX_PERSISTENT_CACHE_*`` thresholds beside it, itself). Otherwise the
cache lives at ``<checkout>/.jax_cache`` — a fixed path, because a
directory that moves between runs never hits — and keeps every executable
up to ``MAX_CACHE_BYTES`` (least recently used go first): JAX's default
skips compiles under one second, and the eager tier IS such compiles — one
program per (operator primitive, shape). Measured on the v5e (PR 25): q6
at tpch.sf1 through worker tasks made 603 XLA compiles, 567 of them under
a second.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")
MAX_CACHE_BYTES = 2 << 30


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache will use."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", MAX_CACHE_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CHECKOUT_CACHE_DIR
