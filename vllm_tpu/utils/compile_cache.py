"""Persistent XLA compilation cache: a restart (or the second process of
one chip run) loads compiled executables from disk instead of re-paying
5-40 s per bucket. Reference analog: torch.compile/CUDA-graph caches are
in-process only — the reference re-captures at every boot; the XLA cache
survives restarts, keyed by HLO + flags + backend hash.

One rule, for the worker and for ``tests/conftest.py`` alike: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and no directory is set
in code; otherwise the cache lives at one fixed path inside the checkout
(never a temporary name: a directory that moves never hits).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

from vllm_tpu import envs
from vllm_tpu.logger import init_logger

logger = init_logger(__name__)

_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_enabled = False


def enable_compilation_cache() -> None:
    global _enabled
    if _enabled:
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    cache_dir = jax.config.jax_compilation_cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    _prune_compilation_cache(cache_dir)
    # Cache every bucket, including fast-compiling small ones: step
    # count (not per-compile time) dominates cold-start latency.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    logger.info("persistent compilation cache: %s", cache_dir)


def _prune_compilation_cache(cache_dir: str) -> None:
    """Bound the on-disk cache: drop least-recently-used entries beyond
    VLLM_TPU_COMPILE_CACHE_MAX_GB (large-model executables are hundreds of
    MB; a host cycling models would otherwise grow the dir forever)."""
    limit = envs.VLLM_TPU_COMPILE_CACHE_MAX_GB * (1 << 30)
    entries = []
    with os.scandir(cache_dir) as it:
        for de in it:
            try:
                if de.is_file():
                    st = de.stat()
                    entries.append((st.st_atime, st.st_size, de.path))
            except FileNotFoundError:  # another process's temp file, renamed
                continue
    total = sum(e[1] for e in entries)
    if total <= limit:
        return
    entries.sort()  # oldest access first
    for _atime, size, path in entries:
        try:
            os.unlink(path)
        except FileNotFoundError:  # another process pruned it first
            pass
        total -= size
        if total <= limit:
            break
    logger.info("pruned compilation cache to %.1f GiB", total / (1 << 30))
