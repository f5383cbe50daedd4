"""Runtime flags (env-var driven, read once per call site).

REPRO_UNROLL_SCANS=1 — replace every lax.scan whose trip count is a small
static constant (layer stacks, CE chunks, microbatches, attention q-chunks,
BFS levels) with a Python loop.  Used by the dry-run: XLA's
HloCostAnalysis counts a while-loop body ONCE regardless of trip count
(verified empirically), so scanned programs under-report FLOPs/bytes by
~L x.  Unrolling makes ``compiled.cost_analysis()`` exact and lets the
partitioner assign per-iteration buffers individually.  Training/serving
keep scans (compile-time O(1) in depth).

:func:`use_compile_cache` places JAX's persistent compilation cache for
the scripts that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset —
#: a fixed path, since the path is part of every cache key
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def unroll_scans() -> bool:
    return os.environ.get("REPRO_UNROLL_SCANS", "0") == "1"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and no
    directory is set here; otherwise the cache is
    :data:`DEFAULT_COMPILE_CACHE`.  Call it from a script's entry point,
    never at import.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
