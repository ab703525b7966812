"""One place that decides where jax's persistent compilation cache lives.

Every entry point that jits at model size calls `enable_compile_cache()`
before its first compile (chip_smoke.py, __graft_entry__.py, the
`pccl_tpu.benchmarks` mains, the TPU peers of comm/native_bench.py, the
examples). Tests do not: a cache shared between test processes would hide
a retrace regression behind a hit.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional


def enable_compile_cache() -> Optional[str]:
    """Returns the directory the cache is kept in.

    `JAX_COMPILATION_CACHE_DIR` set: jax reads it into
    `jax_compilation_cache_dir` itself, so nothing is set here and the
    caller's placement holds. Unset: `<checkout>/.jax_cache`, resolved from
    this file — the directory is part of the cache key, so the path carries
    no temp name, pid or time and two processes of one checkout share it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
