"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``launch/serve.main``, the benchmark
mains) call :func:`use_compile_cache` once, before their first compile;
importing this module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this helper sets nothing.  Otherwise the
cache lives at the fixed ``<checkout>/.jax_cache`` (ignored by git): a
path that never depends on a temporary name, a pid or the time, so a
second run in the same checkout finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (src/repro/launch -> checkout root)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Path:
    """Turn the persistent cache on and return its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR


def cache_entries(path: Path) -> int:
    """Number of compiled programs in a cache directory (JAX writes one
    ``<key>-cache`` file per program, beside an ``<key>-atime`` stamp)."""
    return len(list(Path(path).glob("*-cache")))
