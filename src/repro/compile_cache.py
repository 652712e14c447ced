"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip call :func:`use_compile_cache` once,
before their first compile; no library module calls it on import. A
cache hit needs the same path on every run (the path is part of the
key), so the default is a fixed directory at the repository root, which
``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` when it is set (JAX reads that variable itself, so
    nothing is set here) and at ``<repo root>/.jax_cache`` otherwise.
    Returns the directory in use."""
    if os.environ.get(ENV):
        return Path(os.environ[ENV])
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
