"""Process-level JAX runtime knobs shared by the hot entry points.

The reference ships AOT-compiled native engines (LightGBM/VW/CNTK pay their
compile cost at build time); the XLA equivalent is the persistent compilation
cache — first-ever run of a program shape pays the compile, every later
process reuses it. Enabled lazily from the transform/serving/training entry
points so importing the package never touches jax config.
"""

from __future__ import annotations

import os
from typing import Optional

#: ``<checkout>/.jax_cache`` — a FIXED path (the directory is part of the
#: cache key's environment, so a temp name, pid or time would never hit).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_cache_dir: Optional[str] = None
_cache_resolved = False


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it (JAX reads that itself), else
    ``<checkout>/.jax_cache`` — the same from any working directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def ensure_compile_cache() -> Optional[str]:
    """Enable JAX's persistent compilation cache (idempotent) and return the
    directory in use, or None when the cache is off.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already configured
    itself from the environment and nothing is set here. Otherwise the
    fixed ``<checkout>/.jax_cache`` is used — on accelerators only: CPU
    executables are cheap to rebuild and their cache entries are tied to
    the host's instruction set.
    """
    global _cache_dir, _cache_resolved
    if _cache_resolved:
        return _cache_dir
    _cache_resolved = True
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        if jax.default_backend() == "cpu":
            return None
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    _cache_dir = compile_cache_dir()
    return _cache_dir
