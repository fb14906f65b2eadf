"""JAX's persistent compilation cache, placed for the entry points.

Called at the top of each entry point's ``main`` (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``), never on import, so that a
second run of the same program on the same machine loads its compiled
steps instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Root of the checkout (this file is ``<root>/src/repro/launch/``).
REPO_ROOT = Path(__file__).resolve().parents[3]
#: Where the cache goes unless ``JAX_COMPILATION_CACHE_DIR`` says
#: otherwise. Fixed, so that a later run finds what an earlier one wrote;
#: listed in ``.gitignore``.
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
