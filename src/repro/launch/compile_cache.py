"""Where JAX keeps its persistent compilation cache.

A cache directory is part of the cache's key, so it has to be a fixed path:
one built from a temporary name, a process id or the time never hits
again.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins; otherwise the cache lives in ``.jax_cache/`` at the repository root
(git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its fixed directory.

    Call first in an entry point, before anything is compiled.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
