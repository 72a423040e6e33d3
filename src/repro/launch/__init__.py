"""repro.launch — mesh, dry-run, train, serve entry points."""

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because the path is part of the cache key.
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache at ``<repo>/.jax_cache``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own config has read it
    and nothing is changed.  Called from entry points, never at import.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
