# Launchers: production mesh, multi-pod dry-run, roofline extraction,
# training and serving drivers.
import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed path,
    since the directory is part of the cache key and a moving one never
    hits.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
