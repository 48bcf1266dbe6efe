"""Where the repo's scripts keep JAX's persistent compilation cache."""

from __future__ import annotations

import os

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

# <repo>/.jax_cache: a fixed path, listed in .gitignore. A cache that moves
# between runs is never found again.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call it before the first
    compile, never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already keeps the cache there and nothing is set here; otherwise the
    cache goes to :data:`REPO_CACHE_DIR`. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
