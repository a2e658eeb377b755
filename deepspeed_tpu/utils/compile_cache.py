"""Where JAX's persistent compilation cache lives.

The cache directory is part of the cache key, so it must not move between
runs: a temporary name, a pid or a timestamp never hits. The directory is
placed from OUTSIDE the program when ``JAX_COMPILATION_CACHE_DIR`` is set
(JAX reads that variable itself — nothing is set in code, so nothing can
override it); otherwise it is ``<checkout>/.jax_cache`` (git-ignored).
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process and return its
    directory. Call before the first compilation."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
