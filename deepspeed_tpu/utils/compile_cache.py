"""Where JAX's persistent compilation cache lives.

The cache directory is part of the cache key, so it must not move between
runs: a temporary name, a pid or a timestamp never hits. The directory is
placed from OUTSIDE the program when ``JAX_COMPILATION_CACHE_DIR`` is set
(JAX reads that variable itself — nothing is set in code, so nothing can
override it); otherwise it is ``<checkout>/.jax_cache`` (git-ignored).

An executable's metadata is part of the key here. By default JAX leaves it
out, and two programs of the same operations under other names (a
``jax.named_scope`` added, renamed or moved: ``monitor/scopes.py``) share an
entry: the second is handed the first's executable, whose instructions carry
the FIRST's ``op_name``s, and a device trace read back by those names
(``benchmark/lib/op_scopes.py``, a capture from ``POST /v1/profile``) puts
every operation under the parts of a program that was never run.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process and return its
    directory. Call before the first compilation."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
