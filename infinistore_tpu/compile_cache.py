"""Where this checkout keeps JAX's persistent compilation cache.

Every entry point that compiles for the chip calls :func:`enable` before its
first jitted call. The cache directory is part of each entry's key, so it
must not move between runs: it is whatever ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself — nothing is set here), and otherwise
``<checkout>/.jax_cache``. Never a temp name, a pid or a time.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
