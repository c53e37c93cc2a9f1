"""Where compiled programs persist between processes.

Every process on the chip would otherwise compile the flagship step from
cold. The cache directory is placed from OUTSIDE when the caller's
environment sets ``JAX_COMPILATION_CACHE_DIR`` (jax reads the variable
itself — nothing is set in code), and otherwise at ``.jax_cache/`` in the
checkout: a fixed path derived from this file's location, because the
path is part of the cache key — a directory that moves (``tempfile``, a
pid, the time) never hits.

Called from entry points only (``chip_smoke.py``, ``bench.py``'s
``__main__``) before their first compilation — never at package import.
"""

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(_ENV):
        return os.environ[_ENV]
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
