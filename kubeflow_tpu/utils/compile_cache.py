"""Where JAX's persistent compilation cache lives — the one rule.

Placed from outside: when ``JAX_COMPILATION_CACHE_DIR`` is in the
environment JAX read it at import and this module sets no directory.
Otherwise the cache is ``<checkout>/.jax_cache``, derived from this
package's own location: the path is part of every cache key, so a
directory that moves (a temp name, a pid, a uid, a clock) never hits.
Training workers (``bootstrap.initialize``), predictors (``load()``) and
the AOT proofs call ``ensure()`` before their first jit.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def ensure() -> tuple[str, bool]:
    """Returns ``(directory, placed_by_environment)``. Idempotent; a
    directory already configured (the environment's, or a zygote child's
    re-applied pod value) is left alone."""
    import jax

    # a warm start should compile nothing, however small the program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    current = jax.config.jax_compilation_cache_dir
    if current:
        return current, bool(os.environ.get(ENV))
    path = default_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path, False
