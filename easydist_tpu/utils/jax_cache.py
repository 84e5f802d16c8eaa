"""Where JAX's persistent compilation cache lives: one rule for every entry
point (chip_smoke.py, bench.py, tests/conftest.py)."""

import os

from easydist_tpu import config as edconfig


def configure_jax_cache(min_compile_secs: float = 1.0) -> str:
    """Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
    directory is set here; otherwise the cache sits at
    `<checkout>/.jax_cache`.  Either way the path is fixed — it is part of
    the cache key, so a directory that moves never hits.  Executables that
    compiled faster than `min_compile_secs` are not written.  Returns the
    directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(edconfig.checkout_dir, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return path
