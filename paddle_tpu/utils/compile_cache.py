"""Where JAX's persistent compilation cache lives — one rule for the
entry scripts (`chip_smoke.py`, `bench.py`, `scripts/serving_bench.py`,
`scripts/serving_http_server.py`) and `tests/conftest.py`.

If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no code
names another directory. Otherwise the cache sits at `<checkout>/.jax_cache`:
the directory is part of the cache key, so it is a fixed path and never a
temporary directory, a pid or a timestamp.
"""
import os

import jax

__all__ = ["use_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache():
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
