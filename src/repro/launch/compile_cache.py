"""Where JAX keeps its persistent compilation cache.

One helper for every entry point that compiles for the chip
(``chip_smoke.py``, ``repro.launch.serve``, the benchmark harness): a
cache placed from outside wins, otherwise the cache lives at a fixed
path inside the checkout.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its dir.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable
    itself and nothing else is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
