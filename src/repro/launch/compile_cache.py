"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``, ``benchmarks/bench_des.py``) and the test suite call
:func:`use_compile_cache` once, before JAX compiles anything.  A
``JAX_COMPILATION_CACHE_DIR`` set from outside wins, and JAX reads it
itself; otherwise the cache sits at a fixed ``.jax_cache/`` in the root of
the checkout.  The path is part of what a later run must find again, so it
is never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

#: The checkout root: ``src/repro/launch/`` sits three levels below it.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
