"""JAX's persistent compilation cache for the program's entry points.

Entry points (`repro.launch.serve`, `benchmarks/run.py`, `chip_smoke.py`)
call `enable_compile_cache()` from `main`, never at import. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache there and
nothing is set here. Otherwise the cache goes to `.jax_cache/` at the root
of the checkout: a fixed path, listed in `.gitignore`. A shared cache
directory does not make a checkout at another path hit for the megakernel
program (seen on a v5e): expect to compile it once per checkout path.
"""
from __future__ import annotations

import os

#: <checkout>/.jax_cache — this file lives at src/repro/launch/.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
