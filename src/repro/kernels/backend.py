"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

This is the one place that decides interpret mode. Nothing else in the
program sets it: the kernel wrappers take `interpret=None` and resolve it
here, from the backend JAX runs on.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a `pallas_call`.

    `None` decides from `jax.default_backend()`: the CPU interprets (the
    test suite runs there), a TPU compiles, and any other backend raises.
    An explicit bool is honoured; tests pass `False` to lower a kernel for
    a described TPU from a CPU-only process."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU or are interpreted on the "
        f"CPU; backend {backend!r} is neither")
