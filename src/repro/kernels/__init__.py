"""Pallas TPU kernels for the hot spots the serving and training paths run.

Every kernel wrapper takes `interpret=None` and resolves it here: compiled
on a TPU, interpret mode on the CPU (the test suite), and refused on any
other backend, so a kernel never runs interpreted where a chip was meant.
"""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"JAX's default backend is {backend!r}")
