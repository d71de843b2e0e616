"""Jit'd wrapper for fused RMSNorm."""

from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm_jit(x, w, *, eps, block_rows, interpret):
    return rmsnorm_fwd(x, w, eps=eps, block_rows=block_rows, interpret=interpret)


def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_default()
    return _rmsnorm_jit(x, w, eps=eps, block_rows=block_rows, interpret=interpret)
