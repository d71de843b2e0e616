"""Jit'd wrapper for the RG-LRU Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.rglru.kernel import rglru_fwd


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def _rglru_jit(a, b, h0, *, block_d, chunk, interpret):
    return rglru_fwd(
        a.astype(jnp.float32), b.astype(jnp.float32), h0.astype(jnp.float32),
        block_d=block_d, chunk=chunk, interpret=interpret,
    )


def rglru_pallas(a, b, h0, *, block_d: int = 512, chunk: int = 256,
                 interpret: bool | None = None):
    """a,b: (B,S,D); h0: (B,D). Returns (h_seq (B,S,D) fp32, h_last (B,D))."""
    if interpret is None:
        interpret = interpret_default()
    B, S, D = a.shape
    block_d = min(block_d, D)
    while D % block_d:
        block_d //= 2
    chunk = min(chunk, S)
    if S % chunk:  # pad time with identity steps (a=1 keeps state, b=0)
        pad = chunk - S % chunk
        a2 = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b2 = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        y, h_last = _rglru_jit(a2, b2, h0, block_d=block_d, chunk=chunk,
                               interpret=interpret)
        return y[:, :S], h_last
    return _rglru_jit(a, b, h0, block_d=block_d, chunk=chunk, interpret=interpret)
