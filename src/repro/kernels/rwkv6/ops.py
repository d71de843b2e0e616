"""Jit'd wrapper for the RWKV6 WKV Pallas kernel (model layout adapters)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.rwkv6.kernel import wkv_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv_jit(r, k, v, w, u, s0, *, chunk, interpret):
    # model layout (B,S,H,C) -> kernel layout (B,H,S,C)
    tr = lambda x: jnp.swapaxes(x, 1, 2)
    y, s_last = wkv_fwd(
        tr(r), tr(k), tr(v), tr(w), u, s0, chunk=chunk, interpret=interpret
    )
    return jnp.swapaxes(y, 1, 2), s_last


def wkv_pallas(r, k, v, w, u, s0, *, chunk: int = 32, interpret: bool | None = None):
    """r,k,v,w: (B,S,H,C); u: (H,C); s0: (B,H,C,C). Returns (y, s_last)."""
    if interpret is None:
        interpret = interpret_default()
    S = r.shape[1]
    chunk = min(chunk, S)
    if S % chunk:  # pad to a chunk multiple; padded steps have w=1, k=0
        pad = chunk - S % chunk
        zero = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r2, k2, v2 = zero(r), zero(k), zero(v)
        w2 = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
        y, s_last = _wkv_jit(r2, k2, v2, w2, u, s0, chunk=chunk, interpret=interpret)
        return y[:, :S], s_last
    return _wkv_jit(r, k, v, w, u, s0, chunk=chunk, interpret=interpret)
