"""Jit'd public wrapper for the flash-attention kernel.

Accepts model-layout tensors (B, S, H, D) / (B, T, K, D), handles the
(B, H, S, D) kernel layout, runs compiled on a TPU and in interpret mode on
the CPU (`repro.kernels.interpret_default`), and optional shard_map
distribution: batch over the data(/pod) axes and q-heads over the model
axis when divisible (KV heads are gathered per local q head inside each
shard, so the kernel always runs a per-device dense problem).

Block sizes left unspecified (None) are resolved from the kernel-tuner
cache (repro.autotune.kernel_tuner) keyed by the problem signature, falling
back to the 512x512 default — this is how woven programs and the serving
runtime pick DSE-tuned blocks automatically.  Backward blocks
(`block_q_bwd` / `block_kv_bwd`) resolve the same way and fall back to the
forward blocks when untuned.

The custom VJP runs the *fused Pallas backward* (kernel.flash_attention_bwd,
the §Perf follow-up recorded in PR 1 — done): the forward saves
(q, k, v, out, lse) as residuals and the backward streams the same pruned
block schedule in both directions, never recomputing through the dense
`attention_ref`.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_default
from repro.kernels.flash_attention.kernel import (
    flash_attention_bwd,
    flash_attention_fwd,
)

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
DEFAULT_BLOCK_KV_DEC = 512
DEFAULT_PAGE_SIZE = 128

# Quantized KV-cache dtypes: name -> largest representable magnitude.  The
# per-page-per-head scale is abs_max / qmax, so dequant is value * scale.
CACHE_QMAX: dict[str, float] = {"int8": 127.0, "float8_e4m3fn": 448.0,
                                "float8_e5m2": 57344.0}


def cache_qmax(dtype) -> float:
    """qmax for a quantized-cache dtype (accepts names and jnp dtypes)."""
    name = jnp.dtype(dtype).name if not isinstance(dtype, str) else dtype
    return CACHE_QMAX[name]


def resolve_cache_dtype(name):
    """Map a `cache_dtype` knob value to a jnp storage dtype, or None when
    the value names no quantized format (fp values mean: keep the fp pool)."""
    if name is None:
        return None
    name = str(name)
    if name not in CACHE_QMAX:
        return None
    return jnp.dtype(name)


def kv_scale_from_absmax(absmax, dtype):
    """Per-page scale from a page's abs-max: absmax / qmax, so the stored
    code range spans the full [-qmax, qmax] grid (an absmax scale would
    collapse int8 codes to {-1, 0, 1}).  Keeps the 0.0 free-page sentinel:
    zero absmax stays zero."""
    return absmax / cache_qmax(dtype)


def quantize_kv_write(x, scale, dtype):
    """Quantize K/V values at *fixed* per-page scales: x (..., K, D) against
    scale (..., K).  Values louder than the page's recorded abs-max clip —
    scales are never recomputed on already-written slots, which is what
    keeps speculative rollback and CoW sharing bit-deterministic."""
    qmax = cache_qmax(dtype)
    s = jnp.where(scale > 0, scale, 1.0)[..., None]
    y = jnp.clip(x.astype(jnp.float32) / s, -qmax, qmax)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        y = jnp.round(y)
    return y.astype(dtype)


def dequantize_kv(x, scale):
    """fp32 dequant of (..., K, D) quantized values at (..., K) scales."""
    return x.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)[..., None]


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11),
)
def _flash_core(q, k, v, causal, window, softcap, block_q, block_kv,
                block_q_bwd, block_kv_bwd, pruned, interpret):
    qt = jnp.swapaxes(q, 1, 2)  # (B,H,S,D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_fwd(
        qt, kt, vt,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, pruned=pruned, interpret=interpret,
    )
    return jnp.swapaxes(out, 1, 2)


def _flash_core_fwd(q, k, v, causal, window, softcap, block_q, block_kv,
                    block_q_bwd, block_kv_bwd, pruned, interpret):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t, lse = flash_attention_fwd(
        qt, kt, vt,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, pruned=pruned, interpret=interpret,
        return_lse=True,
    )
    out = jnp.swapaxes(out_t, 1, 2)
    # residuals for the fused backward: inputs + output + softmax stats,
    # all the two-pass recipe needs to recompute probability tiles exactly.
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, window, softcap, block_q, block_kv, block_q_bwd,
                    block_kv_bwd, pruned, interpret, res, g):
    """Fused Pallas backward: dq over pruned KV blocks, dk/dv over the
    transposed pruned Q blocks — no dense `attention_ref` recompute."""
    q, k, v, out, lse = res
    dq_t, dk_t, dv_t = flash_attention_bwd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        jnp.swapaxes(out, 1, 2), lse, jnp.swapaxes(g, 1, 2),
        causal=causal, window=window, softcap=softcap,
        block_q=block_q_bwd, block_kv=block_kv_bwd, pruned=pruned,
        interpret=interpret,
    )
    return (jnp.swapaxes(dq_t, 1, 2), jnp.swapaxes(dk_t, 1, 2),
            jnp.swapaxes(dv_t, 1, 2))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_kv",
        "block_q_bwd", "block_kv_bwd", "pruned", "interpret",
    ),
)
def _flash_local(q, k, v, *, causal, window, softcap, block_q, block_kv,
                 block_q_bwd, block_kv_bwd, pruned, interpret):
    return _flash_core(q, k, v, causal, window, softcap, block_q, block_kv,
                       block_q_bwd, block_kv_bwd, pruned, interpret)


def _resolve_blocks(q, k, *, causal, window, block_q, block_kv,
                    block_q_bwd=None, block_kv_bwd=None):
    """Fill unspecified block sizes from the tuner cache (never fails).

    Returns (block_q, block_kv, block_q_bwd, block_kv_bwd); untuned backward
    blocks fall back to the resolved forward blocks.
    """
    if None not in (block_q, block_kv, block_q_bwd, block_kv_bwd):
        return (int(block_q), int(block_kv),
                int(block_q_bwd), int(block_kv_bwd))
    from repro.autotune.kernel_tuner import tuned_flash_blocks

    tuned = tuned_flash_blocks(q.shape, k.shape[2], q.dtype, causal=causal,
                               window=window)
    bq = int(block_q if block_q is not None
             else tuned.get("block_q", DEFAULT_BLOCK_Q))
    bkv = int(block_kv if block_kv is not None
              else tuned.get("block_kv", DEFAULT_BLOCK_KV))
    bqb = int(block_q_bwd if block_q_bwd is not None
              else tuned.get("block_q_bwd", bq))
    bkvb = int(block_kv_bwd if block_kv_bwd is not None
               else tuned.get("block_kv_bwd", bkv))
    return bq, bkv, bqb, bkvb


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, K, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    block_q_bwd: int | None = None,
    block_kv_bwd: int | None = None,
    pruned: bool = True,
    interpret: bool | None = None,
    mesh: jax.sharding.Mesh | None = None,
    rules: Mapping[str, Any] | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = interpret_default()
    block_q, block_kv, block_q_bwd, block_kv_bwd = _resolve_blocks(
        q, k, causal=causal, window=window, block_q=block_q, block_kv=block_kv,
        block_q_bwd=block_q_bwd, block_kv_bwd=block_kv_bwd,
    )
    call = functools.partial(
        _flash_local,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv,
        block_q_bwd=block_q_bwd, block_kv_bwd=block_kv_bwd,
        pruned=pruned, interpret=interpret,
    )
    if mesh is None:
        return call(q, k, v)

    B, S, H, D = q.shape
    K = k.shape[2]
    rules = dict(rules or {})
    batch_axes = tuple(
        a for a in ("pod", "data")
        if a in mesh.shape and rules.get("batch") and a in _as_tuple(rules.get("batch"))
    )
    model_ok = "model" in mesh.shape and H % mesh.shape["model"] == 0
    head_spec = "model" if model_ok else None
    q_spec = P(batch_axes or None, None, head_spec, None)
    kv_spec = P(batch_axes or None, None, None, None)  # KV heads replicated over model

    group = H // K

    def body(q_l, k_l, v_l):
        if model_ok:
            h_loc = q_l.shape[2]
            off = jax.lax.axis_index("model") * h_loc
            idx = (off + jnp.arange(h_loc)) // group
            k_l = jnp.take(k_l, idx, axis=2)
            v_l = jnp.take(v_l, idx, axis=2)
        return call(q_l, k_l, v_l)

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return shard(q, k, v)


def _as_tuple(x):
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


def paged_gather_kv(pk, pv, tables, kv_len: int, k_scale=None, v_scale=None):
    """Materialize the logical (B, kv_len, K, D) K/V view of a page pool
    through per-request block tables — the XLA-reference twin of the
    indirection the paged `flash_decode` kernel performs in its BlockSpec
    index_map.  Shared (prefix-cached) pages gather exactly like exclusive
    ones: the table row is the only addressing, so refcounted pools need no
    kernel changes.  Used by `Attention._decode_paged`'s reference path and
    the paged-prefill path (suffix tokens attending over pool-resident
    prefixes).

    With `k_scale`/`v_scale` ((P, K) fp32 sidecars of a quantized pool) the
    gathered view is dequantized to fp32 — the reference twin of the
    kernel's in-loop dequant."""
    B, nb = tables.shape
    ps = pk.shape[-3]  # pool layout (P, page_size, K, D)
    k = pk[tables]  # (B, nb, page_size, K, D)
    v = pv[tables]
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[tables][:, :, None, :, None]
        v = v.astype(jnp.float32) * v_scale[tables][:, :, None, :, None]
    k = k.reshape(B, nb * ps, *pk.shape[-2:])[:, :kv_len]
    v = v.reshape(B, nb * ps, *pv.shape[-2:])[:, :kv_len]
    return k, v


# ---------------------------------------------------------------------------
# Decode (a small block of new tokens against a cache) — the serving hot path
# ---------------------------------------------------------------------------


def _fold_decode_q(q, K):
    """Model layout (B, S, H, D) -> widened kernel layout (B, K, S*G, D).

    Heads h = kh*G + g fold into the (K, G) grid/row split the kernel's
    per-KV-head instances expect; with S > 1 (speculative verify / q_offset
    suffix) the S tokens stack token-major so row r = token r // G."""
    B, S, H, D = q.shape
    G = H // K
    qt = q.reshape(B, S, K, G, D)
    qt = jnp.moveaxis(qt, 2, 1)  # (B, K, S, G, D)
    return qt.reshape(B, K, S * G, D)


def _unfold_decode_o(out, B, S, H, D, K):
    """Inverse of `_fold_decode_q`: (B, K, S*G, D) -> (B, S, H, D)."""
    G = H // K
    o = out.reshape(B, K, S, G, D)
    o = jnp.moveaxis(o, 1, 2)  # (B, S, K, G, D)
    return o.reshape(B, S, H, D)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "block_kv", "pruned", "interpret",
                     "scale_page"),
)
def _flash_decode_local(q, k, v, index, k_scale=None, v_scale=None, *,
                        window, softcap, block_kv, pruned, interpret,
                        scale_page=None):
    from repro.kernels.flash_attention.decode import flash_decode_fwd

    B, S, H, D = q.shape
    K = k.shape[2]
    # dense scales arrive model-layout (B, NP, K); kernel wants (B, K, NP)
    ks = jnp.swapaxes(k_scale, 1, 2) if k_scale is not None else None
    vs = jnp.swapaxes(v_scale, 1, 2) if v_scale is not None else None
    out = flash_decode_fwd(
        _fold_decode_q(q, K), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        index,
        window=window, softcap=softcap, block_kv=block_kv,
        pruned=pruned, interpret=interpret, q_span=S,
        k_scale=ks, v_scale=vs, scale_page=scale_page,
    )
    return _unfold_decode_o(out, B, S, H, D, K)


@functools.partial(
    jax.jit,
    static_argnames=("kv_len", "window", "softcap", "block_kv", "pruned",
                     "interpret"),
)
def _flash_decode_paged_local(q, k, v, index, tables, k_scale=None,
                              v_scale=None, *, kv_len, window,
                              softcap, block_kv, pruned, interpret):
    from repro.kernels.flash_attention.decode import flash_decode_fwd

    B, S, H, D = q.shape
    K = k.shape[2]  # pool layout (P, page_size, K, D)
    out = flash_decode_fwd(
        _fold_decode_q(q, K), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        index, tables=tables, kv_len=kv_len,
        window=window, softcap=softcap, block_kv=block_kv,
        pruned=pruned, interpret=interpret, q_span=S,
        k_scale=k_scale, v_scale=v_scale,
    )
    return _unfold_decode_o(out, B, S, H, D, K)


def flash_decode(
    q: jax.Array,        # (B, S, H, D) — the S >= 1 new tokens, post-RoPE
    k_cache: jax.Array,  # (B, T, K, D) cache *with the new tokens written*,
                         # or the (P, page_size, K, D) page pool when paged
    v_cache: jax.Array,
    index: jax.Array,    # () or (B,) int32: the *first* new token's position
    *,
    window: int | None = None,  # linear caches only; ring caches pass None
    softcap: float | None = None,
    block_kv: int | None = None,
    pruned: bool = True,
    interpret: bool | None = None,
    tables: jax.Array | None = None,  # (B, num_blocks) int32 block tables
    kv_len: int | None = None,        # logical cache length (paged only)
    k_scale: jax.Array | None = None,  # quantized caches: fp32 scales —
    v_scale: jax.Array | None = None,  # paged (P, K); dense (B, NP, K)
    scale_page: int | None = None,     # dense only: cache slots per scale row
) -> jax.Array:
    """One decode step over a live-block-pruned cache; see decode.py.

    `block_kv=None` resolves from the kernel-tuner cache (the
    `block_kv_dec` knob under the `vmem_bytes_dec` constraint), falling
    back to the 512 default — the same auto-tuning path as the prefill
    kernel's blocks.  Passing `tables` selects the paged layout: K/V are
    one shared page pool and every request's cache blocks resolve through
    its block-table row (tuned via the `paged_decode` signature, which
    also carries the `page_size` knob the pool was built with).  Prefix
    sharing is pure table plumbing: rows of several requests may name the
    same physical page and the kernel streams it for each — the body never
    changes, so shared-pool output stays bit-identical to unshared.

    With S > 1 q tokens (the widened-q / q_offset variant) token s attends
    through cache slot index + s: one kernel launch verifies a whole draft
    block, or prefills a suffix over a pool-resident shared prefix.  Each q
    row runs the same online softmax over the same block walk as a
    single-token call, so S=1 and sequential decode stay bit-identical.
    """
    if interpret is None:
        interpret = interpret_default()
    index = jnp.asarray(index, jnp.int32)
    if tables is not None:
        if kv_len is None:
            raise ValueError("paged flash_decode requires kv_len")
        if block_kv is None:
            from repro.autotune.kernel_tuner import tuned_paged_blocks

            tuned = tuned_paged_blocks(
                q.shape, int(kv_len), k_cache.shape[2], q.dtype,
                window=window,
            )
            block_kv = int(tuned.get("block_kv_dec", DEFAULT_BLOCK_KV_DEC))
        return _flash_decode_paged_local(
            q, k_cache, v_cache, index, jnp.asarray(tables, jnp.int32),
            k_scale, v_scale,
            kv_len=int(kv_len), window=window, softcap=softcap,
            block_kv=int(block_kv), pruned=pruned, interpret=interpret,
        )
    if block_kv is None:
        from repro.autotune.kernel_tuner import tuned_decode_blocks

        tuned = tuned_decode_blocks(
            q.shape, k_cache.shape[1], k_cache.shape[2], q.dtype,
            window=window,
        )
        block_kv = int(tuned.get("block_kv_dec", DEFAULT_BLOCK_KV_DEC))
    return _flash_decode_local(
        q, k_cache, v_cache, index, k_scale, v_scale,
        window=window, softcap=softcap, block_kv=int(block_kv),
        pruned=pruned, interpret=interpret,
        scale_page=None if scale_page is None else int(scale_page),
    )
