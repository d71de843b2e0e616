"""Pallas TPU flash *decode* attention: one new q token against a KV cache.

The serving hot path.  Prefill/training attention is O(S·W) since the grid
pruning landed, but decode used to run XLA attention over the *entire*
padded cache every token — O(max_len) HBM traffic per step.  This kernel
streams only the *live* cache blocks:

  - grid (batch, kv_heads, kv_steps) with kv_steps = ceil(T / block_kv)
    *static*; the per-request `index` (number of tokens already cached,
    i.e. the new token's absolute position) rides in as a scalar-prefetch
    operand, so the K/V BlockSpec index_map can clamp the streamed block to
    the live interval [lo(index), hi(index)) — steps past the interval
    repeat the previous block index and Pallas elides the DMA, exactly the
    clamp-and-elide walk of the prefill kernel's pruned path.

  - ring caches (slot = pos % W, cache length T == window W): slots
    0..min(index, W-1) are filled and — once the cache has wrapped — every
    slot holds a position inside the window, so liveness is just
    `slot < min(T, index+1)`; the kernel reads exactly
    ceil(min(W, index+1) / block_kv) blocks using the ring `pos`/`index`
    layout, with no gather or rotation of the cache in HBM.

  - linear caches (slot s = absolute position s, T == max_len): blocks
    beyond `index` are pruned the same way, and a sliding window (the
    window >= prefill-length case where `_build_cache` stays linear) also
    prunes blocks *below* the window through the same interval machinery.

  - GQA folds the q-head group into the q block: one kernel instance per KV
    head with a (group, D) q tile, so K/V are never replicated in HBM and
    the single-token MXU op is a (group x block_kv) matmul.  Softcap and
    fp32 online-softmax accumulation match `xla_attention`.

`decode_schedule` mirrors the index remapping in pure numpy so tests and
benches can assert exactly which blocks one decode step streams;
`vmem_bytes_dec` is the analytic VMEM working set used as the autotuner's
capacity constraint for the `block_kv_dec` knob (see
repro.autotune.kernel_tuner).

Paged caches (the vLLM block-table layout): passing `tables` switches the
K/V operands from per-request dense caches (B, K, T, D) to one shared pool
of fixed-size pages (P, K, page_size, D) plus a per-request block table
(B, num_blocks) mapping logical cache block -> physical page.  The kernel
body is *unchanged* — all mask/softmax math stays in logical slot space —
and the indirection lives entirely in the K/V BlockSpec index_map, which
resolves the clamped logical block through the scalar-prefetched table:

    jb   = min(lo + j, hi - 1)                # same clamp-and-elide walk
    page = tables[b, jb // (page_size // block_kv)]
    sub  = jb % (page_size // block_kv)       # sub-block within the page

so the O(min(W, index+1)) live-block bound per token carries over verbatim,
and requests of wildly different lengths share one HBM pool instead of each
padding to max_len.  `block_kv` is clamped to a divisor of `page_size`
(`page_block_kv`) so a streamed block never straddles a page boundary.

Widened q (`q_span` > 1, the `q_offset` variant): the q tile grows from one
token's folded group (G, D) to a draft block's (q_span * G, D) — row
r = s*G + g is draft token s, head-group lane g, and the causal boundary
becomes *per-row*: token s attends through cache position index + s, where
`index` is the position of the *first* new token.  Everything else —
clamp-and-elide walk (hi now covers index + q_span - 1), online softmax
(rows are independent), the paged table indirection — is unchanged, so one
verify step over a k-token draft streams the cache once instead of k times.
The same shape with index = prefix_len and q_span = suffix length is
suffix-over-prefix chunked prefill, which is how the paged prefix-sharing
path runs through Pallas.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import NEG_INF, cdiv


# ---------------------------------------------------------------------------
# Live-block interval + numpy oracle
# ---------------------------------------------------------------------------


def _dec_hi(index, block_kv: int, T: int):
    """One past the last live KV block: the block holding min(T, index+1)-1.

    Works on python ints and traced scalars (index_map arithmetic).
    """
    live = index + 1
    if isinstance(live, int):
        return cdiv(max(1, min(T, live)), block_kv)
    live = jnp.clip(live, 1, T)
    return (live + block_kv - 1) // block_kv


def _dec_lo(index, block_kv: int, window: int | None, hi):
    """First live KV block (linear caches only: positions below the sliding
    window are dead).  Ring caches pass window=None — the ring layout holds
    only in-window positions by construction."""
    if window is None:
        return hi * 0  # 0, but keeps tracer dtype when hi is traced
    lo = (index + 1 - window) // block_kv
    if isinstance(lo, int):
        return min(max(0, lo), hi - 1)
    return jnp.clip(lo, 0, hi - 1)


def decode_steps_for(T: int, block_kv: int, window: int | None = None,
                     q_span: int = 1) -> int:
    """Max live KV blocks one decode step can stream, over all indices.

    Without a window that is the full cache; with one, the in-window slots
    of `q_span` stacked tokens span window + q_span - 1 positions, i.e. at
    most ceil((W + q_span - 2)/block_kv) + 1 blocks (worst case: the span
    straddles block edges on both sides)."""
    nk = cdiv(T, block_kv)
    if window is None:
        return nk
    span = window + q_span - 1
    return max(1, min(nk, cdiv(max(span - 1, 1), block_kv) + 1))


def decode_schedule(
    T: int, index: int, block_kv: int, *,
    window: int | None = None, pruned: bool = True, q_span: int = 1,
) -> list[int]:
    """KV blocks one decode step actually *streams* from a length-T cache.

    Mirrors the kernel's clamp-and-elide index remapping: the pruned path
    walks [lo, hi) and overshoot steps repeat the last block (no DMA).  For
    ring caches (T == window, window=None here) this is exactly
    range(ceil(min(T, index+1) / block_kv)); the dense path streams every
    block.  With `q_span` > 1 the interval widens to cover the *last*
    stacked token (position index + q_span - 1) while lo stays anchored on
    the first — one widened step streams the union of the per-token
    intervals.
    """
    nk = cdiv(T, block_kv)
    if not pruned:
        return list(range(nk))
    hi = _dec_hi(int(index) + q_span - 1, block_kv, T)
    lo = _dec_lo(int(index), block_kv, window, hi)
    return list(range(int(lo), int(hi)))


def page_block_kv(block_kv: int, page_size: int) -> int:
    """Clamp a streamed-block size so it tiles the page exactly.

    A K/V DMA must never straddle a page boundary (adjacent logical pages
    are not adjacent in the pool), so the effective block is the largest
    common divisor — for the power-of-two knob spaces this is simply
    min(block_kv, page_size)."""
    return max(1, math.gcd(int(block_kv), int(page_size)))


def paged_decode_schedule(
    kv_len: int, index: int, block_kv: int, page_size: int, table,
    *, window: int | None = None, pruned: bool = True, q_span: int = 1,
) -> list[tuple[int, int]]:
    """Physical (page, sub_block) pairs one decode step streams from the
    pool — `decode_schedule` mapped through the request's block table.

    `table` is the request's row: table[i] = physical page of logical page
    i.  Tests and benches use this to assert that exactly the pages backing
    the live logical blocks are touched, in logical order."""
    bkv = page_block_kv(block_kv, page_size)
    spb = page_size // bkv
    logical = decode_schedule(kv_len, index, bkv, window=window, pruned=pruned,
                              q_span=q_span)
    return [(int(table[jb // spb]), jb % spb) for jb in logical]


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------


def _flash_decode_kernel(
    idx_ref,  # scalar prefetch: (B,) int32, per-request index (first token)
    *refs,    # [tbl_ref,] [ks_ref, vs_ref,] q, k, v, o refs, m/l/acc scratch
    block_kv: int,
    kv_len: int,   # true cache length T (padding slots >= T are masked)
    window: int | None,
    softcap: float | None,
    scale: float,
    pruned: bool,
    group: int = 1,   # q rows per token (GQA fold); row // group = token off
    q_span: int = 1,  # stacked q tokens; token s sits at position index + s
    paged: bool = False,
    quantized: bool = False,
    kv_heads: int = 1,
    blocks_per_page: int = 1,  # paged: streamed blocks per page (spb)
    scale_page: int = 1,       # dense quantized: cache slots per scale row
    scale_rows: int = 1,       # dense quantized: scale rows per (b, h)
):
    """Scalar-prefetch operands, in order: the per-request index, then the
    (B, num_blocks) block table when `paged`, then the flattened fp32 k/v
    dequant scales when `quantized` — (P*K,) per page and KV head for a
    pool, (B*K*NP,) per scale row for a dense cache.  Scales live in SMEM
    beside the table because one scalar covers a whole streamed block, and
    a per-block (1, 1) VMEM tile is below the TPU's (8, 128) tiling."""
    n_pre = int(paged) + 2 * int(quantized)
    pre = refs[:n_pre]
    q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch = \
        refs[n_pre:]
    tbl_ref = pre[0] if paged else None
    ks_ref, vs_ref = pre[-2:] if quantized else (None, None)
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    index = idx_ref[b]
    hi = _dec_hi(index + q_span - 1, block_kv, kv_len)
    lo = _dec_lo(index, block_kv, window, hi)
    if pruned:
        # the index_map streamed block min(lo+j, hi-1); overshoot steps
        # repeat the last block (no DMA) and skip all compute
        ik = jnp.minimum(lo + j, hi - 1)
        live_step = j < hi - lo
    else:
        # dense baseline: block j streamed; dead blocks still skip the MXU
        ik = j
        live_step = jnp.logical_and(j >= lo, j < hi)
    k_start = ik * block_kv

    @pl.when(live_step)
    def _compute():
        g = q_ref[0, 0].astype(jnp.float32)   # (Gp, D)
        k = k_ref[0, 0].astype(jnp.float32)   # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # dequantize in-loop at the block's page scale; the block never
            # straddles a page (block_kv | page boundary), so one scalar
            # covers the whole tile
            if paged:
                row = tbl_ref[b, ik // blocks_per_page] * kv_heads + h
            else:
                row = ((b * kv_heads + h) * scale_rows
                       + (ik * block_kv) // scale_page)
            k = k * ks_ref[row]
            v = v * vs_ref[row]
        s = jax.lax.dot_general(
            g, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (Gp, bkv)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # per-row causal boundary: q row r is draft token r // group, which
        # sits at position index + r // group and attends slots <= it
        off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        live = jnp.clip(index + off + 1, 1, kv_len)
        mask = kp < live  # ring: filled slots; linear: causal slots <= pos
        if window is not None:  # linear cache under a sliding window
            mask = jnp.logical_and(mask, kp > index + off - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]
        l_prev = l_scratch[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        m_scratch[...] = m_new
        l_scratch[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nj - 1)
    def _fin():
        out = acc_scratch[...] / jnp.maximum(l_scratch[...], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Entry point (kernel layout)
# ---------------------------------------------------------------------------


def flash_decode_fwd(
    q: jax.Array,      # (B, K, q_span * G, D) — row r = token r//G, lane r%G
    k: jax.Array,      # (B, K, T, D) cache — or (P, K, page_size, D) pool
    v: jax.Array,
    index: jax.Array,  # (B,) int32: *first* new token's position
    *,
    window: int | None = None,  # linear caches only; ring passes None
    softcap: float | None = None,
    block_kv: int = 512,
    pruned: bool = True,
    interpret: bool = False,
    tables: jax.Array | None = None,  # (B, num_blocks) int32 page table
    kv_len: int | None = None,        # logical cache length (paged only)
    q_span: int = 1,   # stacked q tokens (draft block / q_offset suffix)
    k_scale: jax.Array | None = None,  # fp32 per-page-per-head dequant scales:
    v_scale: jax.Array | None = None,  # paged (P, K); dense (B, K, NP)
    scale_page: int | None = None,     # dense only: cache slots per scale row
) -> jax.Array:
    """One decode step.  Streams ceil((hi-lo)) live KV blocks per (b, kv
    head); with `pruned=False` every block streams (the dense baseline).

    With `tables`, K/V are one shared page pool (P, K, page_size, D) and
    each request's logical blocks resolve through its block-table row; the
    logical cache length must then come in as `kv_len` (the pool carries no
    per-request extent).

    With `q_span` > 1 the q operand stacks q_span tokens' folded groups
    (rows ordered token-major: row r = token r // G), `index` is the first
    token's position, and token s attends through slot index + s — the
    widened-q / q_offset variant used by speculative verify and by
    suffix-over-prefix paged prefill.

    With `k_scale`/`v_scale`, K/V hold quantized values (int8/fp8) and the
    kernel dequantizes each streamed block at its page's fp32 scale —
    scales ride flattened in SMEM as scalar-prefetch operands, looked up
    per streamed block through the same table indirection as K/V, and the
    fp32 online-softmax accumulation is untouched.  SMEM holds 1 MiB, so
    a pool may carry about 120k (page, KV head) scale pairs.  Paged
    sidecars are (P, K); for dense caches pass (B, NP, K)-shaped scales
    pre-swapped to kernel layout (B, K, NP) with `scale_page` slots per
    scale row (block_kv is clamped to divide it)."""
    B, K, R, D = q.shape
    if R % q_span:
        raise ValueError(f"q rows {R} not divisible by q_span={q_span}")
    G = R // q_span
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("quantized flash_decode requires both k/v scales")
    paged = tables is not None
    if paged:
        if kv_len is None:
            raise ValueError("paged flash_decode requires kv_len")
        T = int(kv_len)
        page_size = k.shape[2]
        # No clamp to T here: pool pages are always full page_size slots
        # (the kp < live mask covers short caches), and min()-ing first
        # would collapse the gcd to slivers for non-power-of-two kv_len.
        block_kv = page_block_kv(block_kv, page_size)
        spb = page_size // block_kv
        tables = jnp.asarray(tables, jnp.int32)
        if tables.shape[0] != B or tables.shape[1] * page_size < T:
            raise ValueError(
                f"block table {tables.shape} cannot cover kv_len={T} at "
                f"page_size={page_size} for batch {B}")
    else:
        T = k.shape[2]
        block_kv = min(block_kv, max(T, 1))
        if quantized:
            if scale_page is None:
                raise ValueError("dense quantized flash_decode requires "
                                 "scale_page (cache slots per scale row)")
            # a streamed block must sit under a single scale row
            block_kv = page_block_kv(block_kv, scale_page)

    # TPU sublane tiling wants >= 8 q rows; pad the folded rows (the padded
    # rows compute garbage that is sliced off — rows are softmax-independent).
    Rp = max(8, R)
    if Rp != R:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))

    if not paged:
        # Ragged cache length: zero-pad KV to a block multiple; `kp < live`
        # masks the padded slots (live <= T always).  Pools need no padding:
        # block_kv divides page_size by construction.
        pad = (-T) % block_kv
        if pad:
            widths = ((0, 0), (0, 0), (0, pad), (0, 0))
            k, v = jnp.pad(k, widths), jnp.pad(v, widths)
    nk = cdiv(T, block_kv)

    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))

    # Static grid pruning on top of the dynamic clamp: no index can reach
    # more than decode_steps_for blocks (ceil((W-1)/bkv)+1 under a window),
    # so the grid itself shrinks — the same trick as the prefill kernel's
    # kv_steps_for.  The per-index interval [lo, hi) then elides within it.
    steps = decode_steps_for(T, block_kv, window, q_span) if pruned else nk

    def logical_block(b, j, idx_ref):
        if pruned:
            hi = _dec_hi(idx_ref[b] + q_span - 1, block_kv, T)
            lo = _dec_lo(idx_ref[b], block_kv, window, hi)
            return jnp.minimum(lo + j, hi - 1)
        return j

    # scalar-prefetch operands: index, [table,] [k/v scales]; every
    # index_map receives all of them after the grid indices
    prefetch = (index,)
    if paged:
        prefetch += (tables,)
    if quantized:
        prefetch += (jnp.asarray(k_scale, jnp.float32).reshape(-1),
                     jnp.asarray(v_scale, jnp.float32).reshape(-1))
    scale_rows = 1 if paged or not quantized else k_scale.shape[-1]

    if paged:
        def kv_index(b, h, j, idx_ref, tbl_ref, *_):
            jb = logical_block(b, j, idx_ref)
            return (tbl_ref[b, jb // spb], h, jb % spb, 0)
    else:
        def kv_index(b, h, j, idx_ref, *_):
            return (b, h, logical_block(b, j, idx_ref), 0)

    def qo_index(b, h, j, *_):
        return (b, h, 0, 0)

    kernel = functools.partial(
        _flash_decode_kernel,
        block_kv=block_kv, kv_len=T, window=window,
        softcap=softcap, scale=1.0 / np.sqrt(D), pruned=pruned,
        group=G, q_span=q_span, paged=paged, quantized=quantized,
        kv_heads=K, blocks_per_page=spb if paged else 1,
        scale_page=int(scale_page or 1), scale_rows=int(scale_rows),
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, K, steps),
        in_specs=[
            pl.BlockSpec((1, 1, Rp, D), qo_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, Rp, D), qo_index),
        scratch_shapes=[
            pltpu.VMEM((Rp, 1), jnp.float32),
            pltpu.VMEM((Rp, 1), jnp.float32),
            pltpu.VMEM((Rp, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Rp, D), q.dtype),
        interpret=interpret,
    )(*prefetch, q, k, v)
    return out[:, :, :R, :]


def vmem_bytes_dec(
    group: int,
    block_kv: int,
    head_dim: int,
    dtype_bytes: int = 2,
    *,
    kv_dtype_bytes: int | None = None,
    q_span: int = 1,
) -> int:
    """Analytic VMEM working set of one decode step — the autotuner's
    capacity constraint for the `block_kv_dec` knob.

    The q/o tiles are (max(8, q_span·group) x D) at the Q dtype, K and V
    blocks at the KV dtype, double-buffered as Pallas pipelines them, plus
    the fp32 scratch (acc + m + l) and the fp32 (rows x block_kv) score
    tile.  The per-request index scalars are noise (4·B bytes in SMEM).
    """
    if kv_dtype_bytes is None:
        kv_dtype_bytes = dtype_bytes
    g = max(8, group * q_span)
    qo = 2 * g * head_dim * dtype_bytes                # q in + o out
    kv = 2 * block_kv * head_dim * kv_dtype_bytes      # k + v
    scratch = (g * (head_dim + 2)) * 4                 # fp32 acc + m + l
    scores = g * block_kv * 4                          # fp32 s/p tile
    return 2 * (qo + kv) + scratch + scores
