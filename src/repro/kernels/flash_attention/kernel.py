"""Pallas TPU flash attention (forward + fused backward): online-softmax over
KV blocks with block-sparse grid pruning in both directions.

TPU mapping (DESIGN.md: adapt, don't port): the grid is
(batch, q_heads, num_q_blocks, kv_steps) with the KV dimension *innermost* —
TPU grid steps on one core execute sequentially, so the fp32 running max /
denominator / accumulator live in VMEM scratch and persist across KV-block
iterations (the TPU analogue of a CUDA thread-block's shared-memory state).
Block shapes are BlockSpec-tiled so each step's working set is
(block_q x D) + 2 x (block_kv x D) + (block_q x block_kv) fp32 in VMEM, with
block sizes kept at MXU-friendly multiples of 128.

Grid pruning (the §Perf follow-up, now implemented): for causal and
sliding-window masks most KV blocks are fully masked for a given q block, so
the pruned path iterates only the reachable KV-block interval [lo(iq), hi(iq))
per q block via an index-remapped KV dimension.  `kv_steps` is the *maximum*
interval length over q blocks; q blocks with fewer reachable KV blocks clamp
the remapped index to their last reachable block, and Pallas elides the DMA
when the block index repeats, so fully-masked blocks are never streamed from
HBM.  For window-W attention the whole grid shrinks to O(S·W/block_kv)
instead of O(S²/block²) — overshoot steps do no DMA and no MXU work
(`pl.when`).  The dense grid remains for non-causal attention and as an
explicit `pruned=False` baseline for benchmarks.

GQA is handled in the K/V index_map (kv_head = q_head // group), so no KV
replication is ever materialized in HBM.  Ragged shapes (`block ∤ S`) are
handled by zero-padding Q/KV up to block multiples in the wrapper; the
in-kernel `kp < kv_len` mask keeps padded KV out of the softmax and the
padded output rows are sliced off.

Backward (the §Perf follow-up recorded in PR 1, now implemented): the fused
two-pass flash recipe.  The forward saves the per-row softmax statistics
`lse = m + log(l)`; the wrapper precomputes `delta = rowsum(dO·O)`; then

  - the dq pass walks the *same* pruned KV interval [lo(iq), hi(iq)) per q
    block as the forward, recomputing the probability tile from (q, k, lse)
    and accumulating dq in fp32 VMEM scratch, and
  - the dk/dv pass transposes the schedule: per KV block it walks the
    reachable *Q*-block interval [q_lo(ik), q_hi(ik)) — the exact mirror of
    the forward remapping — accumulating dk/dv in fp32 scratch.  The grid
    runs over the K *true* KV heads with the GQA group folded into the
    innermost loop (j = g·q_steps + jq), so the accumulators sum the whole
    group before the single (B, K, T, D) HBM write — O(S·K·D) transient
    traffic, not the per-q-head O(S·H·D) a wrapper-side group-sum would pay.

So backward HBM traffic is O(S·W) for window-W attention, matching the
forward, instead of the O(S²) dense reference VJP.

`kv_schedule` / `q_schedule` mirror both index remappings in pure numpy so
tests and benches can assert exactly which blocks a configuration streams in
each direction.  `vmem_bytes` / `vmem_bytes_bwd` are the analytic VMEM
working-set models used as the autotuner's capacity constraints (see
repro.autotune.kernel_tuner).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Reachable KV-block interval per q block
# ---------------------------------------------------------------------------


def _kv_lo(iq, block_q: int, block_kv: int, window: int | None):
    """First reachable KV block for q block `iq` (lowest kp = q_start-window+1).

    Works on python ints and traced scalars (index_map arithmetic).
    """
    if window is None:
        return iq * 0  # 0, but keeps tracer dtype when iq is traced
    lo = (iq * block_q - (window - 1)) // block_kv
    if isinstance(lo, int):
        return max(0, lo)
    return jnp.maximum(lo, 0)


def _kv_hi(iq, block_q: int, block_kv: int, nk: int):
    """One past the last reachable KV block (highest kp = q_start+block_q-1)."""
    hi = (iq * block_q + block_q - 1) // block_kv + 1
    if isinstance(hi, int):
        return min(nk, hi)
    return jnp.minimum(hi, nk)


def _interval_steps(n_outer: int, lo_fn, hi_fn) -> int:
    """Max interval length over outer blocks — the static innermost grid
    length of a pruned pass."""
    steps = 0
    for i in range(n_outer):
        steps = max(steps, hi_fn(i) - lo_fn(i))
    return max(steps, 1)


def _interval_schedule(n_outer: int, steps: int, lo_fn, hi_fn) -> list[list[int]]:
    """The clamp-and-elide walk both pruned passes share: step j of outer
    block i visits min(lo+j, hi-1), and a repeated index streams nothing
    (Pallas elides the DMA) so overshoot steps are dropped from the row."""
    out: list[list[int]] = []
    for i in range(n_outer):
        lo, hi = lo_fn(i), hi_fn(i)
        row: list[int] = []
        for j in range(steps):
            idx = min(lo + j, max(hi - 1, lo))
            if not row or row[-1] != idx:  # repeated index -> no DMA
                row.append(idx)
        out.append(row)
    return out


def kv_steps_for(
    S: int, T: int, block_q: int, block_kv: int,
    causal: bool, window: int | None,
) -> int:
    """Static innermost grid length for the pruned path: max reachable KV
    blocks over all q blocks."""
    nq, nk = cdiv(S, block_q), cdiv(T, block_kv)
    if not causal:
        return nk
    return _interval_steps(
        nq,
        lambda iq: _kv_lo(iq, block_q, block_kv, window),
        lambda iq: _kv_hi(iq, block_q, block_kv, nk),
    )


def block_fully_masked(
    iq: int, ik: int, block_q: int, block_kv: int, *,
    kv_len: int, causal: bool, window: int | None,
) -> bool:
    """True iff no (q, k) pair inside block (iq, ik) survives the mask —
    the oracle the pruning tests/benches check the schedule against."""
    q0, q1 = iq * block_q, iq * block_q + block_q - 1
    k0 = ik * block_kv
    k1 = min(ik * block_kv + block_kv - 1, kv_len - 1)
    if k0 >= kv_len:
        return True
    if not causal:
        return False
    if k0 > q1:  # entirely above the diagonal
        return True
    if window is not None and k1 <= q0 - window:  # entirely out of window
        return True
    return False


def kv_schedule(
    S: int, T: int, block_q: int, block_kv: int, *,
    causal: bool = True, window: int | None = None, pruned: bool = True,
) -> list[list[int]]:
    """Per-q-block list of KV block indices actually *streamed* from HBM.

    Mirrors the kernel's index remapping: the pruned path walks
    [lo, lo+kv_steps) with the index clamped to hi-1, and Pallas elides the
    copy when the block index repeats — so clamped overshoot steps stream
    nothing.  The dense path streams every KV block for every q block.
    """
    nq, nk = cdiv(S, block_q), cdiv(T, block_kv)
    if not (causal and pruned):
        return [list(range(nk)) for _ in range(nq)]
    return _interval_schedule(
        nq,
        kv_steps_for(S, T, block_q, block_kv, causal, window),
        lambda iq: _kv_lo(iq, block_q, block_kv, window),
        lambda iq: _kv_hi(iq, block_q, block_kv, nk),
    )


# ---------------------------------------------------------------------------
# Reachable Q-block interval per KV block (the transposed schedule, used by
# the dk/dv backward pass)
# ---------------------------------------------------------------------------


def _q_lo(ik, block_q: int, block_kv: int, nq: int):
    """First reachable Q block for kv block `ik` (the block containing k0 —
    causal reach starts at qp >= k0)."""
    lo = (ik * block_kv) // block_q
    if isinstance(lo, int):
        return min(lo, nq - 1)
    return jnp.minimum(lo, nq - 1)


def _q_hi(ik, block_q: int, block_kv: int, nq: int, kv_len: int,
          window: int | None):
    """One past the last reachable Q block (highest qp = k1 + window - 1 for
    windowed attention, else every later q block)."""
    if window is None:
        if isinstance(ik, int):
            return nq
        return jnp.full_like(ik, nq)
    k1 = (ik + 1) * block_kv
    if isinstance(k1, int):
        k1 = min(k1, kv_len) - 1
        return max(1, min(nq, (k1 + window - 1) // block_q + 1))
    k1 = jnp.minimum(k1, kv_len) - 1
    return jnp.clip((k1 + window - 1) // block_q + 1, 1, nq)


def q_steps_for(
    S: int, T: int, block_q: int, block_kv: int,
    causal: bool, window: int | None,
) -> int:
    """Static innermost grid length for the pruned dk/dv pass: max reachable
    Q blocks over all kv blocks."""
    nq, nk = cdiv(S, block_q), cdiv(T, block_kv)
    if not causal:
        return nq
    return _interval_steps(
        nk,
        lambda ik: _q_lo(ik, block_q, block_kv, nq),
        lambda ik: _q_hi(ik, block_q, block_kv, nq, T, window),
    )


def q_schedule(
    S: int, T: int, block_q: int, block_kv: int, *,
    causal: bool = True, window: int | None = None, pruned: bool = True,
) -> list[list[int]]:
    """Per-KV-block list of Q block indices actually *streamed* by the dk/dv
    backward pass — the exact transpose of `kv_schedule`, with the same
    clamp-and-elide semantics for overshoot steps."""
    nq, nk = cdiv(S, block_q), cdiv(T, block_kv)
    if not (causal and pruned):
        return [list(range(nq)) for _ in range(nk)]
    return _interval_schedule(
        nk,
        q_steps_for(S, T, block_q, block_kv, causal, window),
        lambda ik: _q_lo(ik, block_q, block_kv, nq),
        lambda ik: _q_hi(ik, block_q, block_kv, nq, T, window),
    )


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def _attend_block(
    q_ref, k_ref, v_ref, m_scratch, l_scratch, acc_scratch,
    q_start, k_start, *,
    block_q: int, block_kv: int, kv_len: int,
    causal: bool, window: int | None, softcap: float | None, scale: float,
):
    """One online-softmax update for the (q_start, k_start) tile."""
    q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = kp < kv_len
    if causal:
        mask = jnp.logical_and(mask, kp <= qp)
        if window is not None:
            mask = jnp.logical_and(mask, kp > qp - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scratch[...]  # (bq, 1)
    l_prev = l_scratch[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scratch[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scratch[...] = m_new
    l_scratch[...] = l_new
    acc_scratch[...] = acc


def _finalize(o_ref, lse_ref, m_scratch, l_scratch, acc_scratch):
    m = m_scratch[...]
    l = l_scratch[...]
    out = acc_scratch[...] / jnp.maximum(l, 1e-30)
    o_ref[0, 0, :, :] = out.astype(o_ref.dtype)
    if lse_ref is not None:  # training path only (return_lse=True)
        # per-row softmax stats for the fused backward: lse = m + log(l).
        # Fully-masked rows keep lse ~ NEG_INF so the backward's
        # exp(s_masked - lse) stays finite (see _bwd_p_ds).
        lse_ref[0, 0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _init_scratch(m_scratch, l_scratch, acc_scratch):
    m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
    l_scratch[...] = jnp.zeros_like(l_scratch)
    acc_scratch[...] = jnp.zeros_like(acc_scratch)


def _flash_kernel_dense(
    q_ref, k_ref, v_ref,  # VMEM blocks
    o_ref,
    *refs,  # [lse_ref if emit_lse,] m_scratch, l_scratch, acc_scratch
    block_q: int,
    block_kv: int,
    kv_len: int,
    causal: bool,
    window: int | None,
    softcap: float | None,
    scale: float,
    emit_lse: bool,
):
    lse_ref = refs[0] if emit_lse else None
    m_scratch, l_scratch, acc_scratch = refs[-3:]
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        _init_scratch(m_scratch, l_scratch, acc_scratch)

    q_start = iq * block_q
    k_start = ik * block_kv

    # Block-level reachability: skip the MXU work for fully-masked KV blocks
    # (they still stream in on this path — the pruned kernel avoids that).
    reachable = jnp.asarray(True)
    if causal:
        reachable = jnp.asarray(k_start <= q_start + block_q - 1)
        if window is not None:
            reachable = jnp.logical_and(
                reachable, k_start + block_kv - 1 > q_start - window
            )

    @pl.when(reachable)
    def _compute():
        _attend_block(
            q_ref, k_ref, v_ref, m_scratch, l_scratch, acc_scratch,
            q_start, k_start,
            block_q=block_q, block_kv=block_kv, kv_len=kv_len,
            causal=causal, window=window, softcap=softcap, scale=scale,
        )

    @pl.when(ik == nk - 1)
    def _fin():
        _finalize(o_ref, lse_ref, m_scratch, l_scratch, acc_scratch)


def _flash_kernel_pruned(
    q_ref, k_ref, v_ref,
    o_ref,
    *refs,  # [lse_ref if emit_lse,] m_scratch, l_scratch, acc_scratch
    block_q: int,
    block_kv: int,
    kv_len: int,
    nk: int,
    causal: bool,
    window: int | None,
    softcap: float | None,
    scale: float,
    emit_lse: bool,
):
    """Index-remapped KV iteration: step j of q block iq visits KV block
    min(lo(iq)+j, hi(iq)-1).  Steps past the interval repeat the last block
    (no DMA) and skip all compute."""
    lse_ref = refs[0] if emit_lse else None
    m_scratch, l_scratch, acc_scratch = refs[-3:]
    iq = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_scratch, l_scratch, acc_scratch)

    lo = _kv_lo(iq, block_q, block_kv, window)
    hi = _kv_hi(iq, block_q, block_kv, nk)
    ik = jnp.minimum(lo + j, hi - 1)

    q_start = iq * block_q
    k_start = ik * block_kv

    @pl.when(j < hi - lo)
    def _compute():
        _attend_block(
            q_ref, k_ref, v_ref, m_scratch, l_scratch, acc_scratch,
            q_start, k_start,
            block_q=block_q, block_kv=block_kv, kv_len=kv_len,
            causal=causal, window=window, softcap=softcap, scale=scale,
        )

    @pl.when(j == nj - 1)
    def _fin():
        _finalize(o_ref, lse_ref, m_scratch, l_scratch, acc_scratch)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, K, T, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    pruned: bool = True,
    interpret: bool = False,
    return_lse: bool = False,
):
    """Forward pass.  With `return_lse=True` also returns the per-row
    softmax statistics `lse = m + log(l)` (B, H, S) fp32 — the residual the
    fused backward (`flash_attention_bwd`) recomputes probabilities from."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)

    # Ragged shapes: zero-pad to block multiples; `kp < kv_len` masks the
    # padded KV and the padded q rows are sliced off below.
    q = _pad_to(q, 2, block_q)
    k = _pad_to(k, 2, block_kv)
    v = _pad_to(v, 2, block_kv)
    Sp, Tp = q.shape[2], k.shape[2]
    nq, nk = Sp // block_q, Tp // block_kv

    use_pruned = pruned and causal
    if use_pruned:
        kv_steps = kv_steps_for(S, Tp, block_q, block_kv, causal, window)
        grid = (B, H, nq, kv_steps)
        kernel = functools.partial(
            _flash_kernel_pruned,
            block_q=block_q, block_kv=block_kv, kv_len=T, nk=nk,
            causal=causal, window=window, softcap=softcap,
            scale=1.0 / np.sqrt(D), emit_lse=return_lse,
        )

        def kv_index(b, h, iq, j):
            lo = _kv_lo(iq, block_q, block_kv, window)
            hi = _kv_hi(iq, block_q, block_kv, nk)
            return (b, h // G, jnp.minimum(lo + j, hi - 1), 0)
    else:
        grid = (B, H, nq, nk)
        kernel = functools.partial(
            _flash_kernel_dense,
            block_q=block_q, block_kv=block_kv, kv_len=T,
            causal=causal, window=window, softcap=softcap,
            scale=1.0 / np.sqrt(D), emit_lse=return_lse,
        )

        def kv_index(b, h, iq, ik):
            return (b, h // G, ik, 0)

    out_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, j: (b, h, iq, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype)]
    if return_lse:  # inference-only calls skip the lse compute + HBM write
        # row stats ride as a trailing unit dim: a (block_q, 1) column block
        # is sublane-aligned and full in the last dim, which the TPU's
        # (8, 128) block tiling accepts (a bare (block_q,) row is not)
        out_specs.append(pl.BlockSpec((1, 1, block_q, 1),
                                      lambda b, h, iq, j: (b, h, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, Sp, 1), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, j: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if return_lse:
        out, lse = res
        return out[:, :, :S, :], lse[:, :, :S, 0]
    (out,) = res
    return out[:, :, :S, :]


# ---------------------------------------------------------------------------
# Fused backward (two-pass flash recipe, pruned in both directions)
# ---------------------------------------------------------------------------


def _bwd_p_ds(
    q, k, v, do, lse, delta, q_start, k_start, *,
    block_q: int, block_kv: int, kv_len: int,
    causal: bool, window: int | None, softcap: float | None, scale: float,
):
    """Recompute the probability tile from saved stats and form dS.

    Returns (p, ds) fp32 (bq, bk) tiles for the (q_start, k_start) pair:
    p = exp(s - lse) restricted to the mask, ds = p * (dP - delta) pushed
    back through the optional softcap.  All operands are fp32.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = kp < kv_len
    if causal:
        mask = jnp.logical_and(mask, kp <= qp)
        if window is not None:
            mask = jnp.logical_and(mask, kp > qp - window)
    maskf = mask.astype(jnp.float32)
    # mask s *before* subtracting lse: fully-masked rows have lse ~ NEG_INF
    # and exp(NEG_INF - NEG_INF) = 1 is finite (then zeroed by the mask),
    # whereas exp(real - NEG_INF) would overflow to inf * 0 = nan.
    s_masked = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s_masked - lse) * maskf

    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    if softcap is not None:  # d/dx [c*tanh(x/c)] = 1 - tanh^2 = 1 - (s/c)^2
        ds = ds * (1.0 - jnp.square(s / softcap))
    return p, ds


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_acc,
    *,
    block_q: int, block_kv: int, kv_len: int, nk: int,
    causal: bool, window: int | None, softcap: float | None, scale: float,
    pruned: bool,
):
    """dq pass: grid (B, H, nq, kv_steps) — the forward's pruned KV
    iteration, accumulating dq for one q block in fp32 scratch."""
    iq = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if pruned and causal:
        lo = _kv_lo(iq, block_q, block_kv, window)
        hi = _kv_hi(iq, block_q, block_kv, nk)
        ik = jnp.minimum(lo + j, hi - 1)
        live = j < hi - lo
    else:
        ik = j
        live = jnp.asarray(True)
        if causal:  # dense path still skips MXU work for dead blocks
            live = jnp.asarray(j * block_kv <= iq * block_q + block_q - 1)
            if window is not None:
                live = jnp.logical_and(
                    live, j * block_kv + block_kv - 1 > iq * block_q - window
                )

    q_start = iq * block_q
    k_start = ik * block_kv

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        _, ds = _bwd_p_ds(
            q, k, v, do, lse, delta, q_start, k_start,
            block_q=block_q, block_kv=block_kv, kv_len=kv_len,
            causal=causal, window=window, softcap=softcap, scale=scale,
        )
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    @pl.when(j == nj - 1)
    def _fin():
        dq_ref[0, 0, :, :] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *,
    block_q: int, block_kv: int, kv_len: int, nq: int, q_steps: int,
    causal: bool, window: int | None, softcap: float | None, scale: float,
    pruned: bool,
):
    """dk/dv pass: grid (B, K, nk, group*q_steps) — the *transposed* pruned
    iteration, walking reachable Q blocks per KV block with the GQA group
    folded into the innermost dimension (j = g*q_steps + jq).  The fp32
    accumulators persist across the whole group loop, so dk/dv come out
    *group-summed* — one (block_kv, D) pair per true KV head, an O(S·K·D)
    HBM write instead of the per-q-head O(S·H·D) transient."""
    ik = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    jq = j % q_steps  # position inside this group member's Q walk

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if pruned and causal:
        lo = _q_lo(ik, block_q, block_kv, nq)
        hi = _q_hi(ik, block_q, block_kv, nq, kv_len, window)
        iq = jnp.minimum(lo + jq, jnp.maximum(hi - 1, lo))
        live = jq < hi - lo
    else:
        iq = jq
        live = jnp.asarray(True)
        if causal:
            live = jnp.asarray(ik * block_kv <= jq * block_q + block_q - 1)
            if window is not None:
                live = jnp.logical_and(
                    live, ik * block_kv + block_kv - 1 > jq * block_q - window
                )

    q_start = iq * block_q
    k_start = ik * block_kv

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        p, ds = _bwd_p_ds(
            q, k, v, do, lse, delta, q_start, k_start,
            block_q=block_q, block_kv=block_kv, kv_len=kv_len,
            causal=causal, window=window, softcap=softcap, scale=scale,
        )
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    @pl.when(j == nj - 1)
    def _fin():
        dk_ref[0, 0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,    # (B, H, S, D)
    k: jax.Array,    # (B, K, T, D)
    v: jax.Array,
    out: jax.Array,  # (B, H, S, D) forward output
    lse: jax.Array,  # (B, H, S) fp32 forward softmax stats
    do: jax.Array,   # (B, H, S, D) output cotangent
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    pruned: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Pallas backward: returns (dq, dk, dv) in kernel layout.

    Two passes over the same pruned schedule machinery as the forward: the
    dq grid iterates [kv_lo, kv_hi) per q block, the dk/dv grid iterates the
    transposed [q_lo, q_hi) per kv block with the GQA group folded into the
    innermost dimension.  `delta = rowsum(dO·O)` is precomputed here (cheap
    XLA elementwise+reduce).  K/V are never replicated for GQA in either
    direction: the forward/dq index_map maps h // group, and the dk/dv pass
    accumulates *group-locally* — grid over the K true KV heads, inner loop
    over the group — so its HBM write is the final fp32 (B, K, T, D)
    gradient pair, O(S·K·D), never a per-q-head O(S·H·D) transient.
    """
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)

    # Ragged shapes: zero-pad like the forward.  Padded q rows have dO = 0,
    # so delta = 0 and every padded contribution to dq/dk/dv vanishes; the
    # `kp < kv_len` mask keeps padded KV out of every tile.
    q = _pad_to(q, 2, block_q)
    out = _pad_to(out, 2, block_q)
    do = _pad_to(do, 2, block_q)
    lse = _pad_to(lse, 2, block_q)[..., None]  # (B, H, Sp, 1) columns
    k = _pad_to(k, 2, block_kv)
    v = _pad_to(v, 2, block_kv)
    Sp, Tp = q.shape[2], k.shape[2]
    nq, nk = Sp // block_q, Tp // block_kv

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    scale = 1.0 / np.sqrt(D)
    use_pruned = pruned and causal

    # -- dq pass: per q block, iterate (pruned) KV blocks ---------------------
    kv_steps = (
        kv_steps_for(S, Tp, block_q, block_kv, causal, window)
        if use_pruned else nk
    )

    def kv_index(b, h, iq, j):
        if use_pruned:
            lo = _kv_lo(iq, block_q, block_kv, window)
            hi = _kv_hi(iq, block_q, block_kv, nk)
            j = jnp.minimum(lo + j, hi - 1)
        return (b, h // G, j, 0)

    def q_row(b, h, iq, j):
        return (b, h, iq, 0)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel,
        block_q=block_q, block_kv=block_kv, kv_len=T, nk=nk,
        causal=causal, window=window, softcap=softcap, scale=scale,
        pruned=use_pruned,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_row),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_index),
            pl.BlockSpec((1, 1, block_q, D), q_row),
            pl.BlockSpec((1, 1, block_q, 1), q_row),
            pl.BlockSpec((1, 1, block_q, 1), q_row),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_row),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # -- dk/dv pass: per KV head x KV block, loop the GQA group over the
    # (pruned) Q blocks — group-local accumulation, so the HBM write is the
    # true (B, K, T, D) gradient, never a per-q-head transient ------------------
    q_steps = (
        q_steps_for(S, T, block_q, block_kv, causal, window)
        if use_pruned else nq
    )

    def q_index(b, kh, ik, j):
        h = kh * G + j // q_steps  # group member this step serves
        jq = j % q_steps
        if use_pruned:
            lo = _q_lo(ik, block_q, block_kv, nq)
            hi = _q_hi(ik, block_q, block_kv, nq, T, window)
            jq = jnp.minimum(lo + jq, jnp.maximum(hi - 1, lo))
        return (b, h, jq, 0)

    def kv_row(b, kh, ik, j):
        return (b, kh, ik, 0)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel,
        block_q=block_q, block_kv=block_kv, kv_len=T, nq=nq, q_steps=q_steps,
        causal=causal, window=window, softcap=softcap, scale=scale,
        pruned=use_pruned,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, K, nk, G * q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_kv, D), kv_row),
            pl.BlockSpec((1, 1, block_kv, D), kv_row),
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_q, 1), q_index),
            pl.BlockSpec((1, 1, block_q, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, D), lambda b, kh, ik, j: (b, kh, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, kh, ik, j: (b, kh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, Tp, D), jnp.float32),
            jax.ShapeDtypeStruct((B, K, Tp, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dq = dq[:, :, :S]
    dk = dk[:, :, :T].astype(k.dtype)
    dv = dv[:, :, :T].astype(v.dtype)
    return dq, dk, dv


def vmem_bytes(
    block_q: int,
    block_kv: int,
    head_dim: int,
    dtype_bytes: int = 2,
    *,
    kv_dtype_bytes: int | None = None,
) -> int:
    """Analytic VMEM working set — the autotuner's capacity constraint.

    Counts the pipelined Q/O blocks at the Q dtype and the K *and* V blocks
    at the KV dtype (they may differ, e.g. bf16 Q against int8 KV cache),
    double-buffered as Pallas pipelines them, plus the fp32 scratch
    (acc + m + l) and the fp32 score tile.
    """
    if kv_dtype_bytes is None:
        kv_dtype_bytes = dtype_bytes
    qo = 2 * block_q * head_dim * dtype_bytes  # q in + o out
    kv = 2 * block_kv * head_dim * kv_dtype_bytes  # k + v
    scratch = (block_q * (head_dim + 2)) * 4  # fp32 acc + m + l
    scores = block_q * block_kv * 4  # fp32 s/p tile
    return 2 * (qo + kv) + scratch + scores  # x2: double-buffered I/O blocks


def vmem_bytes_bwd(
    block_q: int,
    block_kv: int,
    head_dim: int,
    dtype_bytes: int = 2,
    *,
    kv_dtype_bytes: int | None = None,
) -> int:
    """Analytic VMEM working set of the fused backward — the autotuner's
    capacity constraint for the `block_q_bwd`/`block_kv_bwd` knobs.

    Models the larger of the two passes.  Both stream q + dO (Q dtype) and
    K + V (KV dtype) plus the fp32 lse/delta row stats; the dq pass adds the
    dq output block and an fp32 (bq, D) accumulator, the dk/dv pass adds two
    fp32 output blocks and two (bkv, D) accumulators.  Each pass recomputes
    three fp32 (bq, bkv) tiles (s/p, dP, dS).  I/O blocks are counted
    double-buffered as Pallas pipelines them.
    """
    if kv_dtype_bytes is None:
        kv_dtype_bytes = dtype_bytes
    q_in = 2 * block_q * head_dim * dtype_bytes       # q + dO
    kv_in = 2 * block_kv * head_dim * kv_dtype_bytes  # k + v
    stats = 2 * block_q * 4                           # fp32 lse + delta
    tiles = 3 * block_q * block_kv * 4                # fp32 s/p, dP, dS
    dq_pass = (
        2 * (q_in + kv_in + stats + block_q * head_dim * dtype_bytes)
        + block_q * head_dim * 4 + tiles
    )
    dkv_pass = (
        2 * (q_in + kv_in + stats + 2 * block_kv * head_dim * 4)
        + 2 * block_kv * head_dim * 4 + tiles
    )
    return max(dq_pass, dkv_pass)
