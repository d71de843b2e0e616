"""Plain float32 building blocks that the families' references share, and
the comparison that decides `correct`.

They import nothing of the program.  Every matrix product runs in float32
at `HIGHEST` precision (a TPU multiplies float32 in bfloat16 otherwise).
A family's reference runs after the program's state is freed, one
sequence at a time and one layer at a time (`over_sequences`), attention
in blocks of query rows and the matrices in blocks of token rows (`rows`),
so it fits one chip beside the bfloat16 weights.

`control=True` is the precision control: every matrix product takes its
operands rounded to float8 e4m3 (weights scaled per output column,
activations per token row), the step below the bfloat16 the configurations
state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024      # sequences are padded to a multiple of this
Q_BLOCK = 256   # query rows per attention block
ROW_BLOCK = 1024  # token rows per matrix block
HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def dot(x, w, control: bool):
    """x @ w in float32; with `control`, both operands rounded to float8."""
    w = w.astype(jnp.float32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x: (S, heads, D) rotated by position, the two halves of each head
    as the real and imaginary parts."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rows(fn, x):
    """`fn` over blocks of ROW_BLOCK token rows of x (S, ...)."""
    S = x.shape[0]
    out = jax.lax.map(fn, x.reshape(S // ROW_BLOCK, ROW_BLOCK, x.shape[-1]))
    return out.reshape(S, -1)


def causal_attention(q, k, v, pos):
    """Causal grouped-query attention of q (S, H, D) over k, v (S, K, D):
    query head h reads key/value head h // (H / K).  Returns (S, H * D)."""
    S, H, D = q.shape
    K = k.shape[1]
    G = H // K
    kg = k.transpose(1, 0, 2)                      # (K, S, D)
    vg = v.transpose(1, 0, 2)

    def attend(block):
        qb, qpos = block                             # (Q, H, D), (Q,)
        qb = qb.reshape(-1, K, G, D).transpose(1, 2, 0, 3)  # (K, G, Q, D)
        s = jnp.einsum("kgqd,ksd->kgqs", qb, kg,
                       precision=HIGHEST) / np.sqrt(D)
        s = jnp.where(pos[None, None, None] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,ksd->kgqd", p, vg, precision=HIGHEST)
        return o.transpose(2, 0, 1, 3).reshape(-1, H * D)

    nq = S // Q_BLOCK
    return jax.lax.map(attend, (q.reshape(nq, Q_BLOCK, H, D),
                                pos.reshape(nq, Q_BLOCK))).reshape(S, H * D)


def over_sequences(seqs, forward):
    """Logits at the positions that predicted served tokens.

    `seqs`: (tokens, first) pairs, tokens the prompt followed by the served
    tokens but the last, `first` the position of the last prompt token.
    `forward(ids, first, n)` gives the float32 logits of rows first..n-1 of
    `ids`, the tokens zero-padded to a multiple of PAD.  Returns one
    (len(tokens) - first, vocab) float32 array per pair."""
    out = []
    with jax.default_matmul_precision("highest"):
        for tokens, first in seqs:
            n = len(tokens)
            S = -(-n // PAD) * PAD
            ids = np.zeros(S, np.int32)
            ids[:n] = tokens
            out.append(np.asarray(forward(jnp.asarray(ids), first, n)))
    return out


def served_gap(ref_logits, served) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at that position."""
    worst = 0.0
    for ref, toks in zip(ref_logits, served):
        at = np.arange(len(toks))
        worst = max(worst, float(np.max(ref.max(-1) - ref[at, toks])))
    return worst
