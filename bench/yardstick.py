"""Arithmetic of the metrics that every family shares: percentiles, and the
keys a span of tokens attends to.  What a step of a model needs in
operations and bytes, worked out from its shapes, is its family's
(`families/<name>.py`: `forward_flops`, `decode_bytes`).
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of all `values`; +inf counts as
    a value, so misses stay in the tail."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def causal_keys(start: int, stop: int) -> int:
    """Keys that the tokens at positions start..stop-1 attend to, each
    causally to itself and everything before it: the sum over p in
    [start, stop) of p + 1."""
    return (stop * (stop + 1) - start * (start + 1)) // 2
