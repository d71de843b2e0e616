"""DSE-driven kernel block autotuning — closing the paper's Fig. 13–14 loop.

The Lat DSE (paper §4.1) explores per-kernel block knobs, the results become
a mARGOt `KnowledgeBase` (paper §2.5), and the best operating point persists
in an on-disk cache keyed by the kernel's problem signature.  Entry points
(`repro.kernels.*.ops`) and the weaver (`TunedKernelAspect`) consult the
cache, so woven programs and the serving runtime pick tuned blocks
automatically — the DSE output is literally "fed to the autotuner".

Layout of the cache file (JSON):

    {"<signature key>": {"knobs": {...best...},
                         "metrics": {"latency_s": [mean, std], ...},
                         "ops": [{"knobs": ..., "metrics": ...}, ...]}}

For flash attention the knob dict covers both directions:
`block_q` / `block_kv` tile the forward kernel and `block_q_bwd` /
`block_kv_bwd` tile the fused backward passes (dq and dk/dv); the default
measurement times a full fwd+grad step so the DSE optimizes training-step
latency, and the VMEM constraint is the max of the forward
(`vmem_bytes`) and backward (`vmem_bytes_bwd`) analytic working sets.
Entries written before the backward knobs existed simply lack the `_bwd`
keys — consumers (`ops._resolve_blocks`, `TunedKernelAspect`) fall back to
the forward blocks.

Tuning is always *explicit* (benchmarks, launch tooling, tests); lookups on
the hot path are cheap dict reads and never trigger measurement.  The one
sanctioned *implicit* write path is `refine_from_runtime`: serving traffic's
observed latencies (mARGOt error coefficients) rescale the cached operating
points and re-select the knobs under the adjusted constraints — the paper's
"runtime observations as feedback information" closed over the persistent
knowledge base.  The `paged_decode` space adds the serving pool geometry:
`page_size` (allocation quantum of the paged KV cache) jointly explored
with `block_kv_dec` (clamped to a page divisor); its DSE rows also record
`pool_hbm_bytes`, the shared-prefix HBM model (`prefix_shared_pool_bytes`)
— prefix caching shares full prompt pages across requests, and smaller
pages share a longer page-aligned prefix, the capacity counterweight to
large pages' smaller block tables.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import uuid
from typing import Any, Callable, Mapping

from repro.autotune.dse import Lat
from repro.autotune.margot import LE, Goal, KnowledgeBase, Margot, OperatingPoint, State
from repro.kernels.flash_attention.decode import page_block_kv, vmem_bytes_dec
from repro.kernels.flash_attention.kernel import cdiv, vmem_bytes, vmem_bytes_bwd

DEFAULT_VMEM_BUDGET = 16 * 2**20  # bytes per TPU core

_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "bfloat16": 2, "bf16": 2, "float16": 2,
    "int8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def dtype_bytes(dtype: Any) -> int:
    name = getattr(dtype, "name", None) or str(dtype)
    return _DTYPE_BYTES.get(name, 4)


# ---------------------------------------------------------------------------
# Signatures
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSignature:
    """Everything that changes which block configuration is optimal."""

    kernel: str               # flash_attention | rwkv6 | rglru | rmsnorm
    shape: tuple[int, ...]    # problem shape (kernel-specific, see helpers)
    dtype: str = "bfloat16"
    causal: bool = False
    window: int | None = None
    gqa: int = 1              # q heads per kv head

    def key(self) -> str:
        shp = "x".join(str(s) for s in self.shape)
        mask = "c" if self.causal else "f"
        win = str(self.window) if self.window is not None else "-"
        return f"{self.kernel}/{shp}/{self.dtype}/{mask}/w{win}/g{self.gqa}"


def flash_signature(q_shape, kv_heads: int, dtype, *, causal: bool,
                    window: int | None = None) -> KernelSignature:
    """q_shape is the model layout (B, S, H, D)."""
    B, S, H, D = q_shape
    return KernelSignature(
        kernel="flash_attention", shape=(B, S, H, kv_heads, D),
        dtype=str(getattr(dtype, "name", dtype)), causal=causal,
        window=window, gqa=H // max(kv_heads, 1),
    )


def flash_decode_signature(batch: int, cache_len: int, n_heads: int,
                           kv_heads: int, head_dim: int, dtype="bfloat16",
                           *, window: int | None = None) -> KernelSignature:
    """One-token decode against a length-`cache_len` cache.  A separate
    kernel space from `flash_attention`: the knob (`block_kv_dec`) tiles the
    cache stream and the measurement is a full cached-decode step, not a
    training fwd+grad."""
    return KernelSignature(
        kernel="flash_decode",
        shape=(batch, cache_len, n_heads, kv_heads, head_dim),
        dtype=str(getattr(dtype, "name", dtype)), causal=True,
        window=window, gqa=n_heads // max(kv_heads, 1),
    )


def paged_decode_signature(batch: int, cache_len: int, n_heads: int,
                           kv_heads: int, head_dim: int, dtype="bfloat16",
                           *, window: int | None = None) -> KernelSignature:
    """Block-table decode against a shared page pool.  Its own kernel space
    because the pool geometry adds a knob: `page_size` fixes the physical
    block granularity (allocation quantum AND the ceiling of the streamed
    block — `block_kv_dec` is clamped to a divisor of it, the knob
    interaction the DSE explores jointly)."""
    return KernelSignature(
        kernel="paged_decode",
        shape=(batch, cache_len, n_heads, kv_heads, head_dim),
        dtype=str(getattr(dtype, "name", dtype)), causal=True,
        window=window, gqa=n_heads // max(kv_heads, 1),
    )


def quantized_cache_signature(batch: int, cache_len: int, n_heads: int,
                              kv_heads: int, head_dim: int, dtype="bfloat16",
                              *, window: int | None = None) -> KernelSignature:
    """Accuracy-constrained dtype×geometry DSE for the quantized page pool.
    Its own kernel space because the objective flips: instead of minimizing
    latency under VMEM, it maximizes tokens-per-HBM-byte (serving capacity)
    subject to a *measured* logits-error constraint against the fp cache —
    the paper's precision-autotuning shape (DSE over precision versions
    under an accuracy goal).  `dtype` keys the fp *reference* pool; the
    explored `cache_dtype` knob includes fp names as the accuracy-fallback
    arm (meaning: keep the fp pool)."""
    return KernelSignature(
        kernel="quantized_cache",
        shape=(batch, cache_len, n_heads, kv_heads, head_dim),
        dtype=str(getattr(dtype, "name", dtype)), causal=True,
        window=window, gqa=n_heads // max(kv_heads, 1),
    )


def speculative_signature(batch: int, cache_len: int, n_heads: int,
                          kv_heads: int, head_dim: int, dtype="bfloat16",
                          *, window: int | None = None) -> KernelSignature:
    """Speculative-decoding verify step: a widened-q flash_decode call
    scoring `draft_len + 1` tokens per request in one kernel instance.
    Its own kernel space because the governing knob is the draft span
    itself — `draft_len` scales the q tile (rows = (draft_len+1)·group)
    and the per-step work, while the *useful* tokens per step scale with
    the draft's acceptance rate, which only serving traffic can observe
    (`Server.refine_speculative` feeds it back as `tokens_per_step`)."""
    return KernelSignature(
        kernel="speculative",
        shape=(batch, cache_len, n_heads, kv_heads, head_dim),
        dtype=str(getattr(dtype, "name", dtype)), causal=True,
        window=window, gqa=n_heads // max(kv_heads, 1),
    )


def rmsnorm_signature(rows: int, dim: int, dtype="bfloat16") -> KernelSignature:
    """Fused RMSNorm problem: (rows, d) with rows = batch * seq."""
    return KernelSignature(
        kernel="rmsnorm", shape=(rows, dim),
        dtype=str(getattr(dtype, "name", dtype)),
    )


def rwkv6_signature(batch: int, seq_len: int, d_model: int,
                    head_dim: int = 64, dtype="float32") -> KernelSignature:
    """WKV problem signature: (B, S, H, C) with H = d_model // head_dim."""
    return KernelSignature(
        kernel="rwkv6",
        shape=(batch, seq_len, d_model // max(head_dim, 1), head_dim),
        dtype=str(getattr(dtype, "name", dtype)),
    )


def rglru_signature(batch: int, seq_len: int, width: int,
                    dtype="float32") -> KernelSignature:
    """RG-LRU problem signature: (B, S, D) with D the lru width."""
    return KernelSignature(
        kernel="rglru", shape=(batch, seq_len, width),
        dtype=str(getattr(dtype, "name", dtype)),
    )


# ---------------------------------------------------------------------------
# Design spaces + constraints
# ---------------------------------------------------------------------------

KERNEL_SPACES: dict[str, dict[str, tuple[int, ...]]] = {
    "flash_attention": {
        "block_q": (128, 256, 512, 1024),
        "block_kv": (128, 256, 512, 1024),
        "block_q_bwd": (128, 256, 512, 1024),
        "block_kv_bwd": (128, 256, 512, 1024),
    },
    "flash_decode": {"block_kv_dec": (128, 256, 512, 1024)},
    "paged_decode": {
        "page_size": (64, 128, 256, 512),
        "block_kv_dec": (128, 256, 512, 1024),
    },
    "speculative": {
        "draft_len": (1, 2, 4, 8),
        "block_kv_dec": (128, 256, 512, 1024),
    },
    "quantized_cache": {
        # categorical dtype knob: fp16 is the accuracy-fallback arm (keep
        # the fp pool)
        "cache_dtype": ("float16", "int8", "float8_e4m3fn"),
        "page_size": (64, 128, 256, 512),
        "block_kv_dec": (128, 256, 512, 1024),
    },
    "rwkv6": {"chunk": (16, 32, 64, 128)},
    "rglru": {"block_d": (128, 256, 512, 1024), "chunk": (64, 128, 256)},
    "rmsnorm": {"block_rows": (64, 128, 256, 512)},
}


def config_vmem_bytes(sig: KernelSignature, knobs: Mapping[str, int]) -> int:
    """Analytic VMEM working set of one configuration (the LE constraint)."""
    b = dtype_bytes(sig.dtype)
    if sig.kernel == "flash_attention":
        B, S, H, K, D = sig.shape
        fwd = vmem_bytes(
            min(int(knobs["block_q"]), S), min(int(knobs["block_kv"]), S),
            D, b, kv_dtype_bytes=b,
        )
        bqb = int(knobs.get("block_q_bwd", knobs["block_q"]))
        bkvb = int(knobs.get("block_kv_bwd", knobs["block_kv"]))
        bwd = vmem_bytes_bwd(min(bqb, S), min(bkvb, S), D, b,
                             kv_dtype_bytes=b)
        return max(fwd, bwd)
    if sig.kernel == "flash_decode":
        B, T, H, K, D = sig.shape
        return vmem_bytes_dec(
            H // max(K, 1), min(int(knobs["block_kv_dec"]), max(T, 128)),
            D, b, kv_dtype_bytes=b,
        )
    if sig.kernel == "paged_decode":
        B, T, H, K, D = sig.shape
        ps = int(knobs["page_size"])
        eff = page_block_kv(int(knobs["block_kv_dec"]), ps)
        return vmem_bytes_dec(
            H // max(K, 1), min(eff, max(T, 128)), D, b, kv_dtype_bytes=b,
        ) + 4 * cdiv(max(T, 1), ps)  # + the SMEM block-table row
    if sig.kernel == "quantized_cache":
        B, T, H, K, D = sig.shape
        ps = int(knobs["page_size"])
        eff = page_block_kv(int(knobs["block_kv_dec"]), ps)
        # the kernel streams the pool's storage dtype and dequantizes
        # in-register: K/V tiles shrink with the quantized dtype
        qb = _DTYPE_BYTES.get(str(knobs["cache_dtype"]), b)
        return vmem_bytes_dec(
            H // max(K, 1), min(eff, max(T, 128)), D, b, kv_dtype_bytes=qb,
        ) + 4 * cdiv(max(T, 1), ps)  # + the SMEM block-table row
    if sig.kernel == "speculative":
        B, T, H, K, D = sig.shape
        return vmem_bytes_dec(
            H // max(K, 1), min(int(knobs["block_kv_dec"]), max(T, 128)),
            D, b, kv_dtype_bytes=b, q_span=int(knobs["draft_len"]) + 1,
        )
    if sig.kernel == "rwkv6":
        B, S, H, C = sig.shape
        L = int(knobs["chunk"])
        # 4 chunk blocks + pairwise decay (L,L,C) + state (C,C), fp32 math
        return (4 * L * C + L * L * C + C * C) * 4
    if sig.kernel == "rglru":
        B, S, D = sig.shape
        L, Db = int(knobs["chunk"]), int(knobs["block_d"])
        return 3 * L * min(Db, D) * 4
    if sig.kernel == "rmsnorm":
        rows, d = sig.shape
        return 2 * min(int(knobs["block_rows"]), rows) * d * 4
    raise KeyError(sig.kernel)


def prefix_shared_pool_bytes(sig: KernelSignature, knobs: Mapping[str, int],
                             *, prefix_len: int | None = None) -> int:
    """HBM a prefix-shared pool allocates for the signature's batch at the
    knob's pool geometry: full prefix pages are stored *once* (refcounted
    copy-on-write sharing in `repro.runtime.pages`), each request adds only
    its suffix pages plus the prefix/suffix straddling partial.

    This is the shared-page HBM model the pool-geometry DSE weighs against
    block-stream efficiency: sharing rounds the prefix *down* to a page
    boundary, so smaller pages share more of it — the counterweight to
    large pages' smaller tables.  `prefix_len` defaults to half the cache
    (the serving-mix assumption recorded with the DSE rows); callers with a
    known system-prompt length pass it explicitly.
    """
    B, T, H, K, D = sig.shape
    ps = int(knobs["page_size"])
    prefix = min(T // 2 if prefix_len is None else int(prefix_len), T)
    shared_full = prefix // ps           # stored once, every table maps them
    per_request = cdiv(T, ps) - shared_full
    pages = shared_full + B * per_request
    return pages * ps * K * D * 2 * dtype_bytes(sig.dtype)


def quantized_pool_bytes(sig: KernelSignature, knobs: Mapping[str, Any]) -> int:
    """HBM the pool allocates for the signature's batch at the knob's
    dtype×geometry: quantized payload at `cache_dtype` plus the per-page
    fp32 scale sidecars (2 rows of K scales per page: k and v).  Fp dtype
    values model the unquantized pool (no sidecars).  This is the
    denominator of the `tokens_per_hbm_byte` objective."""
    B, T, H, K, D = sig.shape
    ps = int(knobs["page_size"])
    name = str(knobs["cache_dtype"])
    qb = _DTYPE_BYTES.get(name, dtype_bytes(sig.dtype))
    pages = B * cdiv(max(T, 1), ps)
    per_page = 2 * ps * K * D * qb
    if qb == 1:  # quantized formats carry the fp32 scale sidecars
        per_page += 2 * K * 4
    return pages * per_page


def design_space(sig: KernelSignature, *,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET) -> dict[str, list[int]]:
    """Per-kernel knob values, pre-filtered so every value is feasible for
    the signature's shape on its own (cross-knob VMEM feasibility is the
    tuner's point-level constraint)."""
    space = {k: list(v) for k, v in KERNEL_SPACES[sig.kernel].items()}
    if sig.kernel == "flash_attention":
        B, S, H, K, D = sig.shape
        for name in ("block_q", "block_kv", "block_q_bwd", "block_kv_bwd"):
            space[name] = [v for v in space[name] if v <= max(S, 128)]
    elif sig.kernel == "flash_decode":
        T = sig.shape[1]
        space["block_kv_dec"] = [
            v for v in space["block_kv_dec"] if v <= max(T, 128)
        ]
    elif sig.kernel == "paged_decode":
        T = sig.shape[1]
        space["page_size"] = [v for v in space["page_size"] if v <= max(T, 64)]
        space["block_kv_dec"] = [
            v for v in space["block_kv_dec"] if v <= max(T, 128)
        ]
    elif sig.kernel == "quantized_cache":
        T = sig.shape[1]
        space["page_size"] = [v for v in space["page_size"] if v <= max(T, 64)]
        space["block_kv_dec"] = [
            v for v in space["block_kv_dec"] if v <= max(T, 128)
        ]
    elif sig.kernel == "speculative":
        T = sig.shape[1]
        space["block_kv_dec"] = [
            v for v in space["block_kv_dec"] if v <= max(T, 128)
        ]
        # the draft block must fit under the request's decode budget slack
        space["draft_len"] = [v for v in space["draft_len"]
                              if v < max(T, 2)]
    elif sig.kernel == "rwkv6":
        S = sig.shape[1]
        space["chunk"] = [v for v in space["chunk"] if v <= max(S, 16)]
    elif sig.kernel == "rglru":
        B, S, D = sig.shape
        space["block_d"] = [v for v in space["block_d"] if v <= max(D, 128)]
        space["chunk"] = [v for v in space["chunk"] if v <= max(S, 64)]
    # drop single-knob values that can never fit the VMEM budget
    for name in list(space):
        feasible = []
        for v in space[name]:
            probe = {n: min(vals) for n, vals in space.items()}
            probe[name] = v
            if config_vmem_bytes(sig, probe) <= vmem_budget:
                feasible.append(v)
        space[name] = feasible or [min(space[name])]
    return space


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


def default_cache_path() -> str:
    """`REPRO_TUNER_CACHE`, else `<repo>/.tuner_cache/kernel_tuner.json`:
    the block sizes a run compiles come from the checkout, never from a
    file elsewhere on the host."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.environ.get(
        "REPRO_TUNER_CACHE",
        os.path.join(root, ".tuner_cache", "kernel_tuner.json"),
    )


class TunerCache:
    """Tiny JSON-backed store: signature key -> best knobs + DSE rows."""

    def __init__(self, path: str | None = None):
        self.path = path or default_cache_path()
        self._data: dict[str, dict] | None = None
        self.hits = 0
        self.misses = 0

    def _load(self) -> dict[str, dict]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    self._data = json.load(f)
            except (OSError, ValueError):
                self._data = {}
        return self._data

    def get(self, key: str) -> dict | None:
        entry = self._load().get(key)
        if entry is None:
            self.misses += 1
        else:
            self.hits += 1
        return entry

    def put(self, key: str, entry: dict) -> None:
        data = self._load()
        data[key] = entry
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        # unique tmp per writer: concurrent puts must not interleave bytes
        tmp = f"{self.path}.tmp.{os.getpid()}.{uuid.uuid4().hex[:8]}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def __len__(self) -> int:
        return len(self._load())


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def _device_tag() -> str:
    """Measurement substrate of this process's tuner rows.  Interpret-mode
    measurements (the CPU-CI default) are what most entries hold; with
    REPRO_TUNER_ON_DEVICE=1 the tag is the real jax backend, so on-device
    rows key separately and never cross-contaminate interpret lookups."""
    if os.environ.get("REPRO_TUNER_ON_DEVICE") == "1":
        import jax

        return str(jax.default_backend())
    return "interpret"


class KernelTuner:
    """Lat DSE over kernel block knobs, constrained by the analytic VMEM
    model, persisted through a TunerCache."""

    def __init__(self, cache: TunerCache | str | None = None, *,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET):
        if isinstance(cache, TunerCache):
            self.cache = cache
        else:
            self.cache = TunerCache(cache)
        self.vmem_budget = vmem_budget
        self.tuned = 0  # DSE runs performed (cache misses that measured)

    # -- lookup ----------------------------------------------------------------

    def _key(self, sig: KernelSignature) -> str:
        """Cache key for this process's measurement substrate: interpret
        rows keep the bare signature key (every pre-existing entry), while
        on-device rows (REPRO_TUNER_ON_DEVICE=1) append "@<backend>"."""
        dev = _device_tag()
        return sig.key() if dev == "interpret" else f"{sig.key()}@{dev}"

    def lookup(self, sig: KernelSignature) -> dict[str, int] | None:
        entry = self.cache.get(self._key(sig))
        if entry is None:
            return None
        return dict(entry["knobs"])

    def knowledge_base(self, sig: KernelSignature) -> KnowledgeBase | None:
        """Rebuild the mARGOt KnowledgeBase from the cached DSE rows."""
        entry = self.cache.get(self._key(sig))
        if entry is None:
            return None
        ops = [
            OperatingPoint(
                knobs=dict(row["knobs"]),
                metrics={m: tuple(v) for m, v in row["metrics"].items()},
            )
            for row in entry.get("ops", [])
        ]
        return KnowledgeBase(ops)

    # -- tuning ----------------------------------------------------------------

    def tune(
        self,
        sig: KernelSignature,
        measure: Callable[..., float] | None = None,
        *,
        sample: int | None = None,
        num_tests: int = 1,
        seed: int = 0,
    ) -> dict[str, int]:
        """Run the DSE and persist best knobs + the full operating-point set.

        `measure(**knobs) -> latency_s` defaults to timing the real kernel on
        inputs shaped like the signature (interpret mode off-TPU)."""
        if measure is None:
            measure = _default_measure(sig)
        space = design_space(sig, vmem_budget=self.vmem_budget)

        lat = Lat(sig.key()).set_num_tests(num_tests)
        for name, values in space.items():
            lat.add_var(name, values)
        lat.add_metric("latency_s", measure)
        lat.add_metric(
            "vmem_bytes", lambda **knobs: config_vmem_bytes(sig, knobs)
        )
        if sig.kernel == "paged_decode":
            # pool-geometry DSE also records the shared-prefix HBM model:
            # the rows let refine_from_runtime / offline analysis trade the
            # page_size knob against prefix-cache capacity, not just VMEM
            lat.add_metric(
                "pool_hbm_bytes",
                lambda **knobs: float(prefix_shared_pool_bytes(sig, knobs)),
            )
        if sig.kernel == "speculative":
            # expected useful tokens per verify step under the acceptance-1
            # prior; serving traffic's observed mean (acceptance < 1)
            # rescales these expectations through refine_from_runtime
            lat.add_metric(
                "tokens_per_step",
                lambda **knobs: float(int(knobs["draft_len"]) + 1),
            )
        results = lat.tune(sample=sample, seed=seed)

        feasible = [
            r for r in results
            if r["metrics"]["vmem_bytes"][0] <= self.vmem_budget
        ]
        pool = feasible or results
        best = min(pool, key=lambda r: r["metrics"]["latency_s"][0])
        entry = {
            "knobs": {k: v for k, v in best["knobs"].items()},
            "metrics": {m: list(v) for m, v in best["metrics"].items()},
            "ops": [
                {"knobs": r["knobs"],
                 "metrics": {m: list(v) for m, v in r["metrics"].items()}}
                for r in results
            ],
            "device": _device_tag(),
        }
        self.cache.put(self._key(sig), entry)
        self.tuned += 1
        return dict(best["knobs"])

    def get(self, sig: KernelSignature,
            measure: Callable[..., float] | None = None,
            **tune_kw) -> dict[str, int]:
        """Cached best knobs, tuning on first miss."""
        knobs = self.lookup(sig)
        if knobs is not None:
            return knobs
        return self.tune(sig, measure, **tune_kw)


# ---------------------------------------------------------------------------
# Default measurement (the real kernel, small reps)
# ---------------------------------------------------------------------------


def _default_measure(sig: KernelSignature) -> Callable[..., float]:
    import jax
    import jax.numpy as jnp

    dt = {"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(
        sig.dtype, jnp.float32
    )

    if sig.kernel == "flash_attention":
        from repro.kernels.flash_attention.ops import flash_attention

        B, S, H, K, D = sig.shape
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, S, H, D), dt)
        k = jax.random.normal(ks[1], (B, S, K, D), dt)
        v = jax.random.normal(ks[2], (B, S, K, D), dt)
        g = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

        def measure(**knobs):
            # training-step latency: forward + fused backward, so the DSE
            # sees both the fwd and the bwd block knobs.
            def loss(q, k, v):
                out = flash_attention(
                    q, k, v, causal=sig.causal, window=sig.window,
                    block_q=int(knobs["block_q"]),
                    block_kv=int(knobs["block_kv"]),
                    block_q_bwd=int(knobs.get("block_q_bwd",
                                              knobs["block_q"])),
                    block_kv_bwd=int(knobs.get("block_kv_bwd",
                                               knobs["block_kv"])),
                )
                return jnp.sum(out.astype(jnp.float32) * g)

            fn = jax.grad(loss, argnums=(0, 1, 2))
            jax.block_until_ready(fn(q, k, v))  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v))
            return time.perf_counter() - t0

        return measure

    if sig.kernel == "flash_decode":
        from repro.kernels.flash_attention.ops import flash_decode

        B, T, H, K, D = sig.shape
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, 1, H, D), dt)
        k = jax.random.normal(ks[1], (B, T, K, D), dt)
        v = jax.random.normal(ks[2], (B, T, K, D), dt)
        kv_new = jax.random.normal(ks[3], (B, 1, K, D), dt)
        index = jnp.full((B,), T - 1, jnp.int32)  # worst case: full cache

        def measure(**knobs):
            # a full cached-decode step: in-place cache update + attention,
            # so the DSE optimizes what serving actually pays per token.
            @jax.jit
            def step(q, k, v, kv_new, index):
                bidx = jnp.arange(B)
                k = k.at[bidx, index].set(kv_new[:, 0])
                v = v.at[bidx, index].set(kv_new[:, 0])
                return flash_decode(
                    q, k, v, index, window=sig.window,
                    block_kv=int(knobs["block_kv_dec"]),
                )

            jax.block_until_ready(step(q, k, v, kv_new, index))  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(step(q, k, v, kv_new, index))
            return time.perf_counter() - t0

        return measure

    if sig.kernel == "paged_decode":
        from repro.kernels.flash_attention.ops import flash_decode

        B, T, H, K, D = sig.shape
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, 1, H, D), dt)
        kv_new = jax.random.normal(ks[3], (B, 1, K, D), dt)
        index = jnp.full((B,), T - 1, jnp.int32)  # worst case: full cache

        def measure(**knobs):
            # a full *paged* decode step at the knob's pool geometry: the
            # page write + block-table-resolved attention, so the DSE sees
            # the page_size x block_kv_dec interaction end to end.
            ps = int(knobs["page_size"])
            nb = cdiv(T, ps)
            pool = B * nb
            k = jax.random.normal(ks[1], (pool, ps, K, D), dt)
            v = jax.random.normal(ks[2], (pool, ps, K, D), dt)
            tables = jnp.arange(pool, dtype=jnp.int32).reshape(B, nb)

            @jax.jit
            def step(q, k, v, kv_new, index, tables):
                bidx = jnp.arange(B)
                page = tables[bidx, index // ps]
                k = k.at[page, index % ps].set(kv_new[:, 0])
                v = v.at[page, index % ps].set(kv_new[:, 0])
                return flash_decode(
                    q, k, v, index, window=sig.window,
                    tables=tables, kv_len=T,
                    block_kv=int(knobs["block_kv_dec"]),
                )

            args = (q, k, v, kv_new, index, tables)
            jax.block_until_ready(step(*args))  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(step(*args))
            return time.perf_counter() - t0

        return measure

    if sig.kernel == "speculative":
        from repro.kernels.flash_attention.ops import flash_decode

        B, T, H, K, D = sig.shape
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        kv_full = jax.random.normal(ks[1], (B, T, K, D), dt)

        def measure(**knobs):
            # one widened-q verify step: write the draft block in place,
            # then score all draft_len+1 positions in a single kernel call
            # — what a speculative serving round pays on the target model.
            S = int(knobs["draft_len"]) + 1
            q = jax.random.normal(ks[0], (B, S, H, D), dt)
            kv_new = jax.random.normal(ks[3], (B, S, K, D), dt)
            index = jnp.full((B,), T - S, jnp.int32)  # worst case: near-full

            @jax.jit
            def step(q, k, v, kv_new, index):
                bidx = jnp.arange(B)
                slots = index[:, None] + jnp.arange(S)
                k = k.at[bidx[:, None], slots].set(kv_new)
                v = v.at[bidx[:, None], slots].set(kv_new)
                return flash_decode(
                    q, k, v, index, window=sig.window,
                    block_kv=int(knobs["block_kv_dec"]),
                )

            args = (q, kv_full, kv_full, kv_new, index)
            jax.block_until_ready(step(*args))  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(step(*args))
            return time.perf_counter() - t0

        return measure

    if sig.kernel == "quantized_cache":
        return _quantized_cache_measures(sig)[0]

    if sig.kernel == "rwkv6":
        from repro.kernels.rwkv6.ops import wkv_pallas

        B, S, H, C = sig.shape
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        r, k, v = (jax.random.normal(ks[i], (B, S, H, C)) for i in range(3))
        w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, C))))
        u = jax.random.normal(ks[4], (H, C))
        s0 = jnp.zeros((B, H, C, C))

        def measure(**knobs):
            fn = lambda: wkv_pallas(r, k, v, w, u, s0, chunk=int(knobs["chunk"]))[0]
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            return time.perf_counter() - t0

        return measure

    if sig.kernel == "rglru":
        from repro.kernels.rglru.ops import rglru_pallas

        B, S, D = sig.shape
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D)))
        b = jax.random.normal(ks[1], (B, S, D))
        h0 = jax.random.normal(ks[2], (B, D))

        def measure(**knobs):
            fn = lambda: rglru_pallas(
                a, b, h0, block_d=int(knobs["block_d"]), chunk=int(knobs["chunk"])
            )[0]
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            return time.perf_counter() - t0

        return measure

    if sig.kernel == "rmsnorm":
        from repro.kernels.rmsnorm.ops import rmsnorm

        rows, d = sig.shape
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (rows, d), dt)
        w = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)

        def measure(**knobs):
            fn = lambda: rmsnorm(x, w, block_rows=int(knobs["block_rows"]))
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            return time.perf_counter() - t0

        return measure

    raise KeyError(sig.kernel)


def _quantized_cache_measures(sig: KernelSignature):
    """(latency, error) measures for the quantized-cache DSE.

    Both run the real paged `flash_decode` over pools packed at the knob's
    geometry (interpret mode off-TPU).  The error measure is the mARGOt
    error model's ground truth: the max-abs deviation of the decode
    attention output between the quantized pool (per-page scales,
    in-kernel dequant) and the same values served fp — exactly what the
    serving path changes, so the accuracy Goal constrains what users see.
    Fp dtype arms score 0.0 by construction.  Per-geometry fp pools are
    memoized so every dtype arm compares against identical values."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import CACHE_QMAX, flash_decode
    from repro.runtime.pages import quantize_linear_pool

    B, T, H, K, D = sig.shape
    dt = {"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(
        sig.dtype, jnp.float32
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, 1, H, D), dt)
    index = jnp.full((B,), T - 1, jnp.int32)  # worst case: full cache
    fp_pools: dict[int, tuple] = {}

    def fp_pool(ps):
        if ps not in fp_pools:
            nb = cdiv(T, ps)
            pool = B * nb
            k = jax.random.normal(keys[1], (pool, ps, K, D), dt)
            v = jax.random.normal(keys[2], (pool, ps, K, D), dt)
            tables = jnp.arange(pool, dtype=jnp.int32).reshape(B, nb)
            fp_pools[ps] = (k, v, tables)
        return fp_pools[ps]

    def call(k, v, tables, blk, scales=None):
        ksc, vsc = scales if scales is not None else (None, None)
        return flash_decode(q, k, v, index, tables=tables, kv_len=T,
                            block_kv=blk, k_scale=ksc, v_scale=vsc)

    def latency(**knobs):
        ps, blk = int(knobs["page_size"]), int(knobs["block_kv_dec"])
        name = str(knobs["cache_dtype"])
        k, v, tables = fp_pool(ps)
        scales = None
        if name in CACHE_QMAX:
            k, v, ksc, vsc = quantize_linear_pool(k, v, name)
            scales = (ksc, vsc)
        fn = jax.jit(lambda: call(k, v, tables, blk, scales))
        jax.block_until_ready(fn())  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    def error(**knobs):
        ps, blk = int(knobs["page_size"]), int(knobs["block_kv_dec"])
        name = str(knobs["cache_dtype"])
        if name not in CACHE_QMAX:
            return 0.0
        k, v, tables = fp_pool(ps)
        ref = call(k, v, tables, blk)
        qk, qv, ksc, vsc = quantize_linear_pool(k, v, name)
        out = call(qk, qv, tables, blk, (ksc, vsc))
        return float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                     - ref.astype(jnp.float32))))

    return latency, error


# ---------------------------------------------------------------------------
# Process-wide default tuner (hot-path lookups)
# ---------------------------------------------------------------------------

_default_tuner: KernelTuner | None = None
_default_tuner_path: str | None = None


def default_tuner() -> KernelTuner:
    """Singleton over the default cache path (re-created if REPRO_TUNER_CACHE
    changes, so tests can redirect it)."""
    global _default_tuner, _default_tuner_path
    path = default_cache_path()
    if _default_tuner is None or _default_tuner_path != path:
        _default_tuner = KernelTuner(path)
        _default_tuner_path = path
    return _default_tuner


def tuned_flash_blocks(q_shape, kv_heads: int, dtype, *, causal: bool,
                       window: int | None = None) -> dict[str, int]:
    """Non-failing hot-path lookup used by ops.py: {} when untuned."""
    try:
        sig = flash_signature(q_shape, kv_heads, dtype, causal=causal,
                              window=window)
        return default_tuner().lookup(sig) or {}
    except Exception:  # pragma: no cover - never break the kernel call
        return {}


def tuned_decode_blocks(q_shape, cache_len: int, kv_heads: int, dtype, *,
                        window: int | None = None) -> dict[str, int]:
    """Non-failing decode-knob lookup used by ops.flash_decode: {} when
    untuned.  q_shape is the model layout (B, 1, H, D)."""
    try:
        B, _, H, D = q_shape
        sig = flash_decode_signature(B, cache_len, H, kv_heads, D, dtype,
                                     window=window)
        return default_tuner().lookup(sig) or {}
    except Exception:  # pragma: no cover - never break the kernel call
        return {}


def tuned_paged_blocks(q_shape, cache_len: int, kv_heads: int, dtype, *,
                       window: int | None = None) -> dict[str, int]:
    """Non-failing paged-decode knob lookup: {} when untuned.  Falls back
    to the un-paged `flash_decode` entry's block so a pool built before
    paged tuning ran still streams tuned-size blocks."""
    try:
        B, _, H, D = q_shape
        sig = paged_decode_signature(B, cache_len, H, kv_heads, D, dtype,
                                     window=window)
        knobs = default_tuner().lookup(sig)
        if knobs:
            return knobs
        return tuned_decode_blocks(q_shape, cache_len, kv_heads, dtype,
                                   window=window)
    except Exception:  # pragma: no cover - never break the kernel call
        return {}


def tuned_speculative_knobs(batch: int, cache_len: int, n_heads: int,
                            kv_heads: int, head_dim: int, dtype, *,
                            window: int | None = None) -> dict[str, int]:
    """Non-failing speculative-knob lookup (the serving runtime reads
    `draft_len` through the woven "speculative_draft_len" extra): {} when
    untuned — serving then falls back to plain one-token decode."""
    try:
        sig = speculative_signature(batch, cache_len, n_heads, kv_heads,
                                    head_dim, dtype, window=window)
        return default_tuner().lookup(sig) or {}
    except Exception:  # pragma: no cover - never break the serve path
        return {}


# ---------------------------------------------------------------------------
# Quantized-cache DSE: multi-objective (capacity under an accuracy goal)
# ---------------------------------------------------------------------------


def tune_quantized_cache(
    sig: KernelSignature,
    *,
    error_budget: float = 0.05,
    tuner: KernelTuner | None = None,
    measure: Callable[..., float] | None = None,
    error_measure: Callable[..., float] | None = None,
    sample: int | None = None,
    num_tests: int = 1,
    seed: int = 0,
) -> dict[str, Any]:
    """Run the quantized-cache DSE and persist best knobs + all rows.

    Multi-objective in the paper's precision-autotuning shape: every
    `cache_dtype × page_size × block_kv_dec` point records the analytic
    VMEM/HBM models, a measured decode latency AND a measured
    `max_logit_err` against the fp pool (the mARGOt error model); the
    selected point maximizes `tokens_per_hbm_byte` subject to the error
    staying under `error_budget` and VMEM under the tuner's budget.  The
    persisted entry records `error_budget` so `select_cache_knobs` can
    re-select under a tightened accuracy constraint without re-measuring.
    """
    tuner = tuner or default_tuner()
    if measure is None or error_measure is None:
        lat_m, err_m = _quantized_cache_measures(sig)
        measure = measure or lat_m
        error_measure = error_measure or err_m
    space = design_space(sig, vmem_budget=tuner.vmem_budget)
    B, T = sig.shape[0], sig.shape[1]

    lat = Lat(sig.key()).set_num_tests(num_tests)
    for name, values in space.items():
        lat.add_var(name, values)
    lat.add_metric("latency_s", measure)
    lat.add_metric("vmem_bytes", lambda **kn: config_vmem_bytes(sig, kn))
    lat.add_metric("pool_hbm_bytes",
                   lambda **kn: float(quantized_pool_bytes(sig, kn)))
    lat.add_metric(
        "tokens_per_hbm_byte",
        lambda **kn: float(B * T) / quantized_pool_bytes(sig, kn),
    )
    lat.add_metric("max_logit_err", error_measure)
    results = lat.tune(sample=sample, seed=seed)

    fits = [r for r in results
            if r["metrics"]["vmem_bytes"][0] <= tuner.vmem_budget]
    accurate = [r for r in fits
                if r["metrics"]["max_logit_err"][0] <= error_budget]
    pool = accurate or fits or results
    best = max(pool, key=lambda r: r["metrics"]["tokens_per_hbm_byte"][0])
    entry = {
        "knobs": dict(best["knobs"]),
        "metrics": {m: list(v) for m, v in best["metrics"].items()},
        "ops": [
            {"knobs": r["knobs"],
             "metrics": {m: list(v) for m, v in r["metrics"].items()}}
            for r in results
        ],
        "error_budget": float(error_budget),
        "device": _device_tag(),
    }
    tuner.cache.put(tuner._key(sig), entry)
    tuner.tuned += 1
    return dict(best["knobs"])


def select_cache_knobs(
    sig: KernelSignature,
    *,
    error_budget: float,
    tuner: KernelTuner | None = None,
) -> dict[str, Any] | None:
    """Re-select the quantized-cache knobs from the persisted DSE rows
    under a (possibly tightened) accuracy constraint — no re-measurement.

    A mARGOt State maximizes `tokens_per_hbm_byte` subject to
    `max_logit_err <= error_budget` and the VMEM budget; tightening the
    budget below the quantized arms' measured error forces the selection
    back onto the fp fallback arm.  The re-selected knobs and the new
    budget are persisted.  Returns None when the signature was never
    tuned."""
    tuner = tuner or default_tuner()
    entry = tuner.cache.get(tuner._key(sig))
    if entry is None or not entry.get("ops"):
        return None
    ops = [
        OperatingPoint(
            knobs=dict(row["knobs"]),
            metrics={m: tuple(v) for m, v in row["metrics"].items()},
        )
        for row in entry["ops"]
    ]
    state = State("cache", objective_metric="tokens_per_hbm_byte",
                  maximize=True)
    state.subject_to(Goal("vmem", "vmem_bytes", LE, float(tuner.vmem_budget)))
    state.subject_to(Goal("accuracy", "max_logit_err", LE,
                          float(error_budget)))
    best = Margot(KnowledgeBase(ops), [state]).update()
    knobs = {k: (v if isinstance(v, str) else int(v))
             for k, v in best.knobs.items()}
    new_entry = dict(entry)
    new_entry["knobs"] = knobs
    new_entry["metrics"] = {m: list(v) for m, v in best.metrics.items()}
    new_entry["error_budget"] = float(error_budget)
    tuner.cache.put(tuner._key(sig), new_entry)
    return knobs


# ---------------------------------------------------------------------------
# Runtime feedback: mARGOt observations refine the persisted DSE priors
# ---------------------------------------------------------------------------


def refine_from_runtime(
    sig: KernelSignature,
    observed: Mapping[str, float],
    *,
    tuner: KernelTuner | None = None,
    latency_budget: float | None = None,
    objective_knob: str | None = None,
) -> dict[str, int] | None:
    """Fold serving-time observations back into the persisted tuner cache.

    This is the paper's MAPE-K loop closed over the *persistent* knowledge
    base: the cached DSE rows become a mARGOt KnowledgeBase, the observed
    metric on the currently selected operating point yields an error
    coefficient (observed / expected) that rescales every expectation, and
    the operating point is re-selected — maximize the objective knob (by
    default the entry's largest-granularity knob, e.g. `page_size`:
    fewer, larger pages mean smaller tables and less fragmentation)
    subject to the adjusted latency staying under `latency_budget` and the
    analytic VMEM model under the tuner's budget.  The *adjusted* operating
    points and the re-selected knobs are persisted, so the next process
    serving this signature starts from traffic-refined priors.

    Returns the re-selected knobs, or None when the signature was never
    tuned (runtime feedback refines priors; it does not create them).
    """
    tuner = tuner or default_tuner()
    entry = tuner.cache.get(tuner._key(sig))
    if entry is None or not entry.get("ops"):
        return None
    if objective_knob is None:
        names = list(KERNEL_SPACES.get(sig.kernel, entry["knobs"]))
        # categorical knobs (cache_dtype) can't be a maximize objective —
        # default to the first numeric knob of the space
        numeric = [n for n in names
                   if not isinstance(entry["knobs"].get(n, 0), str)]
        objective_knob = (numeric or names)[0]

    ops = []
    for row in entry["ops"]:
        metrics = {m: tuple(v) for m, v in row["metrics"].items()}
        metrics[f"knob:{objective_knob}"] = (
            float(row["knobs"].get(objective_knob, 0)), 0.0)
        ops.append(OperatingPoint(knobs=dict(row["knobs"]), metrics=metrics))
    state = State("serve", objective_metric=f"knob:{objective_knob}",
                  maximize=True)
    state.subject_to(Goal("vmem", "vmem_bytes", LE, float(tuner.vmem_budget)))
    if latency_budget is not None:
        state.subject_to(Goal("latency", "latency_s", LE,
                              float(latency_budget)))
    margot = Margot(KnowledgeBase(ops), [state])
    current_key = tuple(sorted(entry["knobs"].items()))
    margot.current = next(
        (op for op in ops if op.key() == current_key), ops[0])
    for metric, value in observed.items():
        margot.observe(metric, float(value))
    best = margot.update()

    coefs = dict(margot._error_coef)
    adjusted_ops = []
    for row in entry["ops"]:
        metrics = {
            m: [v[0] * coefs.get(m, 1.0), v[1] * coefs.get(m, 1.0)]
            for m, v in row["metrics"].items()
        }
        adjusted_ops.append({"knobs": dict(row["knobs"]), "metrics": metrics})
    knobs = {k: (v if isinstance(v, str) else int(v))
             for k, v in best.knobs.items()}
    new_entry = dict(entry)  # keep error_budget / device / extra columns
    new_entry.update({
        "knobs": knobs,
        "metrics": {
            m: [v[0] * coefs.get(m, 1.0), v[1] * coefs.get(m, 1.0)]
            for m, v in best.metrics.items() if not m.startswith("knob:")
        },
        "ops": adjusted_ops,
        "runtime": {
            "error_coef": coefs,
            "observed": {m: float(v) for m, v in observed.items()},
            "latency_budget": latency_budget,
        },
    })
    tuner.cache.put(tuner._key(sig), new_entry)
    return knobs
