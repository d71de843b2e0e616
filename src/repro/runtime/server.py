"""Serving runtime: batched prefill+decode with mARGOt QoS adaptation.

This is the UC2 (navigation) runtime shape: requests arrive with a prompt,
the server prefils then decodes N tokens; the woven knobs (precision
variant, decode budget, memoization on/off) are adapted by mARGOt against a
quality index + latency/cost constraints — reproducing the paper's
NQI-vs-cost trade-off (Figs. 17–19) in benchmarks/navigation_autotune.py.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.strategies.resilience import (
    DEFAULT_POLICY,
    FaultError,
    NonFiniteLogits,
)
from repro.core.weaver import WovenProgram
from repro.distributed.fault import Watchdog
from repro.memo.table import MemoTable
from repro.monitor.examon import ExamonBroker, get_default_broker
from repro.monitor.sensors import apply_wrappers
from repro.monitor.spans import SpanRecorder
from repro.nn.module import init_params
from repro.runtime.pages import (
    PagedCacheManager,
    PoolAuditor,
    PoolExhausted,
    cdiv,
    paged_compatible,
)
from repro.runtime.steps import (
    build_decode_step,
    build_paged_prefill_step,
    build_prefill_step,
    stack_request_caches,
)
from repro.versioning.libvc import LibVC

# the phases of a `wave` event's `host_ms`: the host spans of the serving
# loop and the page pool, each by its self time, and `consumer`, the time
# serve_stream sat suspended at its yields while its caller worked
WAVE_PHASES = (
    "serve.wave",           # the loop's own work outside every span below
    "serve.admit",          # one admission (rid), one-shot prefill included
    "serve.retire",         # one retirement (rid)
    "serve.prefill_chunk",  # one chunk of a chunked admission (rid)
    "serve.dispatch",       # a jitted step's inputs and call, until it returns
    "serve.wait",           # a blocking read-back of a step's results
    "serve.emit",           # the step's tokens into outputs and events
    "serve.qos",            # the QoS governor's decide and observe
    "pages.compose",        # PagedCacheManager.batch: grow, CoW, compose
    "pages.absorb",         # PagedCacheManager.absorb
    "pages.prefill_view",   # a prefill or re-score view of the pool
    "pages.absorb_prefill",  # a prefill chunk's pool writes
    "pages.admit",          # admission bookkeeping (can_admit, admit_*)
    "pages.release",        # retire / abort: pages back to the pool
    "consumer",
)


def _host_ms(self_s: dict[str, float]) -> dict[str, float]:
    """Milliseconds of self time per phase, every documented phase."""
    out = {p: 0.0 for p in WAVE_PHASES}
    for name, secs in self_s.items():
        out[name] = out.get(name, 0.0) + 1e3 * secs
    return out


@dataclasses.dataclass
class ServerConfig:
    max_cache_len: int = 256
    decode_tokens: int = 8
    seed: int = 0
    # paged / continuous-batching serving (serve_continuous)
    page_size: int | None = None   # None: woven knob or 128 default
    pool_pages: int | None = None  # None: sized for full concurrency
    max_batch: int | None = None   # decode-batch cap (admission gate)
    prefix_sharing: bool = True    # map common prompt prefixes onto shared pages
    # speculative decoding (serve_continuous): tokens the draft model
    # proposes per verify round; None/0 falls back to the woven
    # "speculative_draft_len" knob, then to plain one-token decode
    draft_len: int | None = None
    # quantized page pool (serve_continuous): "int8" / "float8_e4m3fn" /
    # "float8_e5m2" stores pk/pv quantized with per-page-per-KV-head scale
    # sidecars; None falls back to the woven "flash_cache_dtype" knob, and
    # fp names (the tuner's accuracy-fallback arm) mean: keep the fp pool
    cache_dtype: str | None = None
    # resilience (serve_continuous): per-request SLO, bounded retry budget
    # around transient step faults, and PoolAuditor barriers; None falls
    # back to the woven "serve_resilience" policy (ResilienceAspect), then
    # to resilience.DEFAULT_POLICY
    deadline_s: float | None = None
    retries: int | None = None
    pool_audit: bool | None = None
    # QoS-adaptive streaming (serve_stream): tokens of a long admission
    # prefilled per decode wave (0/None: one-shot admission, unless a woven
    # QoS governor drives the knob), and per-request latency SLOs threaded
    # into the QoS policy (TTFT and per-token gap, seconds)
    prefill_chunk: int | None = None
    slo_ttft_s: float | None = None
    slo_tok_s: float | None = None


class Server:
    def __init__(self, woven: WovenProgram, cfg: ServerConfig, *, mesh=None,
                 margot=None, broker: ExamonBroker | None = None,
                 memo: MemoTable | None = None, draft: "Server | None" = None,
                 device: jax.Device | None = None):
        self.woven = woven
        self.cfg = cfg
        # the device holding this replica's params and page pool (None:
        # JAX's default device); every step runs where its params live
        self.device = device
        # draft server for speculative decoding (registry `draft_for`
        # pairing, or any Server over the same vocab); None self-drafts
        # when a draft_len is requested
        self.draft = draft
        self.mesh = mesh
        self.margot = margot
        self.broker = broker or get_default_broker()
        self.memo = memo if memo is not None else woven.state.extra.get("memo_table")
        self.info: dict[str, Any] = {"task_name": woven.program.cfg.name, "knobs": {}}

        def build(kind):
            def builder(variant: str):
                v = None if variant == "__default__" else variant
                if kind == "prefill":
                    fn = build_prefill_step(self.woven, mesh=self.mesh, variant=v)
                elif kind == "probe":
                    # 1-token structure probe for the paged pool: a copied
                    # state pins cache_max_len=0 so the probe cache never
                    # materializes a dense max_len transient
                    fn = build_prefill_step(self.woven, mesh=self.mesh,
                                            variant=v, cache_max_len=0)
                elif kind == "paged_prefill":
                    # the pool cache is donated: the suffix scatter updates
                    # the page buffers in place, so admission's transient
                    # is bounded by the live prompt (one layer at a
                    # time), never a functional copy of the whole pool
                    # (admit_finish replaces the manager's handles with
                    # the step's outputs immediately after)
                    fn = build_paged_prefill_step(self.woven, mesh=self.mesh,
                                                  variant=v)
                    return jax.jit(fn, static_argnames=("prefix_len",),
                                   donate_argnums=(2,))
                elif kind == "rescore":
                    # NOT donated: the re-score step passes the pool
                    # buffers through untouched and its output is
                    # discarded — donating would invalidate the manager's
                    # live handles with nothing to replace them
                    fn = build_decode_step(self.woven, mesh=self.mesh,
                                           variant=v, rescore=True)
                else:
                    # the cache is donated on the decode hot path: the
                    # in-place scatter updates the (possibly pool-sized)
                    # buffers without a functional copy per token; every
                    # caller rebinds the cache to the step's output
                    # (serve/serve_batch loops, manager.absorb)
                    fn = build_decode_step(self.woven, mesh=self.mesh, variant=v)
                    return jax.jit(fn, donate_argnums=(2,))
                return jax.jit(fn)

            # a variant that fails to build raises: falling back to the
            # default would serve a different program than the one asked for
            return LibVC(builder, error_strategy="exit")

        self.prefill_vc = build("prefill")
        self.decode_vc = build("decode")
        self.probe_vc = build("probe")
        self.paged_prefill_vc = build("paged_prefill")
        self.rescore_vc = build("rescore")
        self.params = init_params(woven.program.model, jax.random.PRNGKey(cfg.seed),
                                  woven.state.policies, device=device)
        self.served = 0
        # latency histories are sliding windows (deques), not unbounded
        # lists: a long-running serve_stream session appends per wave, and
        # the feedback consumers (refine_kernel_tuner, the launchers'
        # percentile prints) only ever want recent observations anyway
        self.history_window = 4096
        self.latencies: deque[float] = deque(maxlen=self.history_window)
        self.decode_step_latencies: deque[float] = \
            deque(maxlen=self.history_window)  # serve_stream steps
        self._step_lat_by_batch: dict[int, deque[float]] = {}
        self._paged_sig = None  # last paged-decode signature served
        self._paged_dtype = None
        self.last_pool_stats: dict[str, Any] | None = None  # serve_continuous
        self.last_spec_stats: dict[str, Any] | None = None  # speculative serve
        self.last_fault_stats: dict[str, Any] | None = None  # resilience layer
        self.last_outcomes: list[dict[str, Any]] | None = None  # per request
        self.last_qos_stats: dict[str, Any] | None = None  # QoS governor
        self._last_admit_rescored = False  # last admission was a re-score
        self._last_admit_computed = 0  # prompt tokens its prefill computed
        self._verify_steps: dict[tuple, Callable] = {}  # (variant, S) -> fn

    def _variant(self) -> str | None:
        if self.margot is None:
            return None
        op = self.margot.update()
        self.info["knobs"].update(op.knobs)
        return op.knobs.get("variant") or op.knobs.get("precision_mix")

    def serve(self, tokens: np.ndarray, *, decode_tokens: int | None = None) -> np.ndarray:
        """tokens: (B, S) prompt -> (B, N) generated ids (greedy)."""
        n = decode_tokens or self.cfg.decode_tokens
        key = ("serve", tokens.tobytes(), n)
        if self.memo is not None and self.memo.running:
            hit, out = self.memo.lookup(key)
            if hit:
                return out
        t0 = time.perf_counter()
        variant = self._variant()
        state = self.woven.variant_state(
            None if variant in (None, "__default__") else variant
        )
        state.extra["cache_max_len"] = self.cfg.max_cache_len

        toks = jnp.asarray(tokens)
        B, S = toks.shape
        logits, cache = self.prefill_vc(variant, self.params, {"tokens": toks})
        outs = []
        pos = S
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        for _ in range(n):
            outs.append(tok)
            logits, cache = self.decode_vc(
                variant, self.params,
                {"tokens": tok, "positions": jnp.full((B, 1), pos, jnp.int32)},
                cache,
            )
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            pos += 1
        result = np.asarray(jnp.concatenate(outs, axis=1))
        dt = time.perf_counter() - t0
        self.latencies.append(dt)
        self.served += 1
        self.broker.publish(f"serve/latency/@host{jax.process_index()}", dt)
        if self.margot is not None:
            self.margot.observe("latency", dt)
        if self.memo is not None:
            self.memo.update(key, result)
        return result

    def serve_batch(self, prompts: list[np.ndarray], *,
                    decode_tokens: int | None = None) -> list[np.ndarray]:
        """Serve several requests — of *different* prompt lengths — as one
        batched decode: per-request prefill (each at its own length), caches
        stacked with per-request `index`, then a single decode loop at batch
        size B with per-request positions.  This is the layout the
        flash_decode kernel is built for: every request prunes its own live
        cache blocks through the scalar-prefetched index vector.

        Returns one (decode_tokens,) int array per request; greedy decode,
        bit-identical to serving each request alone.
        """
        n = decode_tokens or self.cfg.decode_tokens
        key = ("serve_batch", tuple(np.asarray(p).tobytes() for p in prompts), n)
        if self.memo is not None and self.memo.running:
            hit, out = self.memo.lookup(key)
            if hit:
                return out
        t0 = time.perf_counter()
        variant = self._variant()
        state = self.woven.variant_state(
            None if variant in (None, "__default__") else variant
        )
        state.extra["cache_max_len"] = self.cfg.max_cache_len

        caches, first_toks = [], []
        for p in prompts:
            toks = jnp.asarray(p, jnp.int32).reshape(1, -1)
            logits, cache = self.prefill_vc(variant, self.params,
                                            {"tokens": toks})
            caches.append(cache)
            first_toks.append(jnp.argmax(logits[0, -1], axis=-1))
        cache = stack_request_caches(self.woven.program.model, caches)

        B = len(prompts)
        pos = jnp.asarray([np.asarray(p).reshape(-1).shape[0] for p in prompts],
                          jnp.int32)
        tok = jnp.stack(first_toks).reshape(B, 1).astype(jnp.int32)
        outs = []
        for _ in range(n):
            outs.append(tok)
            logits, cache = self.decode_vc(
                variant, self.params,
                {"tokens": tok, "positions": pos[:, None]},
                cache,
            )
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            pos = pos + 1
        stacked = np.asarray(jnp.concatenate(outs, axis=1))
        result = [stacked[b] for b in range(B)]
        dt = time.perf_counter() - t0
        self.latencies.append(dt)
        self.served += B
        self.broker.publish(f"serve/latency/@host{jax.process_index()}", dt)
        if self.margot is not None:
            self.margot.observe("latency", dt)
        if self.memo is not None:
            self.memo.update(key, result)
        return result

    # -- paged pool + continuous batching -----------------------------------------

    def _page_size(self, state) -> int:
        from repro.kernels.flash_attention.ops import DEFAULT_PAGE_SIZE

        ps = self.cfg.page_size or state.extra.get("flash_page_size") \
            or DEFAULT_PAGE_SIZE
        return max(1, min(int(ps), self.cfg.max_cache_len))

    def _cache_dtype(self, state) -> str | None:
        """Resolved pool-quantization dtype name: explicit config wins,
        then the woven "flash_cache_dtype" knob.  Names outside CACHE_QMAX
        (the tuner's fp fallback arm, e.g. "float16") mean unquantized."""
        from repro.kernels.flash_attention.ops import CACHE_QMAX

        name = self.cfg.cache_dtype or state.extra.get("flash_cache_dtype")
        if name is None:
            return None
        name = str(name)
        return name if name in CACHE_QMAX else None

    def _resilience(self, state) -> dict[str, Any]:
        """Resolved recovery policy: resilience.DEFAULT_POLICY under the
        woven "serve_resilience" extra (ResilienceAspect), with explicit
        ServerConfig fields winning."""
        pol = dict(DEFAULT_POLICY)
        pol.update(state.extra.get("serve_resilience") or {})
        if self.cfg.deadline_s is not None:
            pol["deadline_s"] = float(self.cfg.deadline_s)
        if self.cfg.retries is not None:
            pol["retries"] = int(self.cfg.retries)
        if self.cfg.pool_audit is not None:
            pol["pool_audit"] = bool(self.cfg.pool_audit)
        return pol

    def _paged_admit(self, manager: PagedCacheManager, rid, prompt,
                     final_len: int, variant, inj=None) -> tuple[int, Any]:
        """Admit one request into the page pool, prefilling *directly into
        pool pages*, and return its first output token.

        The first admission runs a 1-token structure probe (cheap: the
        probe cache is unpadded) to learn the pool's group structure and
        dtypes; every admission then matches the prompt against the prefix
        index — full-page hits map shared physical pages and only the
        non-shared suffix is prefilled, a full-prompt hit skips prefill
        entirely and re-scores the last prompt token for its logits.
        Peak HBM per admission is O(live prompt tokens) for one layer
        at a time — only the non-shared suffix is *computed* — never the
        all-layer dense O(max_len) cache the packing path used to build.

        Returns (first token, fired "paged_prefill" fault spec or None) —
        the join point consults `inj` (a woven FaultInjector) right before
        the dispatch, and the first-token logits are checked finite: a
        NaN/Inf admission rolls its partial pool state back and raises
        `NonFiniteLogits` for the caller's structured-rejection path.
        """
        rec = manager.spans
        toks = jnp.asarray(prompt, jnp.int32).reshape(1, -1)
        toks_np = np.asarray(prompt, np.int64).reshape(-1)
        S = int(toks.shape[1])
        if not manager.has_structure:
            self._init_pool_structure(manager, toks, variant)
        shared_pages, shared_len = manager.match_prefix(toks_np)
        if shared_len >= S:
            # Long prompts compute their unshared first token through the
            # blocked online-softmax path (_attend_dense, S > 2*block);
            # the re-score step's one-shot decode softmax is a different
            # numeric family, so a full-prompt share would break shared ==
            # unshared bit-parity.  Trim the share to keep >= 1 suffix
            # token: the suffix prefill uses the same blocked path.
            state_extra = self.woven.variant_state(
                None if variant in (None, "__default__") else variant
            ).extra
            if S > 2 * int(state_extra.get("xla_attn_block", 1024)):
                ps = manager.page_size
                if shared_len % ps:      # drop the shared tail page
                    shared_pages = shared_pages[:-1]
                    shared_len = (S // ps) * ps
                if shared_len >= S:      # page-aligned prompt: drop a page
                    shared_pages = shared_pages[:-1]
                    shared_len -= ps
        self._last_admit_rescored = shared_len >= S
        self._last_admit_computed = 0 if shared_len >= S else S - shared_len
        # "paged_prefill" join point fires before any pool allocation, so a
        # raise-kind fault leaves nothing to roll back (the caller's abort
        # is then a no-op); a nan-kind poisons the logits below, driving
        # the same non-finite detector a real NaN would hit
        spec = inj.fire("paged_prefill", rid=rid) if inj is not None else None
        if shared_len >= S:
            manager.admit_shared(rid, toks_np, final_len=final_len,
                                 pages=shared_pages)
            view = manager.rescore_view(rid)
            with rec.span("serve.dispatch"):
                logits, _ = self.rescore_vc(
                    variant, self.params,
                    {"tokens": toks[:, -1:],
                     "positions": jnp.full((1, 1), S - 1, jnp.int32)},
                    view,
                )
        else:
            view, start = manager.admit_begin(
                rid, toks_np, final_len=final_len,
                shared_pages=shared_pages, shared_len=shared_len)
            with rec.span("serve.dispatch"):
                pos = jnp.arange(start, S, dtype=jnp.int32)[None]
                logits, new_cache = self.paged_prefill_vc(
                    variant, self.params,
                    {"tokens": toks[:, start:], "positions": pos},
                    view, prefix_len=start,
                )
            manager.admit_finish(rid, new_cache, toks_np)
        if spec is not None and spec.kind == "nan_logits":
            logits = jnp.full_like(logits, jnp.nan)
        return self._first_of(manager, rid, logits), spec

    def _init_pool_structure(self, manager: PagedCacheManager, toks,
                             variant) -> None:
        """Learn the pool's groups and dtypes from a 1-token probe
        prefill (the first admission of a serve)."""
        with manager.spans.span("serve.dispatch"):
            _, probe = self.probe_vc(variant, self.params,
                                     {"tokens": toks[:, :1]})
        if not paged_compatible(probe):
            raise ValueError(
                "model cache is not paged-compatible (SSM/recurrent "
                "state) — use serve_batch")
        ring = manager.window is not None and manager.window < toks.shape[1]
        manager.init_structure(probe, ring=ring)

    @staticmethod
    def _first_of(manager: PagedCacheManager, rid, logits) -> int:
        """The first output token of an admission, read back from its
        prefill logits; non-finite logits roll the admission back and
        raise `NonFiniteLogits`."""
        with manager.spans.span("serve.wait"):
            last = logits[0, -1]
            finite = bool(np.isfinite(float(
                jnp.max(last.astype(jnp.float32)))))
            tok = int(jnp.argmax(last, axis=-1)) if finite else None
        if not finite:
            manager.abort(rid)
            raise NonFiniteLogits(
                f"non-finite prefill logits for request {rid!r}")
        return tok

    def _admit_grouped(self, manager: PagedCacheManager, rid, prompt,
                       final_len: int, first_tok: int) -> int | None:
        """Identical-prompt group admission: the member's full prompt is
        already pool-resident (its donor was just admitted through the
        re-score path), so it maps the donor's pages and reuses the donor's
        re-scored first token — the group shares ONE re-score step instead
        of running one per member.  Returns None (caller falls back to a
        full `_paged_admit`) if the prompt is no longer a full-prefix hit,
        e.g. the donor's pages were retired between the scan and now."""
        toks_np = np.asarray(prompt, np.int64).reshape(-1)
        pages, shared_len = manager.match_prefix(toks_np)
        if shared_len < toks_np.shape[0]:
            return None
        manager.admit_shared(rid, toks_np, final_len=final_len, pages=pages)
        return int(first_tok)

    def _verify_step(self, variant, draft_len: int) -> Callable:
        """Compiled widened-q verify step (S = draft_len + 1 q tokens per
        request), cached per (variant, draft_len); the cache is donated
        exactly like the plain decode step (manager.absorb rebinds)."""
        from repro.runtime.steps import build_verify_step

        key = (variant, draft_len)
        fn = self._verify_steps.get(key)
        if fn is None:
            v = None if variant in (None, "__default__") else variant
            fn = jax.jit(build_verify_step(self.woven, mesh=self.mesh,
                                           variant=v, draft_len=draft_len),
                         donate_argnums=(2,))
            self._verify_steps[key] = fn
        return fn

    def _qos_governor(self, state, qos, slo_ttft_s=None, slo_tok_s=None):
        """Resolve the serving QoS control plane: an explicit QoSGovernor
        (or policy dict) argument wins, then a woven `qos_governor`
        instance, then the woven `serve_qos` policy (QoSAspect); `False`
        forces it off.  ServerConfig / argument SLOs override the policy's
        before the governor is built."""
        from repro.runtime.qos import QoSGovernor

        if qos is False:
            return None
        if isinstance(qos, QoSGovernor):
            return qos
        if qos is None:
            gov = state.extra.get("qos_governor")
            if gov is not None:
                return gov
        pol = qos if isinstance(qos, dict) else state.extra.get("serve_qos")
        if pol is None:
            return None
        pol = dict(pol)
        if self.cfg.slo_ttft_s is not None:
            pol["slo_ttft_s"] = float(self.cfg.slo_ttft_s)
        if self.cfg.slo_tok_s is not None:
            pol["slo_tok_s"] = float(self.cfg.slo_tok_s)
        if slo_ttft_s is not None:
            pol["slo_ttft_s"] = float(slo_ttft_s)
        if slo_tok_s is not None:
            pol["slo_tok_s"] = float(slo_tok_s)
        if not pol.get("enabled", True):
            return None
        return QoSGovernor(pol, broker=self.broker)

    def _qos_enabled(self, qos) -> bool:
        """Cheap pre-check (no governor construction) used by the memo
        gate: would serve_stream run under a QoS control plane?"""
        if qos is False:
            return False
        if qos is not None:
            return True
        extra = self.woven.state.extra
        return extra.get("qos_governor") is not None \
            or extra.get("serve_qos") is not None

    def _paged_admit_chunked(self, manager: PagedCacheManager, rid, prompt,
                             final_len: int, variant, inj=None,
                             chunk: int = 0):
        """Chunked direct-to-pool admission: reserve the block table up
        front, then prefill page-aligned `chunk`-token slices of the
        non-shared suffix one call at a time, so a long admission spreads
        across decode waves instead of stalling the in-flight batch.

        Returns (tok, spec, cont):
          * tok set, cont None — the admission completed in one shot
            (full-prompt prefix hit, ring pool, blocked-softmax prompt, or
            a suffix that fits one chunk): delegated to `_paged_admit`;
          * tok None, cont a closure — call cont() once per wave: it
            returns {"tok": None, "resident": r, "chunk": c} after an
            interior chunk and {"tok": first_token, ...} once the final
            chunk ran (admit_finish absorbed the pool and registered the
            prefix).  A non-finite final chunk aborts the pool state and
            raises NonFiniteLogits exactly like the one-shot path.

        Parity: chunk boundaries are rounded down to page multiples (the
        shared prefix is page-aligned, so every pool page is written by
        exactly one dispatch and quantized first-write scales match a
        one-shot prefill), and each interior chunk runs the same widened-q
        suffix-over-prefix shape a prefix-sharing admission uses — already
        bit-identical to the dense one-shot prefill by the prefix-sharing
        parity suites.  Prompts on the blocked-softmax path (S > 2 *
        xla_attn_block) keep the one-shot prefill: their logits come from
        a different (blocked online-softmax) numeric family and splitting
        would change them.  Ring pools keep it too (eviction on write
        breaks the resident-prefix invariant between chunks).  The
        "paged_prefill" join point fires once, at reservation time,
        exactly like the one-shot path fires it before pool allocation.
        """
        toks = jnp.asarray(prompt, jnp.int32).reshape(1, -1)
        toks_np = np.asarray(prompt, np.int64).reshape(-1)
        S = int(toks.shape[1])
        if not manager.has_structure:
            self._init_pool_structure(manager, toks, variant)
        state_extra = self.woven.variant_state(
            None if variant in (None, "__default__") else variant
        ).extra
        blocked = S > 2 * int(state_extra.get("xla_attn_block", 1024))
        shared_pages, shared_len = manager.match_prefix(toks_np)
        ps = manager.page_size
        step = max(ps, (int(chunk) // ps) * ps)  # page-aligned, >= 1 page
        if (shared_len >= S or manager._ring_pool() or blocked
                or S - shared_len <= step):
            tok, spec = self._paged_admit(manager, rid, prompt, final_len,
                                          variant, inj=inj)
            return tok, spec, None
        self._last_admit_rescored = False
        spec = inj.fire("paged_prefill", rid=rid) if inj is not None else None
        _, start = manager.admit_begin(
            rid, toks_np, final_len=final_len,
            shared_pages=shared_pages, shared_len=shared_len)
        st = {"done": start}

        def cont() -> dict:
            done = st["done"]
            end = min(done + step, S)
            # the view is rebuilt per chunk, never cached: the dispatches
            # interleaved between chunks (decode steps, other admissions)
            # donate the pool arrays, so a held view would reference
            # deleted buffers — prefill_view rebinds to the live pools
            view = manager.prefill_view(rid, done)
            with manager.spans.span("serve.dispatch"):
                pos = jnp.arange(done, end, dtype=jnp.int32)[None]
                logits, new_cache = self.paged_prefill_vc(
                    variant, self.params,
                    {"tokens": toks[:, done:end], "positions": pos},
                    view, prefix_len=done)
            if end < S:
                manager.absorb_prefill(rid, new_cache)
                st["done"] = end
                return {"tok": None, "resident": end, "chunk": end - done}
            manager.admit_finish(rid, new_cache, toks_np)
            if spec is not None and spec.kind == "nan_logits":
                logits = jnp.full_like(logits, jnp.nan)
            return {"tok": self._first_of(manager, rid, logits),
                    "resident": S, "chunk": end - done}

        return None, spec, cont

    @staticmethod
    def _draft_sync(draft_srv: "Server", dmanager: PagedCacheManager,
                    rids, active, outputs, lengths) -> None:
        """Restore the speculative lockstep invariant (draft resident
        length == target accepted length at round start) by replaying the
        target's emitted tokens through the draft cache.  Static-k serves
        never need this — rollback keeps both pools in sync — but a QoS
        governor that lowers draft_len to 0 for some waves leaves the
        draft behind by the tokens those plain waves emitted."""
        for r in rids:
            dlen = int(dmanager._meta[r]["length"])
            tgt = int(active[r]["pos"])
            while dlen < tgt:
                # slot p holds sequence token p; for p >= prompt length
                # that token is outputs[p - S]
                t = outputs[r][dlen - lengths[r]]
                dcache = dmanager.batch([r])
                _, dnew = draft_srv.decode_vc(
                    None, draft_srv.params,
                    {"tokens": jnp.asarray([[t]], jnp.int32),
                     "positions": jnp.asarray([[dlen]], jnp.int32)},
                    dcache)
                dmanager.absorb([r], dnew)
                dlen += 1

    def serve_continuous(self, prompts: list[np.ndarray], *,
                         decode_tokens: int | None = None,
                         page_size: int | None = None,
                         pool_pages: int | None = None,
                         max_batch: int | None = None,
                         prefix_sharing: bool | None = None,
                         draft_len: int | None = None,
                         draft: "Server | None" = None,
                         fault_injector=None,
                         deadline_s: float | None = None,
                         pool_audit: bool | None = None,
                         preemption=None,
                         prefill_chunk: int | None = None,
                         qos=None,
                         arrival_waves=None,
                         slo_ttft_s: float | None = None,
                         slo_tok_s: float | None = None,
                         on_event=None) -> list[np.ndarray]:
        """Continuous batching over a prefix-shared paged KV-cache pool.

        This is the thin compatibility wrapper over the `serve_stream`
        event loop: it handles the memo table (the stream engine never
        touches it), drains the per-token event stream (`on_event`
        receives each event dict when given), and returns the collected
        outputs — token-for-token identical to what the pre-stream
        monolith produced.

        Unlike `serve_batch` — which prefils everything up front, pads
        every request's cache to the same length and decodes the fixed
        batch in lockstep — this scheduler re-forms the decode batch every
        step: waiting requests are admitted as soon as the page pool can
        cover their worst-case growth (and a decode slot is free), each
        admitted request prefils its *non-shared prompt suffix* straight
        into freshly allocated pool pages (common prefixes map existing
        physical pages through the refcounted prefix index; the first
        write into a still-shared page splits it copy-on-write), and
        finished requests retire immediately, releasing their references
        for the next admission.  HBM scales with the *distinct live*
        tokens in flight — shared system prompts are stored once — and a
        long request never blocks a short one from entering mid-flight.

        Greedy decode, bit-identical per request to `serve` / `serve_batch`
        (the paged kernel streams the same live blocks in the same order —
        only the DMA source is page-table-indirected, and shared pages
        hold exactly the bytes an exclusive prefill would have written).
        Requires a cache family the pool can host (attention KV caches);
        SSM / recurrent state models raise — use `serve_batch`.

        Speculative decoding (`draft_len` = k > 0, explicit, from
        ServerConfig, or from the woven "speculative_draft_len" knob): a
        draft model (`draft`, the constructor's pairing, or this server
        itself) proposes k greedy tokens per round from its own page pool,
        and the target scores all k+1 positions in ONE widened-q verify
        step; the longest draft prefix matching the target's own argmax
        chain is accepted, the rejected tail rolls back via O(1)
        refcount truncation (no page copies).  Every emitted token is a
        target argmax, so the output is bit-identical to plain greedy —
        the draft only changes how many target steps it takes.  Ring
        pools fall back to plain decode (eviction breaks the widened
        mask); acceptance stats land in `last_spec_stats`.

        Resilience (woven ResilienceAspect, or the `fault_injector` /
        `deadline_s` / `pool_audit` arguments): faults are isolated
        per-request instead of killing the serve.  Failed or oversized
        admissions get structured `last_outcomes` entries; NaN/Inf logits
        quarantine only the victim (its pages retire, the batch re-forms);
        draft faults degrade speculation to plain decode; overdue requests
        retire with partial output and a `deadline_exceeded` marker;
        transient step faults retry with bounded backoff.  Survivors'
        tokens stay bit-identical to a fault-free serve, and
        `last_fault_stats` / ExaMon `serve/fault/*` topics record every
        event (zero events when nothing is woven).

        Graceful drain (`preemption`, a PreemptionHandler or anything with
        a `.pending` bool): once preemption is requested — SIGTERM on a
        real host, `request()` in tests — no new request is admitted;
        every in-flight request finishes its full decode normally, and the
        undrained waiting queue returns structured `drained` outcomes (so
        a fleet layer can hand those requests to a peer replica).
        """
        if not prompts:
            return []
        n = decode_tokens or self.cfg.decode_tokens
        k = draft_len if draft_len is not None else self.cfg.draft_len
        key = ("serve_continuous",
               tuple(np.asarray(p).tobytes() for p in prompts), n)
        if k:  # spec serves memoize separately (same tokens, different stats)
            key = key + (int(k),)
        cache_dtype = self._cache_dtype(self.woven.state)
        if cache_dtype:  # quantized pools emit different (clipped) logits
            key = key + (("cache_dtype", cache_dtype),)
        # armed fault injection and deadline policies make a serve
        # non-reproducible from its prompt key alone (the memo key carries
        # no pool geometry or fault schedule): bypass the memo entirely —
        # a hit would skip every join point, an update would poison the
        # table with fault-shaped outputs
        pre_inj = fault_injector if fault_injector is not None \
            else self.woven.state.extra.get("fault_injector")
        pre_deadline = deadline_s if deadline_s is not None \
            else self._resilience(self.woven.state)["deadline_s"]
        chunk_pre = prefill_chunk if prefill_chunk is not None \
            else self.cfg.prefill_chunk
        # a preemptible serve may drain mid-queue — same non-reproducibility.
        # Chunked/QoS/arrival serves keep token bit-parity but carry
        # per-wave stats and governor state a memo hit would silently skip,
        # so they bypass the table too (conservative: outputs would match).
        memo_ok = (pre_inj is None or not pre_inj.armed) \
            and pre_deadline is None and preemption is None \
            and not chunk_pre and arrival_waves is None \
            and not self._qos_enabled(qos)
        if memo_ok and self.memo is not None and self.memo.running:
            hit, out = self.memo.lookup(key)
            if hit:
                # a memo hit serves no decode steps and builds no pool:
                # clear the feedback window, the paged signature and the
                # pool stats so a following refine_kernel_tuner (or a
                # stats reader) never sees stale state from an earlier
                # (differently-shaped or differently-knobbed) serve
                self.decode_step_latencies = deque(
                    maxlen=self.history_window)
                self._step_lat_by_batch = {}
                self._paged_sig = None
                self._paged_dtype = None
                self.last_pool_stats = None
                self.last_spec_stats = None
                self.last_fault_stats = None
                self.last_outcomes = None
                self.last_qos_stats = None
                return out
        gen = self.serve_stream(
            prompts, decode_tokens=n, page_size=page_size,
            pool_pages=pool_pages, max_batch=max_batch,
            prefix_sharing=prefix_sharing, draft_len=draft_len,
            draft=draft, fault_injector=fault_injector,
            deadline_s=deadline_s, pool_audit=pool_audit,
            preemption=preemption, prefill_chunk=prefill_chunk, qos=qos,
            arrival_waves=arrival_waves, slo_ttft_s=slo_ttft_s,
            slo_tok_s=slo_tok_s)
        while True:
            try:
                ev = next(gen)
            except StopIteration as stop:
                result = stop.value
                break
            if on_event is not None:
                on_event(ev)
        # fault-shaped results (rejections, quarantines, deadline cuts)
        # must never be memoized: the memo key carries no pool geometry or
        # fault schedule, so a later right-sized serve would replay them
        fs = self.last_fault_stats
        clean = (memo_ok and fs["events"] == 0 and not fs["actions"]
                 and all(o["status"] == "ok" for o in self.last_outcomes))
        if self.memo is not None and clean:
            self.memo.update(key, result)
        return result

    def serve_stream(self, prompts: list[np.ndarray], *,
                     decode_tokens: int | None = None,
                     page_size: int | None = None,
                     pool_pages: int | None = None,
                     max_batch: int | None = None,
                     prefix_sharing: bool | None = None,
                     draft_len: int | None = None,
                     draft: "Server | None" = None,
                     fault_injector=None,
                     deadline_s: float | None = None,
                     pool_audit: bool | None = None,
                     preemption=None,
                     prefill_chunk: int | None = None,
                     qos=None,
                     arrival_waves=None,
                     slo_ttft_s: float | None = None,
                     slo_tok_s: float | None = None):
        """The streaming serving engine: a generator over per-token events.

        This is `serve_continuous`'s wave loop — admission, chunked
        prefill, decode/verify steps, retirement, fault isolation —
        refactored into an event loop that *yields* as tokens appear and
        *returns* the final per-request output list (read it from
        `StopIteration.value`, or use the `serve_continuous` wrapper).
        Event dicts (all carry "wave" — the logical wave index — and "t",
        a `perf_counter` stamp recorded at creation):

          {"event": "admit",         "rid": r}
          {"event": "prefill_chunk", "rid": r, "resident": i, "total": S}
          {"event": "token",  "rid": r, "token": t, "index": i}
          {"event": "outcome","rid": r, "status": s, "reason": ..., "tokens": n}
          {"event": "wave",   "batch": B, "dt_s": dt, "emitted": e,
           "prefill_tokens": p, "k": k_eff, "op": knobs-or-None,
           "host_ms": {phase: ms}}

        A wave that decodes nothing emits no "wave" event; its work carries
        into the next one.  So each "wave" event covers the waves since the
        previous one: `dt_s` is their time inside the loop, `host_ms` that
        time split by phase (`WAVE_PHASES`, self times of the host spans
        of `repro.monitor.spans`, plus `consumer`, the caller's time
        between them), `emitted` and `prefill_tokens` the tokens emitted
        and the prompt tokens prefills computed.  The same spans appear
        on a profiler trace's host plane when one is taken.

        Chunked prefill (`prefill_chunk` > 0, ServerConfig, or the QoS
        governor's knob): a long admission reserves its block table up
        front, then prefills one page-aligned chunk per wave through the
        widened-q suffix-over-prefix shape, so in-flight decodes keep
        emitting a token every wave while the newcomer streams in — token
        outputs stay bit-identical to one-shot admission (see
        `_paged_admit_chunked` for the parity argument and gates).

        QoS control plane (`qos`: a QoSGovernor, a policy dict, a woven
        QoSAspect, or False to force off): the serving operating point —
        max_batch x prefill_chunk x draft_len x frequency (power cap) — is
        a mARGOt application re-selected online as load shifts, with
        per-request TTFT / per-token SLOs as Goal constraints and tokens/s
        or tokens/joule as the objective; observed wave latencies feed
        `Margot.observe` and the modeled power feeds the PowerCapper.
        Every emitted token is still a target argmax, so governor knob
        moves never change the output bytes — only when they appear.

        `arrival_waves` (one int per prompt) lands requests on a logical
        wave clock instead of all-at-wave-0 — the deterministic open-loop
        load ramp the qos bench drives.
        """
        if not prompts:
            return []
        n = decode_tokens or self.cfg.decode_tokens
        k = draft_len if draft_len is not None else self.cfg.draft_len
        t0 = time.perf_counter()
        variant = self._variant()
        state = self.woven.variant_state(
            None if variant in (None, "__default__") else variant
        )
        state.extra["cache_max_len"] = self.cfg.max_cache_len
        ps = page_size or self._page_size(state)
        cache_dtype = self._cache_dtype(state)  # variant knobs win
        res = self._resilience(state)
        if deadline_s is not None:
            res["deadline_s"] = float(deadline_s)
        if pool_audit is not None:
            res["pool_audit"] = bool(pool_audit)
        inj = fault_injector if fault_injector is not None \
            else state.extra.get("fault_injector")
        gov = self._qos_governor(state, qos, slo_ttft_s, slo_tok_s)
        # chunked prefill: explicit argument, then ServerConfig, then the
        # governor's prefill_chunk knob (0/None: one-shot admission).
        # Capacity-routed MoE couples prefill tokens within the group
        # (capacity/drop decisions see the whole dispatch), so a chunked
        # prefill would not be bit-identical — the gate stays off there.
        chunk_ok = self.woven.program.cfg.family != "moe"
        chunk_cfg = prefill_chunk if prefill_chunk is not None \
            else self.cfg.prefill_chunk

        if k is None:
            k = int(state.extra.get("speculative_draft_len", 0) or 0)
        k = max(0, int(k))
        # the governor may raise draft_len at runtime: reserve verify
        # slack (and size the draft pool) for the largest knob value
        k_max = k
        if gov is not None:
            k_max = max([k] + [int(v) for v in gov.knob_values("draft_len")])

        lengths = [int(np.asarray(p).reshape(-1).shape[0]) for p in prompts]
        # speculative verify steps write up to k slots past the accepted
        # length before rolling back — reserve that slack at admission so
        # draft-block writes can never outrun the block table
        finals = [min(S + n - 1 + k_max, self.cfg.max_cache_len)
                  for S in lengths]
        max_batch = max_batch or self.cfg.max_batch or len(prompts)
        pool_pages = pool_pages or self.cfg.pool_pages \
            or max(sum(cdiv(f, ps) for f in finals), 1)
        share = self.cfg.prefix_sharing if prefix_sharing is None \
            else prefix_sharing
        if self.woven.program.cfg.family == "moe":
            # Capacity-routed MoE couples tokens within a sequence group
            # (the capacity C and drop decisions depend on the whole
            # group), so prefix K/V are not request-independent — a
            # sharer's recompute could write *different* bytes into pages
            # the donor still maps.  Prefix sharing stays off; the
            # direct-to-pool paged prefill still applies.
            share = False
        # one recorder times the loop, both pools and the steps (WAVE_PHASES)
        rec = SpanRecorder()
        manager = PagedCacheManager(
            pool_pages, ps, max_len=self.cfg.max_cache_len,
            window=getattr(self.woven.program.cfg, "attn_window", None),
            prefix_sharing=share, cache_dtype=cache_dtype, device=self.device,
            spans=rec,
        )
        # feedback observations are per-knob-setting: start a fresh window,
        # bucketed by batch size (a decode step's cost scales with the live
        # batch, and the DSE signature is keyed to one batch)
        self.decode_step_latencies = deque(maxlen=self.history_window)
        self._step_lat_by_batch = {}

        if k_max and self.woven.program.cfg.family == "moe":
            # Capacity-routed MoE couples tokens within a group: a verify
            # step's S-token router sees different capacity/drop decisions
            # than S sequential one-token steps, so verify logits would
            # not be bit-identical to plain decode.  Speculation stays off.
            k = k_max = 0
        draft_srv = draft or self.draft or self  # self-speculation default
        dmanager: PagedCacheManager | None = None
        if k_max:
            # the draft keeps its own (unshared) page pool with the same
            # continuous-batching dynamics; sized for full concurrency so
            # a draft admission can never fail behind a target admission
            dstate = draft_srv.woven.variant_state(None)
            dstate.extra["cache_max_len"] = self.cfg.max_cache_len
            dmanager = PagedCacheManager(
                max(sum(cdiv(f, ps) for f in finals), 1), ps,
                max_len=self.cfg.max_cache_len,
                window=getattr(draft_srv.woven.program.cfg,
                               "attn_window", None),
                prefix_sharing=False, cache_dtype=cache_dtype,
                device=draft_srv.device, spans=rec,
            )

        # logical-clock arrivals: requests with a future arrival wave sit
        # in `pending` until the wave counter reaches them — the
        # deterministic open-loop ramp the qos bench drives.  Default
        # (None): everything arrives at wave 0, exactly the old semantics.
        arrive_at = None
        if arrival_waves is not None:
            if len(arrival_waves) != len(prompts):
                raise ValueError("arrival_waves must have one wave index "
                                 "per prompt")
            arrive_at = [max(0, int(w)) for w in arrival_waves]
        waiting: deque = deque()              # arrived, not yet admitted
        pending: deque = deque()              # not yet arrived (wave clock)
        if arrive_at is None:
            waiting.extend(range(len(prompts)))
        else:
            pending.extend(sorted(range(len(prompts)),
                                  key=lambda r: (arrive_at[r], r)))
        active: dict[int, dict] = {}          # rid -> {"tok", "pos"}
        prefilling: dict[int, Any] = {}       # rid -> chunked-admit cont
        outputs: dict[int, list[int]] = {}
        seen_batches: set[int] = set()        # batch sizes already compiled
        spec = {"on": False, "checked": False}
        verify_lats: list[float] = []
        stats = {"draft_len": k, "rounds": 0, "request_rounds": 0,
                 "proposed": 0, "accepted": 0, "emitted_spec": 0,
                 "draft_steps": 0, "verify_steps": 0, "decode_steps": 0}

        grouped = {"admissions": 0}  # identical-prompt shared re-scores

        # the live operating point: base values from the arguments/config,
        # re-selected by the governor as load shifts (closures read this)
        knobs = {"max_batch": max_batch,
                 "chunk": int(chunk_cfg or 0) if chunk_ok else 0,
                 "k": k, "freq": 1.0}

        # per-token stream events accumulate here and are yielded at wave
        # boundaries; "t" is stamped at creation so latency math is exact
        # regardless of when the consumer drains
        evq: list[dict] = []
        wave = 0
        # tokens emitted and prefilled since the last wave event
        wavestat = {"emitted": 0, "prefill_tokens": 0}
        now0 = time.perf_counter()
        rq: dict[int, dict] = {
            r: {"arrive_t": now0, "arrive_wave": 0, "first_t": None,
                "first_wave": None, "tok_t": []}
            for r in range(len(prompts))}

        def _emit(kind: str, **kw) -> dict:
            ev = {"event": kind, "wave": wave, "t": time.perf_counter(), **kw}
            evq.append(ev)
            return ev

        def _first_token(rid, tok) -> None:
            outputs[rid] = [tok]
            active[rid] = {"tok": tok, "pos": lengths[rid]}
            wavestat["emitted"] += 1
            m = rq[rid]
            m["first_t"] = _emit("token", rid=rid, token=tok, index=0)["t"]
            m["first_wave"] = wave
            m["tok_t"].append(m["first_t"])
            if gov is not None:
                with rec.span("serve.qos"):
                    gov.observe("ttft_s", m["first_t"] - m["arrive_t"])

        # -- resilience machinery ---------------------------------------------
        # every fault the policy can absorb lands in `outcome` / `actions`
        # instead of escaping serve_continuous; with no injector woven and
        # no deadline policy this layer is pass-through and serving is
        # bit-identical to the fault-free path
        outcome = {r: {"status": "ok", "reason": None}
                   for r in range(len(prompts))}
        actions: list[dict] = []  # recovery actions taken (host side)
        inj_seen = len(inj.events) if inj is not None else 0
        fstats = {"retries": 0, "quarantined": 0, "rejected": 0,
                  "oversized": 0, "deadline_exceeded": 0, "failed": 0,
                  "drained": 0, "degraded": None, "audits": 0,
                  "watchdog_timeouts": 0}
        start_t: dict[int, float] = {}     # admission wall clock per request
        forced_deadline: set[int] = set()  # injected SLO overruns
        deadline_s_eff = res["deadline_s"]
        retries_max = int(res["retries"])
        backoff_s = float(res["backoff_s"])
        watchdog: Watchdog | None = None
        if res["step_deadline_s"]:
            watchdog = Watchdog(
                float(res["step_deadline_s"]),
                lambda: actions.append({"point": "decode_step",
                                        "kind": "watchdog_overrun"}))

        class _StepAbort(Exception):
            """A step failed past the retry budget (or non-transiently):
            the serve drains with structured `failed` outcomes instead of
            letting the exception escape."""

            def __init__(self, point, cause):
                super().__init__(f"{point}: {cause}")
                self.point, self.cause = point, cause

        def _fire(point, *, rid=None, rids=None):
            if inj is None:
                return None
            fired = inj.fire(point, rid=rid, rids=rids)
            if fired is not None and fired.kind == "deadline" \
                    and fired.rid is not None:
                # SLO overrun: the victim is forced past its deadline; the
                # sweep at the next round start retires it with partial
                # output
                forced_deadline.add(fired.rid)
            return fired

        def _retry(point, fn):
            """Bounded retry-with-backoff around one step's transient
            faults (injected raises / pool exhaustion fire *before* the
            jitted dispatch, so re-running is safe even though the step
            donates its cache; manager.batch is idempotent).  Anything
            else aborts the serve's stepping via _StepAbort — never by
            letting the exception escape."""
            attempt = 0
            while True:
                try:
                    return fn()
                except (FaultError, PoolExhausted) as e:
                    attempt += 1
                    fstats["retries"] += 1
                    actions.append({"point": point, "kind": "retry",
                                    "attempt": attempt, "error": str(e)})
                    if attempt > retries_max:
                        raise _StepAbort(point, e) from e
                    if backoff_s:
                        time.sleep(backoff_s * (2 ** (attempt - 1)))
                except Exception as e:  # non-transient: no retry
                    raise _StepAbort(point, e) from e

        def _audit():
            # PoolAuditor barriers under the debug knob: corruption is
            # caught at the fault, not three steps later
            if not res["pool_audit"]:
                return
            fstats["audits"] += 1
            PoolAuditor(manager, check_device=True).audit()
            if dmanager is not None:
                PoolAuditor(dmanager).audit()

        def _reject(rid, reason, status="rejected"):
            outcome[rid] = {"status": status, "reason": reason}
            fstats[status] += 1
            actions.append({"point": "admit", "kind": status, "rid": rid,
                            "reason": reason})
            _emit("outcome", rid=rid, status=status, reason=reason,
                  tokens=len(outputs.get(rid, [])))

        def _drop(rid):
            """Release every trace of `rid` from both pools + the batch."""
            manager.abort(rid)
            if dmanager is not None:
                dmanager.abort(rid)
            active.pop(rid, None)
            prefilling.pop(rid, None)
            start_t.pop(rid, None)
            forced_deadline.discard(rid)

        def _quarantine(rid, reason):
            # NaN/Inf logits quarantine exactly the victim: its pages
            # retire, its partial output survives, the batch re-forms
            outcome[rid] = {"status": "quarantined", "reason": reason}
            fstats["quarantined"] += 1
            actions.append({"point": "decode_step", "kind": "quarantined",
                            "rid": rid, "reason": reason})
            _emit("outcome", rid=rid, status="quarantined", reason=reason,
                  tokens=len(outputs.get(rid, [])))
            _drop(rid)

        def _degrade(reason):
            """Speculation is an optimization: any draft-side fault (or
            repeated all-reject verify rounds under the patience policy)
            turns it off for the rest of the serve — a draft failure never
            touches target state, so output parity holds."""
            if not spec["on"]:
                return
            spec["on"] = False
            fstats["degraded"] = reason
            actions.append({"point": "draft_step", "kind": "degraded",
                            "reason": reason})
            if dmanager is not None:
                for r in list(dmanager.pool.tables):
                    dmanager.abort(r)

        def _retire(rid):
            with rec.span("serve.retire", rid=rid):
                try:
                    _retry("retire", lambda: (_fire("retire", rid=rid),
                                              manager.retire(rid)))
                except _StepAbort as e:
                    # a retire that keeps failing force-drops the
                    # references — leaking pages on a fault path would
                    # starve later admissions
                    manager.abort(rid)
                    actions.append({"point": "retire",
                                    "kind": "forced_abort", "rid": rid,
                                    "error": str(e.cause)})
                if dmanager is not None:
                    dmanager.abort(rid)

        def admit_one(rid, reuse_from=None) -> None:
            aspec = _fire("admit", rid=rid)
            if aspec is not None and aspec.kind == "nan_logits":
                # an admission with poisoned logits has no usable first
                # token: reject it through the non-finite path
                raise NonFiniteLogits(
                    f"injected non-finite admission logits for {rid!r}")
            _emit("admit", rid=rid)
            tok = None
            if reuse_from is not None:
                tok = self._admit_grouped(manager, rid, prompts[rid],
                                          finals[rid],
                                          outputs[reuse_from][0])
                if tok is not None:
                    grouped["admissions"] += 1
            cont = None
            if tok is None:
                chunk = int(knobs["chunk"] or 0)
                if chunk > 0:
                    tok, pspec, cont = self._paged_admit_chunked(
                        manager, rid, prompts[rid], finals[rid], variant,
                        inj=inj, chunk=chunk)
                else:
                    tok, pspec = self._paged_admit(
                        manager, rid, prompts[rid], finals[rid], variant,
                        inj=inj)
                if pspec is not None and pspec.kind == "deadline":
                    forced_deadline.add(rid)
                if cont is None:
                    # a one-shot admission prefilled its prompt this wave —
                    # billed into the wave event like a chunk is, by the
                    # tokens it computed (a shared prefix is not), so the
                    # governor's tok_s observations (and any modeled clock
                    # over the event stream) see admission stalls on both
                    # prefill paths
                    wavestat["prefill_tokens"] += self._last_admit_computed
            start_t[rid] = time.monotonic()
            if cont is not None:
                # chunked admission in flight: the block table is
                # reserved, the prompt streams in one chunk per wave
                prefilling[rid] = cont
            else:
                _first_token(rid, tok)
            if not spec["checked"]:
                # pool family is known after the first admission: ring
                # pools evict on write, which breaks the widened-q verify
                # mask — the server gates speculation to linear pools
                spec["checked"] = True
                spec["on"] = bool(k_max) and not manager._ring_pool()
            if spec["on"]:
                # draft admits in lockstep (its length must equal the
                # target's accepted length at every round start); a draft
                # admission fault degrades speculation but keeps the
                # target admission — the request decodes plain.  This also
                # closes the old leak where a draft throw stranded the
                # target's pages and `active`/`outputs` entries.
                try:
                    draft_srv._paged_admit(dmanager, rid, prompts[rid],
                                           finals[rid], None, inj=inj)
                except Exception as e:
                    _degrade(f"draft admission failed: {e}")

        def try_admit(rid, reuse_from=None) -> bool:
            with rec.span("serve.admit", rid=rid):
                try:
                    admit_one(rid, reuse_from)
                    return True
                except (FaultError, PoolExhausted) as e:
                    # a failed admission is isolated to the one request:
                    # its partial pool state rolls back and it gets a
                    # structured rejection — the serve (and every other
                    # request) goes on
                    outputs.pop(rid, None)
                    _drop(rid)
                    _reject(rid, str(e))
                    _audit()
                    return False

        def admit_ready() -> None:
            # the admission gate counts chunked prefills in flight: they
            # hold reserved pages and will join the decode batch, so the
            # governor's max_batch knob bounds active + prefilling
            while waiting and (len(active) + len(prefilling)
                               < int(knobs["max_batch"])):
                rid = None
                if manager.prefix_sharing and len(waiting) > 1:
                    # prefix-aware admission: a sharer queued behind a
                    # non-sharer jumps the line while its donor's pages
                    # are still live — the shared prefix costs it no fresh
                    # pages, so it can fit where the queue head cannot
                    # (and the hit is lost once the donor retires)
                    for cand in waiting:
                        toks_np = np.asarray(prompts[cand],
                                             np.int64).reshape(-1)
                        _, sl = manager.match_prefix(toks_np)
                        if sl > 0 and manager.can_admit(
                                finals[cand], tokens=prompts[cand]):
                            rid = cand
                            break
                if rid is None:
                    rid = waiting[0]
                    # capacity-checked for the very first admission too: an
                    # oversized request is rejected *before* its prefill
                    # runs, landing on the clean "page pool too small" path
                    # below instead of a raw PoolExhausted out of pool.alloc
                    if not manager.can_admit(finals[rid], tokens=prompts[rid]):
                        return
                ok = try_admit(rid)
                waiting.remove(rid)
                if not (ok and manager.prefix_sharing and waiting
                        and self._last_admit_rescored):
                    continue
                # identical queued prompts admit as a group sharing the
                # re-score that just ran: each member maps the same pages
                # and reuses the donor's first token — one re-score step
                # for the whole group instead of one per member
                base = np.asarray(prompts[rid], np.int64).reshape(-1)
                for cand in [c for c in waiting if np.array_equal(
                        np.asarray(prompts[c], np.int64).reshape(-1), base)]:
                    if (len(active) + len(prefilling)
                            >= int(knobs["max_batch"])) \
                            or not manager.can_admit(
                                finals[cand], tokens=prompts[cand]):
                        break
                    try_admit(cand, reuse_from=rid)
                    waiting.remove(cand)

        def _drain_waiting() -> None:
            """Preemption: hand the not-yet-admitted queue back with
            structured `drained` outcomes — in-flight work is untouched.
            Future arrivals (the logical-clock `pending` queue) drain too:
            a preempted replica will never reach their wave."""
            while pending:
                waiting.append(pending.popleft())
            while waiting:
                rid = waiting.popleft()
                outcome[rid] = {"status": "drained",
                                "reason": "preemption requested: "
                                          "admissions stopped"}
                fstats["drained"] += 1
                actions.append({"point": "drain", "kind": "drained",
                                "rid": rid})
                _emit("outcome", rid=rid, status="drained",
                      reason=outcome[rid]["reason"], tokens=0)

        def _admit_or_drain() -> None:
            if preemption is not None and preemption.pending:
                _drain_waiting()
                return
            admit_ready()

        # prompts the cache could never host are rejected up front — the
        # old path crashed the whole serve mid-flight on the first one
        for r in [r for r in list(waiting) + list(pending)
                  if lengths[r] > self.cfg.max_cache_len]:
            (waiting if r in waiting else pending).remove(r)
            _reject(r, f"prompt ({lengths[r]} tokens) exceeds "
                       f"max_cache_len ({self.cfg.max_cache_len})",
                    status="oversized")

        mismatch_rounds = 0
        aborted: Exception | None = None
        while active or waiting or prefilling or pending:
            while evq:
                ev = evq.pop(0)
                with rec.outside("consumer"):
                    yield ev
            with rec.span("serve.wave", wave=wave) as wave_span:
                # logical-clock arrivals land before anything else this wave
                if pending:
                    arrived = False
                    while pending and arrive_at[pending[0]] <= wave:
                        r = pending.popleft()
                        waiting.append(r)
                        rq[r]["arrive_t"] = time.perf_counter()
                        rq[r]["arrive_wave"] = wave
                        arrived = True
                    if arrived:
                        _admit_or_drain()
                # the governor re-selects the serving operating point as load
                # shifts (every reselect_every waves); knob moves only change
                # scheduling — every emitted token stays a target argmax
                if gov is not None and wave % gov.reselect_every == 0:
                    with rec.span("serve.qos"):
                        op = gov.decide(wave=wave,
                                        waiting=len(waiting) + len(pending),
                                        active=len(active) + len(prefilling))
                    v = op.get("max_batch")
                    knobs["max_batch"] = min(max_batch, int(v)) if v \
                        else max_batch
                    if chunk_ok and chunk_cfg is None \
                            and op.get("prefill_chunk") is not None:
                        knobs["chunk"] = int(op["prefill_chunk"])
                    if op.get("draft_len") is not None:
                        knobs["k"] = min(k_max, int(op["draft_len"]))
                    if op.get("freq") is not None:
                        knobs["freq"] = float(op["freq"])
                if wave == 0:
                    _admit_or_drain()
                # preemption arriving mid-wave drains the queue at the next
                # round boundary; the admitted batch keeps decoding to the end
                if preemption is not None and preemption.pending \
                        and (waiting or pending):
                    _drain_waiting()
                    if not active and not prefilling:
                        break
                # retire before stepping: requests at their budget free pages
                done = [r for r in active if len(outputs[r]) >= n]
                for rid in done:
                    _retire(rid)
                    del active[rid]
                    _emit("outcome", rid=rid, status=outcome[rid]["status"],
                          reason=outcome[rid]["reason"],
                          tokens=len(outputs[rid][:n]))
                # per-request SLO sweep: overdue requests (wall clock past
                # deadline_s, or forced over by an injected `deadline` fault)
                # retire with partial output and a deadline_exceeded marker —
                # chunked admissions still prefilling are swept too (their
                # clock started at reservation)
                overdue = []
                if deadline_s_eff is not None or forced_deadline:
                    now = time.monotonic()
                    overdue = [r for r in list(active) + list(prefilling)
                               if r in forced_deadline
                               or (deadline_s_eff is not None
                                   and now - start_t[r] > deadline_s_eff)]
                for rid in overdue:
                    outcome[rid] = {"status": "deadline_exceeded",
                                    "reason": "request exceeded its deadline"}
                    fstats["deadline_exceeded"] += 1
                    actions.append({"point": "decode_step", "kind": "deadline",
                                    "rid": rid,
                                    "emitted": len(outputs.get(rid, []))})
                    _emit("outcome", rid=rid, status="deadline_exceeded",
                          reason=outcome[rid]["reason"],
                          tokens=len(outputs.get(rid, [])))
                    if rid in prefilling:
                        # mid-prefill: nothing registered yet — abort the
                        # reserved pages instead of retiring
                        del prefilling[rid]
                        _drop(rid)
                    else:
                        _retire(rid)
                        active.pop(rid, None)
                    forced_deadline.discard(rid)
                if done or overdue:
                    _audit()
                    _admit_or_drain()
                # advance chunked prefills: one page-aligned chunk per request
                # per wave — in-flight decodes below never wait on a long
                # admission, the newcomer streams in beside them
                for rid in list(prefilling):
                    with rec.span("serve.prefill_chunk", rid=rid):
                        try:
                            step_r = prefilling[rid]()
                        except (FaultError, PoolExhausted) as e:
                            outputs.pop(rid, None)
                            _drop(rid)
                            _reject(rid, str(e))
                            _audit()
                            continue
                        wavestat["prefill_tokens"] += step_r["chunk"]
                        if step_r["tok"] is None:
                            _emit("prefill_chunk", rid=rid,
                                  resident=step_r["resident"],
                                  total=lengths[rid])
                        else:
                            del prefilling[rid]
                            _first_token(rid, step_r["tok"])
                if not active:
                    if waiting and not prefilling:
                        # pool at its emptiest still can't fit the head
                        # request: reject *it* and keep serving the rest — the
                        # old batch-killing RuntimeError here threw away every
                        # completed request's output with it
                        rid = waiting.popleft()
                        _reject(rid, f"page pool too small: request {rid} "
                                     f"needs more pages than the pool holds")
                        _admit_or_drain()
                        wave += 1
                        continue
                    if prefilling or pending:
                        # nothing to decode this wave: prefill chunks advanced
                        # above / the clock ticks toward the next arrival
                        wave += 1
                        continue
                    break

                rids = list(active)
                # a verify round writes k_eff+1 slots per request; past the
                # final_len clamp (cache capacity) fall back to plain rounds —
                # S stays within the knob grid so only a few step shapes ever
                # compile (static serves keep the old {1, k+1} pair)
                k_eff = int(knobs["k"]) if spec["on"] else 0
                S = k_eff + 1 if (k_eff and spec["on"] and all(
                    active[r]["pos"] + k_eff + 1 <= finals[r] for r in rids)) \
                    else 1

                if S > 1 and dmanager is not None:
                    # dynamic draft_len: plain waves (k_eff == 0 under the
                    # governor) leave the draft cache behind the target's
                    # accepted length — replay the emitted tokens through the
                    # draft before the round so the lockstep invariant holds.
                    # Static-k serves never enter the replay loop.
                    try:
                        self._draft_sync(draft_srv, dmanager, rids, active,
                                         outputs, lengths)
                    except Exception as e:
                        _degrade(f"draft catch-up fault: {e}")
                        S = 1

                if S > 1:
                    pos0 = {r: active[r]["pos"] for r in rids}
                    fed = np.zeros((len(rids), S), np.int64)
                    fed[:, 0] = [active[r]["tok"] for r in rids]
                    # draft proposes k greedy tokens; the final iteration is a
                    # write-only catch-up (its KV for slot pos+k is needed when
                    # every draft token is accepted), its proposal is unused
                    try:
                        for s in range(S):
                            dspec = _fire("draft_step", rids=rids)
                            dcache = dmanager.batch(rids)
                            with rec.span("serve.dispatch"):
                                dpos = jnp.asarray(
                                    [[pos0[r] + s] for r in rids], jnp.int32)
                                dlogits, dnew = draft_srv.decode_vc(
                                    None, draft_srv.params,
                                    {"tokens": jnp.asarray(fed[:, s:s + 1],
                                                           jnp.int32),
                                     "positions": dpos},
                                    dcache)
                            if dspec is not None \
                                    and dspec.kind == "nan_logits":
                                # a poisoned proposal is still a legal token
                                # after argmax (NaN rows argmax to 0): the
                                # verify step rejects garbage proposals, so a
                                # bad draft costs steps, never correctness
                                vi = rids.index(dspec.rid) \
                                    if dspec.rid in rids else 0
                                dlogits = dlogits.at[vi].set(jnp.nan)
                            dmanager.absorb(rids, dnew)
                            stats["draft_steps"] += 1
                            if s < S - 1:
                                with rec.span("serve.wait"):
                                    fed[:, s + 1] = np.asarray(
                                        jnp.argmax(dlogits[:, -1], axis=-1),
                                        np.int64)
                    except Exception as e:
                        # draft-side fault: no target state was touched this
                        # round — degrade to plain decode and re-run the round
                        _degrade(f"draft fault: {e}")
                        wave += 1
                        continue

                    # ONE widened-q target step scores all S draft positions
                    def _verify_round():
                        _fire("cow", rids=rids)
                        cache = manager.batch(rids, tokens=S)
                        vspec = _fire("verify_step", rids=rids)
                        with rec.span("serve.dispatch") as sent:
                            vpos = jnp.asarray(
                                [[pos0[r] + s for s in range(S)]
                                 for r in rids], jnp.int32)
                            if watchdog is not None:
                                watchdog.beat()
                            logits, new_cache = self._verify_step(
                                variant, k_eff)(
                                self.params,
                                {"tokens": jnp.asarray(fed, jnp.int32),
                                 "positions": vpos},
                                cache)
                            if watchdog is not None:
                                watchdog.cancel()
                        return vspec, sent, logits, new_cache

                    try:
                        vspec, sent, logits, new_cache = _retry(
                            "verify_step", _verify_round)
                    except _StepAbort as err:
                        aborted = err
                        break
                    if vspec is not None and vspec.kind == "nan_logits":
                        vi = rids.index(vspec.rid) if vspec.rid in rids else 0
                        logits = logits.at[vi].set(jnp.nan)
                    with rec.span("serve.wait") as waited:
                        finite = np.asarray(jnp.isfinite(jnp.max(
                            logits.astype(jnp.float32), axis=(-2, -1))))
                        targ = np.asarray(jnp.argmax(logits, axis=-1),
                                          np.int64)
                    if stats["verify_steps"]:  # skip the jit-tracing first step
                        verify_lats.append(sent.seconds + waited.seconds)
                    manager.absorb(rids, new_cache, advance=S)
                    stats["verify_steps"] += 1
                    stats["rounds"] += 1
                    stats["request_rounds"] += len(rids)
                    accepted_round = 0
                    rolled = False
                    with rec.span("serve.emit"):
                        for i, rid in enumerate(rids):
                            if not finite[i]:
                                _quarantine(rid, "non-finite verify logits")
                                rolled = True
                                continue
                            # accept the longest draft prefix matching the
                            # target's own argmax chain, plus the correction
                            # token — every emitted token is a target argmax,
                            # so greedy output is bit-identical to plain decode
                            a = 0
                            while a < k_eff and fed[i, a + 1] == targ[i, a]:
                                a += 1
                            e = min(a + 1, n - len(outputs[rid]))
                            idx0 = len(outputs[rid])
                            outputs[rid].extend(int(t) for t in targ[i, :e])
                            new_len = pos0[rid] + e
                            # rejected tail: O(1) refcount rollback, no page copies
                            try:
                                _retry("rollback", lambda rid=rid, nl=new_len: (
                                    _fire("rollback", rid=rid),
                                    manager.rollback(rid, nl),
                                    dmanager.rollback(rid, nl)))
                            except _StepAbort as err:
                                # a rollback that keeps failing leaves the
                                # request's length unknown: quarantine it
                                _quarantine(rid, f"rollback failed: {err.cause}")
                                rolled = True
                                continue
                            active[rid]["tok"] = int(targ[i, e - 1])
                            active[rid]["pos"] = new_len
                            for j in range(e):
                                rq[rid]["tok_t"].append(_emit(
                                    "token", rid=rid, token=int(targ[i, j]),
                                    index=idx0 + j)["t"])
                            wavestat["emitted"] += e
                            stats["proposed"] += k_eff
                            stats["accepted"] += a
                            stats["emitted_spec"] += e
                            accepted_round += a
                            rolled = True
                        if rolled:
                            _audit()
                    if accepted_round == 0:
                        mismatch_rounds += 1
                        patience = res["spec_patience"]
                        if patience is not None \
                                and mismatch_rounds >= int(patience):
                            _degrade(f"{mismatch_rounds} consecutive "
                                     f"all-reject verify rounds")
                    else:
                        mismatch_rounds = 0
                else:
                    def _decode_round():
                        _fire("cow", rids=rids)
                        cache = manager.batch(rids)
                        pspec = _fire("decode_step", rids=rids)
                        with rec.span("serve.dispatch") as sent:
                            tok = jnp.asarray(
                                [[active[r]["tok"]] for r in rids], jnp.int32)
                            pos = jnp.asarray(
                                [[active[r]["pos"]] for r in rids], jnp.int32)
                            if watchdog is not None:
                                watchdog.beat()
                            logits, new_cache = self.decode_vc(
                                variant, self.params,
                                {"tokens": tok, "positions": pos}, cache,
                            )
                            if watchdog is not None:
                                watchdog.cancel()
                        return pspec, sent, logits, new_cache

                    try:
                        pspec, sent, logits, new_cache = _retry(
                            "decode_step", _decode_round)
                    except _StepAbort as err:
                        aborted = err
                        break
                    if pspec is not None and pspec.kind == "nan_logits":
                        vi = rids.index(pspec.rid) if pspec.rid in rids else 0
                        logits = logits.at[vi].set(jnp.nan)
                    with rec.span("serve.wait") as waited:
                        finite = np.asarray(jnp.isfinite(jnp.max(
                            logits[:, -1].astype(jnp.float32), axis=-1)))
                        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                         np.int64)
                    # first step at each batch size pays jit tracing —
                    # excluding it keeps the tuner-feedback observations
                    # compile-free (the DSE expectations were measured
                    # post-compile too)
                    if len(rids) in seen_batches:
                        dt_step = sent.seconds + waited.seconds
                        self.decode_step_latencies.append(dt_step)
                        self._step_lat_by_batch.setdefault(
                            len(rids),
                            deque(maxlen=self.history_window)).append(dt_step)
                    seen_batches.add(len(rids))
                    manager.absorb(rids, new_cache)
                    stats["decode_steps"] += 1
                    with rec.span("serve.emit"):
                        hit_nan = False
                        for i, rid in enumerate(rids):
                            if not finite[i]:
                                _quarantine(rid, "non-finite decode logits")
                                hit_nan = True
                                continue
                            idx0 = len(outputs[rid])
                            outputs[rid].append(int(nxt[i]))
                            active[rid]["tok"] = int(nxt[i])
                            active[rid]["pos"] += 1
                            wavestat["emitted"] += 1
                            rq[rid]["tok_t"].append(_emit(
                                "token", rid=rid, token=int(nxt[i]),
                                index=idx0)["t"])
                        if hit_nan:
                            _audit()

                # wave boundary: one "wave" event carries the batch shape, the
                # operating point in force, and the work since the last wave
                # event (waves that decoded nothing carry into this one): the
                # host time by phase, on the span recorder's clock, and the
                # tokens emitted and prefilled; the governor observes the
                # same numbers through its MAPE-K loop (Margot.observe)
                wave_span.set(batch=len(rids))
                self_s, total_s = rec.take()
                dt_wave = total_s.get("serve.wave", 0.0)
                _emit("wave", batch=len(rids), dt_s=dt_wave,
                      emitted=wavestat["emitted"],
                      prefill_tokens=wavestat["prefill_tokens"],
                      k=(k_eff if S > 1 else 0),
                      op=(dict(knobs) if gov is not None else None),
                      host_ms=_host_ms(self_s))
                if gov is not None:
                    with rec.span("serve.qos"):
                        gov.observe_wave(
                            dt_wave, batch=len(rids),
                            emitted=wavestat["emitted"],
                            prefill_tokens=wavestat["prefill_tokens"],
                            wave=wave)
                wavestat["emitted"] = wavestat["prefill_tokens"] = 0
                wave += 1

        if aborted is not None:
            # a step failed past its retry budget: every in-flight request
            # fails *structurally* (partial output kept, pool released) —
            # the exception itself never escapes
            for rid in list(active) + list(prefilling):
                outcome[rid] = {"status": "failed",
                                "reason": f"{aborted.point} failed: "
                                          f"{aborted.cause}"}
                fstats["failed"] += 1
                if rid in prefilling:
                    outputs.pop(rid, None)
                _emit("outcome", rid=rid, status="failed",
                      reason=outcome[rid]["reason"],
                      tokens=len(outputs.get(rid, [])))
                _drop(rid)
            while pending:
                waiting.append(pending.popleft())
            while waiting:
                _reject(waiting.popleft(),
                        f"serve aborted at {aborted.point}",
                        status="failed")
        if watchdog is not None:
            fstats["watchdog_timeouts"] = watchdog.timeouts
            watchdog.close()
        _audit()  # final barrier: the drained pools must be consistent

        self.last_pool_stats = manager.stats()
        self.last_pool_stats["grouped_admissions"] = grouped["admissions"]
        if k_max:
            p = stats["proposed"]
            stats["acceptance"] = stats["accepted"] / p if p else 0.0
            stats["mean_tokens_per_verify"] = (
                stats["emitted_spec"] / stats["request_rounds"]
                if stats["request_rounds"] else 0.0)
            stats["target_steps"] = (stats["verify_steps"]
                                     + stats["decode_steps"])
            stats["verify_latency_s"] = (
                float(np.mean(verify_lats)) if verify_lats else None)
            self.last_spec_stats = stats
        else:
            self.last_spec_stats = None
        if manager._groups:
            self._paged_dtype = next(iter(manager._groups.values()))["dtype"]
            self._paged_sig = self._paged_signature(
                batch=min(max_batch, len(prompts)), dtype=self._paged_dtype)
        else:
            # every request was rejected before the pool learned its
            # structure — no kernel signature to refine against
            self._paged_dtype = None
            self._paged_sig = None
        injected = list(inj.events[inj_seen:]) if inj is not None else []
        for ev in injected:
            self.broker.publish(
                f"serve/fault/{ev['point']}/{ev['kind']}"
                f"@host{jax.process_index()}", 1.0)
        by_status: dict[str, int] = {}
        for r in range(len(prompts)):
            s = outcome[r]["status"]
            by_status[s] = by_status.get(s, 0) + 1
        self.last_fault_stats = {"events": len(injected),
                                 "injected_events": injected,
                                 "actions": actions,
                                 "outcomes": by_status, **fstats}
        self.last_qos_stats = gov.stats() if gov is not None else None

        def _outcome_row(r):
            m = rq[r]
            row = {"rid": r, "status": outcome[r]["status"],
                   "reason": outcome[r]["reason"],
                   "tokens": len(outputs.get(r, [])[:n]),
                   "ttft_s": None, "ttft_waves": None,
                   "tok_gap_max_s": None}
            if m["first_t"] is not None:
                row["ttft_s"] = m["first_t"] - m["arrive_t"]
                row["ttft_waves"] = m["first_wave"] - m["arrive_wave"]
            tt = m["tok_t"]
            if len(tt) > 1:
                row["tok_gap_max_s"] = max(
                    b - a for a, b in zip(tt, tt[1:]))
            return row

        self.last_outcomes = [_outcome_row(r) for r in range(len(prompts))]
        result = [np.asarray(outputs.get(r, [])[:n], np.int64)
                  for r in range(len(prompts))]
        dt = time.perf_counter() - t0
        self.latencies.append(dt)
        self.served += len(prompts)
        self.broker.publish(f"serve/latency/@host{jax.process_index()}", dt)
        if self.margot is not None:
            self.margot.observe("latency", dt)
        while evq:
            yield evq.pop(0)
        return result

    def _paged_signature(self, *, batch: int, dtype):
        """The signature `ops.flash_decode`'s tuned_paged_blocks lookup
        keys on for this server's decode steps — the served KV dtype and
        the logical cache length the kernel actually sees (the window for
        ring layouts)."""
        from repro.autotune.kernel_tuner import paged_decode_signature

        cfg = self.woven.program.cfg
        cache_len = self.cfg.max_cache_len
        window = getattr(cfg, "attn_window", None)
        if window is not None and window < cache_len:
            cache_len, window = window, None  # ring layout
        return paged_decode_signature(
            batch, cache_len, cfg.n_heads, cfg.kv_heads,
            cfg.resolved_head_dim, dtype, window=window,
        )

    def refine_kernel_tuner(self, *, latency_budget: float,
                            tuner=None) -> dict | None:
        """Feed observed decode-step latencies back into the persistent
        kernel-tuner cache (repro.autotune.kernel_tuner.refine_from_runtime):
        serving traffic refines the DSE priors, so the next server process
        picks page/block knobs selected under *observed* — not predicted —
        latency.  Returns the re-selected knobs (None if never tuned)."""
        from repro.autotune.kernel_tuner import refine_from_runtime

        if self._paged_sig is None or not self._step_lat_by_batch:
            return None
        # continuous batching shrinks the batch as requests retire; a step's
        # cost scales with the live batch, so observe only the best-sampled
        # batch size and refine the signature keyed to *that* batch
        batch = max(self._step_lat_by_batch,
                    key=lambda b: len(self._step_lat_by_batch[b]))
        observed = float(np.mean(self._step_lat_by_batch[batch]))
        sig = self._paged_signature(batch=batch, dtype=self._paged_dtype)
        return refine_from_runtime(
            sig, {"latency_s": observed},
            tuner=tuner, latency_budget=latency_budget,
            objective_knob="page_size",
        )

    def refine_speculative(self, *, latency_budget: float,
                           tuner=None) -> dict | None:
        """Feed the observed draft acceptance back into the persistent
        speculative-space entry: the served `mean_tokens_per_verify`
        (acceptance x draft_len + 1) rescales the cached acceptance-1
        `tokens_per_step` priors and the verify-step latency rescales the
        latency expectations, then the `draft_len` knob is re-selected
        under the adjusted budget.  Returns the re-selected knobs (None
        when the last serve was not speculative or never tuned)."""
        from repro.autotune.kernel_tuner import (
            refine_from_runtime,
            speculative_signature,
        )

        stats = self.last_spec_stats
        if not stats or not stats.get("verify_steps"):
            return None
        cfg = self.woven.program.cfg
        cache_len = self.cfg.max_cache_len
        window = getattr(cfg, "attn_window", None)
        if window is not None and window < cache_len:
            cache_len, window = window, None  # ring layout
        batch = max(1, round(stats["request_rounds"] / max(stats["rounds"], 1)))
        sig = speculative_signature(
            batch, cache_len, cfg.n_heads, cfg.kv_heads,
            cfg.resolved_head_dim, self._paged_dtype or "bfloat16",
            window=window,
        )
        observed = {"tokens_per_step": float(stats["mean_tokens_per_verify"])}
        if stats.get("verify_latency_s"):
            observed["latency_s"] = float(stats["verify_latency_s"])
        return refine_from_runtime(
            sig, observed, tuner=tuner, latency_budget=latency_budget,
            objective_knob="draft_len",
        )
