"""Speculative decoding end-to-end (the PR 6 serving layer).

Covers:
  - the widened-q flash_decode tile: S draft tokens scored in one kernel
    launch are bit-identical to S sequential single-token decodes, dense
    and paged, windowed and not (token s attends through cache slot
    index + s);
  - the draft/verify serving loop: greedy speculative serve_continuous is
    bit-identical to plain greedy (self-draft, registry cross-model
    draft, knob-driven draft_len), with strictly fewer target steps; ring
    pools and capacity-routed MoE gate speculation off and still match;
  - O(1) page-pool rollback: PagePool.truncate / PagedCacheManager.rollback
    refcount semantics, rollback across a copy-on-write boundary leaving
    donor pages untouched, a no-copy spy over a rejection-heavy
    speculative serve, and allocator invariants under random churn that
    now includes truncation;
  - the `speculative` tuner space (draft_len x block_kv_dec under the
    widened-q VMEM model) and the acceptance-feedback refinement loop
    (Server.refine_speculative -> refine_from_runtime).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 1)[0])
from _hypothesis_compat import given, settings, st

from repro.runtime.pages import (
    PagePool,
    PagedCacheManager,
    PoolExhausted,
    build_linear_pool,
    cdiv,
)


def _server(arch, **cfg_kw):
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.runtime.server import Server, ServerConfig

    program = Program.from_arch(arch, kind="serve", reduced=True)
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    return Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4,
                                      **cfg_kw))


def _windowed_server(window=16):
    """Dense-family (non-MoE) sliding-window config: the windowed axis of
    the widened-q mask without mixtral's capacity-routed MoE (which gates
    speculation off for its own reason)."""
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.models.registry import build_model, reduced_config
    from repro.runtime.server import Server, ServerConfig

    cfg = reduced_config("yi-6b").replace(attn_window=window)
    program = Program(model=build_model(cfg), cfg=cfg, kind="serve")
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    return Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4))


PROMPTS = [np.ones((5,), np.int32),
           (np.arange(1, 9) % 50).astype(np.int32),
           np.full((3,), 7, np.int32)]


class TestWidenedQKernel:
    """flash_decode with S > 1 q tokens == S sequential S=1 calls, bit for
    bit: each q row runs the same online softmax over the same block walk,
    with its causal boundary at index + row."""

    @pytest.mark.parametrize("window", [None, 7])
    def test_dense_widened_matches_sequential(self, window):
        from repro.kernels.flash_attention.ops import flash_decode

        B, S, T, H, K, D = 2, 3, 24, 4, 2, 16
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, K, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, K, D)), jnp.float32)
        index = jnp.asarray([5, 9], jnp.int32)
        wide = flash_decode(q, k, v, index, window=window, block_kv=8)
        assert wide.shape == (B, S, H, D)
        for s in range(S):
            one = flash_decode(q[:, s:s + 1], k, v, index + s,
                               window=window, block_kv=8)
            np.testing.assert_array_equal(np.asarray(wide[:, s]),
                                          np.asarray(one[:, 0]))

    def test_paged_widened_matches_sequential(self):
        from repro.kernels.flash_attention.ops import flash_decode

        B, S, H, K, D, ps, T = 2, 3, 4, 2, 16, 8, 24
        rng = np.random.default_rng(1)
        idx = np.array([5, 9], np.int32)  # first new token's position
        ks = [rng.standard_normal((int(i) + S, K, D)).astype(np.float32)
              for i in idx]
        vs = [rng.standard_normal((int(i) + S, K, D)).astype(np.float32)
              for i in idx]
        pk, pv, tables, _ = build_linear_pool(ks, vs, ps, max_len=T)
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        wide = flash_decode(q, pk, pv, jnp.asarray(idx), tables=tables,
                            kv_len=T, block_kv=8)
        for s in range(S):
            one = flash_decode(q[:, s:s + 1], pk, pv, jnp.asarray(idx + s),
                               tables=tables, kv_len=T, block_kv=8)
            np.testing.assert_array_equal(np.asarray(wide[:, s]),
                                          np.asarray(one[:, 0]))


class TestSpeculativeServing:
    """Greedy speculative serve_continuous is bit-identical to plain
    greedy — every emitted token is a target argmax; the draft only
    changes how many target steps the output costs."""

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_self_draft_bit_exact_and_fewer_target_steps(self, k):
        srv = _server("yi-6b")
        plain = srv.serve_continuous(PROMPTS, page_size=8)
        assert srv.last_spec_stats is None  # plain serve leaves no stats
        spec = srv.serve_continuous(PROMPTS, page_size=8, draft_len=k)
        for p, s in zip(plain, spec):
            np.testing.assert_array_equal(p, s)
        stats = srv.last_spec_stats
        assert stats["draft_len"] == k
        assert stats["verify_steps"] >= 1
        # self-drafting: the draft IS the target, every proposal matches
        assert stats["acceptance"] == 1.0
        # n - 1 plain decode steps collapse to ceil((n-1)/(k+1)) verify
        # rounds — k=1 is the ≥1.5x step-reduction acceptance criterion,
        # and the draft_len=1 degenerate case stays bit-exact
        plain_steps = srv.cfg.decode_tokens - 1
        assert stats["target_steps"] == cdiv(plain_steps, k + 1)
        assert stats["target_steps"] < plain_steps

    def test_registry_cross_model_draft_bit_exact(self):
        from repro.models.registry import draft_for

        assert draft_for("yi-6b") == "gemma-2b"
        srv = _server("yi-6b")
        srv.draft = _server(draft_for("yi-6b"))
        plain = srv.serve_continuous(PROMPTS, page_size=8)
        spec = srv.serve_continuous(PROMPTS, page_size=8, draft_len=2)
        for p, s in zip(plain, spec):
            np.testing.assert_array_equal(p, s)
        stats = srv.last_spec_stats
        # a foreign draft mispredicts freely — correctness must not depend
        # on acceptance, only the step count does
        assert 0.0 <= stats["acceptance"] <= 1.0
        assert stats["draft_steps"] == 3 * stats["rounds"]  # k+1 per round
        assert stats["emitted_spec"] + len(PROMPTS) == sum(
            srv.cfg.decode_tokens for _ in PROMPTS)

    def test_knob_driven_draft_len(self):
        """A TunedKernelAspect-woven "speculative_draft_len" extra turns
        speculation on without any explicit argument; an explicit
        draft_len=0 overrides the knob off."""
        srv = _server("yi-6b")
        batched = srv.serve_batch(PROMPTS)
        srv.woven.state.extra["speculative_draft_len"] = 2
        cont = srv.serve_continuous(PROMPTS, page_size=8)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)
        assert srv.last_spec_stats["draft_len"] == 2
        assert srv.last_spec_stats["verify_steps"] >= 1
        srv.serve_continuous(PROMPTS, page_size=8, draft_len=0)
        assert srv.last_spec_stats is None

    def test_windowed_linear_spec_parity(self):
        """Sliding-window arch, prompts inside the window (linear pool):
        the widened per-row window mask must stay bit-exact."""
        srv = _windowed_server()
        plain = srv.serve_continuous(PROMPTS, page_size=8)
        spec = srv.serve_continuous(PROMPTS, page_size=8, draft_len=2)
        for p, s in zip(plain, spec):
            np.testing.assert_array_equal(p, s)
        assert srv.last_spec_stats["verify_steps"] >= 1
        assert srv.last_spec_stats["acceptance"] == 1.0

    def test_ring_pool_gates_speculation_off(self):
        """Prompts past the window ring the pool: eviction-on-write breaks
        the widened verify mask, so the server falls back to plain decode
        rounds — and still matches."""
        srv = _windowed_server()
        prompts = [(np.arange(20) % 50 + 1).astype(np.int32),
                   (np.arange(18) % 31 + 2).astype(np.int32)]
        plain = srv.serve_continuous(prompts, page_size=8)
        spec = srv.serve_continuous(prompts, page_size=8, draft_len=2)
        for p, s in zip(plain, spec):
            np.testing.assert_array_equal(p, s)
        stats = srv.last_spec_stats
        assert stats["verify_steps"] == 0 and stats["decode_steps"] > 0

    def test_moe_capacity_routing_gates_speculation_off(self):
        """Capacity-routed MoE couples tokens within a group: an S-token
        verify router sees different capacity/drop decisions than S
        sequential steps, so speculation stays off entirely (stats are
        cleared) and serving still matches plain."""
        srv = _server("mixtral-8x22b")
        plain = srv.serve_continuous(PROMPTS, page_size=8)
        spec = srv.serve_continuous(PROMPTS, page_size=8, draft_len=2)
        for p, s in zip(plain, spec):
            np.testing.assert_array_equal(p, s)
        assert srv.last_spec_stats is None


class TestRollback:
    def test_pool_truncate_refcount_semantics(self):
        pool = PagePool(8, 8)
        a = pool.alloc("a", 3)
        b = pool.alloc("b", 4, shared=a[:2])
        free_before = pool.free_pages
        freed = pool.truncate("b", 3)  # exclusive tail page frees
        assert freed == [b[3]]
        assert pool.free_pages == free_before + 1
        freed = pool.truncate("b", 1)  # fresh b[2] frees; shared a[1] stays
        assert freed == [b[2]]
        assert pool.refcount(a[1]) == 1 and pool.refcount(a[0]) == 2
        assert pool.tables["b"] == [a[0]]
        assert pool.tables["a"] == a  # donor table untouched throughout
        assert pool.truncate("b", 1) == []  # idempotent at the target
        with pytest.raises(ValueError):
            pool.truncate("b", -1)

    def test_manager_rollback_rewinds_length_pages_and_kv_pos(self):
        srv = _server("yi-6b")
        state = srv.woven.variant_state(None)
        state.extra["cache_max_len"] = 24
        manager = PagedCacheManager(8, 8, max_len=24, window=None)
        p = np.array([3, 1, 4, 1, 5], np.int32)
        srv._paged_admit(manager, 0, p, 12, None)
        # two identity verify rounds: grow + advance past a page boundary
        for _ in range(2):
            cache = manager.batch([0], tokens=3)
            manager.absorb([0], cache, advance=3)
        assert manager._meta[0]["length"] == 11
        assert len(manager.pool.tables[0]) == 2
        ar = np.arange(24)
        np.testing.assert_array_equal(
            np.asarray(manager.batch([0])["kv_pos"][0]),
            np.where(ar < 11, ar, -1))
        freed = manager.rollback(0, 6)
        assert len(freed) == 1  # the grown tail page came back
        assert len(manager.pool.tables[0]) == 1
        assert manager._meta[0]["length"] == 6
        # the next composed map masks the rejected draft slots dead
        kvp = np.asarray(manager.batch([0])["kv_pos"])
        assert kvp.shape == (1, 24)
        np.testing.assert_array_equal(kvp[0], np.where(ar < 6, ar, -1))
        with pytest.raises(ValueError):
            manager.rollback(0, 7)  # beyond the live length
        with pytest.raises(ValueError):
            manager.rollback(0, -1)

    def test_rollback_across_cow_boundary_leaves_donor_pages(self):
        """A verify round that CoW-split a shared page and grew a fresh
        tail, then fully rejected: rollback returns the fresh page, keeps
        the private copy (it holds valid prefix slots), and the donor's
        table, refcounts and bytes are untouched."""
        srv = _server("yi-6b")
        state = srv.woven.variant_state(None)
        state.extra["cache_max_len"] = 24
        manager = PagedCacheManager(8, 2, max_len=24, window=None)
        p = np.array([3, 1, 4, 1, 5], np.int32)
        for rid in (0, 1):  # full-prompt prefix hit: rid 1 maps rid 0's pages
            srv._paged_admit(manager, rid, p, 12, None)
        donor_table = list(manager.pool.tables[0])
        assert manager.pool.tables[1] == donor_table  # all three shared
        donor_bytes = {
            name: np.asarray(pools["pk"])[..., donor_table[2], :, :, :].copy()
            for name, pools in manager._pools.items()
        }
        cache = manager.batch([1], tokens=3)  # writes slots 5..7
        assert manager.cow_splits >= 1        # shared straddling page split
        split_page = manager.pool.tables[1][2]
        assert split_page != donor_table[2]
        manager.absorb([1], cache, advance=3)
        freed = manager.rollback(1, 5)        # full rejection
        assert len(freed) == 1                # only the grown tail page
        assert manager.pool.tables[1] == donor_table[:2] + [split_page]
        # donor untouched: same table, back to exclusive, same bytes
        assert manager.pool.tables[0] == donor_table
        assert manager.pool.refcount(donor_table[2]) == 1
        for name, pools in manager._pools.items():
            np.testing.assert_array_equal(
                np.asarray(pools["pk"])[..., donor_table[2], :, :, :],
                donor_bytes[name])
        pool = manager.pool
        refs = [pool.refcount(q) for q in range(pool.num_pages)]
        entries = [q for t in pool.tables.values() for q in t]
        assert sum(refs) == len(entries) == pool.mapped_pages

    def test_speculative_rollback_performs_no_page_copies(self, monkeypatch):
        """The no-copy criterion, spy-asserted: a rejection-heavy
        cross-model speculative serve (every round rolls back) never runs
        the device page copy inside rollback — truncation is pure
        refcount bookkeeping."""
        import repro.runtime.pages as pages_mod

        copies = {"n": 0}
        real_copy = pages_mod._copy_pool_page

        def spy(pool, src, dst):
            copies["n"] += 1
            return real_copy(pool, src, dst)

        monkeypatch.setattr(pages_mod, "_copy_pool_page", spy)
        in_rollback = {"n": 0}
        real_rollback = pages_mod.PagedCacheManager.rollback

        def wrapped(self, rid, new_length):
            before = copies["n"]
            out = real_rollback(self, rid, new_length)
            in_rollback["n"] += copies["n"] - before
            return out

        monkeypatch.setattr(pages_mod.PagedCacheManager, "rollback", wrapped)
        srv = _server("yi-6b")
        srv.draft = _server("gemma-2b")
        plain = srv.serve_continuous(PROMPTS, page_size=8)
        spec = srv.serve_continuous(PROMPTS, page_size=8, draft_len=2)
        for p, s in zip(plain, spec):
            np.testing.assert_array_equal(p, s)
        assert srv.last_spec_stats["verify_steps"] >= 1
        assert in_rollback["n"] == 0

    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 5)),
                    min_size=1, max_size=80))
    @settings(max_examples=50, deadline=None)
    def test_invariants_under_truncate_churn(self, ops):
        """Random alloc/grow/release/share/cow/truncate sequences preserve
        the refcounted-pool invariants — truncation (the speculative
        rollback primitive) composes with sharing and CoW: freed pages are
        exactly the dropped entries whose refcount hit zero, and a shared
        page dropped by one holder stays live for the others."""
        pool = PagePool(24, 8)
        rid = 0
        for op, arg in ops:
            live = list(pool.tables)
            if op == 0:
                try:
                    pool.alloc(rid, arg)
                except PoolExhausted:
                    assert pool.free_pages < arg
                rid += 1
            elif op == 1 and live:
                target = live[0]
                want = len(pool.tables[target]) + arg
                try:
                    pool.grow_to(target, want)
                except PoolExhausted:
                    assert pool.free_pages < arg
            elif op == 2 and live:
                pool.release(live[0])
            elif op == 3 and live:
                donor = live[arg % len(live)]
                prefix = pool.tables[donor][: max(1, arg)]
                extra = arg % 3
                try:
                    got = pool.alloc(rid, len(prefix) + extra, shared=prefix)
                    assert got[: len(prefix)] == prefix
                except PoolExhausted:
                    assert pool.free_pages < extra
                rid += 1
            elif op == 4 and live:
                target = live[arg % len(live)]
                if pool.tables[target]:  # truncate-to-zero leaves empties
                    logical = arg % len(pool.tables[target])
                    try:
                        pool.cow(target, logical)
                    except PoolExhausted:
                        assert pool.free_pages == 0
            elif op == 5 and live:  # speculative rollback
                target = live[arg % len(live)]
                table = pool.tables[target]
                keep = max(0, len(table) - arg)
                dropped = table[keep:]
                holders_elsewhere = {
                    q for q in dropped
                    if pool.refcount(q) > dropped.count(q)
                }
                freed = pool.truncate(target, keep)
                assert set(freed) <= set(dropped)
                # pages other requests still map are never freed
                assert not (set(freed) & holders_elsewhere)
                assert len(pool.tables[target]) == keep

            entries = [q for t in pool.tables.values() for q in t]
            refs = [pool.refcount(q) for q in range(pool.num_pages)]
            referenced = {q for q in range(pool.num_pages) if refs[q] > 0}
            free = set(pool._free)
            assert all(pool.refcount(q) >= 1 for q in entries)
            assert not (free & referenced)
            assert len(free) + len(referenced) == pool.num_pages
            assert set(entries) == referenced
            assert sum(refs) == len(entries) == pool.mapped_pages
            for t in pool.tables.values():
                assert len(t) == len(set(t))


class TestSpeculativeTuning:
    def test_space_and_vmem_model(self):
        from repro.autotune.kernel_tuner import (
            config_vmem_bytes,
            design_space,
            speculative_signature,
        )

        sig = speculative_signature(2, 128, 4, 2, 16, "float32")
        space = design_space(sig)
        assert space["draft_len"] == [1, 2, 4, 8]
        assert space["block_kv_dec"] == [128]
        v1 = config_vmem_bytes(sig, {"draft_len": 1, "block_kv_dec": 128})
        v8 = config_vmem_bytes(sig, {"draft_len": 8, "block_kv_dec": 128})
        assert v8 > v1 > 0  # the widened q tile costs VMEM

    def test_tune_records_acceptance_prior_and_lookup(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("REPRO_TUNER_CACHE", str(tmp_path / "kt.json"))
        from repro.autotune.kernel_tuner import (
            KernelTuner,
            speculative_signature,
            tuned_speculative_knobs,
        )

        tuner = KernelTuner(str(tmp_path / "kt.json"))
        sig = speculative_signature(1, 64, 4, 2, 16, "float32")
        knobs = tuner.tune(sig, num_tests=1)
        assert set(knobs) == {"draft_len", "block_kv_dec"}
        entry = tuner.cache.get(sig.key())
        # the acceptance-1 prior: draft_len + 1 useful tokens per step
        for row in entry["ops"]:
            assert row["metrics"]["tokens_per_step"][0] == \
                row["knobs"]["draft_len"] + 1
        assert tuned_speculative_knobs(1, 64, 4, 2, 16, "float32") == knobs

    def test_refine_speculative_feeds_acceptance_back(self, tmp_path):
        """Served acceptance rescales the cached tokens_per_step priors
        (error coefficient = observed mean tokens per verify / prior) and
        the draft_len knob is re-selected under the adjusted budget."""
        from repro.autotune.kernel_tuner import (
            KernelTuner,
            config_vmem_bytes,
            speculative_signature,
        )

        srv = _server("yi-6b")
        assert srv.refine_speculative(latency_budget=1.0) is None  # no spec
        srv.serve_continuous(PROMPTS, page_size=8, draft_len=2,
                             decode_tokens=8)
        stats = srv.last_spec_stats
        assert stats["verify_steps"] >= 2  # latency observations recorded

        cfg = srv.woven.program.cfg
        batch = max(1, round(stats["request_rounds"]
                             / max(stats["rounds"], 1)))
        sig = speculative_signature(
            batch, srv.cfg.max_cache_len, cfg.n_heads, cfg.kv_heads,
            cfg.resolved_head_dim, srv._paged_dtype, window=cfg.attn_window)
        tuner = KernelTuner(str(tmp_path / "spec.json"))
        ops = []
        for dl in (1, 2, 4):
            knobs = {"draft_len": dl, "block_kv_dec": 128}
            ops.append({"knobs": dict(knobs), "metrics": {
                "latency_s": [1e-3, 0.0],
                "tokens_per_step": [float(dl + 1), 0.0],
                "vmem_bytes": [float(config_vmem_bytes(sig, knobs)), 0.0],
            }})
        tuner.cache.put(sig.key(), {
            "knobs": {"draft_len": 2, "block_kv_dec": 128},
            "metrics": {"latency_s": [1e-3, 0.0],
                        "tokens_per_step": [3.0, 0.0]},
            "ops": ops,
        })
        got = srv.refine_speculative(latency_budget=10.0, tuner=tuner)
        assert got is not None and got["draft_len"] == 4  # maximized
        entry = tuner.cache.get(sig.key())
        coef = entry["runtime"]["error_coef"]["tokens_per_step"]
        assert coef == pytest.approx(stats["mean_tokens_per_verify"] / 3.0)
