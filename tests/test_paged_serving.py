"""Paged KV-cache pool + continuous batching (the PR 4/5 serving layer).

Covers:
  - PagePool allocator invariants, property-tested over random op
    sequences — now including refcounted prefix *sharing* and
    copy-on-write splits: refcounts >= 1 for every table entry, no page
    both free and referenced, conservation (free + distinct live =
    num_pages), CoW never touches a page another request still maps;
  - reservation-aware admission (deadlock-free growth, including CoW
    exposure and the first-admission capacity check);
  - PagedCacheManager round-trips (admit -> batch -> absorb -> retire);
  - Server.serve_continuous == serve_batch == per-request serve, including
    under interleaved admit/retire (tiny pool / batch caps), shared
    prompt prefixes (linear + ring families, GQA + softcap), full-prompt
    re-score admissions and copy-on-write divergence — the
    continuous-batching + prefix-caching acceptance criteria.
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
    paged_compatible,
)


class TestPagePool:
    def test_alloc_release_roundtrip(self):
        pool = PagePool(8, 16)
        a = pool.alloc("a", 3)
        b = pool.alloc("b", 2)
        assert len(set(a) | set(b)) == 5  # disjoint
        assert pool.free_pages == 3
        pool.release("a")
        assert pool.free_pages == 6
        c = pool.alloc("c", 4)
        assert set(c) & set(a)  # freed pages are reused
        assert not (set(c) & set(b))

    def test_lifo_reuse_keeps_working_set_compact(self):
        pool = PagePool(16, 8)
        first = pool.alloc("a", 2)
        pool.release("a")
        again = pool.alloc("b", 2)
        assert set(again) == set(first)

    def test_exhaustion_raises_and_rolls_back_nothing(self):
        pool = PagePool(4, 8)
        pool.alloc("a", 3)
        with pytest.raises(PoolExhausted):
            pool.alloc("b", 2)
        assert pool.free_pages == 1
        assert "b" not in pool.tables

    def test_grow_appends_at_tail(self):
        pool = PagePool(8, 8)
        start = list(pool.alloc("a", 2))
        new = pool.grow_to("a", 4)
        assert pool.tables["a"][:2] == start  # prefix untouched
        assert pool.tables["a"][2:] == new
        assert pool.grow_to("a", 3) == []  # already covered

    def test_double_alloc_rejected(self):
        pool = PagePool(4, 8)
        pool.alloc("a", 1)
        with pytest.raises(KeyError):
            pool.alloc("a", 1)

    def test_table_rows_pads_with_valid_page(self):
        pool = PagePool(8, 8)
        pool.alloc("a", 2)
        pool.alloc("b", 3)
        rows = pool.table_rows(["a", "b"], width=4)
        assert rows.shape == (2, 4)
        assert (rows >= 0).all() and (rows < 8).all()
        assert list(rows[1, :3]) == pool.tables["b"]

    @given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 5)),
                    min_size=1, max_size=60))
    @settings(max_examples=50, deadline=None)
    def test_invariants_under_random_churn(self, ops):
        """Random alloc/grow/release sequences preserve the allocator
        invariants: live tables are pairwise disjoint, live + free is a
        partition of the pool, and every table entry is a valid page."""
        pool = PagePool(24, 8)
        rid = 0
        live = {}
        for op, arg in ops:
            if op == 0:  # alloc a new request
                try:
                    live[rid] = pool.alloc(rid, arg)
                except PoolExhausted:
                    assert pool.free_pages < arg
                rid += 1
            elif op == 1 and live:  # grow the oldest live request
                target = next(iter(live))
                want = len(pool.tables[target]) + arg
                try:
                    pool.grow_to(target, want)
                    live[target] = pool.tables[target]
                except PoolExhausted:
                    assert pool.free_pages < arg
            elif op == 2 and live:  # release the oldest live request
                target = next(iter(live))
                pool.release(target)
                del live[target]

            allocated = [p for t in pool.tables.values() for p in t]
            assert len(allocated) == len(set(allocated))  # no double alloc
            assert len(allocated) + pool.free_pages == pool.num_pages
            assert all(0 <= p < pool.num_pages for p in allocated)
            assert set(pool.tables) == set(live)


class TestRefcountedPool:
    def test_shared_alloc_bumps_refcounts_not_free_list(self):
        pool = PagePool(8, 8)
        a = pool.alloc("a", 3)
        free_before = pool.free_pages
        b = pool.alloc("b", 4, shared=a[:2])
        assert b[:2] == a[:2]
        assert pool.free_pages == free_before - 2  # only the fresh pages
        assert all(pool.refcount(p) == 2 for p in a[:2])
        assert pool.refcount(a[2]) == 1
        assert pool.live_pages == 5  # distinct: 3 + 2 fresh
        assert pool.mapped_pages == 7

    def test_release_frees_only_at_zero(self):
        pool = PagePool(8, 8)
        a = pool.alloc("a", 2)
        pool.alloc("b", 2, shared=a)
        freed = pool.release("a")
        assert freed == []  # b still maps both pages
        assert all(pool.refcount(p) == 1 for p in a)
        freed = pool.release("b")
        assert set(freed) == set(a)
        assert pool.free_pages == 8

    def test_stale_share_rejected(self):
        pool = PagePool(4, 8)
        a = pool.alloc("a", 1)
        pool.release("a")
        with pytest.raises(ValueError, match="stale"):
            pool.alloc("b", 1, shared=a)

    def test_cow_splits_shared_and_skips_exclusive(self):
        pool = PagePool(8, 8)
        a = pool.alloc("a", 2)
        pool.alloc("b", 2, shared=a)
        assert pool.cow("a", 0) is not None
        old_new = pool.tables["a"][0], pool.tables["b"][0]
        assert old_new[0] != old_new[1]           # remapped, not mutated
        assert pool.tables["b"][0] == a[0]        # b keeps the original
        assert pool.refcount(a[0]) == 1
        assert pool.cow("a", 0) is None           # now exclusive: no split
        assert pool.cow("a", 1) is not None       # second shared page splits

    def test_cow_exhaustion_raises(self):
        pool = PagePool(2, 8)
        a = pool.alloc("a", 2)
        pool.alloc("b", 2, shared=a)
        with pytest.raises(PoolExhausted):
            pool.cow("b", 0)

    @given(st.lists(st.tuples(st.integers(0, 4), st.integers(1, 5)),
                    min_size=1, max_size=80))
    @settings(max_examples=50, deadline=None)
    def test_refcount_invariants_under_shared_churn(self, ops):
        """Random alloc/grow/release/share/cow sequences preserve the
        refcounted-pool invariants: every table entry's refcount >= 1, no
        page is both free and referenced, free + distinct live pages
        partition the pool, total references equal total table entries,
        tables never alias a page twice, and a CoW split leaves the
        original page in every *other* table that mapped it."""
        pool = PagePool(24, 8)
        rid = 0
        for op, arg in ops:
            live = list(pool.tables)
            if op == 0:  # alloc a new request
                try:
                    pool.alloc(rid, arg)
                except PoolExhausted:
                    assert pool.free_pages < arg
                rid += 1
            elif op == 1 and live:  # grow the oldest live request
                target = live[0]
                want = len(pool.tables[target]) + arg
                try:
                    pool.grow_to(target, want)
                except PoolExhausted:
                    assert pool.free_pages < arg
            elif op == 2 and live:  # release the oldest live request
                pool.release(live[0])
            elif op == 3 and live:  # share a donor's prefix + fresh tail
                donor = live[arg % len(live)]
                prefix = pool.tables[donor][: max(1, arg)]
                extra = arg % 3
                try:
                    got = pool.alloc(rid, len(prefix) + extra, shared=prefix)
                    assert got[: len(prefix)] == prefix
                except PoolExhausted:
                    assert pool.free_pages < extra
                rid += 1
            elif op == 4 and live:  # copy-on-write split
                target = live[arg % len(live)]
                table = pool.tables[target]
                logical = arg % len(table)
                before = table[logical]
                holders = [r for r, t in pool.tables.items()
                           if r != target and before in t]
                try:
                    split = pool.cow(target, logical)
                except PoolExhausted:
                    assert pool.free_pages == 0
                    split = None
                if split is not None:
                    old, new = split
                    assert old == before and new != old
                    # the original stays mapped by every other holder
                    for r in holders:
                        assert old in pool.tables[r]

            entries = [p for t in pool.tables.values() for p in t]
            refs = [pool.refcount(p) for p in range(pool.num_pages)]
            referenced = {p for p in range(pool.num_pages) if refs[p] > 0}
            free = set(pool._free)
            assert all(pool.refcount(p) >= 1 for p in entries)
            assert not (free & referenced)  # never both free and referenced
            assert len(free) + len(referenced) == pool.num_pages
            assert set(entries) == referenced
            assert sum(refs) == len(entries) == pool.mapped_pages
            for t in pool.tables.values():  # no within-table aliasing
                assert len(t) == len(set(t))


class TestBuildLinearPool:
    def test_pool_packs_prefixes_and_tables_resolve(self):
        ks = [np.arange(l * 2 * 4, dtype=np.float32).reshape(l, 2, 4)
              for l in (5, 12)]
        pk, pv, tables, pool = build_linear_pool(ks, ks, 4, max_len=16)
        assert pool.live_pages == cdiv(5, 4) + cdiv(12, 4)
        for i, l in enumerate((5, 12)):
            got = np.asarray(pk[tables[i]]).reshape(-1, 2, 4)[:l]
            np.testing.assert_array_equal(got, ks[i])


class TestPagedCacheManager:
    def _prefill_cache(self, model, params, pol, toks):
        from repro.nn.module import Ctx

        ctx = Ctx(policies=pol, extra={"cache_max_len": 24})
        _, cache = model(params, {"tokens": toks}, ctx=ctx, mode="prefill")
        return cache

    def _setup(self):
        from repro.models.registry import build_model, reduced_config
        from repro.nn.dtypes import PolicyResolver
        from repro.nn.module import init_params

        pol = PolicyResolver.default("double")
        cfg = reduced_config("yi-6b")
        model = build_model(cfg)
        params = init_params(model, jax.random.PRNGKey(0), pol)
        return model, params, pol

    def test_admit_batch_absorb_retire_roundtrip(self):
        model, params, pol = self._setup()
        manager = PagedCacheManager(num_pages=12, page_size=8)
        for rid, S in enumerate((3, 7)):
            toks = jnp.ones((1, S), jnp.int32)
            cache = self._prefill_cache(model, params, pol, toks)
            assert paged_compatible(cache)
            assert rid == 0 or manager.can_admit(S + 4)
            manager.admit(rid, cache, final_len=S + 4)
        cache = manager.batch([0, 1])
        assert "block_tables" in cache and "kv_pos" in cache
        group = next(v for k, v in cache.items()
                     if isinstance(v, dict) and "pk" in v)
        assert group["index"].shape[-1] == 2
        np.testing.assert_array_equal(np.asarray(group["index"])[..., 0], 3)
        manager.absorb([0, 1], cache)  # identity step: lengths advance
        assert manager._meta[0]["length"] == 4
        manager.retire(0)
        assert manager.pool.free_pages > 0
        cache2 = manager.batch([1])
        assert cache2["block_tables"].shape[0] == 1

    def test_rejects_mixed_cache_families(self):
        """Sliding-window models ring only when prompt_len > window, so a
        batch straddling W would mix ring and linear layouts in one pool —
        the manager must refuse loudly instead of silently mis-packing."""
        from repro.models.registry import build_model, reduced_config
        from repro.nn.dtypes import PolicyResolver
        from repro.nn.module import Ctx, init_params

        pol = PolicyResolver.default("double")
        cfg = reduced_config("mixtral-8x22b")  # attn_window=16 reduced
        model = build_model(cfg)
        params = init_params(model, jax.random.PRNGKey(0), pol)
        ctx = Ctx(policies=pol, extra={"cache_max_len": 24})
        caches = []
        for S in (3, 20):  # linear (S <= W) then ring (S > W)
            _, cache = model(params,
                             {"tokens": jnp.ones((1, S), jnp.int32)},
                             ctx=ctx, mode="prefill")
            caches.append(cache)
        manager = PagedCacheManager(num_pages=16, page_size=8)
        manager.admit(0, caches[0], final_len=8)
        with pytest.raises(ValueError, match="family mismatch"):
            manager.admit(1, caches[1], final_len=23)

    def test_rejects_ssm_state(self):
        from repro.models.registry import build_model, reduced_config
        from repro.nn.dtypes import PolicyResolver
        from repro.nn.module import Ctx, init_params

        pol = PolicyResolver.default("double")
        cfg = reduced_config("rwkv6-3b")
        model = build_model(cfg)
        params = init_params(model, jax.random.PRNGKey(0), pol)
        ctx = Ctx(policies=pol, extra={"cache_max_len": 16})
        _, cache = model(params, {"tokens": jnp.ones((1, 4), jnp.int32)},
                         ctx=ctx, mode="prefill")
        assert not paged_compatible(cache)
        manager = PagedCacheManager(num_pages=4, page_size=8)
        with pytest.raises(ValueError):
            manager.admit(0, cache, final_len=8)


def _server(arch, **cfg_kw):
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.runtime.server import Server, ServerConfig

    program = Program.from_arch(arch, kind="serve", reduced=True)
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    return Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4,
                                      **cfg_kw))


PROMPTS = [np.ones((5,), np.int32),
           (np.arange(1, 9) % 50).astype(np.int32),
           np.full((3,), 7, np.int32)]


class TestContinuousServer:
    """serve_continuous == serve_batch == per-request serve — bit-identical
    greedy decode over the paged pool (acceptance criterion), for both the
    linear (yi) and ring (mixtral sliding-window) cache families."""

    @pytest.mark.parametrize("arch", ["yi-6b", "mixtral-8x22b"])
    def test_continuous_equals_batch_and_solo(self, arch):
        srv = _server(arch)
        batched = srv.serve_batch(PROMPTS)
        cont = srv.serve_continuous(PROMPTS, page_size=8)
        for p, b, c in zip(PROMPTS, batched, cont):
            np.testing.assert_array_equal(b, c)
            np.testing.assert_array_equal(c, srv.serve(p[None])[0])

    def test_interleaved_admit_retire_parity(self):
        """A batch cap forces late arrivals to wait for a retirement —
        the continuous path must still match the all-at-once batch."""
        srv = _server("yi-6b")
        batched = srv.serve_batch(PROMPTS)
        for max_batch in (1, 2):
            cont = srv.serve_continuous(PROMPTS, page_size=8,
                                        max_batch=max_batch)
            for b, c in zip(batched, cont):
                np.testing.assert_array_equal(b, c)

    def test_page_constrained_admission_parity(self):
        """A pool that cannot hold every request at once must admit in
        waves (pages freed by retirement re-admit the waiters) and still
        match."""
        srv = _server("yi-6b")
        batched = srv.serve_batch(PROMPTS)
        # worst case per request: ceil((8+3)/8) = 2 pages; 4 pages = 2-wide
        cont = srv.serve_continuous(PROMPTS, page_size=8, pool_pages=4)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)

    def test_pool_too_small_rejects_only_unfittable(self):
        """An unfittable request no longer kills the serve (the old path
        raised RuntimeError mid-serve and threw away every completed
        request's output): it gets a structured rejection and everyone
        else's tokens survive, bit-identical to a roomy serve."""
        srv = _server("yi-6b")
        roomy = srv.serve_continuous(PROMPTS, page_size=8)
        out = srv.serve_continuous(PROMPTS, page_size=8, pool_pages=1)
        st = {o["rid"]: o["status"] for o in srv.last_outcomes}
        # prompt 1 (7 tokens -> final 10 -> 2 pages) can never fit 1 page
        assert st[1] == "rejected" and out[1].size == 0
        assert "page pool too small" in srv.last_outcomes[1]["reason"]
        for r in (0, 2):  # 1-page requests serve sequentially, bit-exact
            assert st[r] == "ok"
            np.testing.assert_array_equal(out[r], roomy[r])

    def test_ssm_family_raises(self):
        srv = _server("rwkv6-3b")
        with pytest.raises(ValueError):
            srv.serve_continuous([np.ones((4,), np.int32)])

    def test_memoized_continuous(self):
        from repro.memo.table import MemoTable

        srv = _server("yi-6b")
        srv.memo = MemoTable(size=8)
        a = srv.serve_continuous(PROMPTS[:2], page_size=8)
        b = srv.serve_continuous(PROMPTS[:2], page_size=8)
        assert srv.memo.hits >= 1
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_decode_step_latencies_recorded_and_refine_smoke(self, tmp_path,
                                                            monkeypatch):
        """Serving records per-step decode latencies and can push them into
        the tuner cache once the paged signature has DSE rows."""
        from repro.autotune.kernel_tuner import KernelTuner, config_vmem_bytes

        monkeypatch.setenv("REPRO_TUNER_CACHE", str(tmp_path / "rt.json"))
        srv = _server("yi-6b")
        srv.serve_continuous(PROMPTS, page_size=8)
        assert srv.decode_step_latencies
        assert srv._paged_sig is not None
        assert srv.refine_kernel_tuner(latency_budget=1.0) is None  # untuned

        tuner = KernelTuner(str(tmp_path / "rt.json"))
        sig = srv._paged_sig
        knobs = {"page_size": 64, "block_kv_dec": 128}
        tuner.cache.put(sig.key(), {
            "knobs": dict(knobs),
            "metrics": {"latency_s": [1e-3, 0.0]},
            "ops": [{"knobs": dict(knobs),
                     "metrics": {
                         "latency_s": [1e-3, 0.0],
                         "vmem_bytes": [
                             float(config_vmem_bytes(sig, knobs)), 0.0]}}],
        })
        got = srv.refine_kernel_tuner(latency_budget=10.0, tuner=tuner)
        assert got == knobs
        entry = tuner.cache.get(sig.key())
        assert "runtime" in entry and "error_coef" in entry["runtime"]

    def test_memo_hit_clears_refine_state(self):
        """Regression: a memo hit used to return before the paged
        signature / latency window were refreshed, so a following
        refine_kernel_tuner read stale state from the previous serve.
        Now the hit clears both and refine declines cleanly."""
        from repro.memo.table import MemoTable

        srv = _server("yi-6b")
        srv.memo = MemoTable(size=8)
        srv.serve_continuous(PROMPTS[:2], page_size=8)
        assert srv._paged_sig is not None
        srv.serve_continuous(PROMPTS[:2], page_size=8)  # memo hit
        assert srv.memo.hits >= 1
        assert srv._paged_sig is None and srv._paged_dtype is None
        assert not srv.decode_step_latencies and not srv._step_lat_by_batch
        assert srv.refine_kernel_tuner(latency_budget=1.0) is None


class TestAdmissionControl:
    def test_first_admission_capacity_checked(self):
        """Regression: the first admission used to bypass can_admit (no
        structure yet), wasting a full prefill and dying with a raw
        PoolExhausted out of pool.alloc.  The capacity check now derives
        slots-per-token before packing, so an oversized *first* request
        hits the clean 'page pool too small' rejection without
        prefilling."""
        srv = _server("yi-6b")
        big = (np.arange(12) % 9 + 1).astype(np.int32)  # final 15 -> 2 pages
        out = srv.serve_continuous([big], page_size=8, pool_pages=1)
        assert out[0].size == 0
        assert srv.last_outcomes[0]["status"] == "rejected"
        assert "page pool too small" in srv.last_outcomes[0]["reason"]
        for vc in (srv.prefill_vc, srv.probe_vc, srv.paged_prefill_vc,
                   srv.rescore_vc):
            assert not vc.dispatch_counts  # nothing was prefilled

    def test_clipped_final_len_interleaves_safely(self):
        """Regression: requests whose final_len is clipped by
        max_cache_len must not grow past their reservation (batch() clamps
        at final_len), so a clipped long request interleaved with waiting
        short ones on a tight pool completes without PoolExhausted and
        matches the batch path."""
        srv = _server("yi-6b")  # max_cache_len=24: S=20, n=8 clips to 24
        long_p = (np.arange(20) % 40 + 1).astype(np.int32)
        pr = [long_p, np.full((4,), 9, np.int32), np.full((4,), 11, np.int32)]
        batched = srv.serve_batch(pr, decode_tokens=8)
        # 3 pages (clipped long) + 2 pages (one short): the second short
        # must wait for a retirement
        cont = srv.serve_continuous(pr, decode_tokens=8, page_size=8,
                                    pool_pages=5)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)


BASE16 = np.arange(1, 17, dtype=np.int32)  # two full pages at page_size=8
SHARED_PROMPTS = [
    np.concatenate([BASE16, np.array([21, 22, 23], np.int32)]),
    np.concatenate([BASE16, np.array([31, 32], np.int32)]),
    np.full((3,), 7, np.int32),  # unrelated short request rides along
]


def _softcap_gqa_server():
    """Dense-family GQA config with grok's logit soft-cap: the softcap
    acceptance axis for prefix sharing (the MoE softcap arch can't share —
    capacity routing makes prefix K/V request-dependent)."""
    from repro.configs.base import SHAPES
    from repro.core.program import Program
    from repro.launch.weave import default_weave
    from repro.models.registry import build_model, reduced_config
    from repro.runtime.server import Server, ServerConfig

    cfg = reduced_config("yi-6b").replace(attn_softcap=30.0)
    program = Program(model=build_model(cfg), cfg=cfg, kind="serve")
    woven = default_weave(program, SHAPES["prefill_32k"], {})
    return Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4))


class TestPrefixSharing:
    """Shared-prefix serving is bit-identical to unshared serving at the
    server level (the acceptance criterion), across the linear GQA family
    (yi), GQA + softcap (dense grok-style cap), and the ring family
    (mixtral, prompts past the window — sharing disabled, direct-to-pool
    ring prefill still exact).  Capacity-routed MoE keeps sharing off
    (prefix K/V are group-coupled, not request-independent) but the paged
    prefill must still match exactly."""

    @pytest.mark.parametrize("arch", ["yi-6b", "softcap-gqa"])
    def test_shared_prefix_bit_identical(self, arch):
        srv = _softcap_gqa_server() if arch == "softcap-gqa" \
            else _server(arch)
        batched = srv.serve_batch(SHARED_PROMPTS)
        shared = srv.serve_continuous(SHARED_PROMPTS, page_size=8)
        unshared = srv.serve_continuous(SHARED_PROMPTS, page_size=8,
                                        prefix_sharing=False)
        for b, s, u in zip(batched, shared, unshared):
            np.testing.assert_array_equal(b, s)
            np.testing.assert_array_equal(s, u)
        # the 16-token prefix is two pages, mapped (not copied) for req 1
        assert srv.last_pool_stats["prefix_hits"] == 0  # unshared run
        srv.serve_continuous(SHARED_PROMPTS, page_size=8)
        stats = srv.last_pool_stats
        assert stats["prefix_hits"] >= 2
        assert stats["peak_live_pages"] < stats["peak_mapped_pages"]

    def test_pallas_weave_keeps_sharing_with_parity(self):
        """Flipped from the PR 5 disable-guard: the widened-q (q_offset)
        flash_decode kernel now serves the suffix-over-prefix prefill, so
        a pallas-woven attention impl keeps prefix sharing ON — shared
        serving stays bit-identical to the batch path and the donor's
        prefix pages are mapped, not copied."""
        from repro.configs.base import SHAPES
        from repro.core.program import Program
        from repro.core.strategies.kernels import KernelAspect
        from repro.launch.weave import default_weave
        from repro.runtime.server import Server, ServerConfig

        program = Program.from_arch("yi-6b", kind="serve", reduced=True)
        woven = default_weave(
            program, SHAPES["prefill_32k"], {},
            extra_aspects=[KernelAspect("*", "attention", "pallas")])
        srv = Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4))
        batched = srv.serve_batch(SHARED_PROMPTS)
        cont = srv.serve_continuous(SHARED_PROMPTS, page_size=8)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)
        stats = srv.last_pool_stats
        assert stats["prefix_hits"] >= 2  # the 16-token prefix: two pages
        assert stats["peak_live_pages"] < stats["peak_mapped_pages"]

    def test_sharer_jumps_queue_behind_blocked_nonsharer(self):
        """Prefix-aware admission: a sharer queued behind a non-sharer
        that cannot fit gets admitted while its donor's pages are still
        live — the shared prefix costs it no fresh pages — and maps the
        donor's prefix pages; outputs still match the batch path."""
        donor = np.concatenate([BASE16, np.array([21, 22, 23], np.int32)])
        blocker = (np.arange(19) % 37 + 60).astype(np.int32)  # no prefix
        sharer = np.concatenate([BASE16, np.array([31, 32], np.int32)])
        pr = [donor, blocker, sharer]
        srv = _server("yi-6b")
        batched = srv.serve_batch(pr)
        # donor needs 3 pages; 5-page pool leaves 2 free: the blocker's 3
        # fresh pages don't fit, the sharer's 1 fresh page (2 shared) does
        cont = srv.serve_continuous(pr, page_size=8, pool_pages=5)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)
        # FIFO would stall until the donor retires and share nothing —
        # the hits are the witness that the sharer jumped the queue while
        # the donor still held its pages
        assert srv.last_pool_stats["prefix_hits"] >= 2

    def test_moe_family_keeps_sharing_off_and_matches(self):
        """grok (MoE + softcap + GQA): the scheduler must not share prefix
        pages — capacity routing couples tokens across the group, so a
        sharer's prefix K/V could differ from the donor's — but the
        direct-to-pool paged prefill still serves bit-identically."""
        srv = _server("grok-1-314b")
        batched = srv.serve_batch(SHARED_PROMPTS)
        cont = srv.serve_continuous(SHARED_PROMPTS, page_size=8)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)
        assert srv.last_pool_stats["prefix_hits"] == 0
        assert srv.last_pool_stats["cow_splits"] == 0

    def test_ring_family_paged_prefill_parity(self):
        """Prompts past the sliding window ring the pool: prefix sharing
        stays off (slot contents depend on the wrap) but the direct-to-
        pool ring prefill must still match the batch path exactly."""
        srv = _server("mixtral-8x22b")  # reduced window 16
        prompts = [(np.arange(20) % 50 + 1).astype(np.int32),
                   (np.arange(18) % 31 + 2).astype(np.int32)]
        batched = srv.serve_batch(prompts)
        cont = srv.serve_continuous(prompts, page_size=8)
        for b, c in zip(batched, cont):
            np.testing.assert_array_equal(b, c)
        assert srv.last_pool_stats["prefix_hits"] == 0

    def test_identical_prompts_rescore_and_cow(self):
        """A full-prompt prefix hit admits with zero prefill (the re-score
        decode step supplies the first logits) and the first decode write
        into the shared tail page splits it copy-on-write — outputs stay
        bit-identical to solo serving."""
        srv = _server("yi-6b")
        p = np.array([3, 1, 4, 1, 5], np.int32)  # S % page_size != 0
        out = srv.serve_continuous([p, p], page_size=8)
        solo = srv.serve(p[None])[0]
        np.testing.assert_array_equal(out[0], solo)
        np.testing.assert_array_equal(out[1], solo)
        stats = srv.last_pool_stats
        assert stats["prefix_hits"] >= 1  # the whole prompt rode one page
        assert stats["cow_splits"] >= 1   # first decode write split it
        assert srv.rescore_vc.dispatch_counts  # no-prefill admission ran

    def test_long_prompt_full_share_falls_back_to_suffix_prefill(self):
        """Prompts past the blocked-attention threshold must not take the
        re-score shortcut (their unshared first token comes from the
        blocked online-softmax path — a different numeric family than the
        decode softmax): the share is trimmed so a suffix prefill runs,
        and parity still holds."""
        from repro.configs.base import SHAPES
        from repro.core.program import Program
        from repro.launch.weave import default_weave
        from repro.runtime.server import Server, ServerConfig

        program = Program.from_arch("yi-6b", kind="serve", reduced=True)
        woven = default_weave(program, SHAPES["prefill_32k"], {})
        woven.state.extra["xla_attn_block"] = 2  # S=5 > 2*block
        srv = Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4))
        p = np.array([3, 1, 4, 1, 5], np.int32)
        out = srv.serve_continuous([p, p], page_size=2)
        solo = srv.serve(p[None])[0]
        np.testing.assert_array_equal(out[0], solo)
        np.testing.assert_array_equal(out[1], solo)
        assert not srv.rescore_vc.dispatch_counts  # gate held
        # the trimmed share still maps the full prefix pages
        assert srv.last_pool_stats["prefix_hits"] >= 2

    def test_aligned_full_share_trim_is_reserved(self):
        """Regression: a page-aligned full-prompt hit that the long-prompt
        gate trims back to a suffix prefill costs one fresh page the share
        would have covered — can_admit must reserve it, so a tight pool
        defers the admission instead of hitting PoolExhausted mid-serve."""
        from repro.configs.base import SHAPES
        from repro.core.program import Program
        from repro.launch.weave import default_weave
        from repro.runtime.server import Server, ServerConfig

        program = Program.from_arch("yi-6b", kind="serve", reduced=True)
        woven = default_weave(program, SHAPES["prefill_32k"], {})
        woven.state.extra["xla_attn_block"] = 2  # S=6 > 2*block
        srv = Server(woven, ServerConfig(max_cache_len=24, decode_tokens=4))
        p = np.array([3, 1, 4, 1, 5, 9], np.int32)  # S % page_size == 0
        # final = 9 -> 5 pages each; 7 pages force the second admission to
        # wait (5 growth + trim page > remaining) rather than overcommit
        out = srv.serve_continuous([p, p], page_size=2, pool_pages=7)
        solo = srv.serve(p[None])[0]
        np.testing.assert_array_equal(out[0], solo)
        np.testing.assert_array_equal(out[1], solo)
        assert not srv.rescore_vc.dispatch_counts  # gate held

    def test_mixed_legacy_and_direct_admissions_compose(self):
        """A batch mixing a legacy admit() of a hand-built cache (no
        hoisted kv_pos) with a direct-to-pool admission must still
        compose: the manager synthesizes the missing map."""
        import jax.numpy as jnp

        from repro.runtime.pages import PagedCacheManager

        srv = _server("yi-6b")
        manager = PagedCacheManager(8, 8, max_len=24, window=None)
        p = np.array([3, 1, 4, 1, 5], np.int32)
        srv._paged_admit(manager, 0, p, 12, None)
        # hand-built dense cache without kv_pos, matching the pool groups
        legacy = {}
        for name, info in manager._groups.items():
            shape = (info["n"], 1, info["length"], info["kv_heads"],
                     info["head_dim"])
            legacy[name] = {
                "k": jnp.zeros(shape, info["dtype"]),
                "v": jnp.zeros(shape, info["dtype"]),
                "index": jnp.full((info["n"],), 4, jnp.int32),
            }
        manager.admit(1, legacy, final_len=8)
        cache = manager.batch([0, 1])
        assert cache["kv_pos"].shape == (2, 24)
        np.testing.assert_array_equal(
            np.asarray(cache["kv_pos"][1]),
            np.where(np.arange(24) < 4, np.arange(24), -1))

    def test_cow_divergence_isolates_requests(self):
        """Two requests that share a whole prompt then *diverge* (forced
        different continuations) must never see each other's tokens: the
        split remaps the writer's table, the donor keeps the original
        page, and each stream's logits match its own solo run exactly."""
        import jax.numpy as jnp

        from repro.runtime.pages import PagedCacheManager

        srv = _server("yi-6b")
        state = srv.woven.variant_state(None)
        state.extra["cache_max_len"] = 24
        p = np.array([3, 1, 4, 1, 5], np.int32)
        manager = PagedCacheManager(8, 8, max_len=24, window=None)
        first = [srv._paged_admit(manager, rid, p, 12, None)
                 for rid in (0, 1)]
        assert first[0] == first[1]
        assert manager.prefix_hits >= 1
        shared_page = manager.pool.tables[0][0]
        assert manager.pool.tables[1][0] == shared_page

        forced = {0: [5, 6], 1: [9, 10]}  # divergent continuations
        paged_logits = {0: [], 1: []}
        for step in range(2):
            cache = manager.batch([0, 1])
            tok = jnp.asarray([[forced[0][step]], [forced[1][step]]],
                              jnp.int32)
            pos = jnp.full((2, 1), 5 + step, jnp.int32)
            logits, new_cache = srv.decode_vc(
                None, srv.params, {"tokens": tok, "positions": pos}, cache)
            manager.absorb([0, 1], new_cache)
            paged_logits[0].append(np.asarray(logits[0]))
            paged_logits[1].append(np.asarray(logits[1]))
        assert manager.cow_splits >= 1
        t0, t1 = manager.pool.tables[0], manager.pool.tables[1]
        assert t0[0] != t1[0]  # the written tail page split
        assert shared_page in (t0[0], t1[0])  # one side kept the original

        # each stream matches a solo dense run of the same forced tokens
        for rid in (0, 1):
            toks = jnp.asarray(p, jnp.int32).reshape(1, -1)
            _, cache = srv.prefill_vc(None, srv.params, {"tokens": toks})
            for step in range(2):
                tok = jnp.asarray([[forced[rid][step]]], jnp.int32)
                pos = jnp.full((1, 1), 5 + step, jnp.int32)
                logits, cache = srv.decode_vc(
                    None, srv.params, {"tokens": tok, "positions": pos},
                    cache)
                np.testing.assert_array_equal(paged_logits[rid][step],
                                              np.asarray(logits[0]))


def _legacy_cache(manager, length: int) -> dict:
    """A hand-built dense (batch=1) cache with no hoisted `kv_pos`,
    matching the pool's groups, `length` slots filled."""
    import jax.numpy as jnp

    legacy = {}
    for name, info in manager._groups.items():
        shape = (info["n"], 1, info["length"], info["kv_heads"],
                 info["head_dim"])
        legacy[name] = {
            "k": jnp.zeros(shape, info["dtype"]),
            "v": jnp.zeros(shape, info["dtype"]),
            "index": jnp.full((info["n"],), length, jnp.int32),
        }
    return legacy


class TestComposedKvPos:
    """The pool keeps only host lengths per request and builds each
    wave's (B, T) `kv_pos` map from them.  Every composed row must equal
    the row the previous step returned for that request — the row a pool
    that stored the step's `kv_pos` per request would hand back."""

    T = 24

    def _check(self, manager, rids, expect, cache=None):
        cache = manager.batch(rids) if cache is None else cache
        got = np.asarray(cache["kv_pos"])
        assert got.shape == (len(rids), self.T)
        for i, rid in enumerate(rids):
            np.testing.assert_array_equal(got[i], expect[rid],
                                          err_msg=f"request {rid}")
        return cache

    def _decode(self, srv, manager, rids, expect):
        """One real decode step: the composed map must match `expect`,
        and the step's returned rows become the new expectation."""
        import jax.numpy as jnp

        cache = self._check(manager, rids, expect)
        pos = [[manager._meta[r]["length"]] for r in rids]
        _, new_cache = srv.decode_vc(
            None, srv.params,
            {"tokens": jnp.full((len(rids), 1), 7, jnp.int32),
             "positions": jnp.asarray(pos, jnp.int32)}, cache)
        ret = np.asarray(new_cache["kv_pos"])
        manager.absorb(rids, new_cache)
        for i, rid in enumerate(rids):
            expect[rid] = ret[i]

    @pytest.mark.parametrize("second", ["direct", "chunked", "shared",
                                        "legacy"])
    def test_composed_rows_match_step_rows(self, second):
        import jax.numpy as jnp

        srv = _server("yi-6b")
        srv.woven.variant_state(None).extra["cache_max_len"] = self.T
        manager = PagedCacheManager(8, 8, max_len=self.T, window=None)
        ar = np.arange(self.T)
        p0 = np.array([3, 1, 4, 1, 5], np.int32)
        srv._paged_admit(manager, 0, p0, 20, None)
        expect = {0: np.where(ar < 5, ar, -1)}

        if second == "chunked":
            # an 18-token prompt in 8-token chunks, a decode of request 0
            # between chunks
            p1 = (np.arange(18) % 50 + 1).astype(np.int32)
            tok, _, cont = srv._paged_admit_chunked(manager, 1, p1, 24,
                                                    None, chunk=8)
            assert tok is None and cont is not None
            while tok is None:
                self._decode(srv, manager, [0], expect)
                tok = cont()["tok"]
            S1 = 18
        elif second == "legacy":
            manager.admit(1, _legacy_cache(manager, 4), final_len=16)
            S1 = 4
        else:
            p1 = p0 if second == "shared" else np.arange(1, 10,
                                                         dtype=np.int32)
            srv._paged_admit(manager, 1, p1, 20, None)
            S1 = len(p1)
        if second == "shared":
            assert manager.prefix_hits >= 1
            # the re-score view masks as the admission left it
            self._check(manager, [1], {1: np.where(ar < 5, ar, -1)},
                        cache=manager.rescore_view(1))
        expect[1] = np.where(ar < S1, ar, -1)

        for _ in range(2):
            self._decode(srv, manager, [0, 1], expect)

        # a verify step writes a 3-token draft block, lengths advance by 3
        rids = [0, 1]
        cache = self._check(manager, rids, expect,
                            cache=manager.batch(rids, tokens=3))
        vpos = [[manager._meta[r]["length"] + s for s in range(3)]
                for r in rids]
        _, new_cache = srv._verify_step(None, 2)(
            srv.params, {"tokens": jnp.full((2, 3), 7, jnp.int32),
                         "positions": jnp.asarray(vpos, jnp.int32)}, cache)
        ret = np.asarray(new_cache["kv_pos"])
        manager.absorb(rids, new_cache, advance=3)
        expect = {r: ret[i] for i, r in enumerate(rids)}
        self._check(manager, rids, expect)

        # rollback rewinds: slots at or past the new length mask dead
        for rid, back in ((0, 2), (1, 1)):
            nl = manager._meta[rid]["length"] - back
            manager.rollback(rid, nl)
            expect[rid] = np.where(ar < nl, expect[rid], -1)
        self._decode(srv, manager, [1, 0], expect)
        self._check(manager, [0, 1], expect)

        # the re-score view of a decoding request keeps the decode map
        self._check(manager, [0], expect, cache=manager.rescore_view(0))

    def test_absorb_keeps_no_per_request_device_state(self):
        """Linear pools hold host ints per request, never a device array:
        no `kv_pos` row is stored after admission, decode or rollback."""
        import jax.numpy as jnp

        srv = _server("yi-6b")
        manager = PagedCacheManager(8, 8, max_len=self.T, window=None)
        for rid, n in ((0, 5), (1, 9)):
            srv._paged_admit(manager, rid, np.arange(1, n + 1,
                                                     dtype=np.int32),
                             16, None)
        manager.admit(2, _legacy_cache(manager, 4), final_len=12)
        rids = [0, 1, 2]

        def no_device_rows():
            for rid in rids:
                meta = manager._meta[rid]
                assert "kv_pos" not in meta
                assert meta["pos"] == {}
                assert not any(isinstance(v, jax.Array)
                               for v in meta.values())

        no_device_rows()
        for _ in range(2):
            cache = manager.batch(rids)
            pos = [[manager._meta[r]["length"]] for r in rids]
            _, new_cache = srv.decode_vc(
                None, srv.params,
                {"tokens": jnp.full((3, 1), 7, jnp.int32),
                 "positions": jnp.asarray(pos, jnp.int32)}, cache)
            assert "kv_pos" in new_cache  # the step still returns a map
            manager.absorb(rids, new_cache)
            no_device_rows()
        manager.rollback(1, 9)
        no_device_rows()
        assert [manager._meta[r]["length"] for r in rids] == [7, 9, 6]
