"""Block-sparse grid pruning: the pruned kernel must match the dense kernel
and the oracle across causal / sliding-window / GQA / ragged shapes, and its
KV schedule must never stream a fully-masked block — in *both* directions:
the forward / dq pass streams `kv_schedule`, the fused dk/dv backward pass
streams the transposed `q_schedule` (the PR 2 deliverable)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import (
    block_fully_masked,
    cdiv,
    flash_attention_bwd,
    flash_attention_fwd,
    kv_schedule,
    kv_steps_for,
    q_schedule,
    q_steps_for,
    vmem_bytes,
    vmem_bytes_bwd,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _qkv(key, B, S, H, K, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    return q, k, v


class TestPrunedParity:
    """Interpret-mode outputs: pruned == dense == attention_ref."""

    @pytest.mark.parametrize("name,S,HK,causal,window,bq,bkv", [
        ("causal", 256, (4, 2), True, None, 128, 128),
        ("sliding", 256, (4, 4), True, 64, 64, 64),
        ("window_lt_block", 256, (8, 1), True, 32, 128, 128),
        ("ragged_q", 320, (4, 2), True, None, 128, 128),
        ("ragged_window", 320, (4, 2), True, 96, 128, 64),
        ("tiny", 96, (2, 2), True, 48, 64, 64),
        ("noncausal", 256, (2, 2), False, None, 128, 128),
    ])
    def test_parity(self, key, name, S, HK, causal, window, bq, bkv):
        H, K = HK
        q, k, v = _qkv(key, 2, S, H, K, 64)
        kw = dict(causal=causal, window=window, block_q=bq, block_kv=bkv,
                  interpret=True)
        out_p = flash_attention(q, k, v, pruned=True, **kw)
        out_d = flash_attention(q, k, v, pruned=False, **kw)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                                   rtol=2e-6, atol=2e-6)

    def test_gqa_group_mapping_pruned(self, key):
        """Each q head must attend its own kv group through the remapped
        index maps too."""
        B, S, H, K, D = 1, 128, 4, 2, 64
        q, k, v = _qkv(key, B, S, H, K, D)
        v = v.at[:, :, 1].mul(100.0)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                              pruned=True, interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    def test_bf16_softcap(self, key):
        q, k, v = _qkv(key, 1, 256, 4, 2, 64, jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, window=64, softcap=30.0,
                              block_q=128, block_kv=128, pruned=True,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=True, window=64, softcap=30.0)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_kernel_layout_entry(self, key):
        """flash_attention_fwd (kernel layout) prunes identically."""
        B, H, K, S, D = 1, 4, 2, 320, 64
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, H, S, D))
        k = jax.random.normal(ks[1], (B, K, S, D))
        v = jax.random.normal(ks[2], (B, K, S, D))
        out_p = flash_attention_fwd(q, k, v, causal=True, window=128,
                                    block_q=128, block_kv=128, pruned=True,
                                    interpret=True)
        out_d = flash_attention_fwd(q, k, v, causal=True, window=128,
                                    block_q=128, block_kv=128, pruned=False,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                                   rtol=2e-6, atol=2e-6)


class TestBackwardParity:
    """jax.grad through the fused Pallas backward == the reference VJP."""

    @pytest.mark.parametrize("name,S,HK,causal,window,softcap,bq,bkv", [
        ("causal", 256, (4, 2), True, None, None, 128, 128),
        ("sliding", 256, (4, 4), True, 64, None, 64, 64),
        ("softcap", 128, (2, 2), True, 64, 30.0, 64, 64),
        ("gqa", 128, (8, 2), True, None, None, 64, 64),
        ("ragged", 320, (4, 2), True, 96, None, 128, 64),
        ("noncausal", 192, (2, 2), False, None, None, 64, 64),
    ])
    def test_grad_parity(self, key, name, S, HK, causal, window, softcap,
                         bq, bkv):
        H, K = HK
        q, k, v = _qkv(key, 2, S, H, K, 64)
        g = jax.random.normal(jax.random.fold_in(key, 7), q.shape)
        kw = dict(causal=causal, window=window, softcap=softcap)

        def loss_pallas(q, k, v):
            out = flash_attention(q, k, v, block_q=bq, block_kv=bkv,
                                  block_q_bwd=bq, block_kv_bwd=bkv,
                                  pruned=True, interpret=True, **kw)
            return jnp.sum(out * g)

        def loss_ref(q, k, v):
            return jnp.sum(attention_ref(q, k, v, **kw) * g)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name_g, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name_g)

    def test_bwd_blocks_differ_from_fwd_blocks(self, key):
        """Independently tuned backward blocks must not change gradients."""
        q, k, v = _qkv(key, 1, 256, 4, 2, 64)

        def loss(bqb, bkvb):
            def f(q, k, v):
                out = flash_attention(q, k, v, causal=True, window=96,
                                      block_q=128, block_kv=128,
                                      block_q_bwd=bqb, block_kv_bwd=bkvb,
                                      pruned=True, interpret=True)
                return jnp.sum(out * out)
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        base = loss(128, 128)
        other = loss(64, 256)
        for a, b in zip(base, other):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

    def test_bf16_grad_parity(self, key):
        q, k, v = _qkv(key, 1, 192, 4, 2, 64, jnp.bfloat16)
        g = jax.random.normal(jax.random.fold_in(key, 3), q.shape)

        def loss_pallas(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=64,
                                  block_kv=64, interpret=True)
            return jnp.sum(out.astype(jnp.float32) * g)

        def loss_ref(q, k, v):
            return jnp.sum(attention_ref(q, k, v, causal=True)
                           .astype(jnp.float32) * g)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-2, atol=5e-2)

    def test_backward_never_calls_attention_ref(self, key, monkeypatch):
        """The acceptance criterion: grad no longer recomputes through the
        dense reference.  Poison attention_ref; tracing the backward (fresh
        unseen shape, so no jit-cache hit) must not touch it."""
        import repro.kernels.flash_attention.ref as ref_mod

        def boom(*a, **kw):  # pragma: no cover - fails the test if reached
            raise AssertionError("fused backward recomputed via attention_ref")

        monkeypatch.setattr(ref_mod, "attention_ref", boom)
        q, k, v = _qkv(key, 1, 160, 2, 1, 64)

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, window=80,
                                  block_q=32, block_kv=32, interpret=True)
            return jnp.sum(out * out)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert all(bool(jnp.all(jnp.isfinite(t))) for t in grads)

    def test_kernel_layout_bwd_entry(self, key):
        """flash_attention_bwd (kernel layout) pruned == dense."""
        B, H, K, S, D = 1, 4, 2, 320, 64
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (B, H, S, D))
        k = jax.random.normal(ks[1], (B, K, S, D))
        v = jax.random.normal(ks[2], (B, K, S, D))
        do = jax.random.normal(ks[3], (B, H, S, D))
        out, lse = flash_attention_fwd(q, k, v, causal=True, window=128,
                                       block_q=128, block_kv=128,
                                       interpret=True, return_lse=True)
        kw = dict(causal=True, window=128, block_q=128, block_kv=128,
                  interpret=True)
        grads_p = flash_attention_bwd(q, k, v, out, lse, do, pruned=True, **kw)
        grads_d = flash_attention_bwd(q, k, v, out, lse, do, pruned=False, **kw)
        for a, b in zip(grads_p, grads_d):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


class TestGroupLocalDkv:
    """The dk/dv pass accumulates group-locally: its HBM write is the true
    (B, K, T, D) gradient pair — O(S·K·D) — never a per-q-head (B, H, T, D)
    transient (the PR 3 satellite; was the recorded PR 2 follow-up)."""

    def _captured_bwd_out_shapes(self, key, H, K, monkeypatch):
        import repro.kernels.flash_attention.kernel as kmod

        captured = []
        real = kmod.pl.pallas_call

        def spy(kernel, *args, **kw):
            out_shape = kw.get("out_shape")
            # dk/dv: two (B, K, T, D) outputs; the forward's (out, lse)
            # pair ends in a unit column dim
            if (isinstance(out_shape, list) and len(out_shape) == 2
                    and all(len(s.shape) == 4 and s.shape[-1] > 1
                            for s in out_shape)):
                captured.append([tuple(s.shape) for s in out_shape])  # dk, dv
            return real(kernel, *args, **kw)

        monkeypatch.setattr(kmod.pl, "pallas_call", spy)
        B, S, D = 1, 128, 64
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (B, H, S, D))
        k = jax.random.normal(ks[1], (B, K, S, D))
        v = jax.random.normal(ks[2], (B, K, S, D))
        do = jax.random.normal(ks[3], (B, H, S, D))
        out, lse = flash_attention_fwd(q, k, v, causal=True, block_q=64,
                                       block_kv=64, interpret=True,
                                       return_lse=True)
        grads = flash_attention_bwd(q, k, v, out, lse, do, causal=True,
                                    block_q=64, block_kv=64, interpret=True)
        return captured, grads

    def test_dkv_write_volume_is_kv_heads_sized(self, key, monkeypatch):
        """With a 4:1 GQA group, the dk/dv HBM write must be 4x smaller than
        the per-q-head layout."""
        H, K = 8, 2
        captured, grads = self._captured_bwd_out_shapes(key, H, K, monkeypatch)
        assert len(captured) == 1, "expected exactly one dk/dv pallas_call"
        dk_shape, dv_shape = captured[0]
        B, S, D = 1, 128, 64
        assert dk_shape == (B, K, S, D), dk_shape   # K heads, not H
        assert dv_shape == (B, K, S, D), dv_shape
        written = 2 * np.prod(dk_shape)             # dk + dv fp32 elements
        per_q_head = 2 * B * H * S * D              # the old transient
        assert written * (H // K) == per_q_head     # exactly G-fold smaller
        assert grads[1].shape == (B, K, S, D)
        assert grads[2].shape == (B, K, S, D)

    def test_group_local_grads_match_reference(self, key):
        """Group-local accumulation must equal the reference group-sum."""
        H, K, S, D = 8, 2, 192, 64
        q, k, v = _qkv(key, 2, S, H, K, D)
        g = jax.random.normal(jax.random.fold_in(key, 5), q.shape)

        def loss_pallas(q, k, v):
            out = flash_attention(q, k, v, causal=True, window=64,
                                  block_q=64, block_kv=64, interpret=True)
            return jnp.sum(out * g)

        def loss_ref(q, k, v):
            return jnp.sum(attention_ref(q, k, v, causal=True, window=64) * g)

        got = jax.grad(loss_pallas, argnums=(1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(1, 2))(q, k, v)
        for name, a, b in zip(("dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


class TestQSchedule:
    """The transposed (dk/dv) schedule: exact pruning, and bwd HBM traffic
    stays O(S·W) for windowed attention."""

    @pytest.mark.parametrize("S,T,bq,bkv,window", [
        (1024, 1024, 128, 128, None),
        (1024, 1024, 128, 128, 256),
        (1024, 1024, 256, 128, 384),
        (896, 896, 128, 256, 128),   # ragged + mixed blocks
        (4096, 4096, 512, 512, 512),
    ])
    def test_no_fully_masked_block_streamed(self, S, T, bq, bkv, window):
        sched = q_schedule(S, T, bq, bkv, causal=True, window=window,
                           pruned=True)
        for ik, row in enumerate(sched):
            for iq in row:
                assert not block_fully_masked(
                    iq, ik, bq, bkv, kv_len=T, causal=True, window=window
                ), f"pruned bwd schedule streams dead block (ik={ik}, iq={iq})"

    @pytest.mark.parametrize("S,T,bq,bkv,window", [
        (1024, 1024, 128, 128, None),
        (1024, 1024, 128, 128, 256),
        (896, 896, 128, 256, 128),
    ])
    def test_every_live_block_streamed(self, S, T, bq, bkv, window):
        sched = q_schedule(S, T, bq, bkv, causal=True, window=window,
                           pruned=True)
        nq, nk = cdiv(S, bq), cdiv(T, bkv)
        for ik in range(nk):
            live = {iq for iq in range(nq)
                    if not block_fully_masked(iq, ik, bq, bkv, kv_len=T,
                                              causal=True, window=window)}
            assert live <= set(sched[ik]), (ik, live - set(sched[ik]))

    def test_bwd_window_traffic_is_linear_in_S(self):
        """The full backward (dq pass: kv_schedule, dk/dv pass: q_schedule)
        streams O(S*W) blocks for window-W attention, not O(S^2)."""
        W, b = 512, 128

        def bwd_streamed(S):
            dq = sum(len(r) for r in kv_schedule(S, S, b, b, causal=True,
                                                 window=W, pruned=True))
            dkv = sum(len(r) for r in q_schedule(S, S, b, b, causal=True,
                                                 window=W, pruned=True))
            return dq + dkv

        n1, n2 = bwd_streamed(4096), bwd_streamed(8192)
        steps = (kv_steps_for(8192, 8192, b, b, True, W)
                 + q_steps_for(8192, 8192, b, b, True, W))
        # affine in S modulo the truncated boundary rows
        assert n2 <= 2 * n1 + steps * (steps - 1)
        assert n2 < 0.2 * 2 * (8192 // b) ** 2  # far below dense both-pass S^2

    def test_dense_schedule_streams_everything(self):
        sched = q_schedule(512, 512, 128, 128, causal=True, pruned=False)
        assert all(row == [0, 1, 2, 3] for row in sched)

    def test_q_steps_matches_schedule_width(self):
        for S, W in ((1024, None), (1024, 256), (768, 128)):
            steps = q_steps_for(S, S, 128, 128, True, W)
            sched = q_schedule(S, S, 128, 128, causal=True, window=W,
                               pruned=True)
            assert max(len(r) for r in sched) <= steps


class TestKVSchedule:
    """The schedule (the kernel's exact index remapping, in numpy) streams
    no dead blocks and shrinks with the mask."""

    @pytest.mark.parametrize("S,T,bq,bkv,window", [
        (1024, 1024, 128, 128, None),
        (1024, 1024, 128, 128, 256),
        (1024, 1024, 256, 128, 384),
        (896, 896, 128, 256, 128),   # ragged + mixed blocks
        (4096, 4096, 512, 512, 512),
    ])
    def test_no_fully_masked_block_streamed(self, S, T, bq, bkv, window):
        sched = kv_schedule(S, T, bq, bkv, causal=True, window=window,
                            pruned=True)
        for iq, row in enumerate(sched):
            for ik in row:
                assert not block_fully_masked(
                    iq, ik, bq, bkv, kv_len=T, causal=True, window=window
                ), f"pruned schedule streams dead block (iq={iq}, ik={ik})"

    @pytest.mark.parametrize("S,T,bq,bkv,window", [
        (1024, 1024, 128, 128, None),
        (1024, 1024, 128, 128, 256),
    ])
    def test_every_live_block_streamed(self, S, T, bq, bkv, window):
        """Pruning must be exact, not lossy: every partially-unmasked block
        appears in the schedule."""
        sched = kv_schedule(S, T, bq, bkv, causal=True, window=window,
                            pruned=True)
        nq, nk = cdiv(S, bq), cdiv(T, bkv)
        for iq in range(nq):
            live = {ik for ik in range(nk)
                    if not block_fully_masked(iq, ik, bq, bkv, kv_len=T,
                                              causal=True, window=window)}
            assert live <= set(sched[iq]), (iq, live - set(sched[iq]))

    def test_causal_halves_traffic(self):
        sched = kv_schedule(2048, 2048, 128, 128, causal=True, pruned=True)
        streamed = sum(len(r) for r in sched)
        dense = 16 * 16
        assert streamed == sum(range(1, 17))  # triangular
        assert streamed / dense < 0.6

    def test_window_traffic_is_linear_in_S(self):
        """O(S*W): doubling S doubles streamed blocks under a fixed window
        (dense doubles quadratically)."""
        W, b = 512, 128
        n1 = sum(len(r) for r in kv_schedule(4096, 4096, b, b, causal=True,
                                             window=W, pruned=True))
        n2 = sum(len(r) for r in kv_schedule(8192, 8192, b, b, causal=True,
                                             window=W, pruned=True))
        # affine in S (n = steps*nq - c with c from the truncated first rows),
        # so doubling S doubles the count plus at most that constant
        steps = kv_steps_for(8192, 8192, b, b, True, W)
        assert n2 <= 2 * n1 + steps * (steps - 1) // 2
        assert n2 < 0.2 * (8192 // b) ** 2  # far below dense O(S^2)

    def test_dense_schedule_streams_everything(self):
        sched = kv_schedule(512, 512, 128, 128, causal=True, pruned=False)
        assert all(row == [0, 1, 2, 3] for row in sched)

    def test_kv_steps_matches_schedule_width(self):
        for S, W in ((1024, None), (1024, 256), (768, 128)):
            steps = kv_steps_for(S, S, 128, 128, True, W)
            sched = kv_schedule(S, S, 128, 128, causal=True, window=W,
                                pruned=True)
            assert max(len(r) for r in sched) <= steps


class TestVmemBytes:
    def test_kv_dtype_counted_for_k_and_v(self):
        """K and V must both scale with the KV dtype."""
        base = vmem_bytes(128, 128, 64, 2, kv_dtype_bytes=2)
        wide = vmem_bytes(128, 128, 64, 2, kv_dtype_bytes=4)
        # doubling kv bytes adds exactly 2 (K+V) * block * D * 2 (extra
        # bytes) * 2 (double buffering)
        assert wide - base == 2 * (2 * 128 * 64 * 2)

    def test_monotone_in_blocks(self):
        assert vmem_bytes(256, 256, 64) > vmem_bytes(128, 128, 64)

    def test_default_config_fits_vmem(self):
        assert vmem_bytes(512, 512, 128) < 16 * 2**20

    def test_default_bwd_config_fits_vmem(self):
        assert vmem_bytes(512, 512, 128) < vmem_bytes_bwd(512, 512, 128) \
            < 16 * 2**20
