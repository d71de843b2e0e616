"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
with hypothesis sweeps over shapes/dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import rglru_pallas
from repro.kernels.rglru.ref import rglru_assoc, rglru_scan
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rwkv6.ops import wkv_pallas
from repro.kernels.rwkv6.ref import wkv_chunked, wkv_scan


def _qkv(key, B, S, H, K, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal,window,softcap", [
        (True, None, None), (True, 64, None), (False, None, None),
        (True, None, 30.0), (True, 32, 20.0),
    ])
    def test_masks(self, key, causal, window, softcap):
        q, k, v = _qkv(key, 2, 256, 4, 2, 64)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=128, block_kv=128,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=causal, window=window,
                            softcap=softcap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @settings(max_examples=12, deadline=None)
    @given(
        B=st.sampled_from([1, 2]),
        S=st.sampled_from([128, 256, 384]),
        HK=st.sampled_from([(4, 4), (4, 2), (8, 1)]),
        D=st.sampled_from([64, 128]),
        dtype=st.sampled_from(["float32", "bfloat16"]),
    )
    def test_property_sweep(self, B, S, HK, D, dtype):
        H, K = HK
        dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
        q, k, v = _qkv(jax.random.PRNGKey(B * S + H + D), B, S, H, K, D, dt)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
        )

    def test_gqa_group_mapping(self, key):
        """Each q head must attend its own kv group."""
        B, S, H, K, D = 1, 128, 4, 2, 64
        q, k, v = _qkv(key, B, S, H, K, D)
        # make kv head 1 wildly different; heads 2,3 map to it
        v = v.at[:, :, 1].mul(100.0)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)


class TestWKV6:
    def test_chunked_vs_scan(self, key):
        B, S, H, C = 2, 100, 3, 16
        ks = jax.random.split(key, 5)
        r, k, v = (jax.random.normal(ks[i], (B, S, H, C)) for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, C))) * 0.98 + 0.01
        u = jax.random.normal(ks[4], (H, C))
        s0 = jax.random.normal(key, (B, H, C, C))
        y1, sl1 = wkv_scan(r, k, v, w, u, s0)
        y2, sl2 = wkv_chunked(r, k, v, w, u, s0, chunk=16)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(sl1), np.asarray(sl2), atol=1e-4)

    @settings(max_examples=8, deadline=None)
    @given(
        S=st.sampled_from([32, 64, 96]),
        C=st.sampled_from([8, 16]),
        chunk=st.sampled_from([16, 32]),
        decay_scale=st.sampled_from([0.5, 3.0]),  # strong decays too
    )
    def test_pallas_property(self, S, C, chunk, decay_scale):
        B, H = 2, 2
        key = jax.random.PRNGKey(S * C + chunk)
        ks = jax.random.split(key, 5)
        r, k, v = (jax.random.normal(ks[i], (B, S, H, C)) for i in range(3))
        w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, C)) * decay_scale))
        u = jax.random.normal(ks[4], (H, C))
        s0 = jax.random.normal(key, (B, H, C, C))
        y1, sl1 = wkv_scan(r, k, v, w, u, s0)
        y2, sl2 = wkv_pallas(r, k, v, w, u, s0, chunk=chunk)
        # strong decays amplify fp32 ordering differences; scale-aware tol
        scale = float(np.max(np.abs(np.asarray(y1)))) + 1.0
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=5e-3, atol=5e-3 * scale)
        np.testing.assert_allclose(np.asarray(sl1), np.asarray(sl2),
                                   rtol=5e-3, atol=5e-3)


class TestRGLRU:
    @settings(max_examples=8, deadline=None)
    @given(
        S=st.sampled_from([17, 64, 100]),
        D=st.sampled_from([8, 24, 64]),
        chunk=st.sampled_from([16, 32]),
    )
    def test_pallas_property(self, S, D, chunk):
        B = 2
        key = jax.random.PRNGKey(S * D)
        ks = jax.random.split(key, 3)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D)))
        b = jax.random.normal(ks[1], (B, S, D))
        h0 = jax.random.normal(ks[2], (B, D))
        y1, hl1 = rglru_scan(a, b, h0)
        y2, hl2 = rglru_pallas(a, b, h0, block_d=8, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(hl1), np.asarray(hl2), atol=1e-4)

    def test_assoc_matches_scan(self, key):
        B, S, D = 3, 77, 16
        ks = jax.random.split(key, 3)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D)))
        b = jax.random.normal(ks[1], (B, S, D))
        h0 = jax.random.normal(ks[2], (B, D))
        y1, _ = rglru_scan(a, b, h0)
        y2, _ = rglru_assoc(a, b, h0)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


class TestRMSNorm:
    @settings(max_examples=8, deadline=None)
    @given(rows=st.sampled_from([1, 7, 300]), d=st.sampled_from([64, 128, 512]),
           dtype=st.sampled_from(["float32", "bfloat16"]))
    def test_property(self, rows, d, dtype):
        dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
        key = jax.random.PRNGKey(rows + d)
        x = jax.random.normal(key, (rows, d), dt)
        w = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)
        out = rmsnorm(x, w, block_rows=64)
        ref = rmsnorm_ref(x, w)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestFlashAttentionGrad:
    def test_custom_vjp_matches_reference_grads(self, key):
        """flash_attention is trainable: grads match the oracle's."""
        B, S, H, K, D = 1, 128, 4, 2, 64
        q, k, v = _qkv(key, B, S, H, K, D)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=64,
                                  block_kv=64, interpret=True)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            out = attention_ref(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_default_by_backend(monkeypatch, backend, interpret):
    """Kernels interpret on the CPU, compile on a TPU, and refuse any other
    backend instead of silently running interpreted."""
    from repro.kernels import interpret_default

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            interpret_default()
    else:
        assert interpret_default() is interpret
