"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru.ops import rglru
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref

KEY = jax.random.PRNGKey(0)


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,kh,d,bq,bk", [
        (2, 128, 4, 2, 64, 64, 64),      # GQA
        (1, 256, 4, 4, 32, 128, 64),     # MHA, rectangular blocks
        (1, 64, 8, 1, 64, 32, 32),       # MQA
        (2, 128, 2, 2, 128, 128, 128),   # single block pair
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, b, s, h, kh, d, bq, bk, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
        v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("window", [32, 64])
    def test_sliding_window(self, window):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64))
        k = jax.random.normal(ks[1], (1, 128, 2, 64))
        v = jax.random.normal(ks[2], (1, 128, 2, 64))
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_non_causal(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 64, 2, 32))
        k = jax.random.normal(ks[1], (1, 64, 2, 32))
        v = jax.random.normal(ks[2], (1, 64, 2, 32))
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestWkv6:
    @pytest.mark.parametrize("b,h,s,d,chunk", [
        (2, 2, 128, 64, 64),
        (1, 4, 256, 32, 64),
        (2, 1, 64, 64, 32),
        (1, 2, 128, 64, 128),
    ])
    def test_matches_exact_scan(self, b, h, s, d, chunk):
        ks = jax.random.split(KEY, 5)
        r = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        w = jax.random.uniform(ks[3], (b, h, s, d), minval=0.5, maxval=0.999)
        u = jax.random.normal(ks[4], (h, d)) * 0.5
        out, st = wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
        oref, sref = wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oref),
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(st), np.asarray(sref),
                                   atol=3e-4, rtol=1e-3)

    def test_strong_decay_stable(self):
        """Exponents clip instead of overflowing under harsh decay."""
        ks = jax.random.split(KEY, 5)
        b, h, s, d = 1, 1, 128, 32
        r = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        w = jax.random.uniform(ks[3], (b, h, s, d), minval=1e-4, maxval=0.2)
        u = jnp.zeros((h, d))
        out, st = wkv6(r, k, v, w, u, chunk=64, interpret=True)
        assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(st).all())
        oref, _ = wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oref),
                                   atol=5e-4, rtol=5e-3)

    def test_software_exp_log_match_float64(self):
        """The kernel's own exp/log (the chip's are approximations) stay
        within a few f32 ulp over the ranges its decays use."""
        from repro.kernels.rwkv6.kernel import _exp, _log
        x = np.concatenate([np.linspace(-87, 0, 20001),
                            -np.logspace(-8, 0, 101)]).astype(np.float32)
        exact = np.exp(x.astype(np.float64))
        got = np.asarray(jax.jit(_exp)(x), np.float64)
        assert np.max(np.abs(got - exact) / exact) < 3e-7
        assert float(_exp(jnp.float32(-jnp.inf))) == 0.0
        w = np.concatenate([np.linspace(1e-4, 1, 20001),
                            np.logspace(-37.9, 0, 1001)]).astype(np.float32)
        exact = np.log(w.astype(np.float64))
        got = np.asarray(jax.jit(_log)(w), np.float64)
        np.testing.assert_allclose(got, exact, rtol=3e-7, atol=1e-9)

    def test_model_chunked_path_matches(self):
        """The jnp chunked path used by the model equals the oracle too."""
        from repro.models.layers import rwkv6_linear_attention
        ks = jax.random.split(KEY, 5)
        b, h, s, d = 1, 2, 128, 32
        r = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        w = jax.random.uniform(ks[3], (b, h, s, d), minval=0.6, maxval=0.999)
        u = jax.random.normal(ks[4], (h, d)) * 0.5
        out, st = rwkv6_linear_attention(r, k, v, w, u, chunk=32)
        oref, sref = wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oref),
                                   atol=3e-4, rtol=1e-3)


class TestRglru:
    @pytest.mark.parametrize("b,s,r,chunk", [
        (2, 128, 64, 64),
        (1, 256, 128, 128),
        (3, 64, 32, 16),
    ])
    def test_matches_exact_scan(self, b, s, r, chunk):
        ks = jax.random.split(KEY, 2)
        a = jax.random.uniform(ks[0], (b, s, r), minval=0.001, maxval=0.9995)
        x = jax.random.normal(ks[1], (b, s, r))
        h, hl = rglru(a, x, chunk=chunk, interpret=True)
        href, hlref = rglru_ref(a, x)
        np.testing.assert_allclose(np.asarray(h), np.asarray(href),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(hl), np.asarray(hlref),
                                   atol=1e-5, rtol=1e-5)

    def test_extreme_decay(self):
        """No log-space overflow: exact sequential inner loop."""
        b, s, r = 1, 64, 32
        a = jnp.full((b, s, r), 1e-6)
        x = jnp.ones((b, s, r))
        h, _ = rglru(a, x, chunk=32, interpret=True)
        href, _ = rglru_ref(a, x)
        np.testing.assert_allclose(np.asarray(h), np.asarray(href),
                                   atol=1e-6)

    def test_model_scan_matches_kernel_ref(self):
        from repro.models.layers import rglru_scan
        ks = jax.random.split(KEY, 2)
        a = jax.random.uniform(ks[0], (2, 64, 16), minval=0.1, maxval=0.99)
        x = jax.random.normal(ks[1], (2, 64, 16))
        h_model, hl_model = rglru_scan(a, x)
        # note: model scan multiplies x by sqrt(1-a^2) internally, matching
        href, hlref = rglru_ref(a, x)
        np.testing.assert_allclose(np.asarray(h_model), np.asarray(href),
                                   atol=1e-5, rtol=1e-4)
