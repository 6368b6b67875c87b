"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles (interpret mode),
and the models' choice between the flash kernel and the jnp attention."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as fa_kernel
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


    @pytest.mark.parametrize("causal,window,bq,bk", [
        (True, None, 64, 64),
        (True, None, 128, 64),
        (True, 80, 64, 128),
        (False, None, 128, 128),
    ])
    def test_forward_logsumexp(self, causal, window, bq, bk):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 4, 256, 64))
        k = jax.random.normal(ks[1], (1, 2, 256, 64))
        v = jax.random.normal(ks[2], (1, 2, 256, 64))
        o, lse = fa_kernel.flash_forward(q, k, v, causal=causal,
                                         window=window, block_q=bq,
                                         block_k=bk, interpret=True)
        kr = jnp.repeat(k, 2, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / math.sqrt(64)
        pos = jnp.arange(256)
        keep = jnp.ones((256, 256), bool)
        if causal:
            keep &= pos[None, :] <= pos[:, None]
        if window is not None:
            keep &= pos[None, :] > pos[:, None] - window
        want = jax.nn.logsumexp(jnp.where(keep, logits, -jnp.inf), axis=-1)
        assert lse.shape == (1, 4, 256, fa_kernel.LANES)
        np.testing.assert_allclose(np.asarray(lse),
                                   np.broadcast_to(want[..., None],
                                                   lse.shape),
                                   atol=2e-5, rtol=2e-5)
        ref = attention_ref(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                            causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("b,h,kh,d,window,bq,bk,dtype", [
        (2, 4, 2, 64, None, 64, 64, jnp.float32),       # GQA group 2
        (1, 2, 2, 128, None, 128, 64, jnp.float32),     # rectangular
        (1, 4, 2, 64, 64, 64, 64, jnp.float32),         # window: k blocks
        (1, 4, 4, 64, 96, 128, 64, jnp.float32),        # skipped both sides
        (2, 4, 2, 64, None, 64, 128, jnp.bfloat16),
        (1, 4, 2, 128, 80, 64, 64, jnp.bfloat16),
    ])
    def test_gradients_match_ref(self, b, h, kh, d, window, bq, bk, dtype):
        """dQ, dK and dV through the custom_vjp against jax.vjp of the
        float32 oracle, at S 256."""
        ks = jax.random.split(KEY, 4)
        s = 256
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
        v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
        do = jax.random.normal(ks[3], (b, s, h, d), dtype)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   block_q=bq, block_k=bk, interpret=True)

        def ref(q, k, v):
            return attention_ref(q, k, v, causal=True, window=window)

        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        got = jax.vjp(flash, q, k, v)[1](do)
        want = jax.vjp(ref, *f32)[1](do.astype(jnp.float32))
        tol = 2e-5 if dtype == jnp.float32 else 4e-2
        for name, g, w in zip("qkv", got, want):
            assert g.dtype == dtype, name
            scale = float(jnp.max(jnp.abs(w)))
            np.testing.assert_allclose(np.asarray(g, np.float32) / scale,
                                       np.asarray(w) / scale, atol=tol,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("causal,window,bq,bk", [
        (True, None, 64, 64), (True, None, 128, 32), (True, None, 32, 128),
        (True, 48, 32, 64), (True, 100, 64, 32), (False, None, 64, 32),
    ])
    def test_block_ranges_are_the_unmasked_blocks(self, causal, window,
                                                  bq, bk):
        """The blocks each kernel visits, and clamps its DMA to, are
        exactly those with an unmasked entry; all others are skipped."""
        s = 256
        nq, nk = s // bq, s // bk
        pos = np.arange(s)
        keep = np.ones((s, s), bool)
        if causal:
            keep &= pos[None, :] <= pos[:, None]
        if window is not None:
            keep &= pos[None, :] > pos[:, None] - window
        live = keep.reshape(nq, bq, nk, bk).any(axis=(1, 3))
        for qi in range(nq):
            lo, hi = fa_kernel._k_range(qi, bq, bk, nk, causal, window)
            assert [lo <= ki <= hi for ki in range(nk)] == list(live[qi])
        for ki in range(nk):
            lo, hi = fa_kernel._q_range(ki, bq, bk, nq, causal, window)
            assert [lo <= qi <= hi for qi in range(nq)] == list(live[:, ki])


class TestAttentionDispatch:
    """``models.layers.attention`` takes the flash kernel only where it
    computes the same thing on a TPU: over unpartitioned activations, or
    once per shard where the mesh's batch and head shards divide them."""

    CELL = dict(q_shape=(2, 2048, 16, 128), k_shape=(2, 2048, 16, 128))
    # olmo-1b-16l.pretrain-2k-mesh2x2: batch 16 over data, heads over model
    MESH2X2 = dict(q_shape=(16, 2048, 16, 128), k_shape=(16, 2048, 16, 128),
                   batch_axes=("data",), head_axes=("model",),
                   mesh_shape={"data": 2, "model": 2})

    @pytest.mark.parametrize("change,path", [
        ({}, "flash"),
        ({"platform": "cpu"}, "dense"),
        (MESH2X2, "flash"),
        ({**MESH2X2, "k_shape": (16, 2048, 1, 128)}, "dense"),  # kv < model
        ({**MESH2X2, "q_shape": (3, 2048, 16, 128),
          "k_shape": (3, 2048, 16, 128)}, "dense"),            # B % data
        ({**MESH2X2, "mesh_shape": {}}, "dense"),              # no mesh
        ({"q_offset": 5}, "dense"),
        ({"softcap": 30.0}, "dense"),
        ({"q_shape": (2, 1, 16, 128)}, "dense"),               # decode
        ({"q_shape": (2, 2000, 16, 128),
          "k_shape": (2, 2000, 16, 128)}, "dense"),            # S % 128
    ])
    def test_path(self, change, path):
        from repro.models.layers import attention_path
        args = {**self.CELL, "platform": "tpu", **change}
        q_shape, k_shape = args.pop("q_shape"), args.pop("k_shape")
        assert attention_path(q_shape, k_shape, **args) == path

    @pytest.mark.parametrize("platform,path", [("tpu", "flash"),
                                               ("cpu", "dense")])
    def test_counter_sees_the_model_path(self, monkeypatch, platform, path):
        """A traced olmo forward counts one call per layer scan on the path
        its platform gives, and none on the other."""
        from repro.configs import get_config
        from repro.models import layers, zoo
        from repro.obs import attention_paths
        monkeypatch.setattr(layers, "_platform", lambda: platform)
        cfg = get_config("olmo-1b", smoke=True)
        tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
        before = attention_paths().snapshot()
        jax.eval_shape(lambda p, t: zoo.loss_fn(cfg, p, {"tokens": t,
                                                         "targets": t}),
                       zoo.abstract(cfg), tok)
        after = attention_paths().snapshot()
        other = "dense" if path == "flash" else "flash"
        assert after.get(path, 0) > before.get(path, 0)
        assert after.get(other, 0) == before.get(other, 0)

    def test_counter_sees_the_sharded_kernel(self, monkeypatch):
        """Traced under a (data 2, model 2) mesh with the batch and the
        heads partitioned as ``default_plan`` does, an olmo forward counts
        one kernel call per layer scan and no jnp attention."""
        from repro.configs import get_config
        from repro.models import layers, zoo
        from repro.obs import attention_paths
        monkeypatch.setattr(layers, "_platform", lambda: "tpu")
        cfg = dataclasses.replace(get_config("olmo-1b", smoke=True),
                                  batch_axes=("data",), head_axes=("model",))
        mesh = jax.sharding.AbstractMesh(
            (2, 2), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        before = attention_paths().snapshot()
        with jax.sharding.use_abstract_mesh(mesh):
            jax.eval_shape(lambda p, t: zoo.loss_fn(
                cfg, p, {"tokens": t, "targets": t}), zoo.abstract(cfg), tok)
        after = attention_paths().snapshot()
        assert after.get("flash", 0) == before.get("flash", 0) + 1
        assert after.get("dense", 0) == before.get("dense", 0)

    def test_cross_attention_stays_dense(self, monkeypatch):
        from repro.models import layers
        from repro.obs import attention_paths
        monkeypatch.setattr(layers, "_platform", lambda: "tpu")
        x = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.bfloat16)
        before = attention_paths().snapshot()
        jax.eval_shape(layers.cross_attention, x, x, x)
        after = attention_paths().snapshot()
        assert after.get("dense", 0) == before.get("dense", 0) + 1
        assert after.get("flash", 0) == before.get("flash", 0)


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
