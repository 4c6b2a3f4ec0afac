"""Pallas kernel tests: shape/dtype sweeps, assert_allclose vs ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.quant import ops as quant_ops
from repro.kernels.quant import ref as quant_ref
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.ssd.ops import ssd_scan
from repro.kernels.ssd.ref import ssd_scan_ref

KEY = jax.random.PRNGKey(7)


def tol_for(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5
    )


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,H,K,D,causal,window",
        [
            (2, 256, 4, 2, 64, True, None),      # GQA causal
            (1, 128, 8, 8, 32, True, None),      # MHA
            (1, 333, 4, 1, 64, True, None),      # MQA, ragged seq
            (2, 256, 4, 2, 64, True, 64),        # sliding window
            (1, 192, 2, 2, 128, False, None),    # bidirectional
            (1, 96, 4, 4, 64, True, 8),          # tiny window < block
        ],
    )
    def test_matches_reference(self, B, S, H, K, D, causal, window, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, S, H, D), dtype)
        k = jax.random.normal(ks[1], (B, S, K, D), dtype)
        v = jax.random.normal(ks[2], (B, S, K, D), dtype)
        out = flash_attention(q, k, v, causal, window)
        ref = attention_ref(q, k, v, causal, window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **tol_for(dtype),
        )

    def test_backward_matches_reference_grad(self):
        ks = jax.random.split(KEY, 3)
        B, S, H, K, D = 1, 64, 2, 1, 32
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, K, D))
        v = jax.random.normal(ks[2], (B, S, K, D))

        g1 = jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, True, None)))(q)
        g2 = jax.grad(lambda q_: jnp.sum(attention_ref(q_, k, v, True, None)))(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-4)


class TestRGLRU:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,R", [(2, 200, 96), (1, 64, 256), (3, 17, 33)])
    def test_matches_reference(self, B, S, R, dtype):
        ks = jax.random.split(KEY, 3)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, R))).astype(dtype)
        b = (jax.random.normal(ks[1], (B, S, R)) * 0.1).astype(dtype)
        h0 = jax.random.normal(ks[2], (B, R))
        out = rglru_scan(a, b, h0)
        ref = rglru_scan_ref(a, b, h0)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), **tol_for(dtype)
        )

    def test_zero_initial_state(self):
        a = jnp.full((1, 8, 16), 0.5)
        b = jnp.ones((1, 8, 16))
        out = rglru_scan(a, b, jnp.zeros((1, 16)))
        ref = rglru_scan_ref(a, b, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)


class TestSSD:
    @pytest.mark.parametrize(
        "B,S,H,P,N,chunk",
        [(2, 120, 3, 16, 32, 128), (1, 256, 2, 64, 64, 64), (1, 33, 1, 8, 16, 8)],
    )
    def test_matches_reference(self, B, S, H, P, N, chunk):
        ks = jax.random.split(KEY, 4)
        xh = jax.random.normal(ks[0], (B, S, H, P))
        bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
        cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
        dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
        a = -jnp.exp(jax.random.normal(KEY, (H,)) * 0.2)
        from repro.kernels.ssd.kernel import ssd_scan_fwd

        out = ssd_scan_fwd(xh, bm, cm, dt, a, chunk=chunk, interpret=True)
        ref = ssd_scan_ref(xh, bm, cm, dt, a)
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        assert float(jnp.max(jnp.abs(out - ref))) / scale < 1e-4

    def test_matches_model_chunked_path(self):
        """Kernel == the jnp chunked algorithm used by the model."""
        from repro.models.ssd import ssd_chunked

        ks = jax.random.split(KEY, 4)
        B, S, H, P, N = 1, 64, 2, 16, 32
        xh = jax.random.normal(ks[0], (B, S, H, P))
        bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
        cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
        dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
        a = -jnp.exp(jax.random.normal(KEY, (H,)) * 0.2)
        out = ssd_scan(xh, bm, cm, dt, a)
        y_model, _ = ssd_chunked(xh, bm, cm, dt, a, chunk=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(y_model),
                                   atol=1e-4, rtol=1e-4)

    def test_model_chunk_128_finite_at_init(self):
        """At the configs' chunk of 128 with dt ~ softplus(0) (the
        init), the decay above the diagonal overflows float32's exp;
        the model path must mask it, forward and backward."""
        from repro.models.ssd import ssd_chunked

        ks = jax.random.split(KEY, 4)
        B, S, H, P, N = 1, 128, 2, 8, 16
        xh = jax.random.normal(ks[0], (B, S, H, P))
        bm = jax.random.normal(ks[1], (B, S, N)) * 0.3
        cm = jax.random.normal(ks[2], (B, S, N)) * 0.3
        dt = jax.nn.softplus(0.3 * jax.random.normal(ks[3], (B, S, H)))
        a = -jnp.ones((H,))
        assert float(jnp.sum(dt[0, 1:, 0])) > 88.8   # exp overflows here
        y, _ = ssd_chunked(xh, bm, cm, dt, a, chunk=128)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ssd_scan_ref(xh, bm, cm, dt, a)),
                                   atol=1e-4, rtol=1e-4)
        g = jax.grad(lambda x: jnp.sum(ssd_chunked(x, bm, cm, dt, a, 128)[0]))
        assert bool(jnp.isfinite(g(xh)).all())


class TestQuant:
    @pytest.mark.parametrize("shape", [(100,), (1000, 37), (5, 5, 5)])
    @pytest.mark.parametrize("block", [64, 256, 4096])
    def test_matches_reference(self, shape, block):
        x = jax.random.normal(KEY, shape)
        q, s = quant_ops.quantize_int8(x, block=block)
        qr, sr = quant_ref.quantize_int8_ref(x, block=block)
        assert bool(jnp.all(q == qr))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)

    def test_roundtrip_error_bounded_by_scale(self):
        x = jax.random.normal(KEY, (512, 16)) * 3.0
        rt = quant_ops.roundtrip(x, block=512)
        # per-block bound: |err| <= scale/2
        blocks = np.asarray(x).reshape(-1, 512)
        scales = np.abs(blocks).max(axis=1) / 127.0
        err = np.abs(np.asarray(rt) - np.asarray(x)).reshape(-1, 512)
        assert (err <= scales[:, None] * 0.5 + 1e-6).all()

    def test_zeros_are_exact(self):
        x = jnp.zeros((256,))
        rt = quant_ops.roundtrip(x, block=128)
        assert float(jnp.max(jnp.abs(rt))) == 0.0
