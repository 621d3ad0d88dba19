"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes in Python on CPU; TPU is the compile target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd


def _r(shape, seed, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,H,KH,hd", [
        (1, 128, 4, 4, 64),    # MHA
        (2, 256, 4, 2, 64),    # GQA 2:1
        (1, 128, 8, 1, 32),    # MQA
        (1, 256, 2, 2, 128),   # wide head
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, B, Sq, H, KH, hd, dtype, causal):
        q = _r((B, Sq, H, hd), 0, dtype)
        k = _r((B, Sq, KH, hd), 1, dtype)
        v = _r((B, Sq, KH, hd), 2, dtype)
        out = flash_attention_fwd(q, k, v, causal=causal, bq=64, bk=64,
                                  interpret=True)
        want = ref.attention_ref(q, k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=tol, rtol=tol)

    def test_block_shape_sweep(self):
        q = _r((1, 256, 2, 64), 3)
        k = _r((1, 256, 2, 64), 4)
        v = _r((1, 256, 2, 64), 5)
        want = ref.attention_ref(q, k, v, causal=True)
        for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
            out = flash_attention_fwd(q, k, v, causal=True, bq=bq, bk=bk,
                                      interpret=True)
            np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5,
                                       err_msg=f"bq={bq} bk={bk}")

    def test_ops_xla_equals_pallas(self):
        q, k, v = _r((1, 128, 4, 64), 6), _r((1, 128, 2, 64), 7), _r((1, 128, 2, 64), 8)
        a = ops.flash_attention(q, k, v, True, "xla")
        b = ops.flash_attention(q, k, v, True, "pallas_interpret")
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_grad_through_ops(self):
        q, k, v = _r((1, 64, 2, 32), 9), _r((1, 64, 2, 32), 10), _r((1, 64, 2, 32), 11)
        g1 = jax.grad(lambda q: ops.flash_attention(q, k, v, True, "pallas_interpret").sum())(q)
        g2 = jax.grad(lambda q: ref.attention_ref(q, k, v, causal=True).sum())(q)
        np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)

    # Pinned parity bar for the train-path dispatch (the oracle
    # blockwise_attention is what the fused dist_jit path and the ring
    # executor lower to): interpret-mode Pallas forward AND the custom_vjp
    # backward must track it at fp32 tolerances.  Covers the gap where
    # kops.flash_attention was only reachable off the fused path and had
    # no gradient test against the training oracle.
    FWD_RTOL = 2e-5
    VJP_RTOL = 1e-4

    @pytest.mark.parametrize("B,S,H,KH,hd", [
        (1, 128, 4, 4, 32),    # MHA
        (2, 128, 8, 2, 32),    # GQA 4:1
    ])
    def test_interpret_fwd_and_vjp_parity_vs_blockwise(self, B, S, H, KH, hd):
        from repro.models.attention import blockwise_attention
        q, k, v = (_r((B, S, H, hd), 20), _r((B, S, KH, hd), 21),
                   _r((B, S, KH, hd), 22))
        out, vjp = jax.vjp(
            lambda q, k, v: ops.flash_attention(q, k, v, True,
                                                "pallas_interpret"), q, k, v)
        want, vjp_ref = jax.vjp(
            lambda q, k, v: blockwise_attention(q, k, v, chunk=64,
                                                causal=True), q, k, v)
        np.testing.assert_allclose(out, want, rtol=self.FWD_RTOL,
                                   atol=self.FWD_RTOL)
        g = _r(out.shape, 23)
        for got, ref_g, name in zip(vjp(g), vjp_ref(g), "qkv"):
            np.testing.assert_allclose(got, ref_g, rtol=self.VJP_RTOL,
                                       atol=self.VJP_RTOL, err_msg=name)


class TestSSDScan:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (1, 128, 2, 16, 16, 32),
        (2, 256, 4, 64, 32, 64),
        (1, 64, 1, 32, 128, 16),
        (1, 128, 8, 64, 64, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_naive_recurrence(self, B, S, H, P, N, chunk, dtype):
        x = _r((B, S, H, P), 0, dtype)
        dt = jax.nn.softplus(_r((B, S, H), 1)) * 0.1
        a_neg = -jnp.exp(_r((H,), 2) * 0.2)
        Bm = _r((B, S, N), 3, dtype)
        Cm = _r((B, S, N), 4, dtype)
        out = ssd_scan_fwd(x, dt, a_neg, Bm, Cm, chunk=chunk, interpret=True)
        want, _ = ref.ssd_ref(x, dt, a_neg, Bm, Cm)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=tol, rtol=tol)

    def test_xla_chunked_equals_naive(self):
        # the model's XLA path against the step recurrence
        from repro.models.ssm import ssd_chunked
        x = _r((2, 128, 4, 32), 5)
        dt = jax.nn.softplus(_r((2, 128, 4), 6)) * 0.1
        a_neg = -jnp.exp(_r((4,), 7) * 0.2)
        Bm, Cm = _r((2, 128, 16), 8), _r((2, 128, 16), 9)
        y1, h1 = ssd_chunked(x, dt, a_neg, Bm, Cm, chunk=32)
        y2, h2 = ref.ssd_ref(x, dt, a_neg, Bm, Cm)
        np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(h1, h2, atol=1e-4, rtol=1e-4)

    def test_decode_step_continues_scan(self):
        from repro.models.ssm import ssd_decode_step
        x = _r((1, 65, 2, 16), 10)
        dt = jax.nn.softplus(_r((1, 65, 2), 11)) * 0.1
        a_neg = -jnp.exp(_r((2,), 12) * 0.2)
        Bm, Cm = _r((1, 65, 8), 13), _r((1, 65, 8), 14)
        y_all, _ = ref.ssd_ref(x, dt, a_neg, Bm, Cm)
        _, h64 = ref.ssd_ref(x[:, :64], dt[:, :64], a_neg, Bm[:, :64], Cm[:, :64])
        y_last, _ = ssd_decode_step(x[:, 64:65], dt[:, 64:65], a_neg,
                                    Bm[:, 64:65], Cm[:, 64:65], h64)
        np.testing.assert_allclose(y_last[:, 0], y_all[:, 64], atol=1e-4, rtol=1e-4)


class TestRMSNorm:
    # 250 and 2 x 150 rows: counts the 128-row block does not divide (a
    # partial last block), and counts below it (one whole-rows block)
    @pytest.mark.parametrize("shape", [(4, 256), (2, 8, 512), (128, 1024),
                                       (250, 256), (2, 150, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        x = _r(shape, 0, dtype)
        w = _r(shape[-1:], 1)
        out = rmsnorm_fwd(x, w, interpret=True)
        want = ref.rmsnorm_ref(x, w)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=tol, rtol=tol)

    def test_block_rows_must_tile_sublanes(self):
        with pytest.raises(ValueError, match="multiple of 8"):
            rmsnorm_fwd(_r((64, 128), 0), _r((128,), 1), block_rows=100,
                        interpret=True)


def test_default_impl_follows_platform():
    """impl=None runs the compiled kernel on a TPU and the interpreter
    elsewhere; no environment variable chooses a silent XLA fallback."""
    want = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    assert ops._resolve(None) == want
    assert ops._resolve("xla") == "xla"
    x, w = _r((16, 128), 2), _r((128,), 3)
    np.testing.assert_allclose(ops.rmsnorm(x, w), ref.rmsnorm_ref(x, w),
                               atol=1e-5, rtol=1e-5)
