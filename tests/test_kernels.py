"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing as P
from repro.core import ternary as T
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.fused_norm_quant import ops as rq_ops
from repro.kernels.fused_norm_quant import ref as rq_ref
from repro.kernels.ternary_matmul import ops as tm_ops
from repro.kernels.ternary_matmul import ref as tm_ref
from repro.kernels.tl_gemv import ops as tg_ops
from repro.kernels.tl_gemv import ref as tg_ref


class TestTernaryMatmulKernel:
    @pytest.mark.parametrize("m,n,k", [(1, 128, 128), (5, 256, 200), (130, 64, 384)])
    def test_matches_oracle(self, m, n, k):
        w = jax.random.normal(jax.random.PRNGKey(k), (n, k))
        x = jax.random.normal(jax.random.PRNGKey(m), (m, n))
        w_t, ws = T.ternarize(w)
        x_i8, xs = T.quantize_act(x)
        wp = P.pack2(w_t)
        got = tm_ops.ternary_matmul(x_i8, xs, wp, ws)
        want = tm_ref.ternary_matmul(x_i8, xs, wp, ws)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
    def test_out_dtypes(self, out_dtype):
        n, k = 128, 128
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(0), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(1), (2, n)))
        got = tm_ops.ternary_matmul(x_i8, xs, P.pack2(w_t), ws, out_dtype=out_dtype)
        assert got.dtype == out_dtype

    def test_batched_leading_dims(self):
        n, k = 64, 96
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(0), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(1), (2, 3, n)))
        got = tm_ops.ternary_matmul(x_i8, xs, P.pack2(w_t), ws)
        assert got.shape == (2, 3, k)

    def test_gemv_decode_shape(self):
        # the paper's decode path: M=1 matrix-vector
        n, k = 256, 512
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(0), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(1), (1, n)))
        got = tm_ops.ternary_matmul(x_i8, xs, P.pack2(w_t), ws)
        want = tm_ref.ternary_matmul(x_i8, xs, P.pack2(w_t), ws)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-5)


class TestTlGemvKernel:
    @pytest.mark.parametrize("g", [2, 3])
    @pytest.mark.parametrize("m,n,k", [(1, 252, 128), (2, 96, 200)])
    def test_matches_oracle(self, g, m, n, k):
        n -= n % g
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(0), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(1), (m, n)))
        widx = P.encode_groups(w_t, g)
        got = tg_ops.tl_gemv(x_i8, xs, widx, ws, g=g)
        want = tg_ref.tl_gemv(x_i8, xs, widx, ws, g=g)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-4, atol=1e-4)

    def test_kernel_equals_packed_dequant_kernel(self):
        """Both kernel strategies compute the identical ternary matmul."""
        n, k = 240, 128
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(2), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(3), (2, n)))
        a = tg_ops.tl_gemv(x_i8, xs, P.encode_groups(w_t, 3), ws, g=3)
        b = tm_ops.ternary_matmul(x_i8, xs, P.pack2(w_t), ws)
        np.testing.assert_allclose(np.array(a), np.array(b), rtol=1e-4, atol=1e-4)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("b,h,hk,s,d", [(1, 2, 2, 128, 32), (2, 4, 2, 256, 64),
                                            (1, 8, 2, 384, 32)])
    def test_causal_matches_reference(self, b, h, hk, s, d):
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, hk, s, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, hk, s, d))
        got = fa_ops.flash_attention(q, k, v)
        want = fa_ref.mha_reference(q, k, v)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    def test_dense_schedule_ablation_same_result(self):
        """Paper Table II: dense schedule computes masked blocks too — same
        output, ~2x the block compute (the reverse/skip schedule saving)."""
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 256, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 32))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 32))
        skip = fa_ops.flash_attention(q, k, v, causal_skip=True)
        dense = fa_ops.flash_attention(q, k, v, causal_skip=False)
        np.testing.assert_allclose(np.array(skip), np.array(dense), rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 256, 32))
        k = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 256, 32))
        v = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 256, 32))
        got = fa_ops.flash_attention(q, k, v, window=window)
        want = fa_ref.mha_reference(q, k, v, window=window)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    def test_softcap(self):
        q = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 128, 32)) * 3
        k = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 128, 32)) * 3
        v = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 128, 32))
        got = fa_ops.flash_attention(q, k, v, softcap=20.0)
        want = fa_ref.mha_reference(q, k, v, softcap=20.0)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    def test_unaligned_seq_padding(self):
        q = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 200, 32))
        k = jax.random.normal(jax.random.PRNGKey(10), (1, 2, 200, 32))
        v = jax.random.normal(jax.random.PRNGKey(11), (1, 2, 200, 32))
        got = fa_ops.flash_attention(q, k, v)
        want = fa_ref.mha_reference(q, k, v)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = jax.random.normal(jax.random.PRNGKey(12), (1, 2, 128, 32), dtype)
        k = jax.random.normal(jax.random.PRNGKey(13), (1, 2, 128, 32), dtype)
        v = jax.random.normal(jax.random.PRNGKey(14), (1, 2, 128, 32), dtype)
        got = fa_ops.flash_attention(q, k, v)
        want = fa_ref.mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                                    v.astype(jnp.float32))
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
        np.testing.assert_allclose(np.array(got, np.float32), np.array(want),
                                   rtol=tol, atol=tol)


class TestFusedNormQuantKernel:
    @pytest.mark.parametrize("shape", [(4, 128), (3, 7, 300), (1, 1024)])
    def test_matches_oracle(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 3
        g = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],))
        i8, s = rq_ops.norm_quant(x, g, impl="kernel")
        i8r, sr = rq_ref.norm_quant(x, g)
        np.testing.assert_allclose(np.array(s), np.array(sr), rtol=1e-6)
        np.testing.assert_array_equal(np.array(i8), np.array(i8r))

    def test_fused_equals_two_pass(self):
        """Fusion (paper C3) must not change semantics vs norm-then-quant."""
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 256))
        g = jnp.ones((256,))
        i8, s = rq_ref.norm_quant(x, g)
        normed = rq_ref.rmsnorm(x, g)
        from repro.core.ternary import quantize_act

        i8b, sb = quantize_act(normed)
        np.testing.assert_array_equal(np.array(s), np.array(sb))
        np.testing.assert_array_equal(np.array(i8), np.array(i8b))

    def test_int8_range(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 64)) * 100
        i8, _ = rq_ops.norm_quant(x, jnp.ones((64,)), impl="kernel")
        assert int(np.abs(np.array(i8)).max()) <= 127


class TestWkvKernel:
    """The 5th kernel: VMEM-resident WKV chunk recurrence (rwkv §Perf)."""

    def _inputs(self, b=2, h=3, s=128, n=16, key=0):
        import jax

        ks = jax.random.split(jax.random.PRNGKey(key), 4)
        r = jax.random.normal(ks[0], (b, h, s, n)) * 0.5
        k = jax.random.normal(ks[1], (b, h, s, n)) * 0.5
        v = jax.random.normal(ks[2], (b, h, s, n)) * 0.5
        logw = -jnp.exp(jax.random.normal(ks[3], (b, h, s, n)) * 0.3)
        logw = jnp.clip(logw, -8.0, -1e-4)
        u = jax.random.normal(jax.random.PRNGKey(key + 9), (h, n)) * 0.1
        return r, k, v, logw, u

    @pytest.mark.parametrize("s,chunk", [(128, 64), (96, 32), (64, 64)])
    def test_matches_jnp_oracle(self, s, chunk):
        from repro.kernels.wkv import ops as wkv_ops
        from repro.kernels.wkv import ref as wkv_ref

        r, k, v, logw, u = self._inputs(s=s)
        s0 = jnp.zeros((2, 3, 16, 16), jnp.float32)
        y_ref, sN_ref = wkv_ref.wkv(r, k, v, logw, u, s0, chunk=chunk)
        y, sN = wkv_ops.wkv(r, k, v, logw, u, chunk=chunk)
        np.testing.assert_allclose(np.array(y), np.array(y_ref), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.array(sN), np.array(sN_ref), rtol=1e-4, atol=1e-4)

    def test_matches_sequential_decode(self):
        """Kernel ≡ the O(1)-state sequential recurrence (end-to-end oracle)."""
        import dataclasses

        from repro.core import params as P
        from repro.kernels.wkv import ops as wkv_ops
        from repro.models import rwkv as R

        r, k, v, logw, u = self._inputs(b=1, h=2, s=32, n=8, key=3)
        y, sN = wkv_ops.wkv(r, k, v, logw, u, chunk=16)
        # sequential reference
        S = jnp.zeros((1, 2, 8, 8))
        ys = []
        for t in range(32):
            kv = k[:, :, t, :, None] * v[:, :, t, None, :]
            yt = jnp.einsum("bhn,bhnm->bhm", r[:, :, t],
                            S + u[None, :, :, None] * kv)
            S = jnp.exp(logw[:, :, t])[..., None] * S + kv
            ys.append(yt)
        y_seq = jnp.stack(ys, axis=2)
        np.testing.assert_allclose(np.array(y), np.array(y_seq), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.array(sN), np.array(S), rtol=1e-4, atol=1e-4)


class TestScaleLayoutHelpers:
    """The exact lane-row <-> sublane-column moves the int8 attention
    kernels use on their scale blocks (``kernels/_common.py``)."""

    def test_row_col_roundtrip_is_exact(self):
        from repro.kernels import _common as C

        row = jax.random.uniform(jax.random.PRNGKey(0), (1, 128)) * 1e-3
        col = C.row_to_col(row)
        np.testing.assert_array_equal(np.array(col), np.array(row).T)
        np.testing.assert_array_equal(np.array(C.col_to_row(col)),
                                      np.array(row))

    @pytest.mark.parametrize("start", [0, 64])
    def test_splice_row_keeps_other_lanes(self, start):
        from repro.kernels import _common as C

        old = jax.random.uniform(jax.random.PRNGKey(1), (1, 128))
        col = jax.random.uniform(jax.random.PRNGKey(2), (64, 1)) + 2.0
        got = np.array(C.splice_row(old, col, jnp.int32(start)))
        want = np.array(old).copy()
        want[0, start:start + 64] = np.array(col)[:, 0]
        np.testing.assert_array_equal(got, want)


class TestQuantizeActF32:
    """``quantize_act`` and ``silu_mul`` compute in float32 whatever the
    input dtype — the one definition every path (kernel, XLA, oracle)
    shares, and the only one the TPU's vector unit can lower."""

    def test_bf16_input_quantizes_its_f32_value(self):
        x = (jax.random.normal(jax.random.PRNGKey(3), (4, 96)) * 3
             ).astype(jnp.bfloat16)
        i8, s = T.quantize_act(x)
        i8f, sf = T.quantize_act(x.astype(jnp.float32))
        assert s.dtype == jnp.float32
        np.testing.assert_array_equal(np.array(i8), np.array(i8f))
        np.testing.assert_array_equal(np.array(s), np.array(sf))

    def test_silu_mul_rounds_once(self):
        g = jax.random.normal(jax.random.PRNGKey(4), (4, 64)).astype(jnp.bfloat16)
        u = jax.random.normal(jax.random.PRNGKey(5), (4, 64)).astype(jnp.bfloat16)
        want = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
                ).astype(jnp.bfloat16)
        got = T.silu_mul(g, u)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.array(got, np.float32),
                                      np.array(want, np.float32))
