"""Fused norm→quant→matmul pipeline (DESIGN.md §norm-quant).

Three layers of guarantees:

* kernel ≡ oracle — the Pallas fused_norm_quant / ternary_swiglu kernels
  against the jnp oracle composition (int8 codes exact; scales to a few
  f32 ulp — interpret-mode block shapes reorder the row reductions);
* fused ≡ unfused — the XLA forms of the fused dispatch are the *same op
  sequence* as the unfused path, so equality is exact;
* serving bit-identity — greedy decode through the packed model/engine is
  bit-identical with the fused pipeline on and off (the ISSUE bar).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

# hypothesis-heavy suite: runs in the dedicated `slow` CI job (conftest.py)
pytestmark = pytest.mark.slow
from repro.configs import get_config
from repro.core import bitlinear as BL
from repro.core import packing as P
from repro.core import params as PR
from repro.core import ternary as T
from repro.kernels.fused_norm_quant import ops as nq_ops
from repro.kernels.fused_norm_quant import ref as nq_ref
from repro.kernels.ternary_matmul import ops as tm_ops
from repro.models import layers as L
from repro.models import transformer as Tr
from repro.serving import engine as E


def _assert_quant_close(got, want, *, ulp_rtol=5e-7):
    """Kernel-vs-oracle bar: scales to one quantization-dtype ulp (padded
    interpret-mode blocks reorder the row reductions, so the absmax can land
    one rounding step away), int8 codes within the step that implies."""
    (i8g, sg), (i8w, sw) = got, want
    np.testing.assert_allclose(np.array(sg), np.array(sw), rtol=ulp_rtol)
    assert (np.abs(np.array(i8g, np.int32) - np.array(i8w, np.int32)) <= 1).all()


class TestFusedNormQuant:
    @pytest.mark.parametrize("shape", [(4, 128), (3, 7, 300), (1, 1024), (130, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_matches_oracle(self, shape, dtype):
        x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 3).astype(dtype)
        g = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],))
        _assert_quant_close(nq_ops.norm_quant(x, g, impl="kernel"),
                            nq_ref.norm_quant(x, g),
                            ulp_rtol=4.1e-3 if dtype == jnp.bfloat16 else 5e-7)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_oracle_is_exactly_norm_then_quant(self, dtype):
        """The fused semantics are *defined* as the unfused composition —
        rmsnorm (cast back to input dtype) then quantize_act — exactly."""
        x = (jax.random.normal(jax.random.PRNGKey(2), (6, 96)) * 2).astype(dtype)
        gamma = jax.random.normal(jax.random.PRNGKey(3), (96,))
        i8, s = nq_ref.norm_quant(x, gamma)
        i8b, sb = T.quantize_act(L.rmsnorm({"gamma": gamma}, x))
        np.testing.assert_array_equal(np.array(i8), np.array(i8b))
        np.testing.assert_array_equal(np.array(s), np.array(sb))

    def test_all_zero_rows(self):
        x = jnp.zeros((5, 64))
        i8, s = nq_ops.norm_quant(x, jnp.ones((64,)), impl="kernel")
        assert not np.array(i8).any()
        assert np.isfinite(np.array(s)).all()

    def test_padding_tail_rows_are_dropped(self):
        """m far from the 128-row block: padded rows must not leak out."""
        x = jax.random.normal(jax.random.PRNGKey(4), (129, 32))
        i8, s = nq_ops.norm_quant(x, jnp.ones((32,)), impl="kernel")
        assert i8.shape == (129, 32) and s.shape == (129, 1)
        _assert_quant_close((i8, s), nq_ref.norm_quant(x, jnp.ones((32,))))  # f32

    def test_int8_range(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 64)) * 100
        i8, _ = nq_ops.norm_quant(x, jnp.ones((64,)), impl="kernel")
        assert int(np.abs(np.array(i8)).max()) <= 127

    @given(st.integers(1, 40), st.integers(2, 190), st.booleans(),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_property_fused_equals_two_pass(self, m, n, bf16, seed):
        dtype = jnp.bfloat16 if bf16 else jnp.float32
        k0, k1 = jax.random.split(jax.random.PRNGKey(seed))
        x = (jax.random.normal(k0, (m, n)) * 4).astype(dtype)
        gamma = jax.random.normal(k1, (n,))
        _assert_quant_close(nq_ops.norm_quant(x, gamma, impl="kernel"),
                            T.quantize_act(L.rmsnorm({"gamma": gamma}, x)),
                            ulp_rtol=4.1e-3 if bf16 else 5e-7)

    def test_layer_wrapper_auto_is_xla_off_tpu(self):
        """models.layers.norm_quant: the serving dispatch equals the oracle
        exactly on CPU (impl='auto' -> XLA composition)."""
        x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 64), jnp.bfloat16)
        gamma = jax.random.normal(jax.random.PRNGKey(7), (64,))
        i8, s = L.norm_quant({"gamma": gamma}, x)
        i8r, sr = nq_ref.norm_quant(x, gamma)
        np.testing.assert_array_equal(np.array(i8), np.array(i8r))
        np.testing.assert_array_equal(np.array(s), np.array(sr))


def _swiglu_inputs(m, n, k, seed=0, act_dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    wgt, wgs = T.ternarize(jax.random.normal(ks[0], (n, k)))
    wut, wus = T.ternarize(jax.random.normal(ks[1], (n, k)))
    x = (jax.random.normal(ks[2], (m, n)) * 2).astype(act_dtype)
    xi8, xs = T.quantize_act(x)
    return xi8, xs, (wgt, wgs), (wut, wus)


def _swiglu_unfused(xi8, xs, gate, up, act_dtype):
    g = T.ternary_matmul_ref(xi8, xs, gate[0], gate[1], out_dtype=act_dtype)
    u = T.ternary_matmul_ref(xi8, xs, up[0], up[1], out_dtype=act_dtype)
    return T.quantize_act(T.silu_mul(g, u))


class TestSwigluEpilogue:
    @pytest.mark.parametrize("m,n,k", [(1, 64, 128), (5, 64, 200), (130, 128, 96)])
    @pytest.mark.parametrize("act_dtype", [jnp.bfloat16, jnp.float32])
    def test_kernel_matches_unfused(self, m, n, k, act_dtype):
        xi8, xs, gate, up = _swiglu_inputs(m, n, k, seed=m + k, act_dtype=act_dtype)
        got = tm_ops.ternary_swiglu(xi8, xs, P.pack2(gate[0]), gate[1],
                                    P.pack2(up[0]), up[1], act_dtype=act_dtype)
        _assert_quant_close(got, _swiglu_unfused(xi8, xs, gate, up, act_dtype),
                            ulp_rtol=4.1e-3 if act_dtype == jnp.bfloat16 else 5e-7)

    def test_bitlinear_swiglu_xla_is_exact(self):
        """The XLA side of the dispatch is the identical op sequence."""
        xi8, xs, gate, up = _swiglu_inputs(3, 64, 96, seed=9)
        gp = {"wp": P.pack2(gate[0]), "scale": gate[1]}
        upp = {"wp": P.pack2(up[0]), "scale": up[1]}
        hi8, hs = BL.swiglu(gp, upp, (xi8, xs), use_kernel=False)
        hi8r, hsr = _swiglu_unfused(xi8, xs, gate, up, jnp.bfloat16)
        np.testing.assert_array_equal(np.array(hi8), np.array(hi8r))
        np.testing.assert_array_equal(np.array(hs), np.array(hsr))

    @given(st.integers(1, 24), st.integers(1, 3), st.integers(10, 140),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_property_kernel_matches_unfused(self, m, n4, k, seed):
        n = 4 * 16 * n4  # contraction must pack (%4) — sweep via n4
        xi8, xs, gate, up = _swiglu_inputs(m, n, k, seed=seed)
        got = tm_ops.ternary_swiglu(xi8, xs, P.pack2(gate[0]), gate[1],
                                    P.pack2(up[0]), up[1])
        _assert_quant_close(got, _swiglu_unfused(xi8, xs, gate, up, jnp.bfloat16),
                            ulp_rtol=4.1e-3)


class TestResidualEpilogue:
    @pytest.mark.parametrize("m", [1, 5, 40])
    def test_kernel_residual_equals_post_add(self, m):
        n, k = 64, 200
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(0), (n, k)))
        xi8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(1), (m, n)))
        r = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.bfloat16)
        wp = P.pack2(w_t)
        got = tm_ops.ternary_gemv(xi8, xs, wp, ws, out_dtype=jnp.bfloat16, residual=r)
        want = tm_ops.ternary_gemv(xi8, xs, wp, ws, out_dtype=jnp.bfloat16) + r
        np.testing.assert_array_equal(np.array(got), np.array(want))

    def test_apply_prequant_and_residual(self):
        """bitlinear.apply fused forms ≡ quantize → matmul → add, exactly."""
        n, k = 64, 96
        w = jax.random.normal(jax.random.PRNGKey(3), (n, k))
        pp = BL.pack_params(w)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 3, n), jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(5), (2, 3, k), jnp.bfloat16)
        xq = T.quantize_act(x)
        base = BL.apply(pp, x, mode="packed", use_kernel=False)
        got = BL.apply(pp, xq, mode="packed", use_kernel=False,
                       out_dtype=jnp.bfloat16, residual=r)
        np.testing.assert_array_equal(np.array(got), np.array(base + r))

    def test_fused_forms_rejected_outside_packed(self):
        w = jax.random.normal(jax.random.PRNGKey(6), (16, 8))
        xq = T.quantize_act(jnp.ones((2, 16)))
        with pytest.raises(ValueError):
            BL.apply({"w": w}, xq, mode="train")
        with pytest.raises(ValueError):
            BL.apply({"w": w}, jnp.ones((2, 16)), mode="eval",
                     residual=jnp.ones((2, 8)))


class TestTlDispatch:
    """use_kernel='tl': the paper's table-lookup engine, end-to-end selectable."""

    @pytest.mark.parametrize("n,k", [(64, 128), (96, 64)])
    def test_tl_matches_xla_packed(self, n, k):
        w = jax.random.normal(jax.random.PRNGKey(0), (n, k))
        pp = BL.pack_params(w)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, n))
        a = BL.apply(pp, x, mode="packed", use_kernel="tl")
        b = BL.apply(pp, x, mode="packed", use_kernel=False, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.array(a), np.array(b), rtol=1e-5, atol=1e-5)

    def test_precomputed_indices_match_on_the_fly(self):
        w = jax.random.normal(jax.random.PRNGKey(2), (64, 96))
        pp = BL.pack_params(w)
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 64))
        a = BL.apply(pp, x, mode="packed", use_kernel="tl")
        b = BL.apply(BL.with_tl_indices(pp), x, mode="packed", use_kernel="tl")
        np.testing.assert_array_equal(np.array(a), np.array(b))

    def test_non_multiple_of_group_padded(self):
        # N = 64 is not a multiple of the g=3 grouping: zero-trit padding.
        w = jax.random.normal(jax.random.PRNGKey(4), (64, 128))
        assert BL.with_tl_indices(BL.pack_params(w))["w_idx"].shape[0] == 22


class TestRopeTables:
    def test_tables_match_per_call_rope(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 6, 32), jnp.bfloat16)
        positions = jnp.arange(6, dtype=jnp.int32)[None].repeat(2, 0)
        rope = L.rope_tables(positions, 32, theta=10000.0)
        got = L.apply_rope_tables(x, (rope[0][:, None], rope[1][:, None]))
        want = L.apply_rope(x, positions[:, None], theta=10000.0)
        np.testing.assert_array_equal(np.array(got), np.array(want))

    def test_rope_for_covers_plan_mixers(self):
        cfg = get_config("tellme-0.7b", smoke=True)
        pos = jnp.arange(4, dtype=jnp.int32)[None]
        tables = Tr.rope_for(cfg, pos)
        assert set(tables) == {"attn"}
        assert tables["attn"][0].shape == (1, 4, cfg.head_dim // 2)


class TestServingBitIdentity:
    """The wiring bar: fused on vs off is bit-identical end to end."""

    def _setup(self):
        cfg = get_config("tellme-0.7b", smoke=True)
        specs = Tr.param_specs(cfg)
        params = PR.init_params(specs, jax.random.PRNGKey(0))
        return cfg, Tr.pack_tree(params, specs)

    def test_packed_forward_bit_identical(self):
        cfg, packed = self._setup()
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        lf, _, _ = Tr.forward(packed, {"tokens": toks}, cfg, None, mode="packed",
                              fused=True)
        lu, _, _ = Tr.forward(packed, {"tokens": toks}, cfg, None, mode="packed",
                              fused=False)
        np.testing.assert_array_equal(np.array(lf), np.array(lu))

    def test_greedy_generate_bit_identical(self):
        cfg, packed = self._setup()
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, cfg.vocab_size)
        a = E.generate(packed, cfg, toks, steps=6, mode="packed", fused=True)
        b = E.generate(packed, cfg, toks, steps=6, mode="packed", fused=False)
        np.testing.assert_array_equal(np.array(a.tokens), np.array(b.tokens))

    def test_engine_tokens_bit_identical(self):
        cfg, packed = self._setup()
        prompts = [jax.random.randint(jax.random.PRNGKey(3 + i), (l,), 0,
                                      cfg.vocab_size)
                   for i, l in enumerate((9, 30))]

        def run(fused):
            eng = E.ServingEngine(packed, cfg, slots=2, max_len=128,
                                  mode="packed", fused=fused)
            reqs = [E.Request(rid=i, prompt=p, max_new=5)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            eng.run()
            return [r.generated for r in reqs]

        assert run(True) == run(False)

    def test_prefill_chunk_step_bit_identical(self):
        cfg, packed = self._setup()
        caches = E.init_caches(cfg, 2, 128, dtype=cfg.dtype)
        toks = jax.random.randint(jax.random.PRNGKey(9), (2, 64), 0, cfg.vocab_size)
        off = jnp.zeros((2,), jnp.int32)
        lf, cf = Tr.prefill_chunk_step(packed, {"tokens": toks}, caches, off, cfg,
                                       mode="packed", fused=True)
        lu, cu = Tr.prefill_chunk_step(packed, {"tokens": toks}, caches, off, cfg,
                                       mode="packed", fused=False)
        np.testing.assert_array_equal(np.array(lf), np.array(lu))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.array(a),
                                                                np.array(b)),
                     cf, cu)
