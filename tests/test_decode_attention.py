"""Decode fast path: fused KV-cache attention kernel, GEMV, scan-based generate.

Three oracle layers, matching the repo's kernel-testing convention:
  kernel (interpret mode)  ==  ref.py jnp oracle  ==  prefill last row,
plus end-to-end equivalence of the device-resident ``generate`` scan against
the per-token Python loop it replaced, and the engine's one-transfer-per-tick
contract.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import packing as PK
from repro.core import params as P
from repro.core import ternary as T
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ternary_matmul import ops as tm_ops
from repro.kernels.ternary_matmul import ref as tm_ref
from repro.models import attention as A
from repro.models import transformer as Tr
from repro.serving import engine as E


def _qkv(b, h, hk, m, d, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, hk, m, d))
    v = jax.random.normal(ks[2], (b, hk, m, d))
    return q, k, v


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize("b,h,hk,m,d", [(1, 2, 2, 128, 32), (2, 8, 2, 256, 64),
                                            (3, 4, 1, 200, 32)])
    def test_matches_oracle_ragged_pos(self, b, h, hk, m, d):
        q, k, v = _qkv(b, h, hk, m, d, key=m)
        pos = jax.random.randint(jax.random.PRNGKey(7), (b,), 0, m)
        got = da_ops.decode_attention(q, k, v, pos, interpret=True)
        want = da_ref.decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("window", [32, 128])
    def test_sliding_window(self, window):
        q, k, v = _qkv(2, 4, 2, 256, 32, key=window)
        pos = jnp.array([200, 31], jnp.int32)
        got = da_ops.decode_attention(q, k, v, pos, window=window, interpret=True)
        want = da_ref.decode_attention_reference(q, k, v, pos, window=window)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    def test_softcap(self):
        q, k, v = _qkv(1, 4, 2, 128, 32, key=5)
        q = q * 3
        pos = jnp.array([100], jnp.int32)
        got = da_ops.decode_attention(q, k, v, pos, softcap=20.0, interpret=True)
        want = da_ref.decode_attention_reference(q, k, v, pos, softcap=20.0)
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    def test_scalar_pos_and_unaligned_cache(self):
        # M not a block multiple: wrapper pads, mask discards the padding.
        q, k, v = _qkv(2, 4, 4, 130, 32, key=9)
        got = da_ops.decode_attention(q, k, v, jnp.int32(129), interpret=True)
        want = da_ref.decode_attention_reference(q, k, v, jnp.int32(129))
        np.testing.assert_allclose(np.array(got), np.array(want), rtol=2e-3, atol=2e-3)

    def test_ref_matches_prefill_last_row(self):
        """Decode at position p ≡ row p of full causal prefill attention."""
        b, h, hk, s, d = 2, 4, 2, 48, 32
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q_full = jax.random.normal(ks[0], (b, h, s, d))
        k_full = jax.random.normal(ks[1], (b, hk, s, d))
        v_full = jax.random.normal(ks[2], (b, hk, s, d))
        full = fa_ref.mha_reference(q_full, k_full, v_full)
        p = s - 1
        dec = da_ref.decode_attention_reference(
            q_full[:, :, p], k_full, v_full, jnp.int32(p)
        )
        np.testing.assert_allclose(np.array(dec), np.array(full[:, :, p]),
                                   rtol=2e-3, atol=2e-3)

    def test_kernel_matches_prefill_last_row_padded_cache(self):
        """Kernel over a padded max_len cache ≡ prefill over the live prefix."""
        b, h, hk, s, d, max_len = 1, 8, 2, 40, 32, 256
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q_full = jax.random.normal(ks[0], (b, h, s, d))
        k_full = jax.random.normal(ks[1], (b, hk, s, d))
        v_full = jax.random.normal(ks[2], (b, hk, s, d))
        full = fa_ref.mha_reference(q_full, k_full, v_full)
        pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
        got = da_ops.decode_attention(
            q_full[:, :, s - 1],
            jnp.pad(k_full, pad), jnp.pad(v_full, pad),
            jnp.int32(s - 1), interpret=True,
        )
        np.testing.assert_allclose(np.array(got), np.array(full[:, :, s - 1]),
                                   rtol=2e-3, atol=2e-3)

    def test_models_impl_switch(self):
        """models.decode_attention impl="kernel" ≡ impl="xla"."""
        q, k, v = _qkv(2, 4, 2, 128, 32, key=11)
        pos = jnp.array([90, 17], jnp.int32)
        a = A.decode_attention(q, k, v, pos, impl="xla")
        b_ = A.decode_attention(q, k, v, pos, impl="kernel")
        np.testing.assert_allclose(np.array(a), np.array(b_), rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("at", ["first", "last"])
    def test_stacked_cache_layer_index(self, kv, at):
        """The in-place decode step on a stacked [L, B, HK, M, D] cache:
        the kernel's row write lands at ``layer`` only (every other layer's
        rows are untouched, and the layer equals the rewrite form's write)
        and its attention at ``layer`` matches the oracle on that layer, the
        new row included."""
        n_layers, b, h, hk, m, d = 3, 3, 4, 2, 256, 32
        layer = 0 if at == "first" else n_layers - 1
        ks = jax.random.split(jax.random.PRNGKey(21), 5)
        q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (n_layers, b, hk, m, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (n_layers, b, hk, m, d), jnp.bfloat16)
        k_row = jax.random.normal(ks[3], (b, hk, d), jnp.bfloat16)
        v_row = jax.random.normal(ks[4], (b, hk, d), jnp.bfloat16)
        pos = jnp.array([200, 3, 255], jnp.int32)
        cache = {"k": k, "v": v}
        if kv == "int8":
            (k, k_s), (v, v_s) = T.quantize_kv(k), T.quantize_kv(v)
            cache = {"k": k, "v": v, "k_scale": k_s[..., None, :],
                     "v_scale": v_s[..., None, :]}
        got, *written = da_ops.decode_attention(
            q, cache["k"], cache["v"], pos, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"), layer=jnp.int32(layer),
            k_new=k_row, v_new=v_row, interpret=True)
        new = dict(zip(("k", "v", "k_scale", "v_scale"), written))
        others = np.array([i for i in range(n_layers) if i != layer])
        for name in cache:
            np.testing.assert_array_equal(np.array(new[name][others]),
                                          np.array(cache[name][others]))
        if kv == "int8":
            k1, v1, ks1, vs1 = A.update_kv_cache_quant(
                k[layer], v[layer], k_s[layer], v_s[layer], k_row, v_row, pos)
            want = da_ref.decode_attention_quant_reference(
                q, k1, v1, ks1, vs1, pos)
            for name, w in (("k", k1), ("v", v1)):
                np.testing.assert_array_equal(np.array(new[name][layer]),
                                              np.array(w))
            for name, w in (("k_scale", ks1), ("v_scale", vs1)):
                np.testing.assert_array_equal(
                    np.array(new[name][layer, :, :, 0]), np.array(w))
        else:
            k1, v1 = A.update_kv_cache(k[layer], v[layer], k_row, v_row, pos)
            want = da_ref.decode_attention_reference(q, k1, v1, pos)
            for name, w in (("k", k1), ("v", v1)):
                np.testing.assert_array_equal(np.array(new[name][layer]),
                                              np.array(w))
        np.testing.assert_allclose(np.array(got, np.float32),
                                   np.array(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_schedule_blocks_tracks_frontier(self):
        live, dense = da_ops.schedule_blocks([64, 900], 1024, bkv=128)
        assert dense == 16
        assert live == (64 // 128 + 1) + (900 // 128 + 1)  # 1 + 8
        wlive, _ = da_ops.schedule_blocks([900], 1024, bkv=128, window=128)
        assert wlive <= 2  # window keeps the foot near the frontier


class TestTernaryGemv:
    @pytest.mark.parametrize("m,n,k", [(1, 256, 512), (4, 128, 200), (16, 64, 128)])
    def test_bit_identical_to_ref(self, m, n, k):
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(k), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(m), (m, n)))
        wp = PK.pack2(w_t)
        got = tm_ops.ternary_gemv(x_i8, xs, wp, ws)
        want = tm_ref.ternary_matmul(x_i8, xs, wp, ws)
        np.testing.assert_array_equal(np.array(got), np.array(want))

    def test_large_m_falls_back_to_tiled_path(self):
        n, k = 128, 128
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(0), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(1), (40, n)))
        got = tm_ops.ternary_gemv(x_i8, xs, PK.pack2(w_t), ws)
        want = tm_ref.ternary_matmul(x_i8, xs, PK.pack2(w_t), ws)
        np.testing.assert_array_equal(np.array(got), np.array(want))

    def test_decode_leading_dims(self):
        n, k = 256, 128
        w_t, ws = T.ternarize(jax.random.normal(jax.random.PRNGKey(2), (n, k)))
        x_i8, xs = T.quantize_act(jax.random.normal(jax.random.PRNGKey(3), (4, 1, n)))
        got = tm_ops.ternary_gemv(x_i8, xs, PK.pack2(w_t), ws)
        assert got.shape == (4, 1, k)


# ---------------------------------------------------------------------------
# End-to-end: scan-based generate == the per-token Python loop it replaced
# ---------------------------------------------------------------------------


def _cfg(arch, **kw):
    cfg = get_config(arch, smoke=True)
    return dataclasses.replace(cfg, dtype=jnp.float32, **kw)


def _generate_python_loop(params, cfg, prompts, *, steps, mode="eval"):
    """The seed implementation's host-driven greedy loop (oracle)."""
    b, s = prompts.shape
    prefill = E.make_prefill_step(cfg, mode=mode)
    serve = E.make_serve_step(cfg, mode=mode)
    last_logits, caches = prefill(params, {"tokens": prompts})
    caches = E.grow_caches(caches, cfg, s + steps)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    out = [tok]
    pos = jnp.full((b,), s, jnp.int32)
    for _ in range(steps - 1):
        logits, caches = serve(params, {"tokens": tok[:, None]}, caches, pos)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
        pos = pos + 1
    return jnp.stack(out, axis=1)


class TestDeviceResidentGenerate:
    def test_scan_equals_python_loop_greedy(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
        got = E.generate(params, cfg, prompts, steps=6, mode="eval").tokens
        want = _generate_python_loop(params, cfg, prompts, steps=6, mode="eval")
        np.testing.assert_array_equal(np.array(got), np.array(want))

    def test_eos_masking_freezes_slot(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab_size)
        free = E.generate(params, cfg, prompts, steps=5, mode="eval").tokens
        eos = int(free[0, 1])  # force slot 0's 2nd token to be "EOS"
        toks = E.generate(params, cfg, prompts, steps=5, mode="eval",
                          eos_id=eos).tokens
        row = np.array(toks[0])
        hit = np.argmax(row == eos)
        assert (row[hit:] == eos).all()  # once EOS, only EOS follows

    def test_single_step(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
        toks = E.generate(params, cfg, prompts, steps=1, mode="eval").tokens
        assert toks.shape == (1, 1)


class TestEngineSyncFree:
    def test_one_device_get_per_tick(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        eng = E.ServingEngine(params, cfg, slots=2, max_len=32, mode="eval")
        for i in range(3):
            eng.submit(E.Request(rid=i, prompt=jax.random.randint(
                jax.random.PRNGKey(i), (8,), 0, cfg.vocab_size), max_new=3))
        calls = []
        orig = jax.device_get
        jax.device_get = lambda x: (calls.append(1), orig(x))[1]
        try:
            ticks = 0
            while eng.queue or any(r is not None for r in eng.live):
                if not eng.step():
                    break
                ticks += 1
        finally:
            jax.device_get = orig
        assert ticks > 0
        assert len(calls) == ticks  # exactly one device_get per scheduler tick


class TestGrowCaches:
    def test_idempotent_and_path_matched(self):
        cfg = _cfg("tellme-0.7b")
        caches = E.init_caches(cfg, 2, 16, dtype=jnp.float32)
        grown = E.grow_caches(caches, cfg, 32)
        shapes, _ = Tr.cache_specs(cfg, 2, 32)
        for a, b in zip(jax.tree.leaves(grown), jax.tree.leaves(shapes)):
            assert a.shape == b.shape
        again = E.grow_caches(grown, cfg, 32)  # idempotent: no negative pad
        for a, b in zip(jax.tree.leaves(grown), jax.tree.leaves(again)):
            assert a.shape == b.shape

    def test_non_seq_state_untouched(self):
        cfg = _cfg("jamba-v0.1-52b")  # hybrid: mamba conv/ssm state has no seq axis
        caches = E.init_caches(cfg, 2, 16, dtype=jnp.float32)
        grown = E.grow_caches(caches, cfg, 24)
        shapes, _ = Tr.cache_specs(cfg, 2, 24)

        def rec(c, s):
            if isinstance(c, dict):
                for k in c:
                    rec(c[k], s[k])
                return
            assert c.shape == s.shape

        rec(grown, shapes)


# ---------------------------------------------------------------------------
# The in-place KV path (DESIGN.md §decode) against the rewrite form
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tellme-0.7b", "gemma2-27b"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_in_place_ticks_bit_identical_to_rewrite(arch, kv, monkeypatch):
    """Several engine-shaped ticks through the Pallas kernels (interpreted):
    decode-only and fused decode + chunk ticks, slots at heterogeneous
    positions, slots diverted into the trash tail. The in-place path — the
    stacked cache carried through the layer loop, rows written in place —
    gives logits and caches bit-identical to the rewrite form (scanned
    per-layer caches, one-hot write) running the same kernels."""
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              kv_cache_dtype=kv)
    params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
    b, c, trash = 4, 64, 192
    cache_len = trash + c  # the engine's geometry: max_len 192, chunk 64
    dead = cache_len - 1
    # (decode positions, chunk offsets or None for a decode-only tick)
    ticks = [([dead] * 4, [0, 0, trash, trash]),
             ([64, dead, dead, dead], [trash, 64, 0, trash]),
             ([65, 128, 64, dead], None),
             ([66, 129, 65, dead], [trash, trash, 64, 0]),
             ([67, 130, 128, 64], None)]

    def run():
        # traced afresh on each call, so each run sees the path in force
        decode = jax.jit(lambda caches, tok, pos: Tr.decode_step(
            params, {"tokens": tok}, caches, pos, cfg, mode="eval",
            attn_impl="kernel"))
        chunk_step = jax.jit(lambda caches, tok, off: Tr.prefill_chunk_step(
            params, {"tokens": tok}, caches, off, cfg, mode="eval",
            attn_impl="kernel", last_row=jnp.full((b,), c - 1),
            prefix_limit=trash))
        caches = E.init_caches(cfg, b, cache_len, dtype=jnp.bfloat16)
        key = jax.random.PRNGKey(3)
        outs = []
        for dpos, off in ticks:
            key, k1, k2 = jax.random.split(key, 3)
            tok = jax.random.randint(k1, (b, 1), 0, cfg.vocab_size)
            lg, caches = decode(caches, tok, jnp.array(dpos, jnp.int32))
            outs.append(lg)
            if off is not None:
                chunk = jax.random.randint(k2, (b, c), 0, cfg.vocab_size)
                lg, caches = chunk_step(caches, chunk,
                                        jnp.array(off, jnp.int32))
                outs.append(lg)
        return outs, caches

    assert Tr.kv_in_place("kernel")
    got, got_caches = run()
    monkeypatch.setattr(Tr, "kv_in_place", lambda *a, **k: False)
    want, want_caches = run()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.array(g), np.array(w))
    assert (jax.tree.structure(got_caches)
            == jax.tree.structure(want_caches))
    for g, w in zip(jax.tree.leaves(got_caches),
                    jax.tree.leaves(want_caches)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.array(g), np.array(w))


def test_stats_report_kv_write_path():
    """``stats()["kv_write"]`` (``/v1/stats``) names the cache write the
    ticks compiled: in place on the kernel path, the rewrite on the XLA form
    (the CPU default) and on the paged layout."""
    cfg = _cfg("tellme-0.7b")
    params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))

    def path(cfg, **kw):
        eng = E.ServingEngine(params, cfg, slots=2, max_len=64, mode="eval",
                              **kw)
        return eng.stats()["kv_write"]

    assert path(cfg) == "rewrite"
    assert path(cfg, attn_impl="xla") == "rewrite"
    assert path(cfg, attn_impl="kernel") == "in_place"
    paged = dataclasses.replace(cfg, kv_layout="paged")
    assert path(paged, attn_impl="kernel") == "rewrite"
