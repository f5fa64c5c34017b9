"""Chunked cache-resident prefill: kernel ≡ oracle ≡ one-shot prefill, engine
continuous-batching equivalence, and the compiled-shape budget.

Oracle layers, matching the repo's kernel-testing convention:
  Pallas kernel (interpret mode)  ==  ref.py jnp oracle  ==  XLA serving form,
plus end-to-end: chunked prefill is token-identical in greedy decode to the
one-shot ``prefill_step`` path, a mixed tick (prefilling + decoding slots)
matches sequential per-slot execution, and the engine never compiles more
than ``len(cfg.prefill_chunk_sizes)`` prefill shapes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import params as P
from repro.core import ternary as T
from repro.kernels.prefill_append import ops as pa_ops
from repro.kernels.prefill_append import ref as pa_ref
from repro.models import attention as A
from repro.models import transformer as Tr
from repro.serving import engine as E


def _chunk_inputs(b, h, hk, c, m, d, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(ks[0], (b, h, c, d))
    kn = jax.random.normal(ks[1], (b, hk, c, d))
    vn = jax.random.normal(ks[2], (b, hk, c, d))
    kc = jax.random.normal(ks[3], (b, hk, m, d))
    vc = jax.random.normal(ks[4], (b, hk, m, d))
    return q, kn, vn, kc, vc


def _assert_triple_close(got, want, rtol=2e-3, atol=2e-3):
    for name, g, w in zip(("out", "k_cache", "v_cache"), got, want):
        np.testing.assert_allclose(np.array(g), np.array(w), rtol=rtol,
                                   atol=atol, err_msg=name)


class TestPrefillAppendKernel:
    @pytest.mark.parametrize("c,offs", [(64, [0, 128]), (128, [128, 256]),
                                        (256, [0, 256])])
    def test_matches_oracle_chunk_sizes(self, c, offs):
        q, kn, vn, kc, vc = _chunk_inputs(2, 4, 2, c, 512, 32, key=c)
        off = jnp.array(offs, jnp.int32)
        got = pa_ops.prefill_append(q, kn, vn, kc, vc, off, interpret=True)
        want = pa_ref.prefill_append_reference(q, kn, vn, kc, vc, off)
        _assert_triple_close(got, want)

    def test_gqa_grouping(self):
        q, kn, vn, kc, vc = _chunk_inputs(2, 8, 2, 64, 256, 32, key=1)
        off = jnp.array([64, 128], jnp.int32)
        got = pa_ops.prefill_append(q, kn, vn, kc, vc, off, interpret=True)
        want = pa_ref.prefill_append_reference(q, kn, vn, kc, vc, off)
        _assert_triple_close(got, want)

    @pytest.mark.parametrize("window", [16, 96])
    def test_sliding_window(self, window):
        q, kn, vn, kc, vc = _chunk_inputs(2, 4, 2, 64, 256, 32, key=window)
        off = jnp.array([128, 0], jnp.int32)
        got = pa_ops.prefill_append(q, kn, vn, kc, vc, off, window=window,
                                    interpret=True)
        want = pa_ref.prefill_append_reference(q, kn, vn, kc, vc, off,
                                               window=window)
        _assert_triple_close(got, want)

    def test_softcap(self):
        q, kn, vn, kc, vc = _chunk_inputs(1, 4, 2, 64, 256, 32, key=5)
        q = q * 3
        off = jnp.array([64], jnp.int32)
        got = pa_ops.prefill_append(q, kn, vn, kc, vc, off, softcap=20.0,
                                    interpret=True)
        want = pa_ref.prefill_append_reference(q, kn, vn, kc, vc, off,
                                               softcap=20.0)
        _assert_triple_close(got, want)

    def test_unaligned_cache_len_adjusts_bkv(self):
        # M = 320 is no 128-multiple: the wrapper halves bkv until it divides.
        q, kn, vn, kc, vc = _chunk_inputs(2, 4, 1, 64, 320, 16, key=9)
        off = jnp.array([64, 192], jnp.int32)
        got = pa_ops.prefill_append(q, kn, vn, kc, vc, off, interpret=True)
        want = pa_ref.prefill_append_reference(q, kn, vn, kc, vc, off)
        _assert_triple_close(got, want)

    def test_untouched_cache_rows_stay_resident(self):
        # Only the chunk window [off, off+C) may change: the in-place append
        # never rewrites the rest of the cache.
        q, kn, vn, kc, vc = _chunk_inputs(1, 2, 2, 64, 256, 16, key=11)
        off = jnp.array([64], jnp.int32)
        _, k2, v2 = pa_ops.prefill_append(q, kn, vn, kc, vc, off, interpret=True)
        np.testing.assert_array_equal(np.array(k2[:, :, :64]), np.array(kc[:, :, :64]))
        np.testing.assert_array_equal(np.array(k2[:, :, 128:]), np.array(kc[:, :, 128:]))
        np.testing.assert_allclose(np.array(k2[:, :, 64:128]),
                                   np.array(kn.astype(k2.dtype)), rtol=1e-6)

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("at", ["first", "last"])
    def test_stacked_cache_layer_index(self, kv, at):
        """A stacked [L, B, HK, M, D] cache appended to at ``layer``: output
        and that layer's cache match the oracle on the layer alone, and every
        other layer comes back untouched."""
        n_layers, b, h, hk, c, m, d = 3, 2, 4, 2, 64, 256, 32
        layer = 0 if at == "first" else n_layers - 1
        q, kn, vn, _, _ = _chunk_inputs(b, h, hk, c, m, d, key=31)
        ks = jax.random.split(jax.random.PRNGKey(32), 2)
        kc = jax.random.normal(ks[0], (n_layers, b, hk, m, d))
        vc = jax.random.normal(ks[1], (n_layers, b, hk, m, d))
        off = jnp.array([128, 0], jnp.int32)
        if kv == "int8":
            (kc, k_s), (vc, v_s) = T.quantize_kv(kc), T.quantize_kv(vc)
            got = pa_ops.prefill_append(
                q, kn, vn, kc, vc, off, k_scale=k_s[..., None, :],
                v_scale=v_s[..., None, :], layer=jnp.int32(layer),
                interpret=True)
            before = (kc, vc, k_s[..., None, :], v_s[..., None, :])
            want = pa_ref.prefill_append_quant_reference(
                q, kn, vn, kc[layer], vc[layer], k_s[layer], v_s[layer], off)
            # the oracle's scales are [B, HK, M]; the stack's [.., 1, M]
            got_layer = [got[0]] + [g[layer] for g in got[1:3]] + [
                g[layer, :, :, 0] for g in got[3:]]
        else:
            got = pa_ops.prefill_append(q, kn, vn, kc, vc, off,
                                        layer=jnp.int32(layer),
                                        interpret=True)
            before = (kc, vc)
            want = pa_ref.prefill_append_reference(q, kn, vn, kc[layer],
                                                   vc[layer], off)
            got_layer = [got[0]] + [g[layer] for g in got[1:]]
        for g, w in zip(got_layer, want):
            np.testing.assert_allclose(np.array(g, np.float32),
                                       np.array(w, np.float32),
                                       rtol=2e-3, atol=2e-3)
        others = np.array([i for i in range(n_layers) if i != layer])
        for g, w in zip(got[1:], before):
            assert g.shape == w.shape
            np.testing.assert_array_equal(np.array(g[others]),
                                          np.array(w[others]))

    def test_models_impl_switch(self):
        """models.prefill_append_attention impl="kernel" ≡ impl="xla"."""
        q, kn, vn, kc, vc = _chunk_inputs(2, 4, 2, 64, 256, 32, key=13)
        off = jnp.array([128, 64], jnp.int32)
        a = A.prefill_append_attention(q, kn, vn, kc, vc, off, impl="xla")
        b = A.prefill_append_attention(q, kn, vn, kc, vc, off, impl="kernel")
        _assert_triple_close(a, b)

    def test_schedule_blocks_tracks_frontier(self):
        live, dense = pa_ops.schedule_blocks([0, 512], 1024, bkv=128)
        assert dense == 2 * (8 + 1)
        assert live == (0 + 1) + (4 + 1)  # prefix blocks + the chunk step
        wlive, _ = pa_ops.schedule_blocks([896], 1024, bkv=128, window=128)
        assert wlive <= 3  # window keeps the prefix foot near the frontier


# ---------------------------------------------------------------------------
# Model level: chunked prefill ≡ one-shot prefill
# ---------------------------------------------------------------------------


def _cfg(arch, **kw):
    cfg = get_config(arch, smoke=True)
    return dataclasses.replace(cfg, dtype=jnp.float32, **kw)


ARCHS = ["tellme-0.7b", "gemma2-27b"]  # MHA vs GQA+sliding-window+softcap


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_chunk_step_matches_one_shot_forward(arch, impl):
    # mode="wq": ternary weights, float activations — the chunked and one-shot
    # paths then differ only by float reduction order. (mode="eval"'s int8
    # per-token absmax quantization turns ulp-level drift into ±1 rounding
    # flips, which the greedy token-identity test below covers instead.)
    cfg = _cfg(arch)
    params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
    B, S, C, M = 2, 128, 64, 256
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    logits_full, _, caches_full = Tr.forward(params, {"tokens": toks}, cfg,
                                             mode="wq", collect_cache=True)
    caches = E.init_caches(cfg, B, M, dtype=jnp.float32)
    outs = []
    for i in range(S // C):
        off = jnp.full((B,), i * C, jnp.int32)
        lg, caches = Tr.prefill_chunk_step(
            params, {"tokens": toks[:, i * C:(i + 1) * C]}, caches, off, cfg,
            mode="wq", attn_impl=impl)
        outs.append(lg)
    np.testing.assert_allclose(np.array(jnp.concatenate(outs, axis=1)),
                               np.array(logits_full), rtol=2e-3, atol=2e-3)
    # the appended cache equals the one-shot cache on the live prefix
    kf = caches_full["blocks"]["b0"]["k"]
    kc = caches["blocks"]["b0"]["k"][:, :, :, :S]
    np.testing.assert_allclose(np.array(kc), np.array(kf), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_greedy_decode_bit_identical(arch):
    """Chunked prefill → decode emits the same greedy tokens as the one-shot
    ``prefill_step`` path (``generate``), across ragged prompt lengths."""
    cfg = _cfg(arch)
    params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
    for s in (12, 100):
        prompts = jax.random.randint(jax.random.PRNGKey(s), (2, s), 0,
                                     cfg.vocab_size)
        ref = np.array(E.generate(params, cfg, prompts, steps=4,
                                  mode="eval").tokens)
        chunks = E.chunk_schedule(s)
        padded = jnp.pad(prompts, ((0, 0), (0, sum(chunks) - s)))
        caches = E.init_caches(cfg, 2, E._round_up(s + 4, 64) + 256,
                               dtype=jnp.float32)
        off = 0
        for c in chunks:
            lg, caches = Tr.prefill_chunk_step(
                params, {"tokens": padded[:, off:off + c]},
                caches, jnp.full((2,), off, jnp.int32), cfg, mode="eval")
            row = s - 1 - off
            if 0 <= row < c:
                last = lg[:, row]
            off += c
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        got = [tok]
        pos = jnp.full((2,), s, jnp.int32)
        for _ in range(3):
            lg, caches = Tr.decode_step(params, {"tokens": tok[:, None]},
                                        caches, pos, cfg, mode="eval")
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            got.append(tok)
            pos = pos + 1
        np.testing.assert_array_equal(np.array(jnp.stack(got, 1)), ref)


# ---------------------------------------------------------------------------
# Chunk schedule + bucketed prefill
# ---------------------------------------------------------------------------


class TestChunkSchedule:
    def test_offsets_stay_chunk_aligned(self):
        for length in (1, 63, 64, 65, 200, 256, 700, 1000):
            chunks = E.chunk_schedule(length)
            assert sum(chunks) >= length
            assert sum(chunks) - length < 64  # tail pad < smallest size
            off = 0
            for c in chunks:
                assert off % c == 0, (length, chunks)  # kernel write invariant
                off += c

    def test_rejects_broken_divisibility_chain(self):
        with pytest.raises(ValueError):
            E.chunk_schedule(100, (64, 96))

    def test_bucket_length(self):
        assert E.bucket_length(10) == 64
        assert E.bucket_length(65) == 128
        assert E.bucket_length(200) == 256
        assert E.bucket_length(300) == 512  # beyond the grid: 256-multiples


class TestBucketedPrefill:
    def test_recurrent_state_families_keep_exact_length(self):
        """Pad tokens must never integrate into recurrent caches: rwkv's
        generate() through prefill_bucketed matches the seed's exact-length
        prefill + python decode loop token for token."""
        cfg = _cfg("rwkv6-3b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0,
                                     cfg.vocab_size)
        got = np.array(E.generate(params, cfg, prompts, steps=4,
                                  mode="eval").tokens)
        pre = E.make_prefill_step(cfg, mode="eval")
        srv = E.make_serve_step(cfg, mode="eval")
        last, caches = pre(params, {"tokens": prompts})
        caches = E.grow_caches(caches, cfg, 14)
        tok = jnp.argmax(last, -1).astype(jnp.int32)
        want = [tok]
        pos = jnp.full((1,), 10, jnp.int32)
        for _ in range(3):
            lg, caches = srv(params, {"tokens": tok[:, None]}, caches, pos)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            want.append(tok)
            pos = pos + 1
        np.testing.assert_array_equal(got, np.array(jnp.stack(want, 1)))

    def test_lengths_share_bucket_and_compiled_step(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        E._BUCKETED_PREFILL_CACHE.clear()
        for s in (10, 33, 50):  # all bucket to 64
            prompts = jax.random.randint(jax.random.PRNGKey(s), (1, s), 0,
                                         cfg.vocab_size)
            last, _ = E.prefill_bucketed(params, cfg, prompts, mode="eval")
            full, _, _ = Tr.forward(params, {"tokens": prompts}, cfg, mode="eval")
            np.testing.assert_allclose(np.array(last), np.array(full[:, -1]),
                                       rtol=2e-3, atol=2e-3)
        keys = [k for k in E._BUCKETED_PREFILL_CACHE if k[0] == cfg]
        assert len(keys) == 1  # one compiled step for the whole bucket


# ---------------------------------------------------------------------------
# Engine: continuous batching over the fused chunked tick
# ---------------------------------------------------------------------------


class TestEngineChunkedPrefill:
    def test_mixed_tick_matches_sequential(self):
        """2 decoding + 2 prefilling slots in one tick emit exactly the
        tokens each request gets when served alone."""
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        short = [jax.random.randint(jax.random.PRNGKey(i), (8 + 4 * i,), 0,
                                    cfg.vocab_size) for i in range(2)]
        long = [jax.random.randint(jax.random.PRNGKey(9 + i), (130 + 64 * i,),
                                   0, cfg.vocab_size) for i in range(2)]
        refs = {p.shape[0]: np.array(
            E.generate(params, cfg, p[None], steps=6, mode="eval").tokens[0])
            for p in short + long}

        eng = E.ServingEngine(params, cfg, slots=4, max_len=512, mode="eval")
        reqs = [E.Request(rid=i, prompt=p, max_new=6)
                for i, p in enumerate(short)]
        for r in reqs:
            eng.submit(r)
        eng.step()  # both short prompts prefill (single chunk) and hand off
        assert all(p is None for p in eng._plan)
        longreqs = [E.Request(rid=2 + i, prompt=p, max_new=6)
                    for i, p in enumerate(long)]
        for r in longreqs:
            eng.submit(r)
        mixed_ticks = 0
        while eng.queue or any(s is not None for s in eng.live):
            eng.step()
            n_pre = eng.prefilling_slots
            n_dec = eng.decoding_slots
            if n_pre == 2 and n_dec == 2:
                mixed_ticks += 1
        assert mixed_ticks > 0  # the scenario actually ran mixed
        for r in reqs + longreqs:
            assert r.done
            np.testing.assert_array_equal(np.array(r.generated[:6]),
                                          refs[len(r.prompt)][:6])

    def test_at_most_three_prefill_shapes(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        eng = E.ServingEngine(params, cfg, slots=2, max_len=768, mode="eval")
        for i, s in enumerate((8, 70, 150, 300, 40, 600)):
            eng.submit(E.Request(
                rid=i, prompt=jax.random.randint(jax.random.PRNGKey(s), (s,),
                                                 0, cfg.vocab_size),
                max_new=2))
        eng.run()
        assert all(r is None for r in eng.live)
        assert set(eng._fused) <= set(cfg.prefill_chunk_sizes)
        assert len(eng._fused) <= 3

    def test_one_device_get_per_tick_while_prefilling(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        eng = E.ServingEngine(params, cfg, slots=2, max_len=256, mode="eval")
        for i in range(3):
            eng.submit(E.Request(rid=i, prompt=jax.random.randint(
                jax.random.PRNGKey(i), (100,), 0, cfg.vocab_size), max_new=3))
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
        assert len(calls) == ticks  # chunked prefill adds no extra transfers

    def test_oversized_prompt_rejected_not_fatal(self):
        """One prompt >= max_len must not crash the scheduler: it is marked
        done with no output and the rest of the queue still serves."""
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        eng = E.ServingEngine(params, cfg, slots=1, max_len=64, mode="eval")
        big = E.Request(rid=0, prompt=jax.random.randint(
            jax.random.PRNGKey(0), (64,), 0, cfg.vocab_size), max_new=2)
        ok = E.Request(rid=1, prompt=jax.random.randint(
            jax.random.PRNGKey(1), (8,), 0, cfg.vocab_size), max_new=2)
        eng.submit(big)
        eng.submit(ok)
        eng.run()
        assert big.done and big.generated == []
        assert ok.done and len(ok.generated) >= 2

    def test_legacy_prefill_mode_still_serves(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        prompts = [jax.random.randint(jax.random.PRNGKey(i + 10), (8,), 0,
                                      cfg.vocab_size) for i in range(2)]
        refs = [np.array(E.generate(params, cfg, p[None], steps=4,
                                    mode="eval").tokens[0]) for p in prompts]
        eng = E.ServingEngine(params, cfg, slots=2, max_len=64, mode="eval",
                              prefill="legacy")
        reqs = [E.Request(rid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        for r, ref in zip(reqs, refs):
            assert r.done
            np.testing.assert_array_equal(np.array(r.generated[:4]), ref[:4])

    @pytest.mark.parametrize("prefill", ["chunked", "legacy"])
    def test_max_new_one_emits_exactly_one_token(self, prefill):
        """Both prefill paths apply the retirement predicate to the prefill
        token: a max_new=1 request yields exactly one token."""
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
        eng = E.ServingEngine(params, cfg, slots=1, max_len=64, mode="eval",
                              prefill=prefill)
        r = E.Request(rid=0, prompt=jax.random.randint(
            jax.random.PRNGKey(1), (8,), 0, cfg.vocab_size), max_new=1)
        eng.submit(r)
        eng.run()
        assert r.done and len(r.generated) == 1
