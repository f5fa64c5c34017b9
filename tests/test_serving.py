"""Serving path: prefill→decode consistency, packed weights, batching engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import params as P
from repro.models import transformer as T
from repro.serving import engine as E


def _cfg(arch, **kw):
    cfg = get_config(arch, smoke=True)
    return dataclasses.replace(cfg, dtype=jnp.float32, **kw)


def _batch_full(cfg, B, S, key=1):
    toks = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0, cfg.vocab_size)
    if cfg.frontend != "none":
        emb = jax.random.normal(jax.random.PRNGKey(key + 1),
                                (B, S, T.FRONTEND_DIMS[cfg.frontend]), jnp.float32)
        return {"embeddings": emb}
    return {"tokens": toks}


CASES = [
    ("granite-8b", "eval"),
    ("gemma2-27b", "eval"),
    ("musicgen-medium", "eval"),
    ("internlm2-20b", "packed"),
    ("deepseek-v2-lite-16b", "wq"),  # MLA absorption ⊥ act-quant (models/mla.py)
    ("jamba-v0.1-52b", "eval"),
    ("arctic-480b", "eval"),
    ("rwkv6-3b", "eval"),
]


@pytest.mark.parametrize("arch,mode", CASES)
def test_prefill_decode_matches_full_forward(arch, mode):
    cfg = _cfg(arch, capacity_factor=8.0)
    specs = T.param_specs(cfg)
    params = P.init_params(specs, jax.random.PRNGKey(0))
    if mode == "packed":
        params = T.pack_tree(params, specs)
    B, S, EXT = 2, 16, 4
    batch = _batch_full(cfg, B, S + EXT)
    logits_full, _, _ = T.forward(params, batch, cfg, mode=mode)
    pre = E.make_prefill_step(cfg, mode=mode)
    srv = E.make_serve_step(cfg, mode=mode)
    bslice = lambda lo, hi: {k: v[:, lo:hi] for k, v in batch.items()}
    last, caches = pre(params, bslice(0, S))
    caches = E.grow_caches(caches, cfg, S + EXT)
    np.testing.assert_allclose(np.array(last), np.array(logits_full[:, S - 1]),
                               rtol=2e-3, atol=2e-3)
    for t in range(EXT):
        pos = jnp.int32(S + t)
        lg, caches = srv(params, bslice(S + t, S + t + 1), caches, pos)
        np.testing.assert_allclose(np.array(lg), np.array(logits_full[:, S + t]),
                                   rtol=3e-3, atol=3e-3)


def test_packed_forward_equals_eval_forward():
    for arch in ("granite-8b", "arctic-480b", "rwkv6-3b"):
        cfg = _cfg(arch)
        specs = T.param_specs(cfg)
        params = P.init_params(specs, jax.random.PRNGKey(0))
        packed = T.pack_tree(params, specs)
        batch = _batch_full(cfg, 2, 16)
        le, _, _ = T.forward(params, batch, cfg, mode="eval")
        lp, _, _ = T.forward(packed, batch, cfg, mode="packed")
        np.testing.assert_array_equal(np.array(le), np.array(lp))


def test_packed_specs_structure_matches_pack_tree():
    for arch in ("deepseek-v2-lite-16b", "jamba-v0.1-52b"):
        cfg = _cfg(arch)
        specs = T.param_specs(cfg)
        params = P.init_params(specs, jax.random.PRNGKey(0))
        packed = T.pack_tree(params, specs)
        abstract = P.abstract_params(T.packed_param_specs(cfg))
        assert jax.tree.structure(packed) == jax.tree.structure(abstract)
        for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(abstract)):
            assert a.shape == b.shape and a.dtype == b.dtype


def test_generate_greedy_deterministic():
    cfg = _cfg("tellme-0.7b")
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    r1 = E.generate(params, cfg, prompts, steps=6, mode="eval")
    r2 = E.generate(params, cfg, prompts, steps=6, mode="eval")
    np.testing.assert_array_equal(np.array(r1.tokens), np.array(r2.tokens))
    assert r1.tokens.shape == (2, 6)


def test_continuous_batching_tokens_match_reference():
    cfg = _cfg("tellme-0.7b")
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    prompts = [jax.random.randint(jax.random.PRNGKey(i + 10), (8,), 0, cfg.vocab_size)
               for i in range(3)]
    singles = [np.array(E.generate(params, cfg, p[None], steps=4, mode="eval").tokens[0])
               for p in prompts]
    reqs = [E.Request(rid=i, prompt=p, max_new=4) for i, p in enumerate(prompts)]
    eng = E.ServingEngine(params, cfg, slots=2, max_len=32, mode="eval")
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, ref in zip(reqs, singles):
        assert r.done
        np.testing.assert_array_equal(np.array(r.generated[:4]), ref[:4])


def test_engine_pinned_to_device_serves_identically():
    """``device=`` commits params, caches and every tick to one device (the
    replica pool's one-replica-per-chip placement) without changing a
    token; ``stats()`` names the attention form the ticks dispatch."""
    cfg = _cfg("tellme-0.7b")
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    dev = jax.devices()[-1]
    streams = []
    for kw in ({}, {"device": dev}):
        eng = E.ServingEngine(params, cfg, slots=2, max_len=96, mode="eval",
                              **kw)
        reqs = [E.Request(rid=i, prompt=np.arange(1, 9 + 20 * i), max_new=4)
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        streams.append([list(r.generated) for r in reqs])
        assert eng.stats()["attn_impl"] == "xla"  # "auto" off-TPU
    leaves = jax.tree.leaves((eng.params, eng.caches, eng.pos, eng.cur_tok))
    assert all(x.devices() == {dev} and x.committed for x in leaves)
    assert streams[0] == streams[1]
    eng = E.ServingEngine(params, cfg, slots=2, max_len=96, mode="eval",
                          attn_impl="kernel")
    assert eng.stats()["attn_impl"] == "kernel"


class TestAdmissionEdgeCases:
    """submit/_admit hardening (DESIGN.md §resilience): degenerate prompts
    must reject with a structured status, never crash the scheduler or
    strand co-queued requests."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = _cfg("tellme-0.7b")
        params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
        return cfg, params

    def _engine(self, params, cfg, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", 128)
        return E.ServingEngine(params, cfg, mode="eval", eos_id=-2, **kw)

    def test_empty_prompt_rejected_not_crashed(self, setup):
        from repro.serving import resilience as R
        cfg, params = setup
        eng = self._engine(params, cfg)
        bad = E.Request(rid=0, prompt=np.zeros((0,), np.int64), max_new=4)
        ok = E.Request(rid=1, prompt=np.arange(1, 9), max_new=4)
        eng.submit(bad)
        eng.submit(ok)
        eng.run()
        assert bad.status is R.Status.FAILED
        assert bad.status_detail == "bad_prompt" and bad.generated == []
        assert ok.status is R.Status.OK and len(ok.generated) == 4

    def test_prompt_exactly_on_chunk_grid(self, setup):
        cfg, params = setup
        size = sorted(cfg.prefill_chunk_sizes)[0]
        prompt = jax.random.randint(jax.random.PRNGKey(3), (size,), 0,
                                    cfg.vocab_size)
        ref = np.array(E.generate(params, cfg, prompt[None], steps=4,
                                  mode="eval").tokens[0])
        eng = self._engine(params, cfg, max_len=192)
        req = E.Request(rid=0, prompt=prompt, max_new=4)
        eng.submit(req)
        eng.run()
        np.testing.assert_array_equal(np.array(req.generated), ref)

    def test_prompt_at_max_len_rejected(self, setup):
        from repro.serving import resilience as R
        cfg, params = setup
        eng = self._engine(params, cfg)
        for plen in (eng.max_len, eng.max_len + 7):
            req = E.Request(rid=plen, prompt=np.ones((plen,), np.int64),
                            max_new=4)
            eng.submit(req)
            eng.run()
            assert req.status is R.Status.FAILED
            assert req.status_detail == "bad_prompt"

    def test_prompt_at_max_len_minus_one_emits_one_token(self, setup):
        from repro.serving import resilience as R
        cfg, params = setup
        eng = self._engine(params, cfg)
        req = E.Request(rid=0, prompt=np.ones((eng.max_len - 1,), np.int64),
                        max_new=8)
        eng.submit(req)
        eng.run()
        # one row of headroom: exactly one token, then the cache is full
        assert len(req.generated) == 1
        assert req.status in (R.Status.OK, R.Status.CACHE_EXHAUSTED)

    def test_submit_when_queue_full_is_bounded_rejection(self, setup):
        from repro.serving import resilience as R
        cfg, params = setup
        eng = self._engine(params, cfg, queue_cap=1)
        reqs = [E.Request(rid=i, prompt=np.arange(1, 9), max_new=2)
                for i in range(3)]
        assert [eng.submit(r) for r in reqs] == [True, False, False]
        assert len(eng.queue) == 1  # bounded, not silent growth
        assert all(r.status is R.Status.FAILED
                   and r.status_detail == "queue_full" for r in reqs[1:])
        eng.run()
        assert reqs[0].status is R.Status.OK
