"""Paged KV cache: allocator/COW invariants, kernel oracles, bit-identity.

Layers, matching the repo's testing convention (DESIGN.md §paged-kv):

* ``PageAllocator`` / ``PagedKV`` host bookkeeping — deterministic unit
  tests plus a hypothesis property test driving arbitrary
  admit/write/intern/release interleavings against a shadow refcount model:
  pages never leak, never double-free, refcounts return to zero at drain
  and the high-water mark matches the model.
* Page-indirect Pallas kernels (interpret mode) against the contiguous
  kernels run on the gathered dense view (``ternary.gather_kv_pages``) —
  the paged semantics ARE the contiguous semantics by construction.
* A scribble test: pages returned to the free list are bitwise-dead to
  every live slot (poisoning them changes no output).
* End-to-end ``ServingEngine`` bit-identity: ``kv_layout="paged"`` emits
  token streams identical to ``"contiguous"`` across cache dtypes and
  speculative decoding, including shared-prefix admissions that exercise
  the trie and COW forking.
* Autotune cache schema migration: v1 payloads are dropped wholesale; the
  paged kernel namespaces never read contiguous-tuned entries.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import params as P
from repro.core import ternary as T
from repro.kernels import autotune as AT
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.prefill_append import ops as pa_ops
from repro.models import transformer as Tr
from repro.serving import engine as E
from repro.serving.paging import PageAllocator, PagedKV, PagePoolExhausted

from _hypothesis_compat import given, settings, st

pytestmark = []


# ---------------------------------------------------------------------------
# Host bookkeeping: allocator + PagedKV unit tests
# ---------------------------------------------------------------------------


class TestPageAllocator:
    def test_alloc_free_cycle(self):
        a = PageAllocator(4)
        pages = [a.alloc() for _ in range(4)]
        assert sorted(pages) == [0, 1, 2, 3]
        assert a.used == 4 and a.high_water == 4
        with pytest.raises(PagePoolExhausted):
            a.alloc()
        assert a.deref(pages[0])
        assert a.used == 3
        assert a.alloc() == pages[0]  # LIFO reuse

    def test_refcount_sharing(self):
        a = PageAllocator(2)
        p = a.alloc()
        a.ref(p)
        assert not a.deref(p)  # still one holder
        assert a.deref(p)      # now freed
        assert a.used == 0

    def test_double_free_raises(self):
        a = PageAllocator(2)
        p = a.alloc()
        a.deref(p)
        with pytest.raises(ValueError, match="double free"):
            a.deref(p)

    def test_ref_of_free_page_raises(self):
        a = PageAllocator(2)
        with pytest.raises(ValueError, match="ref of free"):
            a.ref(1)


def _tokens(rng, n):
    return rng.integers(1, 1000, size=n)


class TestPagedKV:
    def _mk(self, *, slots=2, blocks=8, ps=4, num_pages=0, prefix=True):
        return PagedKV(slots=slots, cache_len=blocks * ps, page_size=ps,
                       num_pages=num_pages, prefix_cache=prefix)

    def test_fresh_alloc_no_copy(self):
        kv = self._mk()
        pairs = kv.ensure_writable(0, range(3))
        assert pairs == []  # unmapped -> fresh pages, writer fills them
        assert all(kv.table[0, b] != kv.garbage for b in range(3))
        # idempotent: exclusive blocks are a no-op (XLA-fallback retry safety)
        assert kv.ensure_writable(0, range(3)) == []

    def test_trash_blocks_stay_garbage(self):
        kv = self._mk(blocks=4)
        assert kv.ensure_writable(0, range(2, 8)) == []
        assert (kv.table[0, 2:] != kv.garbage).all()  # in-range mapped
        # out-of-range indices (engine trash region) were skipped silently

    def test_admit_tail_floors_to_chunk(self):
        kv = self._mk(ps=4)
        rng = np.random.default_rng(0)
        toks = _tokens(rng, 19)
        kv.ensure_writable(0, range(5))
        kv._tokens[0] = toks
        assert kv.insert_prefix(0) == 4  # 19 // 4 full pages interned
        # 16 matched tokens are already aligned to chunk granularity 8
        assert kv.admit(1, toks.copy(), chunk0=8) == 16
        # pages 0..3 mapped read-only into slot 1
        assert (kv.table[1, :4] == kv.table[0, :4]).all()
        kv.release(1)
        # coarser chunks floor the same match to 0 -> nothing is mapped
        assert kv.admit(1, toks.copy(), chunk0=32) == 0
        assert (kv.table[1] == kv.garbage).all()
        kv.release(1)
        # an unmatched prompt maps nothing either
        assert kv.admit(1, _tokens(rng, 19), chunk0=8) == 0

    def test_full_hit_keeps_last_token(self):
        """A fully interned prompt still re-prefills >= the final chunk:
        its last-token logits seed decoding."""
        kv = self._mk(ps=4)
        toks = _tokens(np.random.default_rng(1), 16)
        kv.ensure_writable(0, range(4))
        kv._tokens[0] = toks
        kv.insert_prefix(0)
        tail = kv.admit(1, toks.copy(), chunk0=4)
        assert tail == 12  # min(matched=16, len-1=15) floored to 12

    def test_cow_fork_on_shared_write(self):
        kv = self._mk(ps=4)
        rng = np.random.default_rng(2)
        toks = _tokens(rng, 16)
        kv.ensure_writable(0, range(4))
        kv._tokens[0] = toks
        kv.insert_prefix(0)
        kv.admit(1, toks.copy(), chunk0=4)  # maps pages 0..3, tail at 12
        shared = int(kv.table[1, 3])
        pairs = kv.ensure_writable(1, [3])  # tail chunk rewrites block 3
        assert len(pairs) == 1 and pairs[0][0] == shared
        assert kv.table[1, 3] == pairs[0][1] != shared
        assert kv.cow_forks == 1
        assert kv.allocator.refs[shared] >= 1  # original holders keep it

    def test_release_returns_pages_trie_pins_survive(self):
        kv = self._mk(ps=4)
        toks = _tokens(np.random.default_rng(3), 16)
        kv.ensure_writable(0, range(5))
        kv._tokens[0] = toks
        kv.insert_prefix(0)
        used_before = kv.allocator.used
        kv.release(0)
        # the 4 interned pages stay pinned; the 5th (partial) page freed
        assert kv.allocator.used == used_before - 1
        assert (kv.table[0] == kv.garbage).all()
        # trie content still matches a new admission
        assert kv.admit(1, toks.copy(), chunk0=4) == 12

    def test_eviction_backs_pool_pressure(self):
        kv = self._mk(slots=2, blocks=4, ps=4, num_pages=6)  # garbage + 5
        toks = _tokens(np.random.default_rng(4), 8)
        kv.ensure_writable(0, range(2))
        kv._tokens[0] = toks
        kv.insert_prefix(0)
        kv.release(0)  # 2 pages remain, pinned by the trie only
        kv.ensure_writable(1, range(4))  # needs 4: evicts trie leaves
        assert kv.evictions >= 1
        with pytest.raises(PagePoolExhausted):
            kv.ensure_writable(0, range(2))

    def test_prefix_cache_off(self):
        kv = self._mk(prefix=False)
        toks = _tokens(np.random.default_rng(5), 16)
        kv.ensure_writable(0, range(4))
        kv._tokens[0] = toks
        assert kv.insert_prefix(0) == 0
        assert kv.admit(1, toks.copy(), chunk0=4) == 0
        assert kv.stats()["prefix_queries"] == 0

    def test_stats_shape(self):
        st_ = self._mk().stats()
        for key in ("num_pages", "pages_used", "high_water", "utilization",
                    "trie_pages", "prefix_hit_rate", "cow_forks",
                    "evictions", "prefix_hit_tokens"):
            assert key in st_


# ---------------------------------------------------------------------------
# Property test: arbitrary interleavings never corrupt the pool
# ---------------------------------------------------------------------------


def _trie_pins(trie):
    """page -> number of trie pins (one per node holding that page)."""
    pins: dict[int, int] = {}

    def walk(level):
        for node in level.values():
            pins[node.page] = pins.get(node.page, 0) + 1
            walk(node.children)

    walk(trie.root)
    return pins


def _check_invariants(kv: PagedKV):
    a = kv.allocator
    # conservation: a page is free xor referenced
    assert len(a.free_list) + int((a.refs > 0).sum()) == a.num_pages
    assert all(a.refs[p] == 0 for p in a.free_list)
    assert len(set(a.free_list)) == len(a.free_list)  # no double entry
    # exact refcount accounting: slots' table entries + trie pins (+ the
    # permanent garbage self-reference) explain every count
    pins = _trie_pins(kv.trie)
    for p in range(a.num_pages):
        want = int((kv.table == p).sum()) + pins.get(p, 0)
        if p == kv.garbage:
            # garbage table entries hold no reference; only the permanent one
            assert a.refs[p] == 1
        else:
            assert a.refs[p] == want, f"page {p}: refs {a.refs[p]} != {want}"
    assert a.high_water <= a.num_pages


def _drive_interleaving(ops):
    """Run an op sequence against PagedKV, checking pool invariants after
    every op and a full drain at the end. Shared by the hypothesis property
    test and its deterministic fallback."""
    kv = PagedKV(slots=3, cache_len=40, page_size=4, num_pages=20)
    rng = np.random.default_rng(0)
    families = [_tokens(rng, 41) for _ in range(4)]
    active: dict[int, np.ndarray] = {}
    peak = kv.allocator.used
    for op, slot, fam, n in ops:
        try:
            if op == "admit" and slot not in active:
                toks = families[fam][:4 * n + fam]  # ragged lengths
                kv.admit(slot, toks, chunk0=8)
                active[slot] = toks
            elif op == "write" and slot in active:
                pairs = kv.ensure_writable(slot, range(n))
                # COW contract: dsts are fresh + exclusive
                dsts = [d for _, d in pairs]
                assert len(set(dsts)) == len(dsts)
                for d in dsts:
                    assert kv.allocator.refs[d] == 1
            elif op == "intern" and slot in active:
                kv.insert_prefix(slot)
            elif op == "release" and slot in active:
                kv.release(slot)
                del active[slot]
        except PagePoolExhausted:
            # engine contract: the requester is shed and released
            kv.release(slot)
            active.pop(slot, None)
        peak = max(peak, kv.allocator.used)
        _check_invariants(kv)
    assert kv.allocator.high_water == peak
    # drain: releasing every slot + evicting the trie empties the pool
    for slot in list(active):
        kv.release(slot)
    while kv.trie.evict_lru():
        pass
    _check_invariants(kv)
    assert kv.allocator.used == 1  # only the garbage page
    assert (kv.table == kv.garbage).all()


class TestPagedKVProperty:
    @pytest.mark.slow
    @settings(max_examples=60, deadline=None)
    @given(st.lists(
        st.tuples(st.sampled_from(["admit", "write", "intern", "release"]),
                  st.integers(0, 2),      # slot
                  st.integers(0, 3),      # prompt family (shared prefixes)
                  st.integers(1, 10)),    # length / block count
        min_size=1, max_size=40))
    def test_interleavings_never_leak(self, ops):
        _drive_interleaving(ops)

    def test_fixed_interleavings(self):
        """Deterministic twin of the property test (hypothesis optional):
        2000 seeded random ops through the same invariant checker."""
        rng = np.random.default_rng(42)
        names = ["admit", "write", "intern", "release"]
        for seed in range(8):
            ops = [(names[rng.integers(4)], int(rng.integers(3)),
                    int(rng.integers(4)), int(rng.integers(1, 11)))
                   for _ in range(250)]
            _drive_interleaving(ops)


# ---------------------------------------------------------------------------
# Kernel oracles: paged (interpret) == contiguous on the gathered dense view
# ---------------------------------------------------------------------------


def _pool_setup(b, hk, ps, nb, d, key=0, dtype=jnp.float32):
    """Random pool + permutation page table (page 0 = garbage, unmapped)."""
    pages = b * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    k_pool = jax.random.normal(ks[0], (pages, hk, ps, d), dtype)
    v_pool = jax.random.normal(ks[1], (pages, hk, ps, d), dtype)
    perm = jax.random.permutation(ks[2], b * nb) + 1  # never the garbage page
    table = perm.reshape(b, nb).astype(jnp.int32)
    return k_pool, v_pool, table


class TestPagedKernelOracles:
    @pytest.mark.parametrize("b,h,hk,d,ps,nb", [(2, 8, 2, 32, 64, 4),
                                                (1, 4, 4, 64, 128, 2)])
    def test_decode_matches_contiguous(self, b, h, hk, d, ps, nb):
        k_pool, v_pool, table = _pool_setup(b, hk, ps, nb, d, key=ps)
        q = jax.random.normal(jax.random.PRNGKey(1), (b, h, d))
        pos = jax.random.randint(jax.random.PRNGKey(2), (b,), 0, nb * ps)
        got = da_ops.decode_attention_paged(q, k_pool, v_pool, table, pos,
                                            interpret=True)
        want = da_ops.decode_attention(
            q, T.gather_kv_pages(k_pool, table),
            T.gather_kv_pages(v_pool, table), pos, interpret=True)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-3, atol=2e-3)

    def test_decode_int8_matches_contiguous(self):
        b, h, hk, d, ps, nb = 2, 4, 2, 32, 64, 4
        k_pool, v_pool, table = _pool_setup(b, hk, ps, nb, d, key=3)
        kq, ks_ = T.quantize_kv(k_pool)
        vq, vs_ = T.quantize_kv(v_pool)
        q = jax.random.normal(jax.random.PRNGKey(4), (b, h, d))
        pos = jnp.array([ps * nb - 1, 17], jnp.int32)
        got = da_ops.decode_attention_paged(
            q, kq, vq, table, pos, k_scale=ks_, v_scale=vs_, interpret=True)
        want = da_ops.decode_attention(
            q, T.gather_kv_pages(kq, table), T.gather_kv_pages(vq, table),
            pos, k_scale=T.gather_kv_pages(ks_, table),
            v_scale=T.gather_kv_pages(vs_, table), interpret=True)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("c", [64, 128])
    def test_prefill_matches_contiguous(self, c):
        b, h, hk, d, ps, nb = 2, 4, 2, 32, 64, 4
        k_pool, v_pool, table = _pool_setup(b, hk, ps, nb, d, key=c)
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (b, h, c, d))
        k_new = jax.random.normal(ks[1], (b, hk, c, d))
        v_new = jax.random.normal(ks[2], (b, hk, c, d))
        off = jnp.array([c, 0], jnp.int32)  # chunk-aligned frontiers
        k_dense = T.gather_kv_pages(k_pool, table)
        v_dense = T.gather_kv_pages(v_pool, table)
        got, kp, vp = pa_ops.prefill_append_paged(
            q, k_new, v_new, k_pool, v_pool, table, off, interpret=True)
        want, kc, vc = pa_ops.prefill_append(
            q, k_new, v_new, k_dense, v_dense, off, interpret=True)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-3, atol=2e-3)
        # append semantics: the gathered pool equals the contiguous cache
        np.testing.assert_allclose(np.array(T.gather_kv_pages(kp, table)),
                                   np.array(kc), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.array(T.gather_kv_pages(vp, table)),
                                   np.array(vc), rtol=1e-6, atol=1e-6)

    def test_prefill_int8_matches_contiguous(self):
        b, h, hk, d, ps, nb, c = 1, 4, 2, 32, 64, 4, 128
        k_pool, v_pool, table = _pool_setup(b, hk, ps, nb, d, key=7)
        kq, ks_ = T.quantize_kv(k_pool)
        vq, vs_ = T.quantize_kv(v_pool)
        ks2 = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks2[0], (b, h, c, d))
        k_new = jax.random.normal(ks2[1], (b, hk, c, d))
        v_new = jax.random.normal(ks2[2], (b, hk, c, d))
        off = jnp.array([c], jnp.int32)
        got, kp, vp, ksp, vsp = pa_ops.prefill_append_paged(
            q, k_new, v_new, kq, vq, table, off,
            k_scale=ks_, v_scale=vs_, interpret=True)
        want, kc, vc, ksc, vsc = pa_ops.prefill_append(
            q, k_new, v_new, T.gather_kv_pages(kq, table),
            T.gather_kv_pages(vq, table), off,
            k_scale=T.gather_kv_pages(ks_, table),
            v_scale=T.gather_kv_pages(vs_, table), interpret=True)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=2e-3, atol=2e-3)
        # cache bytes are exact: same quantizer on the same rows
        np.testing.assert_array_equal(
            np.array(T.gather_kv_pages(kp, table)), np.array(kc))
        np.testing.assert_array_equal(
            np.array(T.gather_kv_pages(vp, table)), np.array(vc))
        np.testing.assert_allclose(
            np.array(T.gather_kv_pages(ksp, table)), np.array(ksc),
            rtol=1e-6, atol=1e-6)

    def test_prefill_int8_page_sized_chunk_matches_contiguous(self):
        # the engine's narrowest chunk is one page: one (1, 1, ps) scale
        # window per grid step, GQA, two slots at different frontiers
        b, h, hk, d, ps, nb, c = 2, 8, 2, 32, 64, 4, 64
        k_pool, v_pool, table = _pool_setup(b, hk, ps, nb, d, key=9)
        kq, ks_ = T.quantize_kv(k_pool)
        vq, vs_ = T.quantize_kv(v_pool)
        ks2 = jax.random.split(jax.random.PRNGKey(10), 3)
        q = jax.random.normal(ks2[0], (b, h, c, d))
        k_new = jax.random.normal(ks2[1], (b, hk, c, d))
        v_new = jax.random.normal(ks2[2], (b, hk, c, d))
        off = jnp.array([c, 3 * c], jnp.int32)
        got = pa_ops.prefill_append_paged(
            q, k_new, v_new, kq, vq, table, off,
            k_scale=ks_, v_scale=vs_, interpret=True)
        want = pa_ops.prefill_append(
            q, k_new, v_new, T.gather_kv_pages(kq, table),
            T.gather_kv_pages(vq, table), off,
            k_scale=T.gather_kv_pages(ks_, table),
            v_scale=T.gather_kv_pages(vs_, table), interpret=True)
        np.testing.assert_allclose(np.array(got[0]), np.array(want[0]),
                                   rtol=2e-3, atol=2e-3)
        for pool, dense in zip(got[1:], want[1:]):
            np.testing.assert_array_equal(
                np.array(T.gather_kv_pages(pool, table)), np.array(dense))


# ---------------------------------------------------------------------------
# End-to-end engine bit-identity + prefix reuse + scribble
# ---------------------------------------------------------------------------


def _cfg(arch="tellme-0.7b", **kw):
    return dataclasses.replace(get_config(arch, smoke=True),
                               dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def smoke_setup():
    cfg = _cfg()
    params = P.init_params(Tr.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _run_engine(params, cfg, prompts, *, max_new=4, max_len=128, slots=2,
                sequential_first=False, **ekw):
    eng = E.ServingEngine(params, cfg, mode="eval", eos_id=-2, slots=slots,
                          max_len=max_len, **ekw)
    reqs = [E.Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    if sequential_first:
        eng.submit(reqs[0])
        eng.run()
        reqs_rest = reqs[1:]
    else:
        reqs_rest = reqs
    for r in reqs_rest:
        eng.submit(r)
    eng.run()
    return eng, [r.generated for r in reqs]


class TestEngineBitIdentity:
    @pytest.mark.parametrize("kv_dtype,spec", [
        ("bf16", False), ("int8", False), ("bf16", True), ("int8", True)])
    def test_paged_equals_contiguous(self, smoke_setup, kv_dtype, spec):
        cfg, params = smoke_setup
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, cfg.vocab_size, size=n)
                   for n in (5, 37, 64, 70)]
        _, out_c = _run_engine(params, cfg, prompts, speculative=spec)
        cfg_p = dataclasses.replace(cfg, kv_layout="paged")
        eng_p, out_p = _run_engine(params, cfg_p, prompts, speculative=spec)
        assert out_c == out_p
        assert eng_p.stats()["kv_layout"] == "paged"
        assert eng_p.stats()["paged"]["pages_used"] >= 1

    def test_paged_requires_chunked_prefill(self, smoke_setup):
        cfg, params = smoke_setup
        bad = dataclasses.replace(cfg, kv_layout="paged")
        with pytest.raises(ValueError, match="chunked"):
            E.ServingEngine(params, bad, mode="eval", slots=1, max_len=64,
                            prefill="legacy")


class TestPrefixReuse:
    @pytest.fixture(scope="class")
    def shared_prefix_runs(self, smoke_setup):
        cfg, params = smoke_setup
        rng = np.random.default_rng(11)
        prefix = rng.integers(1, cfg.vocab_size, size=320)
        prompts = [np.concatenate([prefix, rng.integers(
            1, cfg.vocab_size, size=n)]) for n in (32, 17, 8)]
        _, out_c = _run_engine(params, cfg, prompts, max_len=512,
                               sequential_first=True)
        cfg_p = dataclasses.replace(cfg, kv_layout="paged")
        eng_p, out_p = _run_engine(params, cfg_p, prompts, max_len=512,
                                   sequential_first=True)
        return eng_p, out_c, out_p

    def test_streams_identical(self, shared_prefix_runs):
        _, out_c, out_p = shared_prefix_runs
        assert out_c == out_p

    def test_prefix_hits_and_cow(self, shared_prefix_runs):
        eng_p, _, _ = shared_prefix_runs
        st_ = eng_p.stats()["paged"]
        assert st_["prefix_hits"] == 2       # both followers hit
        assert st_["prefix_hit_tokens"] >= 2 * 256  # cmax-floored prefix
        assert st_["cow_forks"] >= 1         # tail rewrites the shared page
        assert st_["prefix_hit_rate"] > 0

    def test_events_emitted(self, shared_prefix_runs):
        eng_p, _, _ = shared_prefix_runs
        kinds = {e["kind"] for e in eng_p.events}
        assert "prefix_hit" in kinds and "cow_fork" in kinds


class TestScribble:
    def test_freed_pages_are_bitwise_dead(self, smoke_setup):
        """Poisoning every free page between runs changes no output: freed
        pages are unreachable through any live table and fresh allocations
        are fully written before any un-masked read."""
        cfg, params = smoke_setup
        rng = np.random.default_rng(13)
        prefix = rng.integers(1, cfg.vocab_size, size=320)
        prompts = [np.concatenate([prefix, rng.integers(
            1, cfg.vocab_size, size=n)]) for n in (32, 17)]
        _, out_ref = _run_engine(params, cfg, prompts, max_len=512,
                                 sequential_first=True)
        cfg_p = dataclasses.replace(cfg, kv_layout="paged")
        eng = E.ServingEngine(params, cfg_p, mode="eval", eos_id=-2,
                              slots=2, max_len=512)
        reqs = [E.Request(rid=i, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]
        eng.submit(reqs[0])
        eng.run()

        free = jnp.asarray(np.array(eng.paged.free_pages(), np.int32))
        axes_tree = Tr.cache_specs(cfg_p, 1, 1, kv_pages=1)[1]

        def poison(caches):
            def rec(c, a):
                if isinstance(c, dict):
                    return {k: rec(c[k], a[k]) for k in c}
                if "kv_pages" not in a:
                    return c
                bad = 113 if c.dtype == jnp.int8 else 3.0e4
                return c.at[free].set(jnp.asarray(bad, c.dtype))

            return rec(caches, axes_tree)

        assert int(free.shape[0]) > 0
        eng.caches = jax.jit(poison, donate_argnums=(0,))(eng.caches)

        eng.submit(reqs[1])
        eng.run()
        assert [r.generated for r in reqs] == out_ref


# ---------------------------------------------------------------------------
# Autotune cache schema migration
# ---------------------------------------------------------------------------


@pytest.fixture
def isolated_cache(tmp_path):
    AT.set_cache_path(tmp_path / "autotune.json")
    yield tmp_path / "autotune.json"
    AT.set_cache_path(None)


class TestAutotuneMigration:
    @pytest.mark.parametrize("payload", [
        # v1 schema: pre-paged layout; its entries were measured against a
        # different memory layout and must be dropped wholesale
        {"version": 1, "device": "cpu",
         "kernels": {"decode_attention": {"b=1": {"knobs": {"bkv": 512},
                                                  "us": 1.0}}}},
        # corrupt / foreign payloads degrade to an empty cache
        {"version": "x"},
        [1, 2, 3],
    ])
    def test_stale_payload_dropped(self, isolated_cache, payload):
        isolated_cache.write_text(json.dumps(payload))
        AT.set_cache_path(isolated_cache)  # force reload
        assert AT.lookup("decode_attention", "b=1") is None
        # and the rewritten file carries the current version
        AT.record("decode_attention", "b=1", {"bkv": 128}, 2.0)
        on_disk = json.loads(isolated_cache.read_text())
        assert on_disk["version"] == AT._VERSION

    def test_current_payload_survives(self, isolated_cache):
        AT.record("decode_attention.paged", "ps=64,nb=4", {"bkv": 64}, 1.0)
        AT.set_cache_path(isolated_cache)  # reload from disk
        assert AT.lookup("decode_attention.paged",
                         "ps=64,nb=4") == {"bkv": 64}

    def test_paged_namespace_isolated(self, isolated_cache):
        """A contiguous-tuned entry never answers a paged lookup: the paged
        namespaces key on (ps, nb) under their own kernel name."""
        AT.record("decode_attention", "b=2,d=32,h=4,hk=2,s=256",
                  {"bkv": 256}, 1.0)
        key = AT.shape_key(b=2, h=4, hk=2, d=32, ps=64, nb=4)
        assert AT.lookup("decode_attention.paged", key) is None
        assert AT.best("decode_attention.paged", key,
                       {"bkv": 64}) == {"bkv": 64}

    def test_paged_smoke_shapes_registered(self):
        assert "decode_attention.paged" in AT.SMOKE_SHAPES
        assert "prefill_append.paged" in AT.SMOKE_SHAPES
