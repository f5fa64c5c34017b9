"""Jitted wrapper + analytic schedule model for the prefill-append kernel."""

from __future__ import annotations

import jax.numpy as jnp

from .. import _common as C
from .. import autotune
from .kernel import (prefill_append_kernel, prefill_append_kernel_quant,
                     prefill_append_paged_kernel,
                     prefill_append_paged_kernel_quant)


def prefill_append(
    q: jax.Array,        # [B, H, C, D] chunk queries (rope'd at offset..offset+C-1)
    k_new: jax.Array,    # [B, HK, C, D] chunk keys
    v_new: jax.Array,    # [B, HK, C, D]
    k_cache: jax.Array,  # [B, HK, M, D] batched cache (bf16/f32, or int8)
    v_cache: jax.Array,  # [B, HK, M, D]
    offset: jax.Array,   # [B] (or scalar) per-slot write base, ≡ 0 (mod C)
    *,
    k_scale: jax.Array | None = None,  # [B, HK, M] f32 (int8 cache only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    bkv: int | None = None,
    prefix_limit: int = 0,
    interpret=None,
):
    """Fused chunk prefill: attend to cache prefix + self, append K/V in place.

    Returns (out [B, H, C, D], k_cache', v_cache') — with ``k_scale`` /
    ``v_scale`` set (int8 cache, DESIGN.md §kv-cache) the tuple grows to
    (out, k_cache', v_cache', k_scale', v_scale'): the chunk's rows are
    quantized in VMEM at append time and the scale side arrays updated through
    their own aliased chunk windows. The cache length M must be a multiple of
    the chunk size C (the engine pads ``max_len`` accordingly); ``bkv`` is
    halved until it divides M so unaligned smoke caches still run.
    ``prefix_limit > 0`` marks offsets at/past it as *write-only* (the
    engine's trash-diverted slots): their prefix blocks all go dead instead
    of streaming the whole cache for an output nobody reads.
    """
    interpret = C.resolve_interpret(interpret)
    b, h, c, d = q.shape
    hk, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))

    if bkv is None:
        bkv = autotune.best(
            "prefill_append",
            autotune.shape_key(b=b, c=c, d=d, h=h, hk=hk, s=m),
            {"bkv": 128})["bkv"]
    bkv = min(bkv, m)
    while m % bkv:
        bkv //= 2

    qg = q.reshape(b, hk, g, c, d).reshape(b * hk, g * c, d)
    if k_scale is not None:
        out, k_cache, v_cache, k_scale, v_scale = prefill_append_kernel_quant(
            qg,
            k_new.reshape(b * hk, c, d),
            v_new.reshape(b * hk, c, d),
            k_cache.reshape(b * hk, m, d),
            v_cache.reshape(b * hk, m, d),
            k_scale.reshape(b * hk, 1, m).astype(jnp.float32),
            v_scale.reshape(b * hk, 1, m).astype(jnp.float32),
            offset,
            bkv=bkv, window=window, softcap=softcap, scale=scale,
            prefix_limit=prefix_limit, interpret=interpret,
        )
        return (
            out.reshape(b, hk, g, c, d).reshape(b, h, c, d),
            k_cache.reshape(b, hk, m, d),
            v_cache.reshape(b, hk, m, d),
            k_scale.reshape(b, hk, m),
            v_scale.reshape(b, hk, m),
        )
    out, k_cache, v_cache = prefill_append_kernel(
        qg,
        k_new.reshape(b * hk, c, d),
        v_new.reshape(b * hk, c, d),
        k_cache.reshape(b * hk, m, d),
        v_cache.reshape(b * hk, m, d),
        offset,
        bkv=bkv, window=window, softcap=softcap, scale=scale,
        prefix_limit=prefix_limit, interpret=interpret,
    )
    return (
        out.reshape(b, hk, g, c, d).reshape(b, h, c, d),
        k_cache.reshape(b, hk, m, d),
        v_cache.reshape(b, hk, m, d),
    )


def prefill_append_paged(
    q: jax.Array,           # [B, H, C, D] chunk queries (rope'd at offset..)
    k_new: jax.Array,       # [B, HK, C, D] chunk keys
    v_new: jax.Array,       # [B, HK, C, D]
    k_pool: jax.Array,      # [P, HK, ps, D] page pool (bf16, or int8 + scales)
    v_pool: jax.Array,      # [P, HK, ps, D]
    page_table: jax.Array,  # [B, NB] int32 (NB·ps = logical cache length)
    offset: jax.Array,      # [B] (or scalar) per-slot write base, ≡ 0 (mod C)
    *,
    k_scale: jax.Array | None = None,  # [P, HK, ps] f32 (int8 pool only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    bkv: int | None = None,
    prefix_limit: int = 0,
    interpret=None,
):
    """Page-indirect fused chunk prefill (DESIGN.md §paged-kv).

    Same contract as :func:`prefill_append` with the batched cache replaced
    by a page pool + per-slot page table: prefix blocks stream from pool rows
    through the frontier-skip index map, and the chunk appends through
    page-sized aliased pool windows at ``pt[slot, offset/ps + t]``. Requires
    ``C % page_size == 0`` (the engine enforces the divisibility chain via
    ``ServingConfig.kv_page_size``); the caller must have COW-resolved every
    written page to refcount 1 (``PagedKV.ensure_writable``) first. ``bkv``
    lives in the ``prefill_append.paged`` autotune namespace and is halved
    until it divides the page size.
    """
    interpret = C.resolve_interpret(interpret)
    b, h, c, d = q.shape
    p_pages, hk, ps = k_pool.shape[:3]
    nb = page_table.shape[1]
    g = h // hk
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    page_table = page_table.astype(jnp.int32)

    if bkv is None:
        bkv = autotune.best(
            "prefill_append.paged",
            autotune.shape_key(b=b, c=c, d=d, h=h, hk=hk, ps=ps, nb=nb),
            {"bkv": min(ps, 128)})["bkv"]
    bkv = min(bkv, ps)
    while ps % bkv:
        bkv //= 2

    qg = q.reshape(b, hk, g, c, d).reshape(b * hk, g * c, d)
    if k_scale is not None:
        out, k_pool, v_pool, k_scale, v_scale = prefill_append_paged_kernel_quant(
            qg,
            k_new.reshape(b * hk, c, d),
            v_new.reshape(b * hk, c, d),
            k_pool.reshape(p_pages * hk, ps, d),
            v_pool.reshape(p_pages * hk, ps, d),
            k_scale.reshape(p_pages * hk, 1, ps).astype(jnp.float32),
            v_scale.reshape(p_pages * hk, 1, ps).astype(jnp.float32),
            page_table, offset,
            bkv=bkv, window=window, softcap=softcap, scale=scale,
            prefix_limit=prefix_limit, interpret=interpret,
        )
        return (
            out.reshape(b, hk, g, c, d).reshape(b, h, c, d),
            k_pool.reshape(p_pages, hk, ps, d),
            v_pool.reshape(p_pages, hk, ps, d),
            k_scale.reshape(p_pages, hk, ps),
            v_scale.reshape(p_pages, hk, ps),
        )
    out, k_pool, v_pool = prefill_append_paged_kernel(
        qg,
        k_new.reshape(b * hk, c, d),
        v_new.reshape(b * hk, c, d),
        k_pool.reshape(p_pages * hk, ps, d),
        v_pool.reshape(p_pages * hk, ps, d),
        page_table, offset,
        bkv=bkv, window=window, softcap=softcap, scale=scale,
        prefix_limit=prefix_limit, interpret=interpret,
    )
    return (
        out.reshape(b, hk, g, c, d).reshape(b, h, c, d),
        k_pool.reshape(p_pages, hk, ps, d),
        v_pool.reshape(p_pages, hk, ps, d),
    )


def schedule_blocks(offsets, max_len: int, *, bkv: int = 128, window: int = 0):
    """Analytic kv-block counts for one chunk-append step (per slot·kv-head).

    Returns ``(live, dense)``: blocks the frontier-skipping schedule runs
    (live prefix blocks + the chunk step, which is one grid step whatever the
    chunk size) vs the dense schedule's ``ceil(max_len/bkv) + 1``. The
    prefill analogue of ``decode_attention.ops.schedule_blocks``.
    """
    import numpy as np

    offsets = np.atleast_1d(np.asarray(offsets))
    nkv = -(-max_len // bkv)
    dense = nkv + 1
    hi = -(-offsets // bkv)  # blocks with j*bkv < offset
    lo = np.zeros_like(hi)
    if window > 0:
        lo = np.minimum(np.maximum(offsets - window, 0) // bkv, hi)
    live = (hi - lo + 1).astype(np.int64)  # prefix blocks + the chunk step
    return int(live.sum()), int(dense * offsets.size)
