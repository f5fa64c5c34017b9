"""Jitted wrapper + analytic schedule model for the prefill-append kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import _common as C
from .. import autotune
from .kernel import (prefill_append_kernel, prefill_append_kernel_quant,
                     prefill_append_paged_kernel,
                     prefill_append_paged_kernel_quant)


def _write_chunk(cache, rows, offset, layer, axis):
    """Each slot's chunk rows into ``cache`` [L, B, HK, X, Y] at
    ``[layer, b, :, ...]``, starting at ``offset[b]`` on ``axis``: one
    ``dynamic_update_slice`` per slot, in place on whatever layout the cache
    is held in. ``rows`` [B, HK, ...] match the cache's trailing axes."""
    zero = jnp.int32(0)
    for i in range(rows.shape[0]):
        start = [layer, jnp.int32(i), zero, zero, zero]
        start[axis] = offset[i]
        cache = jax.lax.dynamic_update_slice(
            cache, rows[i][None, None].astype(cache.dtype), start)
    return cache


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
    layer: jax.Array | int | None = None,
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
    (out, k_cache', v_cache', k_scale', v_scale'): the kernel absmax-quantizes
    the chunk's rows in VMEM, attends to the dequantized quantized rows, as
    every later reader will, and hands them back for the cache. The kernel
    reads the prefix transposed, ``[.., D, M]``, the layout a TPU holds a
    ``[.., M, D]`` cache in when D is under 128 lanes, so the view moves
    nothing there; then the chunk's rows land in the cache in place, one
    update per slot and leaf. ``bkv`` is the largest multiple of 128 that
    divides M, or M whole (the cache is not padded).
    ``prefix_limit > 0`` marks offsets at/past it as *write-only* (the
    engine's trash-diverted slots): their prefix blocks all go dead instead
    of streaming the whole cache for an output nobody reads.

    With ``layer`` set the caches are every layer's, stacked (DESIGN.md
    §decode): k/v [L, B, HK, M, D], scales [L, B, HK, 1, M]. The chunk is
    written to and the prefix read from layer ``layer``, and the returned
    caches are the whole stack.
    """
    interpret = C.resolve_interpret(interpret)
    b, h, c, d = q.shape
    hk, m = k_cache.shape[-3], k_cache.shape[-2]
    g = h // hk
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    rows = k_cache.size // (m * d)  # L*B*HK
    li = jnp.asarray(0 if layer is None else layer, jnp.int32)
    quantized = k_scale is not None

    if bkv is None:
        bkv = autotune.best(
            "prefill_append",
            autotune.shape_key(b=b, c=c, d=d, h=h, hk=hk, s=m),
            {"bkv": 128})["bkv"]
    bkv = C.lane_block(m, bkv)

    # every leaf as [L, B, HK, X, Y], the chunk written along X (data) or Y
    kc = k_cache.reshape(-1, b, hk, m, d)
    vc = v_cache.reshape(-1, b, hk, m, d)

    def stack_t(x):  # [L, B, HK, M, D] -> [L*B*HK, D, M]
        return jnp.swapaxes(x, -1, -2).reshape(rows, d, m)

    args = (q.reshape(b, hk, g, c, d).reshape(b * hk, g * c, d),
            k_new.reshape(b * hk, c, d), v_new.reshape(b * hk, c, d),
            stack_t(kc), stack_t(vc))
    kw = dict(bkv=bkv, window=window, softcap=softcap, scale=scale,
              prefix_limit=prefix_limit, interpret=interpret)
    layer_op = li.reshape(1)
    # the kernel reads the prefix; the chunk's rows then land in place
    if quantized:
        ksc = k_scale.reshape(-1, b, hk, 1, m)
        vsc = v_scale.reshape(-1, b, hk, 1, m)
        out, kq, vq, ksq, vsq = prefill_append_kernel_quant(
            *args, ksc.reshape(rows, 1, m), vsc.reshape(rows, 1, m), offset,
            layer_op, **kw)
        kn, vn = kq.reshape(b, hk, c, d), vq.reshape(b, hk, c, d)
        ksc = _write_chunk(ksc, ksq.reshape(b, hk, 1, c), offset, li, 4)
        vsc = _write_chunk(vsc, vsq.reshape(b, hk, 1, c), offset, li, 4)
    else:
        out = prefill_append_kernel(*args, offset, layer_op, **kw)
        kn, vn = k_new, v_new
    kc = _write_chunk(kc, kn, offset, li, 3)
    vc = _write_chunk(vc, vn, offset, li, 3)
    caches = (kc.reshape(k_cache.shape), vc.reshape(v_cache.shape))
    if quantized:
        caches += (ksc.reshape(k_scale.shape), vsc.reshape(v_scale.shape))
    return (out.reshape(b, hk, g, c, d).reshape(b, h, c, d),) + caches


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
