"""Jitted wrapper + analytic schedule model for the decode attention kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import _common as C
from .. import autotune
from .kernel import (decode_attention_kernel, decode_attention_kernel_quant,
                     decode_attention_paged_kernel,
                     decode_attention_paged_kernel_quant)


def decode_attention(
    q: jax.Array,        # [B, H, D] single new token per slot
    k_cache: jax.Array,  # [B, HK, M, D] (bf16/f32, or int8 with scales)
    v_cache: jax.Array,  # [B, HK, M, D]
    pos: jax.Array,      # [B] (or scalar) attend-to-<=pos frontier
    *,
    k_scale: jax.Array | None = None,  # [B, HK, M] f32 (int8 cache only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    bkv: int | None = None,
    interpret=None,
) -> jax.Array:
    """Fused decode attention; returns [B, H, D].

    Pads the cache length to a ``bkv`` multiple (padded keys sit past every
    slot's frontier, so the in-kernel mask discards them) and the GQA group to
    the 8-row sublane (padded q rows are sliced away). With ``k_scale`` /
    ``v_scale`` set the caches are int8 and dequantized per block in VMEM
    (DESIGN.md §kv-cache); padded scale rows are zero, which dequantizes to
    zero K/V — masked out like any past-frontier key.
    """
    interpret = C.resolve_interpret(interpret)
    b, h, d = q.shape
    hk, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    quantized = k_scale is not None

    if bkv is None:
        bkv = autotune.best(
            "decode_attention",
            autotune.shape_key(b=b, h=h, hk=hk, d=d, s=m),
            {"bkv": 128})["bkv"]
    bkv = min(bkv, C.round_up(m, 128))
    mp = C.round_up(m, bkv)
    if mp != m:
        pad = ((0, 0), (0, 0), (0, mp - m), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        if quantized:
            spad = ((0, 0), (0, 0), (0, mp - m))
            k_scale = jnp.pad(k_scale, spad)
            v_scale = jnp.pad(v_scale, spad)

    gp = C.round_up(g, 8)  # sublane shape for the grouped-query block
    qg = q.reshape(b, hk, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    if quantized:
        out = decode_attention_kernel_quant(
            qg.reshape(b * hk, gp, d),
            k_cache.reshape(b * hk, mp, d),
            v_cache.reshape(b * hk, mp, d),
            k_scale.reshape(b * hk, 1, mp).astype(jnp.float32),
            v_scale.reshape(b * hk, 1, mp).astype(jnp.float32),
            pos,
            bkv=bkv, window=window, softcap=softcap, scale=scale,
            interpret=interpret,
        )
    else:
        out = decode_attention_kernel(
            qg.reshape(b * hk, gp, d),
            k_cache.reshape(b * hk, mp, d),
            v_cache.reshape(b * hk, mp, d),
            pos,
            bkv=bkv, window=window, softcap=softcap, scale=scale,
            interpret=interpret,
        )
    return out.reshape(b, hk, gp, d)[:, :, :g].reshape(b, h, d)


def decode_attention_paged(
    q: jax.Array,           # [B, H, D] single new token per slot
    k_pool: jax.Array,      # [P, HK, ps, D] page pool (bf16, or int8 + scales)
    v_pool: jax.Array,      # [P, HK, ps, D]
    page_table: jax.Array,  # [B, NB] int32 (NB·ps = logical cache length)
    pos: jax.Array,         # [B] attend-to-<=pos frontier
    *,
    k_scale: jax.Array | None = None,  # [P, HK, ps] f32 (int8 pool only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    bkv: int | None = None,
    interpret=None,
) -> jax.Array:
    """Page-indirect decode attention (DESIGN.md §paged-kv); returns [B, H, D].

    The contiguous kernel's frontier-skip schedule with its kv index map
    composed with a page-table lookup. ``bkv`` is tuned under its own
    ``decode_attention.paged`` autotune namespace (contiguous-tuned block
    sizes never leak in — they were measured against a different memory
    layout) and must divide the page size, so it is halved until it does.
    """
    interpret = C.resolve_interpret(interpret)
    b, h, d = q.shape
    p_pages, hk, ps = k_pool.shape[:3]
    nb = page_table.shape[1]
    g = h // hk
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    page_table = page_table.astype(jnp.int32)
    quantized = k_scale is not None

    if bkv is None:
        bkv = autotune.best(
            "decode_attention.paged",
            autotune.shape_key(b=b, h=h, hk=hk, d=d, ps=ps, nb=nb),
            {"bkv": min(ps, 128)})["bkv"]
    bkv = min(bkv, ps)
    while ps % bkv:
        bkv //= 2

    gp = C.round_up(g, 8)  # sublane shape for the grouped-query block
    qg = q.reshape(b, hk, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    if quantized:
        out = decode_attention_paged_kernel_quant(
            qg.reshape(b * hk, gp, d),
            k_pool.reshape(p_pages * hk, ps, d),
            v_pool.reshape(p_pages * hk, ps, d),
            k_scale.reshape(p_pages * hk, 1, ps).astype(jnp.float32),
            v_scale.reshape(p_pages * hk, 1, ps).astype(jnp.float32),
            page_table, pos,
            bkv=bkv, window=window, softcap=softcap, scale=scale,
            interpret=interpret,
        )
    else:
        out = decode_attention_paged_kernel(
            qg.reshape(b * hk, gp, d),
            k_pool.reshape(p_pages * hk, ps, d),
            v_pool.reshape(p_pages * hk, ps, d),
            page_table, pos,
            bkv=bkv, window=window, softcap=softcap, scale=scale,
            interpret=interpret,
        )
    return out.reshape(b, hk, gp, d)[:, :, :g].reshape(b, h, d)


def schedule_blocks(pos, max_len: int, *, bkv: int = 128, window: int = 0):
    """Analytic kv-block counts for one decode step (per slot·kv-head).

    Returns ``(live, dense)``: blocks the frontier-skipping schedule runs vs
    the dense schedule's ``ceil(max_len / bkv)``. This is the decode analogue
    of ``benchmarks.bench_attention_schedule.schedule_counts`` and what
    ``benchmarks/bench_decode.py`` reports.
    """
    import numpy as np

    pos = np.atleast_1d(np.asarray(pos))
    dense = -(-max_len // bkv)
    jmax = np.minimum(pos // bkv, dense - 1)
    jmin = np.zeros_like(jmax)
    if window > 0:
        jmin = np.maximum(pos - window + 1, 0) // bkv
    live = (jmax - jmin + 1).astype(np.int64)
    return int(live.sum()), int(dense * pos.size)
