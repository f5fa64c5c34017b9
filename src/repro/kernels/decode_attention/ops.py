"""Jitted wrapper + analytic schedule model for the decode attention kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import ternary
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
    layer: jax.Array | int | None = None,
    k_new: jax.Array | None = None,    # [B, HK, D] rows to write at pos
    v_new: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    bkv: int | None = None,
    interpret=None,
):
    """Fused decode attention; returns [B, H, D].

    Pads the cache length to a ``bkv`` multiple (padded keys sit past every
    slot's frontier, so the in-kernel mask discards them) and the GQA group to
    the 8-row sublane (padded q rows are sliced away). With ``k_scale`` /
    ``v_scale`` set the caches are int8 and dequantized per block in VMEM
    (DESIGN.md §kv-cache); padded scale rows are zero, which dequantizes to
    zero K/V — masked out like any past-frontier key. The kernel reads the
    cache transposed, ``[.., D, M]``: a TPU holds a ``[.., M, D]`` cache
    with D under 128 lanes in that layout, so the view moves nothing there.

    With ``layer`` set the caches are every layer's, stacked (DESIGN.md
    §decode): k/v [L, B, HK, M, D], scales [L, B, HK, 1, M], and the kernel
    reads layer ``layer`` through its index map. A stacked cache, or one
    written to, is never padded — that would copy it — so ``bkv`` is the
    largest multiple of 128 that divides M instead, or M whole (the block's
    lane axis). With ``k_new``/``v_new`` set (float rows,
    quantized here for an int8 cache as ``ternary.quantize_kv`` does) the
    kernel first writes each slot's row at ``pos``, in place, and the call
    returns ``(out, k_cache, v_cache[, k_scale, v_scale])``.
    """
    interpret = C.resolve_interpret(interpret)
    b, h, d = q.shape
    hk, m = k_cache.shape[-3], k_cache.shape[-2]
    g = h // hk
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    quantized = k_scale is not None
    shapes = (k_cache.shape, k_scale.shape if quantized else None)

    if bkv is None:
        # 256 rows a block: the kernel is bound by its cost per grid step,
        # not by bytes, so half the steps of 128 (DESIGN.md §decode)
        bkv = autotune.best(
            "decode_attention",
            autotune.shape_key(b=b, h=h, hk=hk, d=d, s=m),
            {"bkv": 256})["bkv"]
    if layer is not None or k_new is not None:
        bkv = C.lane_block(m, bkv)
    else:
        bkv = min(bkv, C.round_up(m, 128))
    mp = C.round_up(m, bkv)
    if mp != m:
        k_cache = C.pad_to(k_cache, -2, mp)
        v_cache = C.pad_to(v_cache, -2, mp)
        if quantized:
            k_scale = C.pad_to(k_scale, -1, mp)
            v_scale = C.pad_to(v_scale, -1, mp)
    rows = k_cache.size // (mp * d)  # L*B*HK
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer, jnp.int32),
                        (1,))

    def stack_t(x):  # [.., M, D] -> [L*B*HK, D, M]
        return jnp.swapaxes(x, -1, -2).reshape(rows, d, mp)

    def unstack_t(x):
        return jnp.swapaxes(x.reshape(shapes[0][:-2] + (d, mp)), -1, -2)

    gp = C.round_up(g, 8)  # sublane shape for the grouped-query block
    qg = q.reshape(b, hk, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    args = [qg.reshape(b * hk, gp, d), stack_t(k_cache), stack_t(v_cache)]
    new = None
    if k_new is not None:
        if quantized:
            (kq, ks), (vq, vs) = (ternary.quantize_kv(k_new),
                                  ternary.quantize_kv(v_new))
            new = (kq.reshape(b * hk, d, 1), vq.reshape(b * hk, d, 1),
                   ks.reshape(b * hk, 1, 1), vs.reshape(b * hk, 1, 1))
        else:
            new = tuple(x.astype(k_cache.dtype).reshape(b * hk, d, 1)
                        for x in (k_new, v_new))
    kw = dict(bkv=bkv, window=window, softcap=softcap, scale=scale,
              interpret=interpret)
    if quantized:
        res = decode_attention_kernel_quant(
            *args, k_scale.reshape(rows, 1, mp).astype(jnp.float32),
            v_scale.reshape(rows, 1, mp).astype(jnp.float32), pos, layer,
            new, **kw)
    else:
        res = decode_attention_kernel(*args, pos, layer, new, **kw)
    out = res[0] if new is not None else res
    out = out.reshape(b, hk, gp, d)[:, :, :g].reshape(b, h, d)
    if new is None:
        return out
    caches = (unstack_t(res[1]), unstack_t(res[2]))
    if quantized:
        caches += (res[3].reshape(shapes[1]), res[4].reshape(shapes[1]))
    return (out,) + caches


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
