"""Pallas TPU kernel: chunked cache-append prefill attention.

The prefill twin of ``kernels/decode_attention`` (DESIGN.md §prefill). One
grid step owns (slot·kv-head, kv-block); a q-chunk of ``C`` tokens at
absolute positions ``offset .. offset+C-1`` attends to the slot's existing
KV-cache *prefix* (positions ``< offset``) plus itself. The wrapper writes the
chunk's K/V into the batched cache at the slot's offset first, in place
(``ops.prefill_append``) — per-request caches are never materialized or
host-scattered — and the contiguous kernel only reads the cache, transposed
(``[.., D, M]``: the layout a TPU holds it in when D is under 128 lanes).

Schedule, mirroring the paper's reversed-reorder saving (§III-B) mapped onto
a cache prefix:

  * the per-slot ``offset`` vector is scalar-prefetched into SMEM; prefix
    kv-blocks past the slot's frontier (``j·bkv >= offset``) are skipped via
    ``pl.when`` — chunk cost tracks the *live* prefix length, not the padded
    ``max_len`` — and the k/v ``index_map`` clamps skipped block indices into
    the live range so they also move no HBM traffic;
  * the chunk's own K/V ride in VMEM as separate operands (C ≤ 256): the last
    grid step attends causally within the chunk — the lower-triangular half
    only, same work shape as the flash kernel's diagonal block. The paged
    kernel stores the chunk through aliased page windows instead (the engine
    keeps ``offset ≡ 0 (mod C)``).

GQA uses the same index-map trick as the decode kernel: q is pre-grouped to
[B·HK, G·C, D] so the G query heads sharing a kv head contract against one
streamed k/v block; the causal mask depends on the row's intra-chunk index
``row % C`` only.

**Int8 cache path** (DESIGN.md §kv-cache): with ``quantized=True`` the cache
operands are int8 with per-row f32 scale side arrays [B·HK, 1, M] (the unit
axis keeps a (1, bkv) scale block legal under the TPU's (8, 128) tiling
rule), streamed by the same clamped index map (skipped prefix blocks move no
scale bytes either). The contiguous kernel takes the chunk's rows quantized,
with their scales, as the cache holds them; the paged kernel quantizes them
*in VMEM* and appends them through its aliased page windows. Either way the
chunk's self-attention runs on the dequantized quantized rows, so the chunk
sees exactly the K/V every later reader will.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import ternary
from .. import _common as C

_NEG_INF = -1e30


def _kernel(
    off_ref, layer_ref, q_ref, kn_ref, vn_ref, kc_ref, vc_ref, *rest,
    scale: float, bkv: int, c: int, window: int, softcap: float,
    nkv: int, hk: int, prefix_limit: int, quantized: bool = False,
):
    del layer_ref  # consumed by the index maps only
    if quantized:
        (ks_ref, vs_ref, o_ref, kq_ref, vq_ref, ksq_ref, vsq_ref,
         acc_ref, m_ref, l_ref) = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    bh = pl.program_id(0)
    j = pl.program_id(1)
    off = off_ref[bh // hk]  # this slot's cache frontier (chunk write base)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = q_ref.shape[1]  # G*C
    # intra-chunk index of each grouped-q row (row = g*C + i)
    def _row_i(cols):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) % c

    def _online_update(s, kpos, v, v_key_axis):
        qpos = off + _row_i(s.shape[1])
        mask = kpos <= qpos
        if window > 0:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (v_key_axis,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # --- prefix phase: frontier-skipped kv blocks of the existing cache -----
    live = jnp.logical_and(j < nkv, j * bkv < off)
    if prefix_limit > 0:
        # slots diverted into the trash tail (off >= prefix_limit) are
        # write-only: their prefix scan is dead, not a full-cache stream
        live = jnp.logical_and(live, off < prefix_limit)
    if window > 0:
        # lowest prefix position any chunk row attends is off - window + 1
        live = jnp.logical_and(live, (j + 1) * bkv - 1 >= off - window + 1)

    @pl.when(live)
    def _prefix():
        q = q_ref[0]  # [G*C, D]
        k = kc_ref[0]  # [D, bkv]: the cache is read transposed
        v = vc_ref[0]
        if quantized:
            # in-VMEM dequant right before the QK matmul (§kv-cache); one
            # cache row per lane, as its [1, bkv] scales
            k = ternary.dequantize_rows(k, ks_ref[0], q_ref.dtype)
            v = ternary.dequantize_rows(v, vs_ref[0], q_ref.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # prefix keys only: positions >= off belong to the chunk phase
        kpos = jnp.where(kpos < off, kpos, jnp.int32(2**30))
        _online_update(s, kpos, v, 1)

    # --- chunk phase: causal self-attention ---------------------------------
    @pl.when(j == nkv)
    def _chunk():
        q = q_ref[0]
        kn = kn_ref[0]  # [C, D]
        vn = vn_ref[0]
        if quantized:
            # the fused QDQ unit: quantize the chunk's rows in VMEM, hand
            # them back for the cache, and attend to the *dequantized* rows —
            # the chunk sees exactly the K/V every later reader will
            # (``quantize_kv`` semantics, with the scales kept as columns)
            kq, ks_n = ternary.quantize_act(kn)
            vq, vs_n = ternary.quantize_act(vn)
            kq_ref[0], vq_ref[0], ksq_ref[0], vsq_ref[0] = kq, vq, ks_n, vs_n
            kn = ternary.dequantize_rows(kq, ks_n, q_ref.dtype)
            vn = ternary.dequantize_rows(vq, vs_n, q_ref.dtype)
        s = jax.lax.dot_general(
            q, kn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_update(s, kpos, vn, 0)

        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _call(q, k_new, v_new, k_cache, v_cache, offset, layer, scales, *,
          bkv, window, softcap, scale, prefix_limit, interpret):
    """Shared pallas_call builder for the dense and int8-cache paths.

    ``k_cache``/``v_cache`` are [L*B*HK, D, M]: every layer's cache,
    stacked, and seen transposed — the layout a TPU holds a ``[.., M, D]``
    cache in when D is under 128 lanes, so the view is free — of which
    ``layer`` ([1] int32, a second scalar-prefetch operand) is read: the
    index maps offset each row by ``layer * B*HK``. A single layer's cache
    is the case L = 1, ``layer`` 0. The kernel only reads the cache; the
    chunk's rows, ``k_new``/``v_new`` [B*HK, C, D], ride in as operands.
    ``scales`` is ``None`` (dense) or ``(k_scale, v_scale)``, the cache's
    [L*B*HK, 1, M] f32 per-row scales of an int8 cache; the call then also
    returns the chunk's rows quantized, ``(out, k_q, v_q, k_q_scale,
    v_q_scale)`` ([B*HK, C, D] int8, [B*HK, C, 1] f32), for the cache."""
    bhk, gc, d = q.shape
    c = k_new.shape[1]
    m = k_cache.shape[2]
    b = offset.shape[0]
    hk = bhk // b
    assert m % bkv == 0, (m, bkv)
    assert gc % c == 0, (gc, c)
    scale = scale if scale is not None else 1.0 / d**0.5
    nkv = m // bkv
    quantized = scales is not None

    kern = functools.partial(
        _kernel, scale=scale, bkv=bkv, c=c, window=window, softcap=softcap,
        nkv=nkv, hk=hk, prefix_limit=prefix_limit, quantized=quantized,
    )

    def live_j(bh, j, off_ref):
        # Clamp skipped prefix indices into the live [window-foot, frontier]
        # range: a repeated block index is never re-fetched by the pipeline,
        # so skipped blocks move no HBM traffic. The chunk step (j == nkv)
        # also lands on the frontier block (fetched but unused).
        off = off_ref[bh // hk]
        hi = jnp.maximum(off - 1, 0) // bkv
        lo = jnp.maximum(off - window, 0) // bkv if window > 0 else 0
        return jnp.clip(j, lo, hi)

    def kv_index(bh, j, off_ref, layer_ref):
        # k/v blocks and their scales: the same clamped schedule
        return (layer_ref[0] * bhk + bh, 0, live_j(bh, j, off_ref))

    def slot_index(bh, j, off_ref, layer_ref):
        return (bh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, gc, d), slot_index),
        pl.BlockSpec((1, c, d), slot_index),
        pl.BlockSpec((1, c, d), slot_index),
        pl.BlockSpec((1, d, bkv), kv_index),
        pl.BlockSpec((1, d, bkv), kv_index),
    ]
    operands = [offset, layer, q, k_new, v_new, k_cache, v_cache]
    out_specs = [pl.BlockSpec((1, gc, d), slot_index)]
    out_shape = [jax.ShapeDtypeStruct((bhk, gc, d), q.dtype)]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bkv), kv_index)] * 2
        operands += list(scales)
        out_specs += [pl.BlockSpec((1, c, d), slot_index)] * 2
        out_specs += [pl.BlockSpec((1, c, 1), slot_index)] * 2
        out_shape += [jax.ShapeDtypeStruct((bhk, c, d), jnp.int8)] * 2
        out_shape += [jax.ShapeDtypeStruct((bhk, c, 1), jnp.float32)] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhk, nkv + 1),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((gc, d), jnp.float32),
            pltpu.VMEM((gc,), jnp.float32),
            pltpu.VMEM((gc,), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return tuple(outs) if quantized else outs[0]


def _paged_kernel(
    off_ref, pt_ref, q_ref, kn_ref, vn_ref, kc_ref, vc_ref, *rest,
    scale: float, bkv: int, c: int, ps: int, window: int, softcap: float,
    nkv: int, npg: int, hk: int, prefix_limit: int, quantized: bool = False,
):
    """Page-indirect twin of :func:`_kernel` (DESIGN.md §paged-kv).

    Prefix phase identical (kv blocks arrive from pool rows via the index
    map; logical positions are still ``j*bkv + iota``). The chunk phase
    splits into ``npg = C / page_size`` grid steps — one per chunk page — so
    each aliased output window is exactly one pool page row, addressed at
    ``pt[slot, off/ps + t]``; the causal mask orders the sub-steps' online
    updates exactly like one fused chunk step."""
    if quantized:
        (ks_ref, vs_ref, o_ref, ko_ref, vo_ref, kso_ref, vso_ref,
         acc_ref, m_ref, l_ref) = rest
    else:
        o_ref, ko_ref, vo_ref, acc_ref, m_ref, l_ref = rest
    del pt_ref  # consumed by the index maps only
    bh = pl.program_id(0)
    j = pl.program_id(1)
    off = off_ref[bh // hk]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = q_ref.shape[1]  # G*C

    def _row_i(cols):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) % c

    def _online_update(s, kpos, v):
        qpos = off + _row_i(s.shape[1])
        mask = kpos <= qpos
        if window > 0:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # --- prefix phase: frontier-skipped pool pages of the existing cache ----
    live = jnp.logical_and(j < nkv, j * bkv < off)
    if prefix_limit > 0:
        live = jnp.logical_and(live, off < prefix_limit)
    if window > 0:
        live = jnp.logical_and(live, (j + 1) * bkv - 1 >= off - window + 1)

    @pl.when(live)
    def _prefix():
        q = q_ref[0]  # [G*C, D]
        k = kc_ref[0]  # [bkv, D] — a pool page sub-block
        v = vc_ref[0]
        if quantized:
            k = ternary.dequantize_rows(k, C.row_to_col(ks_ref[0]), q_ref.dtype)
            v = ternary.dequantize_rows(v, C.row_to_col(vs_ref[0]), q_ref.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kpos = jnp.where(kpos < off, kpos, jnp.int32(2**30))
        _online_update(s, kpos, v)

    # --- chunk phase: one page-sized sub-block per step + the page append ---
    t = j - nkv  # chunk page index (only meaningful when j >= nkv)

    @pl.when(j >= nkv)
    def _chunk():
        q = q_ref[0]
        kn = kn_ref[0]  # [ps, D] — chunk page t
        vn = vn_ref[0]
        if quantized:
            kn_q, ks_n = ternary.quantize_act(kn)
            vn_q, vs_n = ternary.quantize_act(vn)
            kn_d = ternary.dequantize_rows(kn_q, ks_n, q_ref.dtype)
            vn_d = ternary.dequantize_rows(vn_q, vs_n, q_ref.dtype)
        else:
            kn_d, vn_d = kn, vn
        s = jax.lax.dot_general(
            q, kn_d, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = off + t * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_update(s, kpos, vn_d)
        if quantized:
            ko_ref[0] = kn_q
            vo_ref[0] = vn_q
            kso_ref[0] = C.col_to_row(ks_n)
            vso_ref[0] = C.col_to_row(vs_n)
        else:
            ko_ref[0] = kn_ref[0].astype(ko_ref.dtype)
            vo_ref[0] = vn_ref[0].astype(vo_ref.dtype)

    @pl.when(j == nkv + npg - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _call_paged(q, k_new, v_new, k_pool, v_pool, page_table, offset, scales,
                *, bkv, window, softcap, scale, prefix_limit, interpret):
    """Page-indirect pallas_call builder. ``k_pool``/``v_pool`` are page
    pools reshaped to [P*HK, ps, D] (row = page·HK + kv-head); ``scales`` is
    None or their [P*HK, 1, ps] f32 side pools. The chunk appends through
    aliased (1, ps, D) pool windows — each chunk page's last grid visit
    writes the whole window, so the write-back is always complete."""
    bhk, gc, d = q.shape
    c = k_new.shape[1]
    p_hk, ps, _ = k_pool.shape
    b, nb = page_table.shape
    hk = bhk // b
    assert ps % bkv == 0, (ps, bkv)
    assert c % ps == 0 and gc % c == 0, (c, ps, gc)
    scale = scale if scale is not None else 1.0 / d**0.5
    nkv = nb * ps // bkv
    npg = c // ps
    quantized = scales is not None

    kern = functools.partial(
        _paged_kernel, scale=scale, bkv=bkv, c=c, ps=ps, window=window,
        softcap=softcap, nkv=nkv, npg=npg, hk=hk, prefix_limit=prefix_limit,
        quantized=quantized,
    )

    def live_j(bh, j, off_ref, pt_ref):
        off = off_ref[bh // hk]
        hi = jnp.maximum(off - 1, 0) // bkv
        lo = jnp.maximum(off - window, 0) // bkv if window > 0 else 0
        return jnp.clip(j, lo, hi)

    def kv_index(bh, j, off_ref, pt_ref):
        lj = live_j(bh, j, off_ref, pt_ref)
        page = pt_ref[bh // hk, (lj * bkv) // ps]
        return (page * hk + bh % hk, lj % (ps // bkv), 0)

    def scale_index(bh, j, off_ref, pt_ref):
        lj = live_j(bh, j, off_ref, pt_ref)
        page = pt_ref[bh // hk, (lj * bkv) // ps]
        return (page * hk + bh % hk, 0, lj % (ps // bkv))

    def kn_index(bh, j, off_ref, pt_ref):
        return (bh, jnp.clip(j - nkv, 0, npg - 1), 0)

    def chunk_out_row(bh, j, off_ref, pt_ref):
        t = jnp.clip(j - nkv, 0, npg - 1)
        page = pt_ref[bh // hk, off_ref[bh // hk] // ps + t]
        return page * hk + bh % hk

    def chunk_out_index(bh, j, off_ref, pt_ref):
        return (chunk_out_row(bh, j, off_ref, pt_ref), 0, 0)

    def scale_out_index(bh, j, off_ref, pt_ref):
        return (chunk_out_row(bh, j, off_ref, pt_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, gc, d), lambda bh, j, off_ref, pt_ref: (bh, 0, 0)),
        pl.BlockSpec((1, ps, d), kn_index),
        pl.BlockSpec((1, ps, d), kn_index),
        pl.BlockSpec((1, bkv, d), kv_index),
        pl.BlockSpec((1, bkv, d), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, gc, d), lambda bh, j, off_ref, pt_ref: (bh, 0, 0)),
        pl.BlockSpec((1, ps, d), chunk_out_index),
        pl.BlockSpec((1, ps, d), chunk_out_index),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bhk, gc, d), q.dtype),
        jax.ShapeDtypeStruct((p_hk, ps, d), k_pool.dtype),
        jax.ShapeDtypeStruct((p_hk, ps, d), v_pool.dtype),
    ]
    operands = [offset, page_table, q, k_new, v_new, k_pool, v_pool]
    aliases = {5: 1, 6: 2}
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bkv), scale_index),
                     pl.BlockSpec((1, 1, bkv), scale_index)]
        out_specs += [pl.BlockSpec((1, 1, ps), scale_out_index),
                      pl.BlockSpec((1, 1, ps), scale_out_index)]
        out_shape += [jax.ShapeDtypeStruct((p_hk, 1, ps), jnp.float32),
                      jax.ShapeDtypeStruct((p_hk, 1, ps), jnp.float32)]
        operands += list(scales)
        aliases.update({7: 3, 8: 4})

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhk, nkv + npg),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((gc, d), jnp.float32),
            pltpu.VMEM((gc,), jnp.float32),
            pltpu.VMEM((gc,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale",
                              "prefix_limit", "interpret")
)
def prefill_append_paged_kernel(
    q: jax.Array,           # [B*HK, G*C, D] grouped chunk queries
    k_new: jax.Array,       # [B*HK, C, D] chunk keys (to append)
    v_new: jax.Array,       # [B*HK, C, D]
    k_pool: jax.Array,      # [P*HK, ps, D] page pool
    v_pool: jax.Array,      # [P*HK, ps, D]
    page_table: jax.Array,  # [B, NB] int32
    offset: jax.Array,      # [B] int32 frontier / write base (≡ 0 mod C)
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    prefix_limit: int = 0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    return _call_paged(q, k_new, v_new, k_pool, v_pool, page_table, offset,
                       None, bkv=bkv, window=window, softcap=softcap,
                       scale=scale, prefix_limit=prefix_limit,
                       interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale",
                              "prefix_limit", "interpret")
)
def prefill_append_paged_kernel_quant(
    q: jax.Array,           # [B*HK, G*C, D] grouped chunk queries
    k_new: jax.Array,       # [B*HK, C, D] chunk keys (float; quantized in VMEM)
    v_new: jax.Array,       # [B*HK, C, D]
    k_pool: jax.Array,      # [P*HK, ps, D] int8 page pool
    v_pool: jax.Array,      # [P*HK, ps, D]
    k_scale: jax.Array,     # [P*HK, 1, ps] f32 per-row scales
    v_scale: jax.Array,     # [P*HK, 1, ps]
    page_table: jax.Array,  # [B, NB] int32
    offset: jax.Array,      # [B] int32 frontier / write base (≡ 0 mod C)
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    prefix_limit: int = 0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Int8-pool twin of :func:`prefill_append_paged_kernel`."""
    return _call_paged(q, k_new, v_new, k_pool, v_pool, page_table, offset,
                       (k_scale, v_scale), bkv=bkv, window=window,
                       softcap=softcap, scale=scale,
                       prefix_limit=prefix_limit, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale",
                              "prefix_limit", "interpret")
)
def prefill_append_kernel(
    q: jax.Array,        # [B*HK, G*C, D] grouped chunk queries
    k_new: jax.Array,    # [B*HK, C, D] chunk keys
    v_new: jax.Array,    # [B*HK, C, D]
    k_cache: jax.Array,  # [L*B*HK, D, M] stacked cache, transposed
    v_cache: jax.Array,  # [L*B*HK, D, M]
    offset: jax.Array,   # [B] int32 per-slot frontier (≡ 0 mod C)
    layer: jax.Array,    # [1] int32 layer of the stack to read
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    prefix_limit: int = 0,  # >0: offsets past it are write-only (no prefix scan)
    interpret: bool = False,
) -> jax.Array:
    """Chunk attention over the cache prefix and the chunk: [B*HK, G*C, D]."""
    return _call(q, k_new, v_new, k_cache, v_cache, offset, layer, None,
                 bkv=bkv, window=window, softcap=softcap, scale=scale,
                 prefix_limit=prefix_limit, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale",
                              "prefix_limit", "interpret")
)
def prefill_append_kernel_quant(
    q: jax.Array,            # [B*HK, G*C, D] grouped chunk queries
    k_new: jax.Array,        # [B*HK, C, D] chunk keys (float; quantized in VMEM)
    v_new: jax.Array,        # [B*HK, C, D]
    k_cache: jax.Array,      # [L*B*HK, D, M] stacked int8 cache, transposed
    v_cache: jax.Array,      # [L*B*HK, D, M]
    k_scale: jax.Array,      # [L*B*HK, 1, M] f32 per-row scales
    v_scale: jax.Array,      # [L*B*HK, 1, M]
    offset: jax.Array,       # [B] int32 per-slot frontier (≡ 0 mod C)
    layer: jax.Array,        # [1] int32 layer of the stack to read
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    prefix_limit: int = 0,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Int8-cache twin of :func:`prefill_append_kernel`: prefix blocks are
    dequantized in VMEM, the chunk's rows absmax-quantized there. Returns
    (out, k_q, v_q, k_q_scale, v_q_scale), the chunk's rows for the cache."""
    return _call(q, k_new, v_new, k_cache, v_cache, offset, layer,
                 (k_scale, v_scale), bkv=bkv,
                 window=window, softcap=softcap, scale=scale,
                 prefix_limit=prefix_limit, interpret=interpret)
