"""Pallas TPU kernel: chunked cache-append prefill attention.

The prefill twin of ``kernels/decode_attention`` (DESIGN.md §prefill). One
grid step owns (slot·kv-head, kv-block); a q-chunk of ``C`` tokens at
absolute positions ``offset .. offset+C-1`` attends to the slot's existing
KV-cache *prefix* (positions ``< offset``) plus itself, and the chunk's K/V
are written straight into the batched cache at the slot's offset — per-request
caches are never materialized or host-scattered.

Schedule, mirroring the paper's reversed-reorder saving (§III-B) mapped onto
a cache prefix:

  * the per-slot ``offset`` vector is scalar-prefetched into SMEM; prefix
    kv-blocks past the slot's frontier (``j·bkv >= offset``) are skipped via
    ``pl.when`` — chunk cost tracks the *live* prefix length, not the padded
    ``max_len`` — and the k/v ``index_map`` clamps skipped block indices into
    the live range so they also move no HBM traffic;
  * the chunk's own K/V ride in VMEM as separate operands (C ≤ 256): the last
    grid step attends causally within the chunk — the lower-triangular half
    only, same work shape as the flash kernel's diagonal block — and stores
    the chunk into the cache through aliased output blocks of shape (1, C, D)
    at block index ``offset // C`` (the engine keeps ``offset ≡ 0 (mod C)``).

GQA uses the same index-map trick as the decode kernel: q is pre-grouped to
[B·HK, G·C, D] so the G query heads sharing a kv head contract against one
streamed k/v block; the causal mask depends on the row's intra-chunk index
``row % C`` only.

**Int8 cache path** (DESIGN.md §kv-cache): with ``quantized=True`` the cache
operands are int8 with per-row f32 scale side arrays [B·HK, 1, M] (the unit
axis keeps a (1, bkv) scale block legal under the TPU's (8, 128) tiling
rule), streamed by the same clamped index map (skipped prefix blocks move no
scale bytes either) and appended through their own aliased scale windows
(at least 128 lanes wide, the chunk spliced into the window's old contents).
The chunk's K/V are absmax-quantized *in VMEM* before anything is stored — the
QDQ unit fused into the append, so full-precision K/V never reaches HBM —
and the chunk's self-attention runs on the dequantized quantized rows, so
the chunk sees exactly the K/V every later reader will.
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
    off_ref, q_ref, kn_ref, vn_ref, kc_ref, vc_ref, *rest,
    scale: float, bkv: int, c: int, window: int, softcap: float,
    nkv: int, hk: int, prefix_limit: int, quantized: bool = False,
    sw: int = 0,
):
    if quantized:
        (ks_ref, vs_ref, ksw_ref, vsw_ref, o_ref, ko_ref, vo_ref, kso_ref,
         vso_ref, acc_ref, m_ref, l_ref) = rest
    else:
        o_ref, ko_ref, vo_ref, acc_ref, m_ref, l_ref = rest
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
        k = kc_ref[0]  # [bkv, D]
        v = vc_ref[0]
        if quantized:
            # in-VMEM dequant right before the QK matmul (§kv-cache)
            k = ternary.dequantize_rows(k, C.row_to_col(ks_ref[0]), q_ref.dtype)
            v = ternary.dequantize_rows(v, C.row_to_col(vs_ref[0]), q_ref.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # prefix keys only: positions >= off belong to the chunk phase
        kpos = jnp.where(kpos < off, kpos, jnp.int32(2**30))
        _online_update(s, kpos, v)

    # --- chunk phase: causal self-attention + the cache append --------------
    @pl.when(j == nkv)
    def _chunk():
        q = q_ref[0]
        kn = kn_ref[0]  # [C, D]
        vn = vn_ref[0]
        if quantized:
            # the fused QDQ unit: quantize the chunk's rows in VMEM, store
            # int8 + scale, and attend to the *dequantized* rows — the chunk
            # sees exactly the K/V every later reader will dequantize.
            # (``quantize_kv`` semantics, with the scales kept as columns.)
            kn_q, ks_n = ternary.quantize_act(kn)
            vn_q, vs_n = ternary.quantize_act(vn)
            kn = ternary.dequantize_rows(kn_q, ks_n, q_ref.dtype)
            vn = ternary.dequantize_rows(vn_q, vs_n, q_ref.dtype)
        s = jax.lax.dot_general(
            q, kn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_update(s, kpos, vn)

        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        if quantized:
            ko_ref[0] = kn_q
            vo_ref[0] = vn_q
            kso_ref[0] = C.splice_row(ksw_ref[0], ks_n, off % sw)
            vso_ref[0] = C.splice_row(vsw_ref[0], vs_n, off % sw)
        else:
            ko_ref[0] = kn_ref[0].astype(ko_ref.dtype)
            vo_ref[0] = vn_ref[0].astype(vo_ref.dtype)


def _call(q, k_new, v_new, k_cache, v_cache, offset, scales, *,
          bkv, window, softcap, scale, prefix_limit, interpret):
    """Shared pallas_call builder for the dense and int8-cache paths.

    ``scales`` is ``None`` (dense) or ``(k_scale, v_scale)`` — [B*HK, 1, M]
    f32 per-row side arrays, aliased to outputs just like the caches. Their
    chunk append goes through a (1, 1, sw) window, sw = 128 for chunks
    narrower than the TPU's 128-lane tile: the window's old scales ride in
    as an input block and the chunk's are spliced in at lane ``off % sw``."""
    bhk, gc, d = q.shape
    c = k_new.shape[1]
    m = k_cache.shape[1]
    b = offset.shape[0]
    hk = bhk // b
    assert m % bkv == 0, (m, bkv)
    assert m % c == 0 and gc % c == 0, (m, gc, c)
    scale = scale if scale is not None else 1.0 / d**0.5
    nkv = m // bkv
    quantized = scales is not None
    sw = 128 if c < 128 and m % 128 == 0 else c

    kern = functools.partial(
        _kernel, scale=scale, bkv=bkv, c=c, window=window, softcap=softcap,
        nkv=nkv, hk=hk, prefix_limit=prefix_limit, quantized=quantized,
        sw=sw,
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

    def kv_index(bh, j, off_ref):
        return (bh, live_j(bh, j, off_ref), 0)

    def scale_index(bh, j, off_ref):
        return (bh, 0, live_j(bh, j, off_ref))

    def chunk_out_index(bh, j, off_ref):
        return (bh, off_ref[bh // hk] // c, 0)

    def scale_window_index(bh, j, off_ref):
        return (bh, 0, off_ref[bh // hk] // sw)

    in_specs = [
        pl.BlockSpec((1, gc, d), lambda bh, j, off_ref: (bh, 0, 0)),
        pl.BlockSpec((1, c, d), lambda bh, j, off_ref: (bh, 0, 0)),
        pl.BlockSpec((1, c, d), lambda bh, j, off_ref: (bh, 0, 0)),
        pl.BlockSpec((1, bkv, d), kv_index),
        pl.BlockSpec((1, bkv, d), kv_index),
    ]
    out_specs = [
        pl.BlockSpec((1, gc, d), lambda bh, j, off_ref: (bh, 0, 0)),
        pl.BlockSpec((1, c, d), chunk_out_index),
        pl.BlockSpec((1, c, d), chunk_out_index),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bhk, gc, d), q.dtype),
        jax.ShapeDtypeStruct((bhk, m, d), k_cache.dtype),
        jax.ShapeDtypeStruct((bhk, m, d), v_cache.dtype),
    ]
    operands = [offset, q, k_new, v_new, k_cache, v_cache]
    # cache operands alias their outputs: the only blocks written back are
    # the (1, C, D) chunk windows (and, quantized, the (1, 1, sw) scale
    # windows) — the rest of the cache stays resident.
    aliases = {4: 1, 5: 2}
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bkv), scale_index),
                     pl.BlockSpec((1, 1, bkv), scale_index),
                     pl.BlockSpec((1, 1, sw), scale_window_index),
                     pl.BlockSpec((1, 1, sw), scale_window_index)]
        out_specs += [pl.BlockSpec((1, 1, sw), scale_window_index),
                      pl.BlockSpec((1, 1, sw), scale_window_index)]
        out_shape += [jax.ShapeDtypeStruct((bhk, 1, m), jnp.float32),
                      jax.ShapeDtypeStruct((bhk, 1, m), jnp.float32)]
        operands += list(scales) * 2
        aliases.update({8: 3, 9: 4})

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bhk, nkv + 1),
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
    k_new: jax.Array,    # [B*HK, C, D] chunk keys (to append)
    v_new: jax.Array,    # [B*HK, C, D]
    k_cache: jax.Array,  # [B*HK, M, D] batched cache (M a bkv multiple)
    v_cache: jax.Array,  # [B*HK, M, D]
    offset: jax.Array,   # [B] int32 per-slot frontier / write base (≡ 0 mod C)
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    prefix_limit: int = 0,  # >0: offsets past it are write-only (no prefix scan)
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    return _call(q, k_new, v_new, k_cache, v_cache, offset, None,
                 bkv=bkv, window=window, softcap=softcap, scale=scale,
                 prefix_limit=prefix_limit, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale",
                              "prefix_limit", "interpret")
)
def prefill_append_kernel_quant(
    q: jax.Array,        # [B*HK, G*C, D] grouped chunk queries
    k_new: jax.Array,    # [B*HK, C, D] chunk keys (float; quantized in VMEM)
    v_new: jax.Array,    # [B*HK, C, D]
    k_cache: jax.Array,  # [B*HK, M, D] int8 cache
    v_cache: jax.Array,  # [B*HK, M, D] int8 cache
    k_scale: jax.Array,  # [B*HK, 1, M] f32 per-row scales
    v_scale: jax.Array,  # [B*HK, 1, M]
    offset: jax.Array,   # [B] int32 per-slot frontier / write base (≡ 0 mod C)
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    prefix_limit: int = 0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Int8-cache twin of :func:`prefill_append_kernel`: prefix blocks are
    dequantized in VMEM, the chunk's rows are absmax-quantized in VMEM before
    the aliased append. Returns (out, k_cache', v_cache', k_scale', v_scale')."""
    return _call(q, k_new, v_new, k_cache, v_cache, offset,
                 (k_scale, v_scale), bkv=bkv, window=window, softcap=softcap,
                 scale=scale, prefix_limit=prefix_limit, interpret=interpret)
