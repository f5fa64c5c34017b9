"""Pallas TPU kernel: single-token q vs KV cache with frontier block skipping.

The decode twin of ``kernels/flash_attention`` (DESIGN.md §decode). One grid
step owns (slot·kv-head, kv-block); the online-softmax state (m, l, acc) for
the slot's query group lives in VMEM scratch across the kv-block loop, exactly
the prefill recurrence with the q axis collapsed to the GQA group.

Where the prefill kernel skips *upper-triangular* blocks, decode skips blocks
past each slot's **frontier**: the per-slot position vector is scalar-prefetched
into SMEM, and

  * ``pl.when`` predication — blocks with ``j*bkv > pos[b]`` (or entirely below
    the sliding-window foot) never execute their dot/softmax/aggregate body, so
    per-slot compute tracks the live context length, not the padded ``max_len``
    (the decode analogue of the paper's reversed-reorder work saving, §III-B);
  * the k/v ``index_map`` clamps past-frontier block indices to the frontier
    block — Pallas's pipeline never re-fetches a block whose index repeats, so
    the skipped blocks also cost no HBM traffic.

Slots at heterogeneous positions therefore coexist in one batched call: each
``b`` reads its own ``pos[b]`` frontier. GQA uses the same index-map trick as
the prefill kernel: q is pre-grouped to [B·HK, G, D] so the G query heads that
share a kv head contract against one streamed k/v block.

The contiguous kernel reads the cache transposed, ``(1, D, bkv)`` blocks of
[.., D, M] — the layout a TPU holds a [.., M, D] cache in when D is under 128
lanes — and can write each slot's new row first, into the frontier block it
reads anyway (DESIGN.md §decode). The paged kernel reads ``(1, bkv, D)``
blocks of its row-major page pool.

**Int8 cache path** (DESIGN.md §kv-cache): with ``quantized=True`` the k/v
operands are int8 with per-row f32 scales riding alongside as [B·HK, 1, M]
arrays (the unit axis keeps a (1, bkv) scale block legal under the TPU's
(8, 128) tiling rule), blocked by the *same* clamped index map — so a
skipped block's scales move no HBM traffic either. The block is dequantized in VMEM right before the
QK matmul (``ternary.dequantize_kv`` semantics: f32 multiply, one cast to the
query dtype); full-precision K/V never exists in HBM, which is the point —
the phase is bound on cache bytes, and int8+scale halves them.
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
    pos_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
    scale: float, bkv: int, window: int, softcap: float, nkv: int, hk: int,
    quantized: bool = False, transposed: bool = False, write: bool = False,
):
    del layer_ref  # consumed by the index maps only
    rest = list(rest)

    def take(n, cond):
        return [rest.pop(0) for _ in range(n)] if cond else [None] * n

    ks_ref, vs_ref = take(2, quantized)
    kn_ref, vn_ref = take(2, write)
    ksn_ref, vsn_ref = take(2, write and quantized)
    (o_ref,) = take(1, True)
    ko_ref, vo_ref = take(2, write)
    kso_ref, vso_ref = take(2, write and quantized)
    acc_ref, m_ref, l_ref = rest
    bh = pl.program_id(0)
    j = pl.program_id(1)
    p = pos_ref[bh // hk]  # this slot's frontier position

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Frontier skip: only blocks intersecting [max(p-window+1, 0), p] run.
    jmax = p // bkv
    live = j <= jmax
    if window > 0:
        jmin = jnp.maximum(p - window + 1, 0) // bkv
        live = jnp.logical_and(live, j >= jmin)

    @pl.when(live)
    def _step():
        q = q_ref[0]  # [G, D]
        k = k_ref[0]  # [D, bkv] transposed, else [bkv, D]
        v = v_ref[0]
        ks, vs = (ks_ref[0], vs_ref[0]) if quantized else (None, None)
        if write:
            # the slot's new row lands at position p, in the frontier block:
            # spliced in here, so this step attends to it, and stored back
            # through the aliased frontier block (other blocks: no match)
            at = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) == p - j * bkv
            k = jnp.where(at, kn_ref[0], k)
            v = jnp.where(at, vn_ref[0], v)
            if quantized:
                at = (jax.lax.broadcasted_iota(jnp.int32, ks.shape, 1)
                      == p - j * bkv)
                ks = jnp.where(at, ksn_ref[0], ks)
                vs = jnp.where(at, vsn_ref[0], vs)

            @pl.when(j == jmax)
            def _store():
                ko_ref[0] = k
                vo_ref[0] = v
                if quantized:
                    kso_ref[0] = ks
                    vso_ref[0] = vs

        if quantized:
            # in-VMEM dequant right before the QK matmul: the int8 block and
            # its per-row scales are all that ever crossed HBM. A transposed
            # block holds one row per lane, as its [1, bkv] scales do.
            if not transposed:
                ks, vs = C.row_to_col(ks), C.row_to_col(vs)
            k = ternary.dequantize_rows(k, ks, q_ref.dtype)
            v = ternary.dequantize_rows(v, vs, q_ref.dtype)
        kd = 0 if transposed else 1  # the D axis of a k/v block
        s = jax.lax.dot_general(
            q, k, (((1,), (kd,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, bkv]
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= p
        if window > 0:
            mask = jnp.logical_and(mask, p - kpos < window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[:, None])
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + pexp.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (1 - kd,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nkv - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _call(q, k, v, pos, layer, scales, new, *, bkv, window, softcap, scale,
          interpret):
    """Shared pallas_call builder for the dense and int8-cache paths.

    ``k``/``v`` are [L*B*HK, D, M]: every layer's cache, stacked, and seen
    transposed — the layout a TPU holds a ``[.., M, D]`` cache in when D is
    under 128 lanes, so the view is free — of which ``layer`` ([1] int32, a
    second scalar-prefetch operand) is read: the kv index map offsets each
    row by ``layer * B*HK``, so no layer is sliced out. A single layer's
    cache is the case L = 1, ``layer`` 0. ``scales`` is ``None`` (dense bf16
    cache) or ``(k_scale, v_scale)`` — the [L*B*HK, 1, M] f32 per-row side
    arrays of an int8 cache.

    ``new`` (or ``None``) writes each slot's new row at its ``pos`` first:
    ``(k_row, v_row)`` [B*HK, D, 1] (int8 cache: and their [B*HK, 1, 1]
    scales), spliced into the frontier block, which goes back through an
    output aliased to the cache — the call then returns
    ``(out, k, v[, k_scale, v_scale])``."""
    bhk, g, d = q.shape
    m = k.shape[2]
    b = pos.shape[0]
    hk = bhk // b
    assert m % bkv == 0, (m, bkv)
    scale = scale if scale is not None else 1.0 / d**0.5
    nkv = m // bkv
    quantized = scales is not None
    write = new is not None

    kern = functools.partial(
        _kernel, scale=scale, bkv=bkv, window=window, softcap=softcap,
        nkv=nkv, hk=hk, quantized=quantized, transposed=True, write=write,
    )

    def live_j(bh, j, pos_ref):
        # Clamp skipped indices into the live [window-foot, frontier] range: a
        # repeated block index is not re-fetched by the pipeline, so skipped
        # blocks — past the frontier or below the window foot — move no HBM
        # traffic either.
        p = pos_ref[bh // hk]
        lo = jnp.maximum(p - window + 1, 0) // bkv if window > 0 else 0
        return jnp.clip(j, lo, p // bkv)

    def kv_index(bh, j, pos_ref, layer_ref):
        # k/v blocks and their scales: the same clamped schedule
        return (layer_ref[0] * bhk + bh, 0, live_j(bh, j, pos_ref))

    def frontier_index(bh, j, pos_ref, layer_ref):
        return (layer_ref[0] * bhk + bh, 0, pos_ref[bh // hk] // bkv)

    def slot_index(bh, j, pos_ref, layer_ref):
        return (bh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, g, d), slot_index),
        pl.BlockSpec((1, d, bkv), kv_index),
        pl.BlockSpec((1, d, bkv), kv_index),
    ]
    operands = [pos, layer, q, k, v]
    out_specs = [pl.BlockSpec((1, g, d), slot_index)]
    out_shape = [jax.ShapeDtypeStruct((bhk, g, d), q.dtype)]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bkv), kv_index)] * 2
        operands += list(scales)
    aliases = {}
    if write:
        in_specs += [pl.BlockSpec((1, d, 1), slot_index)] * 2
        out_specs += [pl.BlockSpec((1, d, bkv), frontier_index)] * 2
        out_shape += [jax.ShapeDtypeStruct(k.shape, k.dtype)] * 2
        aliases = {3: 1, 4: 2}
        if quantized:
            in_specs += [pl.BlockSpec((1, 1, 1), slot_index)] * 2
            out_specs += [pl.BlockSpec((1, 1, bkv), frontier_index)] * 2
            out_shape += [jax.ShapeDtypeStruct(scales[0].shape,
                                               jnp.float32)] * 2
            aliases.update({5: 3, 6: 4})
        operands += list(new)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhk, nkv),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
    return tuple(outs) if write else outs[0]


def _paged_kernel(pt_ref, pos_ref, *args, **kw):
    # Page-indirect wrapper: the page table rides as a second scalar-prefetch
    # operand consumed *only* by the index maps — the online-softmax body is
    # the contiguous kernel unchanged (logical kv positions are j*bkv+iota
    # whatever pool row the block was fetched from).
    del pt_ref
    return _kernel(pos_ref, None, *args, **kw)


def _call_paged(q, k, v, page_table, pos, scales, *, bkv, window, softcap,
                scale, interpret):
    """Page-indirect pallas_call builder (DESIGN.md §paged-kv).

    ``k``/``v`` are page pools reshaped to [P*HK, ps, D] (row = page·HK +
    kv-head) and ``page_table`` [B, NB] maps each slot's logical kv block to
    a page. The kv index map composes the contiguous frontier clamp with a
    table lookup: logical block ``lj`` → page ``pt[slot, lj·bkv ÷ ps]`` →
    pool row — so skipped blocks still repeat a block index and move zero
    bytes, page lookup included. ``scales`` pools are [P*HK, 1, ps]."""
    bhk, g, d = q.shape
    p_hk, ps, _ = k.shape
    b, nb = page_table.shape
    hk = bhk // b
    assert ps % bkv == 0, (ps, bkv)
    scale = scale if scale is not None else 1.0 / d**0.5
    nkv = nb * ps // bkv
    quantized = scales is not None

    kern = functools.partial(
        _paged_kernel, scale=scale, bkv=bkv, window=window, softcap=softcap,
        nkv=nkv, hk=hk, quantized=quantized,
    )

    def live_j(bh, j, pt_ref, pos_ref):
        # same clamp as the contiguous kernel: skipped blocks repeat an index
        p = pos_ref[bh // hk]
        lo = jnp.maximum(p - window + 1, 0) // bkv if window > 0 else 0
        return jnp.clip(j, lo, p // bkv)

    def kv_index(bh, j, pt_ref, pos_ref):
        lj = live_j(bh, j, pt_ref, pos_ref)
        page = pt_ref[bh // hk, (lj * bkv) // ps]
        return (page * hk + bh % hk, lj % (ps // bkv), 0)

    def scale_index(bh, j, pt_ref, pos_ref):
        lj = live_j(bh, j, pt_ref, pos_ref)
        page = pt_ref[bh // hk, (lj * bkv) // ps]
        return (page * hk + bh % hk, 0, lj % (ps // bkv))

    in_specs = [
        pl.BlockSpec((1, g, d), lambda bh, j, pt_ref, pos_ref: (bh, 0, 0)),
        pl.BlockSpec((1, bkv, d), kv_index),
        pl.BlockSpec((1, bkv, d), kv_index),
    ]
    operands = [page_table, pos, q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bkv), scale_index),
                     pl.BlockSpec((1, 1, bkv), scale_index)]
        operands += list(scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhk, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, d),
                               lambda bh, j, pt_ref, pos_ref: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhk, g, d), q.dtype),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale", "interpret")
)
def decode_attention_paged_kernel(
    q: jax.Array,           # [B*HK, G, D] grouped queries
    k: jax.Array,           # [P*HK, ps, D] page pool
    v: jax.Array,           # [P*HK, ps, D]
    page_table: jax.Array,  # [B, NB] int32
    pos: jax.Array,         # [B] int32 per-slot frontier
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    return _call_paged(q, k, v, page_table, pos, None, bkv=bkv, window=window,
                       softcap=softcap, scale=scale, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale", "interpret")
)
def decode_attention_paged_kernel_quant(
    q: jax.Array,           # [B*HK, G, D] grouped queries
    k: jax.Array,           # [P*HK, ps, D] int8 page pool
    v: jax.Array,           # [P*HK, ps, D]
    k_scale: jax.Array,     # [P*HK, 1, ps] f32 per-row scales
    v_scale: jax.Array,     # [P*HK, 1, ps]
    page_table: jax.Array,  # [B, NB] int32
    pos: jax.Array,         # [B] int32 per-slot frontier
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Int8-pool twin of :func:`decode_attention_paged_kernel`."""
    return _call_paged(q, k, v, page_table, pos, (k_scale, v_scale), bkv=bkv,
                       window=window, softcap=softcap, scale=scale,
                       interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale", "interpret")
)
def decode_attention_kernel(
    q: jax.Array,      # [B*HK, G, D] grouped queries (G padded to sublane)
    k: jax.Array,      # [L*B*HK, D, M] stacked cache, transposed (M a bkv multiple)
    v: jax.Array,      # [L*B*HK, D, M]
    pos: jax.Array,    # [B] int32 per-slot frontier
    layer: jax.Array,  # [1] int32 layer of the stack to read
    new=None,          # (k_row, v_row) [B*HK, D, 1] to write at pos first
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
):
    """Returns out [B*HK, G, D], or with ``new`` (out, k, v)."""
    return _call(q, k, v, pos, layer, None, new, bkv=bkv, window=window,
                 softcap=softcap, scale=scale, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bkv", "window", "softcap", "scale", "interpret")
)
def decode_attention_kernel_quant(
    q: jax.Array,        # [B*HK, G, D] grouped queries
    k: jax.Array,        # [L*B*HK, D, M] stacked int8 cache, transposed
    v: jax.Array,        # [L*B*HK, D, M]
    k_scale: jax.Array,  # [L*B*HK, 1, M] f32 per-row scales
    v_scale: jax.Array,  # [L*B*HK, 1, M]
    pos: jax.Array,      # [B] int32 per-slot frontier
    layer: jax.Array,    # [1] int32 layer of the stack to read
    new=None,            # (k_row, v_row, k_row_scale, v_row_scale)
    *,
    bkv: int = 128,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
):
    """Int8-cache twin of :func:`decode_attention_kernel`: blocks are
    dequantized in VMEM right before the QK matmul. With ``new`` (int8
    rows [B*HK, D, 1], f32 scales [B*HK, 1, 1]) returns
    (out, k, v, k_scale, v_scale)."""
    return _call(q, k, v, pos, layer, (k_scale, v_scale), new, bkv=bkv,
                 window=window, softcap=softcap, scale=scale,
                 interpret=interpret)
