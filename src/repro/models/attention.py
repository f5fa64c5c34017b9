"""Attention execution paths (XLA forms; the Pallas kernel is the TPU twin).

Two phases, specialized exactly as the paper argues (§III-B/C):

* ``prefill_attention`` — fused causal attention with the *reverse-attention*
  work saving: only the lower-triangular half of the attention map is ever
  computed. XLA form: a static (python) loop over q chunks, each contracting
  only against its causal kv prefix, with an online-softmax ``lax.scan`` over
  kv blocks so the [S, S] score matrix never materializes. Compiled FLOPs
  therefore scale as N²/2 + N·bkv/2, which the roofline extraction sees —
  this is the paper's Table II saving, visible in ``cost_analysis()``.
  On real TPU the Pallas kernel (kernels/flash_attention) implements the same
  schedule; this XLA twin is what the multi-pod dry-run lowers.

* ``decode_attention`` — the paper's decoupled score → softmax → aggregate
  path: a [1, M] score vector is cheap to keep "on chip", so no fusion
  machinery is needed; the phase is memory-bound on the KV-cache stream.

GQA is computed in grouped form (no kv repetition: q reshaped to
[B, HK, G, S, D]); sliding windows (gemma2 local layers) restrict each chunk
to its window slice, giving O(N·W) work.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..core import ternary
from ..parallel import constrain
from .layers import scoped

_NEG = -1e30


def _chunk_attend(q, k, v, q_start, k_start, *, scale, softcap, window, dtype):
    """One (q chunk × kv block) online-softmax partial: returns (m, l, o).

    q [B, H, C, D]; k/v [B, H, bkv, D] (kv already repeated to full heads —
    the repeat is a per-shard broadcast under the head-sharded TP layout).
    """
    s = jnp.einsum("bhqd,bhpd->bhqp", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    qpos = q_start + jnp.arange(q.shape[2])[:, None]
    kpos = k_start + jnp.arange(k.shape[2])[None, :]
    mask = qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None], s, _NEG)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhqp,bhpd->bhqd", p.astype(dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


@scoped("attn/core")
def prefill_attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, HK, S, D]
    v: jax.Array,  # [B, HK, S, D]
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    q_chunks: int = 4,
    kv_block: int | None = None,
) -> jax.Array:
    b, h, s, d = q.shape
    hk = k.shape[1]
    dv = v.shape[-1]
    g = h // hk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # TP layout: q stays sharded on full heads; kv (few heads, often not
    # divisible by the model axis) is repeated to full heads per kv *block*
    # inside the scan — a per-shard broadcast, so each device only
    # materializes its own head slice of one block.
    q = constrain(q, "act_batch", "act_heads", None, None)

    if s % q_chunks:
        q_chunks = 1
    c = s // q_chunks
    bkv = kv_block or c
    outs = []
    for i in range(q_chunks):
        qi = q[:, :, i * c : (i + 1) * c]
        # causal prefix for this chunk (static slice — the Table II saving)
        hi = (i + 1) * c
        lo = 0
        if window > 0:
            lo = max(0, hi - (window + c - 1))
            lo = (lo // bkv) * bkv  # align to block
        kp = k[:, :, lo:hi]
        vp = v[:, :, lo:hi]
        plen = hi - lo
        if plen % bkv:
            pad = bkv - plen % bkv
            kp = jnp.pad(kp, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vp = jnp.pad(vp, ((0, 0), (0, 0), (0, pad), (0, 0)))
            plen += pad
        nblk = plen // bkv
        kb = kp.reshape(b, hk, nblk, bkv, d).transpose(2, 0, 1, 3, 4)
        vb = vp.reshape(b, hk, nblk, bkv, dv).transpose(2, 0, 1, 3, 4)

        def step(carry, kv, qi=qi, i=i, lo=lo, c=c, bkv=bkv):
            m_prev, l_prev, o_prev, jblk = carry
            kj, vj = kv
            if g > 1:
                kj = jnp.repeat(kj, g, axis=1)
                vj = jnp.repeat(vj, g, axis=1)
            kj = constrain(kj, "act_batch", "act_heads", None, None)
            vj = constrain(vj, "act_batch", "act_heads", None, None)
            mj, lj, oj = _chunk_attend(
                qi, kj, vj, i * c, lo + jblk * bkv,
                scale=scale, softcap=softcap, window=window, dtype=q.dtype,
            )
            m_new = jnp.maximum(m_prev, mj)
            a_prev = jnp.exp(m_prev - m_new)
            a_j = jnp.exp(mj - m_new)
            l_new = l_prev * a_prev + lj * a_j
            o_new = o_prev * a_prev[..., None] + oj * a_j[..., None]
            return (m_new, l_new, o_new, jblk + 1), None

        init = (
            jnp.full((b, h, c), _NEG, jnp.float32),
            jnp.zeros((b, h, c), jnp.float32),
            jnp.zeros((b, h, c, dv), jnp.float32),
            jnp.int32(0),
        )
        (m, l, o, _), _ = jax.lax.scan(step, init, (kb, vb))
        outs.append((o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype))
    out = jnp.concatenate(outs, axis=2)  # [B, H, S, Dv]
    return out


@scoped("attn/core")
def decode_attention(
    q: jax.Array,  # [B, H, D] — the single new token (paper C4 decoupled path)
    k_cache: jax.Array,  # [B, HK, M, D] (bf16/f32, or int8 with scales)
    v_cache: jax.Array,  # [B, HK, M, D]
    pos: jax.Array,  # [B] current position (attend to <= pos)
    *,
    k_scale: jax.Array | None = None,  # [B, HK, M] f32 (int8 cache only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    impl: str = "auto",
    layer: jax.Array | None = None,
    k_new: jax.Array | None = None,
    v_new: jax.Array | None = None,
):
    """Single-token attention against the KV cache.

    ``impl`` selects the execution path:
      * ``"kernel"`` — the fused Pallas kernel (kernels/decode_attention):
        online-softmax over kv blocks with per-slot frontier skipping, so
        compute tracks the live context length rather than the padded cache;
      * ``"xla"``    — this module's dense XLA form over the full padded
        cache (the interpret/CPU fallback and the dry-run lowering);
      * ``"auto"``   — kernel on TPU, XLA elsewhere.

    With ``k_scale``/``v_scale`` set the caches are int8 (DESIGN.md
    §kv-cache): the kernel dequantizes per VMEM block; the XLA form
    dequantizes the whole cache up front — dense compute either way, so the
    materialization is the documented fallback cost, not the serving path.

    ``layer`` and ``k_new``/``v_new`` (kernel only, DESIGN.md §decode):
    read one layer of the stacked cache the layer loop carries (k/v
    [L, B, HK, M, D], scales [L, B, HK, 1, M]), and write each slot's new
    row [B, HK, D] at ``pos`` in place first; the call then returns
    ``(out, k_cache, v_cache[, k_scale, v_scale])``.
    """
    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    if (layer is not None or k_new is not None) and impl != "kernel":
        raise ValueError("decode_attention: the stacked cache (layer=...) "
                         "and the in-place row write are the kernel's only")
    if impl == "kernel":
        from ..kernels.decode_attention import ops as da_ops

        return da_ops.decode_attention(
            q, k_cache, v_cache, pos, k_scale=k_scale, v_scale=v_scale,
            layer=layer, k_new=k_new, v_new=v_new, window=window,
            softcap=softcap, scale=scale
        )
    if k_scale is not None:
        k_cache = ternary.dequantize_kv(k_cache, k_scale, q.dtype)
        v_cache = ternary.dequantize_kv(v_cache, v_scale, q.dtype)
    b, h, d = q.shape
    hk, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
    qg = q.reshape(b, hk, g, d)
    # (1) attention scores — matrix-vector over the cached keys.
    # (§Perf C1 — computing the score dot in cache dtype — was tried to kill
    # the backend's f32 ghost of the stacked KV cache and *refuted*: the CPU
    # backend promotes bf16 dots either way, and the bf16-dot form regressed
    # musicgen decode 1.5×. Reverted; see EXPERIMENTS.md §Perf cell 3.)
    s = jnp.einsum("bkgd,bkpd->bkgp", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    kpos = jnp.arange(m)[None, :]
    mask = kpos <= pos[:, None]
    if window > 0:
        mask &= (pos[:, None] - kpos) < window
    s = jnp.where(mask[:, None, None], s, _NEG)
    # (2) softmax on the [1, M] score vector
    p = jax.nn.softmax(s, axis=-1)
    # (3) value aggregation
    o = jnp.einsum("bkgp,bkpd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, h, d)


@scoped("attn/core")
def prefill_append_attention(
    q: jax.Array,        # [B, H, C, D] chunk queries (positions offset..offset+C-1)
    k_new: jax.Array,    # [B, HK, C, D] chunk keys
    v_new: jax.Array,    # [B, HK, C, D]
    k_cache: jax.Array,  # [B, HK, M, D] batched KV cache
    v_cache: jax.Array,  # [B, HK, M, D]
    offset: jax.Array,   # [B] (or scalar) per-slot cache frontier, ≡ 0 (mod C)
    *,
    k_scale: jax.Array | None = None,  # [B, HK, M] f32 (int8 cache only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    impl: str = "auto",
    prefix_limit: int = 0,
    aligned: bool = True,
    layer: jax.Array | None = None,
):
    """Chunked prefill against a cache prefix (the ``mode="prefill_chunk"`` path).

    A chunk of ``C`` tokens attends to the slot's existing cache prefix
    (positions ``< offset``, frontier-masked) plus itself (causal within the
    chunk), and the chunk's K/V are appended to the cache at
    ``[offset, offset+C)``. Returns (out [B, H, C, D], k_cache', v_cache') —
    with ``k_scale``/``v_scale`` set (int8 cache, DESIGN.md §kv-cache) the
    chunk's rows are absmax-quantized at append time, its self-attention runs
    on the dequantized quantized rows, and the tuple grows to
    (out, k_cache', v_cache', k_scale', v_scale').

    ``impl`` selects the execution path:
      * ``"kernel"`` — the fused Pallas kernel (kernels/prefill_append):
        frontier-skipped prefix blocks, so skipped cache blocks move no HBM
        traffic, and the chunk appended in place (DESIGN.md §decode);
      * ``"xla"``    — this module's dense form over the full padded cache
        (the interpret/CPU fallback and the dry-run lowering);
      * ``"auto"``   — kernel on TPU, XLA elsewhere.

    ``prefix_limit > 0`` (serving: the engine's trash-tail base) marks
    offsets at/past it write-only: the kernel skips their whole prefix scan.
    The XLA form ignores it — its compute is dense either way, and diverted
    rows' outputs are garbage by contract (their rows still quantize exactly
    like live ones, so the trash tail keeps the same int8+scale layout).

    ``aligned`` declares the caller's offset contract: the kernel path is held
    to ``offset ≡ 0 (mod C)``, which the paged kernel's aliased page windows
    need (the engine's chunk schedule guarantees it); speculative verify
    chunks land at *arbitrary*
    decode frontiers and pass ``aligned=False``, which pins ``"auto"`` to the
    XLA form (its masked-select append handles any offset) and rejects an
    explicit ``"kernel"`` rather than mis-writing the cache.

    ``layer`` (kernel only) appends to one layer of the stacked cache the
    layer loop carries (DESIGN.md §decode) and returns the whole stack.
    """
    if impl == "auto":
        impl = "kernel" if aligned and jax.default_backend() == "tpu" else "xla"
    if impl == "kernel" and not aligned:
        raise ValueError(
            "prefill_append_attention: impl='kernel' requires chunk-aligned "
            "offsets (aligned=True); speculative verify frontiers are "
            "arbitrary")
    if layer is not None and impl != "kernel":
        raise ValueError("prefill_append_attention: a stacked cache "
                         "(layer=...) is appended to by the kernel only")
    if impl == "kernel":
        from ..kernels.prefill_append import ops as pa_ops

        return pa_ops.prefill_append(
            q, k_new, v_new, k_cache, v_cache, offset,
            k_scale=k_scale, v_scale=v_scale, layer=layer,
            window=window, softcap=softcap, scale=scale,
            prefix_limit=prefix_limit,
        )
    b, h, c, d = q.shape
    hk, m = k_cache.shape[1], k_cache.shape[2]
    g = h // hk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    quantized = k_scale is not None
    if quantized:
        k_cache, v_cache, k_scale, v_scale = append_kv_cache_quant(
            k_cache, v_cache, k_scale, v_scale, k_new, v_new, offset)
        kd = ternary.dequantize_kv(k_cache, k_scale, q.dtype)
        vd = ternary.dequantize_kv(v_cache, v_scale, q.dtype)
    else:
        k_cache, v_cache = append_kv_cache(k_cache, v_cache, k_new, v_new, offset)
        kd, vd = k_cache, v_cache
    # grouped GQA form (no kv repetition), dense over the padded cache
    qg = q.reshape(b, hk, g, c, d)
    s = jnp.einsum("bkgcd,bkpd->bkgcp", qg, kd,
                   preferred_element_type=jnp.float32)
    s = s * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    qpos = offset[:, None] + jnp.arange(c)[None, :]  # [B, C]
    kpos = jnp.arange(m)[None, None, :]  # [1, 1, M]
    mask = kpos <= qpos[:, :, None]
    if window > 0:
        mask &= (qpos[:, :, None] - kpos) < window
    s = jnp.where(mask[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcp,bkpd->bkgcd", p.astype(vd.dtype), vd)
    if quantized:
        return o.reshape(b, h, c, d), k_cache, v_cache, k_scale, v_scale
    return o.reshape(b, h, c, d), k_cache, v_cache


@scoped("attn/core")
def decode_attention_paged(
    q: jax.Array,           # [B, H, D]
    k_pool: jax.Array,      # [P, HK, ps, D] page pool (bf16, or int8 + scales)
    v_pool: jax.Array,      # [P, HK, ps, D]
    page_table: jax.Array,  # [B, NB] int32 (NB*ps == the logical cache_len)
    pos: jax.Array,         # [B]
    *,
    k_scale: jax.Array | None = None,  # [P, HK, ps] f32 (int8 pool only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Page-indirect twin of :func:`decode_attention` (DESIGN.md §paged-kv).

    The frontier write (``update_kv_cache``'s role) happens *before* this
    call via ``ternary.update_kv_pages`` — the pools passed here already hold
    the new token's row. ``"xla"`` gathers the dense per-slot view
    (``ternary.gather_kv_pages``) and runs the contiguous XLA form on it, so
    paged semantics are the contiguous semantics by construction; ``"kernel"``
    is the Pallas form whose index maps translate kv-block → page-table entry
    → pool row, keeping the clamped frontier-skip (skipped blocks move zero
    bytes, page lookups included).
    """
    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    if impl == "kernel":
        from ..kernels.decode_attention import ops as da_ops

        return da_ops.decode_attention_paged(
            q, k_pool, v_pool, page_table, pos, k_scale=k_scale,
            v_scale=v_scale, window=window, softcap=softcap, scale=scale)
    kd = ternary.gather_kv_pages(k_pool, page_table)
    vd = ternary.gather_kv_pages(v_pool, page_table)
    ks = vs = None
    if k_scale is not None:
        ks = ternary.gather_kv_pages(k_scale, page_table)
        vs = ternary.gather_kv_pages(v_scale, page_table)
    return decode_attention(q, kd, vd, pos, k_scale=ks, v_scale=vs,
                            window=window, softcap=softcap, scale=scale,
                            impl="xla")


@scoped("attn/core")
def prefill_append_attention_paged(
    q: jax.Array,           # [B, H, C, D] chunk queries
    k_new: jax.Array,       # [B, HK, C, D]
    v_new: jax.Array,       # [B, HK, C, D]
    k_pool: jax.Array,      # [P, HK, ps, D] page pool
    v_pool: jax.Array,      # [P, HK, ps, D]
    page_table: jax.Array,  # [B, NB] int32
    offset: jax.Array,      # [B] chunk-aligned frontier (≡ 0 mod C)
    *,
    k_scale: jax.Array | None = None,  # [P, HK, ps] f32 (int8 pool only)
    v_scale: jax.Array | None = None,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    impl: str = "auto",
    prefix_limit: int = 0,
    aligned: bool = True,
):
    """Page-indirect twin of :func:`prefill_append_attention`.

    ``"xla"`` gathers the dense view, runs the contiguous XLA form on it
    (append included), and scatters the full view back through the table —
    the engine's ``ensure_writable`` guarantees every block the chunk writes
    is exclusively owned, and unmodified shared blocks scatter back their
    own values. ``"kernel"`` appends through aliased pool windows addressed
    by the page table, so only the chunk's pages move. Same ``aligned``
    contract as the contiguous form: speculative verify frontiers pass
    ``aligned=False`` and pin the XLA form.
    """
    if impl == "auto":
        impl = "kernel" if aligned and jax.default_backend() == "tpu" else "xla"
    if impl == "kernel" and not aligned:
        raise ValueError(
            "prefill_append_attention_paged: impl='kernel' requires "
            "chunk-aligned offsets (aligned=True); verify frontiers are "
            "arbitrary and pin the XLA form")
    if impl == "kernel":
        from ..kernels.prefill_append import ops as pa_ops

        return pa_ops.prefill_append_paged(
            q, k_new, v_new, k_pool, v_pool, page_table, offset,
            k_scale=k_scale, v_scale=v_scale, window=window, softcap=softcap,
            scale=scale, prefix_limit=prefix_limit)
    kv = ternary.gather_kv_pages(k_pool, page_table)
    vv = ternary.gather_kv_pages(v_pool, page_table)
    quantized = k_scale is not None
    if quantized:
        ksv = ternary.gather_kv_pages(k_scale, page_table)
        vsv = ternary.gather_kv_pages(v_scale, page_table)
        out, kv, vv, ksv, vsv = prefill_append_attention(
            q, k_new, v_new, kv, vv, offset, k_scale=ksv, v_scale=vsv,
            window=window, softcap=softcap, scale=scale, impl="xla",
            prefix_limit=prefix_limit, aligned=aligned)
        return (out,
                ternary.scatter_kv_pages(k_pool, page_table, kv),
                ternary.scatter_kv_pages(v_pool, page_table, vv),
                ternary.scatter_kv_pages(k_scale, page_table, ksv),
                ternary.scatter_kv_pages(v_scale, page_table, vsv))
    out, kv, vv = prefill_append_attention(
        q, k_new, v_new, kv, vv, offset, window=window, softcap=softcap,
        scale=scale, impl="xla", prefix_limit=prefix_limit, aligned=aligned)
    return (out,
            ternary.scatter_kv_pages(k_pool, page_table, kv),
            ternary.scatter_kv_pages(v_pool, page_table, vv))


@scoped("attn/kv_write")
def append_kv_cache(k_cache, v_cache, k_new, v_new, offset):
    """Write a C-token chunk's K/V at ``[offset, offset+C)``. k_new [B, HK, C, D].

    Per-slot ``offset [B]`` uses a gather + masked select on the seq axis —
    full-cache elementwise like ``update_kv_cache``'s one-hot form, but
    sharding-safe (no dynamic scatter, which would defeat GSPMD sharding of
    the cache). The kernel path never calls this: it appends the chunk in
    place (DESIGN.md §decode).
    """
    b, hk, m, d = k_cache.shape
    c = k_new.shape[2]
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    rel = jnp.arange(m)[None, :] - offset[:, None]  # [B, M] intra-chunk index
    inside = (rel >= 0) & (rel < c)
    idx = jnp.clip(rel, 0, c - 1)[:, None, :, None]  # [B, 1, M, 1]
    gk = jnp.take_along_axis(k_new.astype(k_cache.dtype), idx, axis=2)
    gv = jnp.take_along_axis(v_new.astype(v_cache.dtype), idx, axis=2)
    sel = inside[:, None, :, None]
    return jnp.where(sel, gk, k_cache), jnp.where(sel, gv, v_cache)


@scoped("attn/kv_write")
def append_kv_cache_quant(k_cache, v_cache, k_scale, v_scale, k_new, v_new,
                          offset):
    """Int8-cache twin of :func:`append_kv_cache`: quantize the chunk's rows
    (per-row absmax, the paper's QDQ unit fused into the append) and write
    int8 data + f32 scales at ``[offset, offset+C)`` with the same
    sharding-safe gather + masked select. k_new [B, HK, C, D] float;
    k_scale [B, HK, M] f32. Returns (k', v', k_scale', v_scale')."""
    b, hk, m, d = k_cache.shape
    c = k_new.shape[2]
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    kq, ks = ternary.quantize_kv(k_new)  # i8 [B,HK,C,D], f32 [B,HK,C]
    vq, vs = ternary.quantize_kv(v_new)
    rel = jnp.arange(m)[None, :] - offset[:, None]  # [B, M] intra-chunk index
    inside = (rel >= 0) & (rel < c)
    idx = jnp.clip(rel, 0, c - 1)[:, None, :, None]  # [B, 1, M, 1]
    gk = jnp.take_along_axis(kq, idx, axis=2)
    gv = jnp.take_along_axis(vq, idx, axis=2)
    gks = jnp.take_along_axis(ks, idx[..., 0], axis=2)  # [B, HK, M]
    gvs = jnp.take_along_axis(vs, idx[..., 0], axis=2)
    sel = inside[:, None, :, None]
    sel_s = inside[:, None, :]
    return (jnp.where(sel, gk, k_cache), jnp.where(sel, gv, v_cache),
            jnp.where(sel_s, gks, k_scale), jnp.where(sel_s, gvs, v_scale))


@scoped("attn/kv_write")
def update_kv_cache_quant(k_cache, v_cache, k_scale, v_scale, k_new, v_new,
                          pos):
    """Int8-cache twin of :func:`update_kv_cache`: the new token's K/V row is
    absmax-quantized at the frontier write (full precision never reaches the
    cache) and the f32 scale lands in the [B, HK, M] side array at ``pos``.
    k_new [B, HK, D] float. Same two forms as the dense path: scalar ``pos``
    uses ``dynamic_update_slice``; per-batch ``pos [B]`` a one-hot masked
    select (never a dynamic scatter — GSPMD would all-gather the cache)."""
    kq, ks = ternary.quantize_kv(k_new)  # i8 [B,HK,D], f32 [B,HK]
    vq, vs = ternary.quantize_kv(v_new)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, kq[:, :, None, :], pos, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, vq[:, :, None, :], pos, axis=2)
        k_scale = jax.lax.dynamic_update_slice_in_dim(
            k_scale, ks[:, :, None], pos, axis=2)
        v_scale = jax.lax.dynamic_update_slice_in_dim(
            v_scale, vs[:, :, None], pos, axis=2)
        return k_cache, v_cache, k_scale, v_scale
    m = k_cache.shape[2]
    oh = jnp.arange(m)[None, :] == pos[:, None]  # [B, M] bool
    ohk = oh[:, None, :, None]
    ohs = oh[:, None, :]
    return (jnp.where(ohk, kq[:, :, None, :], k_cache),
            jnp.where(ohk, vq[:, :, None, :], v_cache),
            jnp.where(ohs, ks[:, :, None], k_scale),
            jnp.where(ohs, vs[:, :, None], v_scale))


@scoped("attn/kv_write")
def update_kv_cache(k_cache, v_cache, k_new, v_new, pos):
    """Write the new token's K/V at ``pos``. k_new [B, HK, D].

    The rewrite form, for the XLA attention path (the CPU, ``attn_impl=
    "xla"``, the GSPMD dry-run lowerings) and the scanned per-layer caches it
    reads; the kernel path writes rows in place instead (the decode kernel's
    ``k_new``, DESIGN.md §decode). Two forms:
    * scalar ``pos`` (synchronized decode, the decode_* dry-run shapes):
      ``dynamic_update_slice`` on the seq axis — slice-sized traffic, shards
      cleanly under GSPMD;
    * per-batch ``pos [B]`` (heterogeneous slots off the kernel path):
      one-hot masked write — it rewrites the whole layer cache, but is
      sharding-safe. A per-batch *scatter* is not used here: dynamic scatter
      indices defeat GSPMD sharding of the cache (it would all-gather it per
      layer).
    """
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new[:, :, None, :].astype(k_cache.dtype), pos, axis=2
        )
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new[:, :, None, :].astype(v_cache.dtype), pos, axis=2
        )
        return k_cache, v_cache
    m = k_cache.shape[2]
    oh = (jnp.arange(m)[None, :] == pos[:, None]).astype(k_cache.dtype)  # [B, M]
    ohk = oh[:, None, :, None]
    k_cache = k_cache * (1 - ohk) + k_new[:, :, None, :].astype(k_cache.dtype) * ohk
    v_cache = v_cache * (1 - ohk) + v_new[:, :, None, :].astype(v_cache.dtype) * ohk
    return k_cache, v_cache

