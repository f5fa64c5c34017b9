"""Ternary (1.58-bit) quantization core — BitNet-1.58 style.

Implements the quantization scheme TeLLMe executes in hardware:

* weights  -> ternary {-1, 0, +1} with a per-tensor (or per-channel) absmean
  scale  (BitNet b1.58 recipe, the model family the paper deploys);
* activations -> int8 with a per-token absmax scale (the paper's "Absmax
  Quantization" unit, Sec. III-D).

Both come in two flavours:

* ``*_ste``  — fake-quant with a straight-through estimator, used on the QAT
  training path (the forward value is the quantized one, the gradient flows
  as identity);
* plain     — hard quantization used on the inference path, returning the
  integer tensors + scales that the packed kernels consume.

The invariant tying the two together (tested in tests/test_quant_consistency):
for the same weights, the STE forward and the integer inference path produce
identical results up to float re-association.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Epsilon guarding divisions by zero scales (all-zero tensors).
_EPS = 1e-8

# ---------------------------------------------------------------------------
# Weight ternarization (absmean, BitNet-1.58)
# ---------------------------------------------------------------------------


def ternary_scale(w: jax.Array, *, axis=None) -> jax.Array:
    """BitNet-1.58 absmean scale: gamma = mean(|W|).

    ``axis=None`` gives the per-tensor scale the paper uses; passing an axis
    yields per-channel scales (a beyond-paper option, see DESIGN.md §6).
    """
    return jnp.maximum(jnp.mean(jnp.abs(w), axis=axis, keepdims=axis is not None), _EPS)


def ternarize(w: jax.Array, *, axis=None) -> tuple[jax.Array, jax.Array]:
    """Hard-ternarize weights.

    Returns ``(w_t, scale)`` with ``w_t`` in {-1, 0, +1} (int8) such that the
    dequantized weight is ``w_t * scale``.
    """
    scale = ternary_scale(w, axis=axis)
    w_t = jnp.clip(jnp.round(w / scale), -1, 1).astype(jnp.int8)
    return w_t, scale.astype(jnp.float32)


def ternarize_ste(w: jax.Array, *, axis=None) -> jax.Array:
    """Fake-quant ternarization with straight-through gradients.

    forward:  w_q = round(clip(w/γ)) * γ   (value identical to inference path)
    backward: dL/dw = dL/dw_q              (identity; the round is transparent)

    The quantization arithmetic runs in f32 but the result is cast back to
    ``w.dtype`` — the value is an int-level anyway, and keeping the stream in
    bf16 halves QAT elementwise HBM traffic (EXPERIMENTS.md §Perf, A2).
    """
    scale = ternary_scale(w, axis=axis)
    w_q = jnp.clip(jnp.round(w / scale), -1, 1) * scale
    # Straight-through: detach the non-differentiable part.
    return (w + jax.lax.stop_gradient(w_q.astype(w.dtype) - w)).astype(w.dtype)


# ---------------------------------------------------------------------------
# Activation quantization (absmax int8, per-token)
# ---------------------------------------------------------------------------


def absmax_scale(x: jax.Array, *, axis: int = -1) -> jax.Array:
    """Per-token absmax scale; pass 1 of the paper's two-pass quant unit."""
    return jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), _EPS) / 127.0


def quantize_act(x: jax.Array, *, axis: int = -1) -> tuple[jax.Array, jax.Array]:
    """Hard int8 absmax quantization. Returns (x_i8, scale).

    The arithmetic runs in float32 whatever ``x.dtype`` is: the TPU vector
    unit has no bf16 rounding, so the Pallas kernels that trace this
    function could not lower a bf16 form, and every path (kernels, XLA
    forms, oracles) shares this one definition."""
    xf = x.astype(jnp.float32)
    scale = absmax_scale(xf, axis=axis)
    x_i8 = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return x_i8, scale


def silu_mul(g: jax.Array, u: jax.Array) -> jax.Array:
    """SwiGLU's hidden activation ``silu(g) * u``, computed in float32 and
    rounded once to ``g.dtype``. The fused SwiGLU kernels, their XLA forms
    and the unfused MLP all call this, so the int8 codes requantized from it
    agree bit-for-bit (Mosaic lowers no bf16 SiLU on TPU v5e)."""
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(g.dtype)


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row absmax int8 for KV-cache rows (the paper's QDQ unit applied to
    the cache stream). ``x [..., D]`` → ``(x_i8 [..., D], scale [...])`` with
    the scale axis squeezed — the cache stores scales as side arrays, one f32
    per (slot, head, position) row. Shared by the jnp oracles, the XLA serving
    forms, *and* the Pallas kernels' in-VMEM quant, so all three agree
    bit-for-bit on what lands in the cache."""
    x_i8, scale = quantize_act(x, axis=-1)
    return x_i8, jnp.squeeze(scale, -1)


def dequantize_kv(x_i8: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv`: ``x_i8 [..., D]`` × ``scale [...]`` →
    ``[..., D]`` in ``dtype`` (the attention compute dtype). The dequant runs
    in f32 and casts once at the end — the semantics every quantized attention
    path (kernel, XLA form, oracle) implements on the VMEM-resident block."""
    return dequantize_rows(x_i8, scale[..., None], dtype)


def dequantize_rows(x_i8: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    """:func:`dequantize_kv` with the scale axis kept (``scale [..., 1]``),
    the form a Pallas kernel holds its per-row scales in."""
    return (x_i8.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def quantize_act_ste(x: jax.Array, *, axis: int = -1) -> jax.Array:
    """Fake-quant int8 activations with straight-through gradients: the
    :func:`quantize_act` arithmetic in float32, the value cast back to
    ``x.dtype`` (see ternarize_ste / §Perf A2)."""
    xf = x.astype(jnp.float32)
    scale = absmax_scale(xf, axis=axis)
    x_q = jnp.clip(jnp.round(xf / scale), -127, 127) * scale
    return (x + jax.lax.stop_gradient(x_q.astype(x.dtype) - x)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Cache-frontier rollback helpers (speculative decoding)
# ---------------------------------------------------------------------------


def mask_past_frontier(x, frontier, *, seq_axis: int, batch_axis: int = 0):
    """Zero every element at sequence positions ``>= frontier``.

    The rollback invariant (DESIGN.md §speculative): cache rows at/past a
    slot's frontier are *dead* — every attention read clamps its key range to
    the frontier, and the next append lands exactly on them — so rejecting
    drafted tokens rolls back by rewinding the frontier pointer, O(1), no row
    surgery. Int8 scale side arrays carry the same ``act_kv_seq`` axis and
    rewind with it for free.

    This helper canonicalizes that invariant for *state equality checks*
    (tests, debugging): two caches are equivalent iff they agree after
    masking dead rows. ``frontier`` is a scalar or per-slot [B] vector
    broadcast along ``batch_axis``.
    """
    n = x.shape[seq_axis]
    idx_shape = [1] * x.ndim
    idx_shape[seq_axis] = n
    idx = jnp.arange(n, dtype=jnp.int32).reshape(idx_shape)
    frontier = jnp.asarray(frontier, jnp.int32)
    if frontier.ndim:
        f_shape = [1] * x.ndim
        f_shape[batch_axis] = frontier.shape[0]
        frontier = frontier.reshape(f_shape)
    return jnp.where(idx < frontier, x, jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# Paged KV-cache view helpers (DESIGN.md §paged-kv)
# ---------------------------------------------------------------------------
#
# The paged layout stores K/V in a page pool ``[P, HK, page_size, D]`` (scale
# side arrays ``[P, HK, page_size]``) addressed through a per-slot page table
# ``[B, NB]`` int32, NB = cache_len / page_size. These three helpers define
# the XLA semantics the Pallas page-indirect kernels are tested against: the
# gathered dense view is *exactly* the contiguous cache layout, so the
# contiguous attention forms run on it unchanged and paged outputs are
# bit-identical by construction. They use advanced-index gather/scatter,
# which defeats GSPMD sharding of the pool — the paged layout is a
# single-device serving concern (the engine), never a training path.


def gather_kv_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Dense per-slot view of a page pool: ``[P, HK, ps, ...]`` gathered by
    ``page_table [B, NB]`` → ``[B, HK, NB*ps, ...]`` (the contiguous cache
    shape — garbage-page rows land at masked positions and are never read
    un-masked, same contract as the contiguous trash tail)."""
    view = pool[page_table]                 # [B, NB, HK, ps, ...]
    view = jnp.moveaxis(view, 1, 2)         # [B, HK, NB, ps, ...]
    b, hk, nb, ps = view.shape[:4]
    return view.reshape(b, hk, nb * ps, *view.shape[4:])


def scatter_kv_pages(pool: jax.Array, page_table: jax.Array,
                     view: jax.Array) -> jax.Array:
    """Inverse of :func:`gather_kv_pages`: write a dense ``[B, HK, NB*ps,
    ...]`` view back through the table. Duplicate pages across slots are
    either shared-prefix pages written back unmodified (identical values) or
    the garbage page (content free by contract), so the scatter's
    duplicate-index order never matters."""
    b, hk, m = view.shape[:3]
    nb = page_table.shape[1]
    ps = m // nb
    blocks = view.reshape(b, hk, nb, ps, *view.shape[3:])
    blocks = jnp.moveaxis(blocks, 2, 1)     # [B, NB, HK, ps, ...]
    flat = blocks.reshape(b * nb, hk, ps, *view.shape[3:])
    return pool.at[page_table.reshape(-1)].set(flat.astype(pool.dtype))


def update_kv_pages(pool: jax.Array, page_table: jax.Array, val: jax.Array,
                    pos: jax.Array, page_size: int) -> jax.Array:
    """Single-row frontier write through the table (the decode append):
    ``val [B, HK, ...]`` lands at row ``pos % page_size`` of page
    ``table[b, pos // page_size]``. Slots whose block is unmapped hit the
    shared garbage page — colliding writes there carry only dead rows."""
    page = jnp.take_along_axis(
        page_table, (pos // page_size)[:, None], axis=1)[:, 0]  # [B]
    row = pos % page_size
    return pool.at[page, :, row].set(val.astype(pool.dtype))


# ---------------------------------------------------------------------------
# Reference ternary matmul semantics (the oracle every kernel is tested on)
# ---------------------------------------------------------------------------


def ternary_matmul_ref(
    x_i8: jax.Array,
    x_scale: jax.Array,
    w_t: jax.Array,
    w_scale: jax.Array,
    *,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Dequantized ternary matmul: (x_i8·sx) @ (w_t·sw), computed in int32.

    x_i8:   [..., N]   int8 activations
    x_scale:[..., 1]   per-token scales
    w_t:    [N, K]     ternary int8 weights
    w_scale: scalar or [1, K] weight scale
    """
    acc = jnp.matmul(
        x_i8.astype(jnp.int32), w_t.astype(jnp.int32), preferred_element_type=jnp.int32
    )
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def fake_quant_matmul(x: jax.Array, w: jax.Array, *, w_axis=None) -> jax.Array:
    """QAT forward: fake-quant activations & weights, dense matmul.

    This is the training-path twin of ``ternary_matmul_ref`` — numerically it
    computes the same quantity but keeps everything in float so gradients flow
    (STE through both quantizers).
    """
    xq = quantize_act_ste(x)
    wq = ternarize_ste(w, axis=w_axis)
    return jnp.matmul(xq, wq)
