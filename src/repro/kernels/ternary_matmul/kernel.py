"""Pallas TPU kernel: packed-ternary × int8 matmul with fused epilogues.

TPU-native form of TeLLMe's TL-based ternary matmul (DESIGN.md §2, C1):
weights stream from HBM at 2 bits/weight (the bandwidth win that makes the
memory-bound decode GEMV fast) and are expanded to int8 bit-planes *in VMEM*,
immediately feeding the MXU. The activation block is loaded once and reused
against every weight tile — the same reuse structure as the paper's
"grouped activations + online precomputation", with the VMEM block in the
role of the LUT-RAM table group.

Blocking:
  grid = (M/bm, K/bk); each step owns out block [bm, bk]
  x block  [bm, N]   int8  (full contraction resident in VMEM)
  wp block [N/4, bk] uint8 (planar pack2: bit-plane j = rows jN/4..(j+1)N/4)
  epilogue: acc_i32 * x_scale[bm,1] * w_scale -> out block (fused dequant)

Fused epilogues (DESIGN.md §norm-quant):

* residual — the projection's residual add runs on the out block before the
  HBM write (out = dequant(acc) + r), so the o/down projections of the
  int8-resident layer stack never round-trip a separate [M, K] float add.
* SwiGLU requant (``ternary_swiglu_kernel``) — gate AND up matmuls in one
  kernel; dequant → SiLU → (×up) → absmax-int8 requant all happen on the
  VMEM-resident [bm, K] hidden block, emitting int8 + per-token scale. The
  MLP's hidden activation never exists in HBM as float. Grid runs over M
  only (both weights' full K resident per step), so the per-token absmax
  sees the whole row — the requant scale is exactly the unfused one.

VMEM budget at defaults (bm=128, bk=128, N=16384):
  x 2 MiB + wp 0.5 MiB + planes 2 MiB + acc 64 KiB  << 16 MiB.
For N > 32768 (e.g. llama3-405B d_ff=53248) ops.py halves bm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import ternary


def _plane_matmul(x_ref, wp_ref):
    """Contract the int8 activation block against a planar-packed wp block,
    plane-by-plane: plane j holds weight rows [j*N/4, (j+1)*N/4)."""
    n4 = wp_ref.shape[0]
    bm = x_ref.shape[0]
    acc = jnp.zeros((bm, wp_ref.shape[1]), dtype=jnp.int32)
    # Widen before the shift: the TPU vector unit has no 8-bit shifts.
    wp = wp_ref[...].astype(jnp.int32)
    for j in range(4):
        plane = (((wp >> (2 * j)) & 0x3) - 1).astype(jnp.int8)
        xj = x_ref[:, j * n4 : (j + 1) * n4]
        acc = acc + jax.lax.dot_general(
            xj,
            plane,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    return acc


def _kernel(x_ref, xs_ref, wp_ref, ws_ref, *rest, out_dtype, residual: bool):
    o_ref = rest[-1]
    acc = _plane_matmul(x_ref, wp_ref)
    # Fused dequant epilogue (paper C3: dequant lives in the Linear output).
    out = (acc.astype(jnp.float32) * xs_ref[...] * ws_ref[0, 0]).astype(out_dtype)
    if residual:
        # Residual add on the VMEM block: same dtype arithmetic as the
        # unfused ``x + y`` (bit-identical, adds commute).
        out = out + rest[0][...]
    o_ref[...] = out


def _swiglu_kernel(x_ref, xs_ref, wg_ref, wgs_ref, wu_ref, wus_ref,
                   i8_ref, s_ref, *, act_dtype):
    xs = xs_ref[...]
    g = (_plane_matmul(x_ref, wg_ref).astype(jnp.float32) * xs
         * wgs_ref[0, 0]).astype(act_dtype)
    u = (_plane_matmul(x_ref, wu_ref).astype(jnp.float32) * xs
         * wus_ref[0, 0]).astype(act_dtype)
    # dequant → SiLU → (×up) → requant, all on the VMEM-resident block;
    # op-for-op the unfused sequence, so the int8 codes are bit-identical.
    h_i8, h_s = ternary.quantize_act(ternary.silu_mul(g, u))
    i8_ref[...] = h_i8
    s_ref[...] = h_s


def _mm_specs(bm, n, n4, bk, residual, *, gemv: bool):
    """(in_specs, out_spec) shared by the matmul/gemv entry points; gemv has
    a 1-D grid over K (activations fully resident), matmul tiles M too."""
    if gemv:
        xmap, wmap, omap = (lambda j: (0, 0)), (lambda j: (0, j)), (lambda j: (0, j))
    else:
        xmap, wmap, omap = (lambda i, j: (i, 0)), (lambda i, j: (0, j)), (lambda i, j: (i, j))
    in_specs = [
        pl.BlockSpec((bm, n), xmap),
        pl.BlockSpec((bm, 1), xmap if gemv else (lambda i, j: (i, 0))),
        pl.BlockSpec((n4, bk), wmap),
        pl.BlockSpec((1, 1), (lambda j: (0, 0)) if gemv else (lambda i, j: (0, 0))),
    ]
    if residual:
        in_specs.append(pl.BlockSpec((bm, bk), omap))
    return in_specs, pl.BlockSpec((bm, bk), omap)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "out_dtype", "interpret"))
def ternary_gemv_kernel(
    x_i8: jax.Array,  # [bm, N] int8 — decode activations, bm ∈ {8, 16}
    x_scale: jax.Array,  # [bm, 1] f32
    wp: jax.Array,  # [N/4, K] uint8 (planar pack2)
    w_scale: jax.Array,  # [1, 1] f32
    residual: jax.Array | None = None,  # [bm, K] out_dtype, added in-epilogue
    *,
    bm: int = 8,
    bk: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Small-M decode path: 1-D grid over K, activations fully VMEM-resident.

    The prefill kernel's grid tiles M; at decode M is a handful of slots, so
    the whole sublane-shaped activation block [bm<=16, N] stays in VMEM for the
    entire weight stream and each packed weight byte is touched exactly once —
    HBM traffic is the 2-bit weight stream plus one [bm, K] output, the
    memory-bound regime the paper's decode analysis targets (§III-C).
    """
    m, n = x_i8.shape
    n4, k = wp.shape
    assert n4 * 4 == n, (n4, n)
    assert m == bm and bm <= 16 and k % bk == 0, (m, bm, k, bk)
    has_r = residual is not None
    in_specs, out_spec = _mm_specs(bm, n, n4, bk, has_r, gemv=True)
    args = (x_i8, x_scale, wp, w_scale) + ((residual,) if has_r else ())
    return pl.pallas_call(
        functools.partial(_kernel, out_dtype=out_dtype, residual=has_r),
        grid=(k // bk,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "out_dtype", "interpret"))
def ternary_matmul_kernel(
    x_i8: jax.Array,  # [M, N] int8
    x_scale: jax.Array,  # [M, 1] f32
    wp: jax.Array,  # [N/4, K] uint8 (planar pack2)
    w_scale: jax.Array,  # [1, 1] f32
    residual: jax.Array | None = None,  # [M, K] out_dtype, added in-epilogue
    *,
    bm: int = 128,
    bk: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, n = x_i8.shape
    n4, k = wp.shape
    assert n4 * 4 == n, (n4, n)
    assert m % bm == 0 and k % bk == 0, (m, k, bm, bk)
    has_r = residual is not None
    in_specs, out_spec = _mm_specs(bm, n, n4, bk, has_r, gemv=False)
    args = (x_i8, x_scale, wp, w_scale) + ((residual,) if has_r else ())
    return pl.pallas_call(
        functools.partial(_kernel, out_dtype=out_dtype, residual=has_r),
        grid=(m // bm, k // bk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("bm", "act_dtype", "interpret"))
def ternary_swiglu_kernel(
    x_i8: jax.Array,  # [M, N] int8 (post norm-quant prologue)
    x_scale: jax.Array,  # [M, 1] f32
    wg: jax.Array,  # [N/4, K] uint8 gate weights (planar pack2)
    wg_scale: jax.Array,  # [1, 1] f32
    wu: jax.Array,  # [N/4, K] uint8 up weights
    wu_scale: jax.Array,  # [1, 1] f32
    *,
    bm: int = 128,
    act_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused SwiGLU: (h_i8 [M, K], h_scale [M, 1]) with h = silu(x·Wg)·(x·Wu).

    Grid runs over M only — each step holds both weight matrices' full K and
    the whole hidden row block in VMEM, so the requant absmax is the true
    per-token maximum (identical to the unfused two-matmul + XLA epilogue).
    """
    m, n = x_i8.shape
    n4, k = wg.shape
    assert n4 * 4 == n and wu.shape == wg.shape, (n4, n, wu.shape)
    assert m % bm == 0, (m, bm)
    out_shape = (
        jax.ShapeDtypeStruct((m, k), jnp.int8),
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
    )
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, act_dtype=act_dtype),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((n4, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((n4, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(x_i8, x_scale, wg, wg_scale, wu, wu_scale)
