"""Pallas kernels: faithful TeLLMe Algorithm-1 table-lookup ternary matmul.

This package is the faithful port of the paper's TL-based matmul (G-trit
group indices, 3^G-entry tables built online from the activations, lookup +
accumulate). TeLLMe v2 promotes it from a decode-only GEMV curiosity to the
*primary* engine for both phases, so three kernels live here:

* ``tl_gemv_kernel``     — the original decode GEMV (grid over K only,
  activations fully VMEM-resident);
* ``tl_matmul_kernel``   — the prefill-shaped generalization: grid
  (M/bm, K/bk), per-output-channel ``w_scale`` row, optional fused residual
  add, and an optional *precomputed-tables* input so the table build can be
  hoisted into the fused norm-quant prologue (the paper's "online
  precomputation" — tables are built once per token row and reused by every
  projection consuming that row);
* ``tl_swiglu_kernel``   — gate+up TL matmuls plus the dequant → SiLU →
  (×up) → absmax-int8 requant epilogue in one kernel, emitting int8 + scale
  so the TL engine slots into the int8-resident pipeline exactly like
  ``ternary_swiglu``.

Stage structure inside one grid step:

  1. table build — the paper's "precompute unit" of 3^G adder/subtractor
     combinations is literally the matmul  A_groups [bm·T, G] @ COMBOS [G, 3^G]
     (T = N/G tables, all built in one MXU call); skipped entirely when the
     prologue already delivered the tables;
  2. lookup-accumulate — TL_TABLE[t, W_idx[t, k]] summed over t, expressed as
     a one-hot contraction so it also lands on the MXU rather than a VPU
     gather (the TPU replacement for URAM multi-port reads).

All accumulation is f32 over exact small integers (|table entry| <= 3·127,
partial sums < 2^24 for any N <= 16384), so the TL engine is *bit-identical*
to the packed int32 path after the shared dequant epilogue ordering
``(acc · x_scale) · w_scale`` — the property the dispatcher relies on.

VMEM: tables [bm, T·3^G] f32 (e.g. N=4096, G=3, bm=128 -> 128·1366·27·4
≈ 18 MiB is too fat — ops.py drops bm for wide N), w_idx block [T, bk]
int32, out [bm, bk].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import ternary
from ...core.packing import combo_matrix_np


def _build_tables(x, combos, *, g: int, t: int):
    """In-kernel stage 1: int8 rows [bm, n<=t·g] -> tables [bm, t, 3^g] f32."""
    bm, n = x.shape
    if n < t * g:  # ragged contraction tail: zero trits pad the last group
        x = jnp.concatenate(
            [x, jnp.zeros((bm, t * g - n), x.dtype)], axis=1)
    a_groups = x.reshape(bm * t, g).astype(jnp.float32)
    return jax.lax.dot_general(
        a_groups, combos, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(bm, t, 3**g)


def _lookup_acc(tables, idx):
    """In-kernel stage 2: tables [bm, t, 3^g] × idx [t, bk] -> acc [bm, bk]
    f32, as a one-hot MXU contraction."""
    bm, t, c = tables.shape
    bk = idx.shape[1]
    onehot = (idx[:, :, None] == jnp.arange(c, dtype=jnp.int32)[None, None, :]
              ).astype(jnp.float32)  # [t, bk, 3^g]
    return jax.lax.dot_general(
        tables.reshape(bm, t * c),
        onehot.transpose(0, 2, 1).reshape(t * c, bk),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _kernel(x_ref, xs_ref, widx_ref, ws_ref, combos_ref, o_ref, *, g: int):
    t = widx_ref.shape[0]
    tables = _build_tables(x_ref[...], combos_ref[...], g=g, t=t)
    acc = _lookup_acc(tables, widx_ref[...])
    # dequant epilogue: per-token activation scale × per-output-channel (or
    # broadcast per-tensor) weight scale row for this K block
    o_ref[...] = acc * xs_ref[...] * ws_ref[...]


@functools.partial(jax.jit, static_argnames=("g", "bk", "interpret"))
def tl_gemv_kernel(
    x_i8: jax.Array,  # [M, N] int8 (M small; decode GEMV)
    x_scale: jax.Array,  # [M, 1] f32
    w_idx: jax.Array,  # [N/g, K] int32 group indices
    w_scale: jax.Array,  # [1, K] f32 per-output-channel scale row
    *,
    g: int = 3,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, n = x_i8.shape
    t, k = w_idx.shape
    assert t * g == n and k % bk == 0 and w_scale.shape == (1, k)
    combos = combo_matrix_np(g)
    return pl.pallas_call(
        functools.partial(_kernel, g=g),
        grid=(k // bk,),
        in_specs=[
            pl.BlockSpec((m, n), lambda j: (0, 0)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
            pl.BlockSpec((t, bk), lambda j: (0, j)),
            pl.BlockSpec((1, bk), lambda j: (0, j)),
            pl.BlockSpec((g, 3**g), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bk), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(x_i8, x_scale, w_idx, w_scale, combos)


def _mm_kernel(a_ref, xs_ref, widx_ref, ws_ref, *rest, g: int,
               from_tables: bool, residual: bool, out_dtype):
    o_ref = rest[-1]
    t = widx_ref.shape[0]
    if from_tables:
        bm = a_ref.shape[0]
        tables = a_ref[...].reshape(bm, t, 3**g)
    else:
        tables = _build_tables(a_ref[...], rest[0][...], g=g, t=t)
    acc = _lookup_acc(tables, widx_ref[...])
    out = (acc * xs_ref[...] * ws_ref[...]).astype(out_dtype)
    if residual:
        # residual add on the VMEM block, same dtype arithmetic as the
        # unfused ``out + r`` (parity with ternary_matmul_kernel)
        out = out + rest[-2][...]
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=(
    "g", "bm", "bk", "from_tables", "out_dtype", "interpret"))
def tl_matmul_kernel(
    a: jax.Array,  # [M, N] int8 activations, or [M, T·3^g] f32 tables
    x_scale: jax.Array,  # [M, 1] f32
    w_idx: jax.Array,  # [T, K] int32 group indices (T = ⌈N/g⌉)
    w_scale: jax.Array,  # [1, K] f32 per-output-channel scale row
    residual: jax.Array | None = None,  # [M, K] out_dtype, added in-epilogue
    *,
    g: int = 3,
    bm: int = 128,
    bk: int = 128,
    from_tables: bool = False,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Prefill-shaped TL matmul: grid (M/bm, K/bk).

    With ``from_tables`` the first operand is the prologue's precomputed
    table block (stage 1 skipped entirely); otherwise tables are built
    in-kernel from the int8 block. Either way the result is bit-identical to
    the packed kernel at the same shape.
    """
    m = a.shape[0]
    t, k = w_idx.shape
    na = a.shape[1]
    # int8 input may stop short of t·g: the last (ragged) group is zero-trit
    # padded inside the kernel, mirroring tl_indices' weight-side padding
    assert (na == t * 3**g if from_tables
            else (t - 1) * g < na <= t * g), (na, t, g, from_tables)
    assert m % bm == 0 and k % bk == 0 and w_scale.shape == (1, k)
    has_r = residual is not None
    in_specs = [
        pl.BlockSpec((bm, na), lambda i, j: (i, 0)),
        pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((t, bk), lambda i, j: (0, j)),
        pl.BlockSpec((1, bk), lambda i, j: (0, j)),
    ]
    args = [a, x_scale, w_idx, w_scale]
    if not from_tables:
        in_specs.append(pl.BlockSpec((g, 3**g), lambda i, j: (0, 0)))
        args.append(combo_matrix_np(g))
    if has_r:
        in_specs.append(pl.BlockSpec((bm, bk), lambda i, j: (i, j)))
        args.append(residual)
    return pl.pallas_call(
        functools.partial(_mm_kernel, g=g, from_tables=from_tables,
                          residual=has_r, out_dtype=out_dtype),
        grid=(m // bm, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        interpret=interpret,
    )(*args)


def _swiglu_kernel(a_ref, xs_ref, wg_ref, wgs_ref, wu_ref, wus_ref, *rest,
                   g: int, from_tables: bool, act_dtype):
    i8_ref, s_ref = rest[-2], rest[-1]
    t = wg_ref.shape[0]
    if from_tables:
        bm = a_ref.shape[0]
        tables = a_ref[...].reshape(bm, t, 3**g)
    else:
        tables = _build_tables(a_ref[...], rest[0][...], g=g, t=t)
    xs = xs_ref[...]
    gate = (_lookup_acc(tables, wg_ref[...]) * xs * wgs_ref[0, 0]).astype(act_dtype)
    up = (_lookup_acc(tables, wu_ref[...]) * xs * wus_ref[0, 0]).astype(act_dtype)
    # dequant → SiLU → (×up) → requant, op-for-op the packed swiglu kernel's
    # epilogue, so the int8 codes are bit-identical across engines
    h_i8, h_s = ternary.quantize_act(ternary.silu_mul(gate, up))
    i8_ref[...] = h_i8
    s_ref[...] = h_s


@functools.partial(jax.jit, static_argnames=(
    "g", "bm", "from_tables", "act_dtype", "interpret"))
def tl_swiglu_kernel(
    a: jax.Array,  # [M, N] int8 activations, or [M, T·3^g] f32 tables
    x_scale: jax.Array,  # [M, 1] f32
    wg_idx: jax.Array,  # [T, K] int32 gate group indices
    wg_scale: jax.Array,  # [1, 1] f32
    wu_idx: jax.Array,  # [T, K] int32 up group indices
    wu_scale: jax.Array,  # [1, 1] f32
    *,
    g: int = 3,
    bm: int = 128,
    from_tables: bool = False,
    act_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """TL twin of ``ternary_swiglu_kernel``: (h_i8 [M, K], h_scale [M, 1]).

    Grid runs over M only — both index matrices' full K resident per step —
    so the requant absmax sees the whole hidden row (the scale is exactly
    the unfused one). Padded K columns must carry the all-zero-trit group
    index so they cannot move the absmax (ops.py's ``_pad_idx_cols``).
    """
    m = a.shape[0]
    t, k = wg_idx.shape
    na = a.shape[1]
    assert (na == t * 3**g if from_tables
            else (t - 1) * g < na <= t * g), (na, t, g, from_tables)
    assert wu_idx.shape == wg_idx.shape and m % bm == 0
    in_specs = [
        pl.BlockSpec((bm, na), lambda i: (i, 0)),
        pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        pl.BlockSpec((t, k), lambda i: (0, 0)),
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
        pl.BlockSpec((t, k), lambda i: (0, 0)),
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
    ]
    args = [a, x_scale, wg_idx, wg_scale, wu_idx, wu_scale]
    if not from_tables:
        in_specs.append(pl.BlockSpec((g, 3**g), lambda i: (0, 0)))
        args.append(combo_matrix_np(g))
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, g=g, from_tables=from_tables,
                          act_dtype=act_dtype),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ),
        interpret=interpret,
    )(*args)
