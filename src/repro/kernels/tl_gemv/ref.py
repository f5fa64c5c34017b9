"""Oracles for the table-lookup engine = ``core.tl_matmul`` (the single
definition of TL semantics — group packing, zero-trit padding, table build)."""

from __future__ import annotations

import jax.numpy as jnp

from ...core import ternary
from ...core.tl_matmul import build_tables  # noqa: F401  (re-exported oracle)
from ...core.tl_matmul import tl_matmul as _tl


def _pad_groups(x_i8, t: int, g: int):
    n = x_i8.shape[-1]
    if n < t * g:
        pads = [(0, 0)] * (x_i8.ndim - 1) + [(0, t * g - n)]
        x_i8 = jnp.pad(x_i8, pads)
    return x_i8


def tl_gemv(x_i8, x_scale, w_idx, w_scale, *, g: int = 3, out_dtype=jnp.float32):
    return _tl(x_i8, x_scale, w_idx, w_scale, g=g, out_dtype=out_dtype)


def tl_matmul(x_i8, x_scale, w_idx, w_scale, *, g: int = 3, residual=None,
              out_dtype=jnp.float32):
    """Multi-row oracle: zero-trit pads the ragged contraction tail, then
    the exact Algorithm-1 integer path + shared dequant epilogue. Leading
    dims flatten to M (``core.tl_matmul`` is strictly 2-D); the residual is
    a plain post-add, exactly the packed XLA form."""
    t, k = w_idx.shape
    lead = x_i8.shape[:-1]
    x2 = _pad_groups(x_i8, t, g).reshape(-1, t * g)
    s2 = jnp.reshape(x_scale, (-1, 1))
    out = _tl(x2, s2, w_idx, w_scale, g=g, out_dtype=out_dtype)
    out = out.reshape(*lead, k)
    return out if residual is None else out + residual


def tl_swiglu(x_i8, x_scale, wg_idx, wg_scale, wu_idx, wu_scale, *,
              g: int = 3, act_dtype=jnp.bfloat16):
    """Unfused oracle of ``tl_swiglu_kernel``: two TL matmuls + the exact
    requant op sequence the packed swiglu paths share."""
    gate = tl_matmul(x_i8, x_scale, wg_idx, wg_scale, g=g, out_dtype=act_dtype)
    up = tl_matmul(x_i8, x_scale, wu_idx, wu_scale, g=g, out_dtype=act_dtype)
    return ternary.quantize_act(ternary.silu_mul(gate, up))
