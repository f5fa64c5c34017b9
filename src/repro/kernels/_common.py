"""Shared wrapper boilerplate for the kernel packages.

Every ``ops.py`` in this tree repeats the same three moves before a
``pallas_call``: resolve ``interpret=None`` to "interpret everywhere but
TPU", flatten the caller's leading batch dims into one row axis, and pad
axes up to block multiples (sliced back off after the call). They live here
once so the policies stay in lockstep across kernels.

The in-kernel helpers at the bottom move int8-KV scales between the two
layouts the attention kernels need: a lane-major ``[1, n]`` row (how the
scale side arrays are blocked, one unit axis above the row axis so the
block obeys the TPU's (8, 128) tiling rule) and a sublane-major ``[n, 1]``
column (how a per-row scale broadcasts against a ``[n, D]`` K/V block).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret mode everywhere but real TPU (the shared
    default of every kernel wrapper)."""
    return (not on_tpu()) if interpret is None else interpret


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def lane_block(n: int, want: int) -> int:
    """Block length along a lane axis of length ``n`` that ``n`` needs no
    padding for: the largest multiple of 128 up to ``want`` that divides
    ``n``, else ``n`` whole — the two lengths the TPU's (8, 128) tiling rule
    allows on the minor axis."""
    for b in range(want - want % 128, 0, -128):
        if n % b == 0:
            return b
    return n


def flatten_lead(x: jax.Array) -> tuple[jax.Array, tuple, int]:
    """[..., N] -> ([M, N], lead_shape, M): one row per leading-dim element."""
    *lead, n = x.shape
    m = 1
    for d in lead:
        m *= d
    return x.reshape(m, n), tuple(lead), m


def pad_to(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad ``axis`` up to ``target`` elements (no-op when already there)."""
    n = x.shape[axis]
    if n == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads)


def _eye(n: int) -> jax.Array:
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    return i == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)


def row_to_col(row: jax.Array) -> jax.Array:
    """[1, n] -> [n, 1], exactly: a masked lane reduction in place of a
    transpose (each output sums one value with zeros)."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_eye(n), row, 0.0), axis=1, keepdims=True)


def splice_row(row: jax.Array, col: jax.Array, start) -> jax.Array:
    """``row [1, w]`` with lanes ``start .. start+n-1`` replaced by the
    ``[n, 1]`` column ``col``, exactly (``start`` may be traced)."""
    n, w = col.shape[0], row.shape[1]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, w), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, w), 1)
    new = jnp.sum(jnp.where(lane == start + i, col, 0.0), axis=0,
                  keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    return jnp.where((lane >= start) & (lane < start + n), new, row)


def col_to_row(col: jax.Array) -> jax.Array:
    """[n, 1] -> [1, n], exactly (the inverse of :func:`row_to_col`)."""
    return splice_row(jnp.zeros((1, col.shape[0]), col.dtype), col, 0)

