"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the pod
axis carries only DP gradient all-reduces (+ optionally FSDP all-gathers for
the 100B+ models), i.e. the lowest-frequency collectives, matching the slow
inter-pod links.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` and leaves the rest to GSPMD, which the
``Explicit`` default of ``jax.make_mesh`` rejects.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1×1 mesh over however many devices exist (CPU tests)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def dp_degree(mesh) -> int:
    d = 1
    for a in data_axes(mesh):
        d *= mesh.shape[a]
    return d
