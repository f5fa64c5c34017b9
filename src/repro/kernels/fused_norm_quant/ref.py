"""Pure-jnp oracle for the fused normalize-quantize (NQD prologue) unit.

Semantics are *defined* as the composition the unfused packed path runs —
``rmsnorm`` (f32 arithmetic, result cast back to the input dtype, exactly
``models.layers.rmsnorm``) followed by ``core.ternary.quantize_act`` — so
the fused path is bit-identical to norm-then-quant by construction, dtype
rounding included. Tests assert *exact* integer equality against this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import ternary


def rmsnorm(x, gamma, *, eps: float = 1e-5):
    """Twin of ``models.layers.rmsnorm`` (kept here so the kernel package is
    importable without the model layer stack)."""
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * gamma.astype(jnp.float32)).astype(x.dtype)


def norm_quant(x, gamma, *, eps: float = 1e-5):
    """x [..., N] float, gamma [N] -> (x_i8 [..., N] int8, scale [..., 1] f32).

    Exactly ``quantize_act(rmsnorm(x, gamma))`` — including the cast of the
    normalized row back to ``x.dtype`` before the absmax pass (quantizing a
    bf16-rounded row gives different int8 codes than quantizing the f32 row,
    and the unfused path quantizes the bf16 one).
    """
    return ternary.quantize_act(rmsnorm(x, gamma, eps=eps))


def norm_quant_tables(x, gamma, *, eps: float = 1e-5, tl_g: int = 3):
    """Oracle for the prologue + online TL table precompute: exactly
    :func:`norm_quant` followed by ``core.tl_matmul.build_tables`` on the
    quantized row — the fused kernel must match all three outputs bitwise.
    """
    from ...core.tl_matmul import build_tables

    x_i8, s = norm_quant(x, gamma, eps=eps)
    t = (x.shape[-1] + tl_g - 1) // tl_g
    return x_i8, s, build_tables(x_i8, t=t, g=tl_g)


def swiglu_requant(g, u):
    """Unfused epilogue oracle: dequantized gate/up outs -> (h_i8, h_scale).

    ``ternary.silu_mul(g, u)`` (f32, rounded once to the activation dtype),
    then per-token absmax int8 — the exact op sequence the unfused packed
    MLP runs between the gate/up and down matmuls.
    """
    return ternary.quantize_act(ternary.silu_mul(g, u))
