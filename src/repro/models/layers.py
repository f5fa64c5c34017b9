"""Shared model layers: norms, RoPE, embeddings, SwiGLU MLP (all BitLinear).

The quantization pipeline mirrors TeLLMe Fig. 1: RMSNorm → absmax int8 quant →
ternary Linear → (dequant fused) → SiLU fused after the gate projection.
On the training path the same pipeline runs as differentiable fake-quant; on
the serving path ``mode="packed"`` consumes 2-bit packed weights.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..core import bitlinear, ternary
from ..core.params import ParamSpec
from ..parallel import constrain

# ---------------------------------------------------------------------------
# RMSNorm (paper C3: fused with absmax quant on the hardware path)
# ---------------------------------------------------------------------------


def rmsnorm_spec(dim: int) -> dict:
    return {"gamma": ParamSpec((dim,), (None,), init="ones")}


def rmsnorm(params: dict, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * params["gamma"].astype(jnp.float32)).astype(x.dtype)


def norm_quant(params: dict, x: jax.Array, *, eps: float = 1e-5,
               impl: str = "auto", tables: bool = False) -> tuple:
    """Fused NQD prologue: RMSNorm + per-token absmax int8 in one pass.

    Returns ``(x_i8 [..., N], x_scale [..., 1])`` — bit-identical to
    ``quantize_act(rmsnorm(params, x))`` (kernels/fused_norm_quant), ready
    for ``bitlinear.apply``'s pre-quantized fused form. With ``tables=True``
    the tuple grows to ``(x_i8, x_scale, tables)``: the TL engine's online
    table precompute rides the same VMEM pass, and every TL matmul consuming
    this row skips its stage-1 build (DESIGN.md §table-lookup). The first
    two outputs are bit-identical either way.
    """
    from ..kernels.fused_norm_quant import ops as nq_ops

    if tables:
        return nq_ops.norm_quant_tables(x, params["gamma"], eps=eps, impl=impl)
    return nq_ops.norm_quant(x, params["gamma"], eps=eps, impl=impl)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_tables(positions: jax.Array, head_dim: int, *,
                theta: float = 10000.0) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) [..., S, D/2] for ``positions [..., S]``.

    Computed once per forward/decode step and threaded through the layer
    stack: every layer rotates with the same angles, so recomputing
    ``rope_freqs`` + trig per layer (per scan iteration!) was pure waste.
    """
    freqs = rope_freqs(head_dim, theta)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_tables(x: jax.Array, rope: tuple[jax.Array, jax.Array]) -> jax.Array:
    """x [..., S, D] (D even) rotated by precomputed (cos, sin) [..., S, D/2]."""
    d = x.shape[-1]
    cos, sin = rope
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2 :].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float = 10000.0,
               rope: tuple[jax.Array, jax.Array] | None = None) -> jax.Array:
    """x [..., S, D] (D even), positions [..., S] -> rotated x.

    ``rope`` short-circuits the per-call table build with tables from
    :func:`rope_tables` (same values — the tables are a hoisted common
    subexpression, not a different rotation).
    """
    if rope is None:
        rope = rope_tables(positions, x.shape[-1], theta=theta)
    return apply_rope_tables(x, rope)


# ---------------------------------------------------------------------------
# Embedding + LM head (kept high-precision, per BitNet-1.58 practice)
# ---------------------------------------------------------------------------


def embedding_spec(vocab: int, dim: int) -> dict:
    return {"table": ParamSpec((vocab, dim), ("vocab", "embed"), init="embed", scale=0.02)}


def embed(params: dict, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    table = params["table"]
    if isinstance(table, jax.Array) or isinstance(tokens, jax.core.Tracer):
        # Under a trace, numpy tables must become jax values (numpy indexing
        # rejects tracers); the conversion is constant-folded into the jaxpr.
        return jnp.asarray(table).astype(dtype)[tokens]
    # Eager numpy (checkpoint-restored) table: gather the [B, S] rows
    # host-side rather than uploading the whole [vocab, dim] table per call.
    return jnp.asarray(table[tokens]).astype(dtype)


def lm_head_spec(dim: int, vocab: int) -> dict:
    return bitlinear.dense_spec(dim, vocab, ("embed", "vocab"))


def lm_head(params: dict, x: jax.Array, *, softcap: float = 0.0) -> jax.Array:
    logits = bitlinear.dense_apply(params, x, out_dtype=jnp.float32)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


# ---------------------------------------------------------------------------
# SwiGLU MLP on BitLinear (gate/up/down ternary; SiLU fused after gate)
# ---------------------------------------------------------------------------


def mlp_spec(dim: int, hidden: int) -> dict:
    return {
        "gate": bitlinear.spec(dim, hidden, ("embed", "mlp")),
        "up": bitlinear.spec(dim, hidden, ("embed", "mlp")),
        "down": bitlinear.spec(hidden, dim, ("mlp", "embed")),
    }


def mlp(params: dict, x: jax.Array, *, mode: str = "train") -> jax.Array:
    g = bitlinear.apply(params["gate"], x, mode=mode)
    u = bitlinear.apply(params["up"], x, mode=mode)
    h = ternary.silu_mul(g, u)  # SiLU fused into the gate matmul epilogue on HW
    h = constrain(h, "act_batch", None, "act_mlp")
    return bitlinear.apply(params["down"], h, mode=mode)


def mlp_fused(params: dict, xq: tuple, *, out_dtype, residual=None,
              use_kernel: bool | str = "auto") -> jax.Array:
    """Packed SwiGLU MLP over the fused NQD pipeline (DESIGN.md §norm-quant).

    ``xq = (x_i8, x_scale)`` from :func:`norm_quant`; the gate/up matmuls,
    SiLU and the requant run in one fused unit, and the down projection
    folds ``residual`` into its dequant epilogue — so between the norm-quant
    prologue and this function's output the hidden state crosses HBM only
    as int8 + one scale per token. Bit-identical to :func:`mlp` on the
    packed path (the sharding constraint is the one thing dropped: the
    int8-resident stack is a single-device serving path).
    """
    hq = bitlinear.swiglu(params["gate"], params["up"], xq,
                          use_kernel=use_kernel, act_dtype=out_dtype)
    return bitlinear.apply(params["down"], hq, mode="packed", fused=True,
                           use_kernel=use_kernel, out_dtype=out_dtype,
                           residual=residual)


# ---------------------------------------------------------------------------
# Cross-entropy (vocab-sharded logits friendly)
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, labels: jax.Array, *, ignore_id: int = -1):
    """logits [B, S, V] f32, labels [B, S] int32 -> mean NLL over valid tokens."""
    valid = labels != ignore_id
    labels_safe = jnp.where(valid, labels, 0)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    picked = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = (lse - picked) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def softcap_logits(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap) if cap > 0 else x
