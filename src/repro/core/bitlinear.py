"""BitLinear — the paper's ternary linear layer as a composable JAX module.

Three execution paths over one weight declaration:

* ``mode="train"``  — QAT: absmax-int8 fake-quant activations × absmean
  ternary fake-quant weights, dense bf16 matmul, STE gradients. This is how
  BitNet-1.58 models (the family TeLLMe deploys) are trained.
* ``mode="eval"``   — hard-quantized integer path on unpacked weights
  (bit-exact twin of the packed path; used for validation).
* ``mode="packed"`` — serving path: weights live 2-bit-packed in HBM
  (uint8, 4 trits/byte) and are dequantized on the fly inside the matmul —
  the TPU-native form of the paper's TL-based matmul (DESIGN.md §2, C1).
  Dequantization of the *output* (x_scale · w_scale) is fused into the
  epilogue, as the paper fuses dequant into the Linear output pipeline.

The packed matmul routes through ``kernels.ternary_matmul`` when
``use_kernel=True`` (TPU target; interpret-mode on CPU), else an XLA path with
identical semantics (used for CPU tests and as the dry-run lowering).
``use_kernel="tl"`` selects the paper-faithful table-lookup GEMV
(``kernels.tl_gemv``) instead — group-index weights, online 3^G tables.

**Fused NQD pipeline** (DESIGN.md §norm-quant): with ``fused`` on (the
default for ``mode="packed"``), ``x`` may be a pre-quantized
``(x_i8, x_scale)`` pair — the output of the fused norm-quant prologue or
of the fused SwiGLU epilogue — so hidden states cross HBM in int8 wherever
a ternary matmul follows; ``residual`` is folded into the dequant epilogue.
Both are bit-identical to the unfused quantize→matmul→add sequence.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from . import ternary
from .packing import pack2, unpack2
from .params import ParamSpec
from .tl_matmul import GROUP as TL_GROUP  # paper: 27-entry tables
from .tl_matmul import tl_indices as _tl_indices_impl


def spec(n_in: int, n_out: int, axes: tuple, *, dtype=jnp.float32, scale=None) -> dict:
    """Declare a BitLinear weight [n_in, n_out] with logical ``axes``."""
    return {"w": ParamSpec((n_in, n_out), axes, dtype=dtype, scale=scale, quant="ternary")}


def packed_spec(s: ParamSpec) -> dict:
    """Serving-side declaration for a ternary ParamSpec: packed + scale.

    The contraction axis (second-to-last) is packed 4 trits/byte. Leading
    stack axes (scanned layers, experts) are preserved, with one scale per
    stacked matrix.
    """
    n_in = s.shape[-2]
    if n_in % 4:
        raise ValueError(f"contraction dim {n_in} not packable (need %4==0)")
    lead = s.shape[:-2]
    shape = lead + (n_in // 4, s.shape[-1])
    return {
        "wp": ParamSpec(shape, s.axes, dtype=jnp.uint8, init="zeros"),
        "scale": ParamSpec(lead, s.axes[:-2], dtype=jnp.float32, init="ones"),
    }


def pack_params(w) -> dict:
    """Convert a trained float weight [..., N, K] into the packed serving form."""
    if w.ndim == 2:
        w_t, w_scale = ternary.ternarize(w)
        return {"wp": pack2(w_t), "scale": w_scale}
    flat = w.reshape((-1,) + w.shape[-2:])
    packed = []
    scales = []
    for i in range(flat.shape[0]):
        w_t, w_scale = ternary.ternarize(flat[i])
        packed.append(pack2(w_t))
        scales.append(w_scale)
    wp = jnp.stack(packed).reshape(w.shape[:-2] + (w.shape[-2] // 4, w.shape[-1]))
    scale = jnp.stack(scales).reshape(w.shape[:-2])
    return {"wp": wp, "scale": scale}


def with_tl_indices(params: dict, *, g: int = TL_GROUP) -> dict:
    """Precompute the table-lookup group indices for a packed param node.

    Returns the node extended with ``w_idx [..., ⌈N/g⌉, K] int32`` (the
    paper's Offline_preprocess, ``core.tl_matmul.tl_indices`` — the single
    definition of the group packing and its zero-trit padding), so the TL
    path skips the per-call unpack→encode. Stacked (scanned-layer) weights
    get a leading-stacked index tensor, sliced per layer inside the scan.
    """
    return dict(params, w_idx=_tl_indices(params["wp"], g))


def _tl_indices(wp, g: int):
    return _tl_indices_impl(wp, g=g)


def with_tl_tree(params, *, g: int = TL_GROUP):
    """Add ``w_idx`` to every packed BitLinear node in a whole param tree.

    The serving-side Offline_preprocess: run once after ``pack_tree`` so the
    TL engine (``matmul_engine="tl"`` or a measured ``"auto"`` resolution)
    never unpacks/encodes weights inside a jitted step. Idempotent; nodes
    without packed weights pass through untouched.
    """
    def rec(node):
        if isinstance(node, dict):
            if "wp" in node and "scale" in node:
                return node if "w_idx" in node else with_tl_indices(node, g=g)
            return {k: rec(v) for k, v in node.items()}
        return node

    return rec(params)


def _quantized_input(x, fused: bool):
    """Accept float x (quantize here), a pre-quantized ``(x_i8, scale)``
    pair, or the tables-carrying triple ``(x_i8, scale, tables)`` from the
    fused prologue. Returns ``(x_i8, x_scale, tables-or-None)``."""
    if isinstance(x, tuple):
        if not fused:
            raise ValueError("pre-quantized input requires fused=True")
        return x if len(x) == 3 else (*x, None)
    return (*ternary.quantize_act(x), None)


def resolve_engine(params: dict, m: int, *, use_kernel: bool | str = "auto") -> str:
    """Static (trace-time) TL-vs-packed choice for one projection call.

    ``"tl"`` forces the table-lookup engine. ``"auto"`` consults the
    autotuner's measured per-shape engine table (``kernels.autotune``) —
    but only for nodes whose ``w_idx`` was precomputed (``with_tl_tree``):
    deriving indices inside a jitted serving step would unpack the weights
    per call. Everything else (including ``"packed"``, the pinned packed
    path) resolves to ``"packed"``. The two engines are bit-identical, so
    this is purely a performance dispatch.
    """
    if use_kernel == "tl":
        return "tl"
    if use_kernel == "auto" and "w_idx" in params and params["wp"].ndim == 2:
        from ..kernels import autotune

        n4, k = params["wp"].shape
        if autotune.choose_engine(m, n4 * 4, k) == "tl":
            return "tl"
    return "packed"


def apply(params: dict, x, *, mode: str = "train", use_kernel: bool | str = "auto",
          out_dtype: Any = None, fused: bool | None = None, residual=None):
    """Apply BitLinear. ``x`` is [..., n_in]; returns [..., n_out].

    ``use_kernel`` selects the matmul engine (all choices bit-identical):
      * ``"auto"``   — measured dispatch: nodes with precomputed ``w_idx``
        consult the autotuner's per-shape TL-vs-packed table
        (``kernels.autotune.choose_engine``); unmeasured shapes and plain
        nodes fall back to the packed path (Pallas kernels on TPU, the
        bit-identical XLA form elsewhere);
      * ``"packed"`` — pin the packed path (the pre-dispatcher ``"auto"``);
      * ``"tl"``     — force the table-lookup engine (2-D weights only;
        indices derived on the fly when not precomputed);
      * ``True``/``False`` — force the packed Pallas kernel / XLA form.
    Stacked weights (MoE experts fed as [E, N/4, K]) always use the XLA form.

    ``fused`` (default: on for ``mode="packed"``, off — and rejected — for
    train/eval) admits pre-quantized ``(x_i8, x_scale)`` input — or the
    fused prologue's ``(x_i8, x_scale, tables)`` triple, whose precomputed
    TL tables the TL engine consumes directly — and a ``residual`` folded
    into the matmul epilogue.
    """
    if fused is None:
        fused = mode == "packed"
    if (residual is not None or isinstance(x, tuple)) and not (
            fused and mode == "packed"):
        raise ValueError(
            "fused epilogue/prologue forms are packed-serving only "
            f"(mode={mode!r}, fused={fused})")
    if out_dtype is None:
        if isinstance(x, tuple) and residual is None:
            # The pair carries no activation dtype (x[1] is the f32 scale) —
            # a silent f32 default would break fused/unfused bit-identity.
            raise ValueError("pre-quantized input requires out_dtype= "
                             "(or a residual to infer it from)")
        out_dtype = residual.dtype if residual is not None else x.dtype
    if mode == "train":
        w = params["w"]
        return ternary.fake_quant_matmul(x, w.astype(x.dtype)).astype(out_dtype)
    if mode == "eval":
        w_t, w_scale = ternary.ternarize(params["w"])
        x_i8, x_scale = ternary.quantize_act(x)
        return ternary.ternary_matmul_ref(x_i8, x_scale, w_t, w_scale, out_dtype=out_dtype)
    if mode == "packed":
        x_i8, x_scale, tables = _quantized_input(x, fused)
        if use_kernel in ("auto", "tl", "packed"):
            rows = 1
            for d in x_i8.shape[:-1]:
                rows *= d
            if resolve_engine(params, rows, use_kernel=use_kernel) == "tl":
                return _apply_tl(params, x_i8, x_scale, out_dtype=out_dtype,
                                 residual=residual, tables=tables)
        if use_kernel in ("auto", "packed"):
            import jax

            use_kernel = jax.default_backend() == "tpu" and params["wp"].ndim == 2
        if use_kernel:
            from ..kernels.ternary_matmul import ops as tm_ops

            # ternary_gemv owns the decode-shape dispatch: small M takes the
            # sublane weight-streaming path, larger M the tiled matmul. The
            # residual add rides the kernels' dequant epilogue.
            return tm_ops.ternary_gemv(
                x_i8, x_scale, params["wp"], params["scale"],
                out_dtype=out_dtype, residual=residual
            )
        # XLA path: unpack (fused by XLA into the matmul producer) + int matmul.
        w_t = unpack2(params["wp"])
        out = ternary.ternary_matmul_ref(
            x_i8, x_scale, w_t, params["scale"], out_dtype=out_dtype
        )
        return out if residual is None else out + residual
    if mode in ("wq", "wq_packed"):
        # weight-only quantization ablation: ternary weights, float activations.
        # (Also the exact-match twin of MLA weight absorption, which cannot
        # commute with activation quantization — see models/mla.py.)
        w = material_weight(params, mode="eval" if mode == "wq" else "packed",
                            dtype=x.dtype)
        return jnp.matmul(x, w).astype(out_dtype)
    raise ValueError(f"unknown mode {mode!r}")


def _apply_tl(params, x_i8, x_scale, *, out_dtype, residual=None, tables=None):
    """Table-lookup engine path (paper Algorithm 1, ``kernels.tl_gemv``).

    Group indices come from ``params["w_idx"]`` when precomputed (see
    :func:`with_tl_indices` / :func:`with_tl_tree`), else are derived from
    the packed weights on the fly — selectable end-to-end either way;
    precompute for speed. ``tables`` (the fused prologue's online
    precompute) skips the in-kernel table build; the ``residual`` rides the
    TL kernel's dequant epilogue, parity with the packed kernels.
    """
    from ..kernels.tl_gemv import ops as tl_ops

    if params["wp"].ndim != 2:
        raise ValueError("use_kernel='tl' supports 2-D weights only")
    w_idx = params.get("w_idx")
    if w_idx is None:
        w_idx = _tl_indices(params["wp"], TL_GROUP)
    if tables is not None and tables.shape[-1] != w_idx.shape[0] * 3**TL_GROUP:
        tables = None  # prologue tables are for a different contraction dim
    return tl_ops.tl_matmul(x_i8, x_scale, w_idx, params["scale"],
                            g=TL_GROUP, tables=tables, residual=residual,
                            out_dtype=out_dtype)


def swiglu(gate_params: dict, up_params: dict, xq: tuple, *,
           use_kernel: bool | str = "auto", act_dtype=jnp.bfloat16) -> tuple:
    """Fused packed SwiGLU: (x_i8, x_scale[, tables]) -> (h_i8, h_scale).

    Gate and up matmuls plus the dequant→SiLU→(×up)→requant epilogue run in
    one kernel (``ternary_swiglu``, or its TL twin ``tl_swiglu`` when the
    engine dispatch resolves to table-lookup) so the MLP's hidden activation
    never materializes in float; the XLA fallback is the bit-identical op
    sequence. Every side of the dispatch shares the contract: int8 in,
    int8 + per-token scale out. A tables-carrying triple (the fused
    prologue's online precompute) feeds the TL kernel's lookup directly.
    """
    x_i8, x_scale, tables = xq if len(xq) == 3 else (*xq, None)
    if use_kernel in ("auto", "tl", "packed"):
        rows = 1
        for d in x_i8.shape[:-1]:
            rows *= d
        if resolve_engine(gate_params, rows, use_kernel=use_kernel) == "tl":
            return _swiglu_tl(gate_params, up_params, x_i8, x_scale,
                              tables=tables, act_dtype=act_dtype)
    if use_kernel in ("auto", "packed"):
        import jax

        use_kernel = (jax.default_backend() == "tpu"
                      and gate_params["wp"].ndim == 2)
    if use_kernel:
        from ..kernels.ternary_matmul import ops as tm_ops

        return tm_ops.ternary_swiglu(
            x_i8, x_scale, gate_params["wp"], gate_params["scale"],
            up_params["wp"], up_params["scale"], act_dtype=act_dtype,
        )
    g = ternary.ternary_matmul_ref(
        x_i8, x_scale, unpack2(gate_params["wp"]), gate_params["scale"],
        out_dtype=act_dtype)
    u = ternary.ternary_matmul_ref(
        x_i8, x_scale, unpack2(up_params["wp"]), up_params["scale"],
        out_dtype=act_dtype)
    return ternary.quantize_act(ternary.silu_mul(g, u))


def _swiglu_tl(gate_params, up_params, x_i8, x_scale, *, tables, act_dtype):
    """TL-engine SwiGLU (``tl_swiglu_kernel``): bit-identical to the packed
    forms, with the gate/up lookups sharing one table set — precomputed by
    the prologue when available, built in-kernel otherwise."""
    from ..kernels.tl_gemv import ops as tl_ops

    if gate_params["wp"].ndim != 2:
        raise ValueError("use_kernel='tl' supports 2-D weights only")
    wg_idx = gate_params.get("w_idx")
    if wg_idx is None:
        wg_idx = _tl_indices(gate_params["wp"], TL_GROUP)
    wu_idx = up_params.get("w_idx")
    if wu_idx is None:
        wu_idx = _tl_indices(up_params["wp"], TL_GROUP)
    if tables is not None and tables.shape[-1] != wg_idx.shape[0] * 3**TL_GROUP:
        tables = None
    return tl_ops.tl_swiglu(
        x_i8, x_scale, wg_idx, gate_params["scale"], wu_idx,
        up_params["scale"], g=TL_GROUP, tables=tables, act_dtype=act_dtype)


# ---------------------------------------------------------------------------
# Dense (non-ternary) linear — embeddings / LM head / frontends stay high
# precision, per BitNet-1.58 practice.
# ---------------------------------------------------------------------------


def material_weight(params: dict, *, mode: str = "train", dtype=jnp.bfloat16):
    """Effective (dequantized) float weight for paths that need the matrix
    itself (e.g. MLA weight absorption): train -> STE fake-quant value,
    eval/wq -> ternarized, packed -> unpacked · scale."""
    if mode == "train":
        return ternary.ternarize_ste(params["w"]).astype(dtype)
    if mode in ("eval", "wq"):
        w_t, s = ternary.ternarize(params["w"])
        return (w_t.astype(jnp.float32) * s).astype(dtype)
    if mode in ("packed", "wq_packed"):
        return (unpack2(params["wp"]).astype(jnp.float32) * params["scale"]).astype(dtype)
    raise ValueError(mode)


def dense_spec(n_in: int, n_out: int, axes: tuple, *, dtype=jnp.float32, scale=None) -> dict:
    return {"w": ParamSpec((n_in, n_out), axes, dtype=dtype, scale=scale)}


def dense_apply(params: dict, x, *, out_dtype: Any = None):
    out_dtype = out_dtype or x.dtype
    return jnp.matmul(x, params["w"].astype(x.dtype)).astype(out_dtype)
