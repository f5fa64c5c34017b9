"""Decoder-only LM assembly covering all assigned architecture families.

A model is a *block plan*: an optional unscanned prelude (e.g. DeepSeek's
dense first layer) plus a homogeneous period of blocks scanned ``n_periods``
times (``lax.scan`` keeps the HLO size O(period) — 126-layer llama compiles
as one layer body). Families map to period contents:

  dense      [attn+mlp]                     (gemma2: [local-attn, global-attn])
  moe        [attn + (moe ∥ dense residual)]          (arctic)
  mla_moe    prelude [mla+dense]; period [mla + moe+shared]   (deepseek)
  hybrid     period of 8: mamba×7 + attn×1, moe every 2nd     (jamba)
  ssm        [rwkv time-mix + channel-mix]                    (rwkv6)

Three execution paths share the parameters (mode = train / eval / packed) —
the packed path consumes 2-bit ternary weights (TeLLMe serving form).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..core import bitlinear, ternary
from ..core.params import ParamSpec, _map_specs
from ..parallel import constrain
from . import attention as attn_ops
from . import layers as L
from . import mamba as mamba_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rwkv as rwkv_mod


@dataclasses.dataclass(frozen=True)
class LayerKind:
    mixer: str  # attn | mla | mamba | rwkv
    ffn: str  # dense | moe | moe_shared | moe_dense | rwkv_channel
    local: bool = False  # sliding-window attention (gemma2 local layers)


# ---------------------------------------------------------------------------
# Block plan
# ---------------------------------------------------------------------------


def block_plan(cfg) -> tuple[list[LayerKind], list[LayerKind], int]:
    """Returns (prelude_kinds, period_kinds, n_periods)."""
    if cfg.family == "dense":
        if cfg.local_global_period:
            period = [
                LayerKind("attn", "dense", local=(i % cfg.local_global_period == 0))
                for i in range(cfg.local_global_period)
            ]
        else:
            period = [LayerKind("attn", "dense")]
        assert cfg.n_layers % len(period) == 0
        return [], period, cfg.n_layers // len(period)
    if cfg.family == "moe":
        period = [LayerKind("attn", "moe_dense" if cfg.dense_residual else "moe")]
        return [], period, cfg.n_layers
    if cfg.family == "mla_moe":
        prelude = [LayerKind("mla", "dense")] * cfg.first_dense_layers
        period = [LayerKind("mla", "moe_shared" if cfg.n_shared_experts else "moe")]
        return prelude, period, cfg.n_layers - cfg.first_dense_layers
    if cfg.family == "hybrid":
        p = cfg.attn_layer_period
        period = []
        for i in range(p):
            mixer = "attn" if i % p == cfg.attn_layer_offset else "mamba"
            ffn = "moe" if (cfg.n_experts and i % cfg.moe_every == cfg.moe_every - 1) else "dense"
            period.append(LayerKind(mixer, ffn))
        assert cfg.n_layers % p == 0
        return [], period, cfg.n_layers // p
    if cfg.family == "ssm":
        return [], [LayerKind("rwkv", "rwkv_channel")], cfg.n_layers
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def _attn_spec(cfg) -> dict:
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "q": bitlinear.spec(d, h * hd, ("embed", "heads")),
        "k": bitlinear.spec(d, hk * hd, ("embed", "kv_heads")),
        "v": bitlinear.spec(d, hk * hd, ("embed", "kv_heads")),
        "o": bitlinear.spec(h * hd, d, ("heads", "embed")),
    }


def _ffn_spec(cfg, kind: LayerKind, *, dense_ff: int | None = None) -> dict:
    if kind.ffn == "dense":
        ff = dense_ff if dense_ff else (cfg.dense_ff if cfg.family == "mla_moe" else cfg.d_ff)
        if cfg.family in ("dense", "hybrid", "moe"):
            ff = cfg.d_ff
        return L.mlp_spec(cfg.d_model, ff)
    if kind.ffn in ("moe", "moe_shared", "moe_dense"):
        spec = {"moe": moe_mod.moe_spec(cfg.d_model, cfg.d_ff, cfg.n_experts)}
        if kind.ffn == "moe_shared":
            ff = (cfg.shared_expert_ff or cfg.d_ff) * cfg.n_shared_experts
            spec["shared"] = L.mlp_spec(cfg.d_model, ff)
        if kind.ffn == "moe_dense":
            spec["dense"] = L.mlp_spec(cfg.d_model, cfg.dense_ff or cfg.d_ff)
        return spec
    if kind.ffn == "rwkv_channel":
        return {}  # lives inside the rwkv layer spec
    raise ValueError(kind.ffn)


def layer_spec(cfg, kind: LayerKind) -> dict:
    if kind.mixer == "rwkv":
        s = rwkv_mod.rwkv_spec(cfg)
        return {
            "ln1": L.rmsnorm_spec(cfg.d_model),
            "ln2": L.rmsnorm_spec(cfg.d_model),
            "time": s["time"],
            "channel": s["channel"],
        }
    spec: dict[str, Any] = {"ln1": L.rmsnorm_spec(cfg.d_model), "ln2": L.rmsnorm_spec(cfg.d_model)}
    if kind.mixer == "attn":
        spec["attn"] = _attn_spec(cfg)
    elif kind.mixer == "mla":
        spec["attn"] = mla_mod.mla_spec(cfg)
    elif kind.mixer == "mamba":
        spec["mamba"] = mamba_mod.mamba_spec(cfg)
    else:
        raise ValueError(kind.mixer)
    if kind.ffn != "rwkv_channel":
        spec["ffn"] = _ffn_spec(cfg, kind, dense_ff=cfg.dense_ff if cfg.family == "mla_moe" else None)
    return spec


def _stack_specs(tree, n: int):
    return _map_specs(
        lambda p, s: ParamSpec(
            (n,) + s.shape, ("layers",) + s.axes, dtype=s.dtype, init=s.init,
            scale=s.scale, quant=s.quant,
        ),
        tree,
    )


FRONTEND_DIMS = {"audio": 128, "vision": 1024}


def param_specs(cfg) -> dict:
    prelude, period, n_periods = block_plan(cfg)
    specs: dict[str, Any] = {}
    if cfg.frontend != "none":
        dfe = FRONTEND_DIMS[cfg.frontend]
        specs["frontend"] = bitlinear.dense_spec(dfe, cfg.d_model, (None, "embed"))
    specs["embed"] = L.embedding_spec(cfg.padded_vocab, cfg.d_model)
    for i, kind in enumerate(prelude):
        specs[f"prelude_{i}"] = layer_spec(cfg, kind)
    specs["blocks"] = _stack_specs(
        {f"b{i}": layer_spec(cfg, k) for i, k in enumerate(period)}, n_periods
    )
    specs["final_norm"] = L.rmsnorm_spec(cfg.d_model)
    specs["lm_head"] = L.lm_head_spec(cfg.d_model, cfg.padded_vocab)
    return specs


def packed_param_specs(cfg) -> dict:
    """Serving-side spec tree: ternary weights replaced by packed+scale.

    Replaces each ``{"w": ParamSpec(quant="ternary")}`` node with
    ``{"wp": uint8 packed, "scale": f32}`` so ``bitlinear.apply`` finds the
    packed leaves at the same level it would find ``w``.
    """

    def rec(node):
        if isinstance(node, ParamSpec):
            return node
        if (
            isinstance(node, dict)
            and isinstance(node.get("w"), ParamSpec)
            and node["w"].quant == "ternary"
        ):
            out = bitlinear.packed_spec(node["w"])
            out.update({k: rec(v) for k, v in node.items() if k != "w"})
            return out
        return {k: rec(v) for k, v in node.items()}

    return rec(param_specs(cfg))


def pack_tree(params, specs):
    """Pack a trained float param tree into the serving form."""

    def rec(p, s):
        if isinstance(s, ParamSpec):
            return p
        if set(s) == {"w"} and isinstance(s["w"], ParamSpec) and s["w"].quant == "ternary":
            return bitlinear.pack_params(p["w"])
        return {k: rec(p[k], s[k]) for k in s}

    return rec(params, specs)


# ---------------------------------------------------------------------------
# Forward blocks
# ---------------------------------------------------------------------------


def _apply_attn(bp, x, cfg, kind, positions, *, mode, cache=None, pos=None,
                attn_impl="auto", prefix_limit=0, aligned=True, rope=None,
                xq=None, residual=None, use_kernel="auto", page_table=None,
                layer=None):
    """``xq`` (the fused norm-quant prologue's ``(x_i8, x_scale[, tables])``)
    replaces ``x`` as the projection input on the int8-resident path;
    ``residual`` is folded into the o-projection's dequant epilogue. ``rope``
    carries the step's precomputed (cos, sin) tables (built here when absent).
    ``aligned`` is the chunk path's offset contract (False for speculative
    verify — see ``prefill_append_attention``). ``use_kernel`` is the matmul
    engine selector threaded from ``cfg.matmul_engine`` on the packed path
    (``bitlinear.apply``'s TL-vs-packed dispatch). ``page_table`` ([B, NB]
    int32, DESIGN.md §paged-kv) switches the cache leaves' interpretation to
    page pools and routes reads/writes through the page-indirect forms.
    ``layer`` (DESIGN.md §decode) marks ``cache`` as every layer's, stacked
    and carried through the layer loop: this layer's new rows are written
    in place and the kernels read the layer from the stack."""
    b, s, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if kind.local else 0
    src = xq if xq is not None else x
    uk = use_kernel if mode == "packed" else "auto"
    with jax.named_scope("attn/qkv"):
        q = bitlinear.apply(bp["q"], src, mode=mode, out_dtype=x.dtype,
                            use_kernel=uk)
        q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        k = bitlinear.apply(bp["k"], src, mode=mode, out_dtype=x.dtype,
                            use_kernel=uk)
        k = k.reshape(b, s, hk, hd).transpose(0, 2, 1, 3)
        v = bitlinear.apply(bp["v"], src, mode=mode, out_dtype=x.dtype,
                            use_kernel=uk)
        v = v.reshape(b, s, hk, hd).transpose(0, 2, 1, 3)
        if rope is None:
            rope = L.rope_tables(positions, hd, theta=cfg.rope_theta)
        rope_h = (rope[0][:, None], rope[1][:, None])  # broadcast over heads
        q = L.apply_rope_tables(q, rope_h)
        k = L.apply_rope_tables(k, rope_h)
        q = constrain(q, "act_batch", "act_heads", None, None)
    # int8-resident cache (DESIGN.md §kv-cache): quantize at every append
    # site, dequantize inside the attention read — full-precision K/V never
    # exists in HBM. The cache dict itself carries the layout (scale leaves
    # present ⇔ int8), so every caller threads it without signature changes.
    # Train mode is exempt: the hard quant has no straight-through estimator,
    # so it would block K/V gradients — the knob is a serving-time layout,
    # and QAT of the cache would need a dedicated STE path.
    quant = cfg.kv_cache_dtype == "int8" and mode != "train"
    if page_table is not None and cache is None:
        raise ValueError("paged attention requires an existing cache pool")
    if layer is not None:  # in place on the stacked cache (kv_in_place)
        scales = ({"k_scale": cache["k_scale"], "v_scale": cache["v_scale"]}
                  if quant else {})
        if s > 1:
            out, *kv = attn_ops.prefill_append_attention(
                q, k, v, cache["k"], cache["v"], pos, **scales,
                window=window, softcap=cfg.attn_logit_softcap, impl="kernel",
                prefix_limit=prefix_limit, layer=layer,
            )
        else:
            out, *kv = attn_ops.decode_attention(
                q[:, :, 0], cache["k"], cache["v"], pos, **scales,
                window=window, softcap=cfg.attn_logit_softcap, impl="kernel",
                layer=layer, k_new=k[:, :, 0], v_new=v[:, :, 0],
            )
            out = out[:, :, None, :].transpose(0, 2, 1, 3)
        new_cache = dict(zip(("k", "v", "k_scale", "v_scale"), kv))
    elif cache is None:  # prefill / train
        if quant:
            # quantize-then-attend: one-shot prefill sees the same
            # dequantized rows every later reader (and the chunked prefill
            # path) will, so chunked ≡ one-shot survives on the int8 path.
            k_i8, ks = ternary.quantize_kv(k)
            v_i8, vs = ternary.quantize_kv(v)
            k = ternary.dequantize_kv(k_i8, ks, k.dtype)
            v = ternary.dequantize_kv(v_i8, vs, v.dtype)
            new_cache = {"k": k_i8, "k_scale": ks, "v": v_i8, "v_scale": vs}
        out = attn_ops.prefill_attention(
            q, k, v, window=window, softcap=cfg.attn_logit_softcap,
        )
        if not quant:
            new_cache = {"k": k, "v": v}
    elif s > 1:  # mode="prefill_chunk": chunk attends to cache prefix + self
        if page_table is not None:
            if quant:
                out, k_c, v_c, ks_c, vs_c = attn_ops.prefill_append_attention_paged(
                    q, k, v, cache["k"], cache["v"], page_table, pos,
                    k_scale=cache["k_scale"], v_scale=cache["v_scale"],
                    window=window, softcap=cfg.attn_logit_softcap,
                    impl=attn_impl, prefix_limit=prefix_limit, aligned=aligned,
                )
                new_cache = {"k": k_c, "k_scale": ks_c, "v": v_c,
                             "v_scale": vs_c}
            else:
                out, k_c, v_c = attn_ops.prefill_append_attention_paged(
                    q, k, v, cache["k"], cache["v"], page_table, pos,
                    window=window, softcap=cfg.attn_logit_softcap,
                    impl=attn_impl, prefix_limit=prefix_limit, aligned=aligned,
                )
                new_cache = {"k": k_c, "v": v_c}
        elif quant:
            out, k_c, v_c, ks_c, vs_c = attn_ops.prefill_append_attention(
                q, k, v, cache["k"], cache["v"], pos,
                k_scale=cache["k_scale"], v_scale=cache["v_scale"],
                window=window, softcap=cfg.attn_logit_softcap, impl=attn_impl,
                prefix_limit=prefix_limit, aligned=aligned,
            )
            new_cache = {"k": k_c, "k_scale": ks_c, "v": v_c, "v_scale": vs_c}
        else:
            out, k_c, v_c = attn_ops.prefill_append_attention(
                q, k, v, cache["k"], cache["v"], pos,
                window=window, softcap=cfg.attn_logit_softcap, impl=attn_impl,
                prefix_limit=prefix_limit, aligned=aligned,
            )
            new_cache = {"k": k_c, "v": v_c}
    else:
        if page_table is not None:
            ps = cache["k"].shape[2]
            if quant:
                with jax.named_scope("attn/kv_write"):
                    k_i8, ks_n = ternary.quantize_kv(k[:, :, 0])
                    v_i8, vs_n = ternary.quantize_kv(v[:, :, 0])
                    k_c = ternary.update_kv_pages(cache["k"], page_table,
                                                  k_i8, pos, ps)
                    v_c = ternary.update_kv_pages(cache["v"], page_table,
                                                  v_i8, pos, ps)
                    ks_c = ternary.update_kv_pages(cache["k_scale"],
                                                   page_table, ks_n, pos, ps)
                    vs_c = ternary.update_kv_pages(cache["v_scale"],
                                                   page_table, vs_n, pos, ps)
                out = attn_ops.decode_attention_paged(
                    q[:, :, 0], k_c, v_c, page_table, pos, k_scale=ks_c,
                    v_scale=vs_c, window=window,
                    softcap=cfg.attn_logit_softcap, impl=attn_impl,
                )[:, :, None, :].transpose(0, 2, 1, 3)
                new_cache = {"k": k_c, "k_scale": ks_c, "v": v_c,
                             "v_scale": vs_c}
            else:
                with jax.named_scope("attn/kv_write"):
                    k_c = ternary.update_kv_pages(cache["k"], page_table,
                                                  k[:, :, 0], pos, ps)
                    v_c = ternary.update_kv_pages(cache["v"], page_table,
                                                  v[:, :, 0], pos, ps)
                out = attn_ops.decode_attention_paged(
                    q[:, :, 0], k_c, v_c, page_table, pos, window=window,
                    softcap=cfg.attn_logit_softcap, impl=attn_impl,
                )[:, :, None, :].transpose(0, 2, 1, 3)
                new_cache = {"k": k_c, "v": v_c}
        elif quant:
            k_c, v_c, ks_c, vs_c = attn_ops.update_kv_cache_quant(
                cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
                k[:, :, 0], v[:, :, 0], pos
            )
            out = attn_ops.decode_attention(
                q[:, :, 0], k_c, v_c, pos, k_scale=ks_c, v_scale=vs_c,
                window=window, softcap=cfg.attn_logit_softcap, impl=attn_impl,
            )[:, :, None, :].transpose(0, 2, 1, 3)
            new_cache = {"k": k_c, "k_scale": ks_c, "v": v_c, "v_scale": vs_c}
        else:
            k_c, v_c = attn_ops.update_kv_cache(
                cache["k"], cache["v"], k[:, :, 0].astype(cache["k"].dtype),
                v[:, :, 0].astype(cache["v"].dtype), pos
            )
            out = attn_ops.decode_attention(
                q[:, :, 0], k_c, v_c, pos, window=window,
                softcap=cfg.attn_logit_softcap, impl=attn_impl,
            )[:, :, None, :].transpose(0, 2, 1, 3)
            new_cache = {"k": k_c, "v": v_c}
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    out = constrain(out, "act_batch", None, "act_heads")
    return bitlinear.apply(bp["o"], out, mode=mode, out_dtype=x.dtype,
                           residual=residual, use_kernel=uk), new_cache


def _apply_ffn(fp, x, cfg, kind, pcfg, *, mode):
    aux = jnp.float32(0.0)
    if kind.ffn == "dense":
        return L.mlp(fp, x, mode=mode), aux
    if kind.ffn in ("moe", "moe_shared", "moe_dense"):
        out, aux = moe_mod.moe_ffn(
            fp["moe"], x, top_k=cfg.experts_per_tok,
            capacity_factor=cfg.capacity_factor,
            group_size=pcfg.moe_group_size if pcfg else 1024, mode=mode,
        )
        if kind.ffn == "moe_shared":
            out = out + L.mlp(fp["shared"], x, mode=mode)
        if kind.ffn == "moe_dense":
            out = out + L.mlp(fp["dense"], x, mode=mode)
        return out, aux
    raise ValueError(kind.ffn)


def apply_block(kind: LayerKind, bp, x, cfg, pcfg, positions, *, mode, cache=None,
                pos=None, attn_impl="auto", prefix_limit=0, aligned=True,
                rope=None, fused=None, page_table=None, layer=None):
    """Returns (x, new_cache, aux).

    ``layer`` marks an attention mixer's ``cache`` as the stacked carry of
    the in-place path (:func:`kv_in_place`).

    ``rope`` is the step's precomputed table dict from :func:`rope_for`
    (per-mixer (cos, sin); built lazily when absent). ``fused`` routes
    attn+dense blocks through the int8-resident NQD pipeline — default on
    for ``mode="packed"`` (bit-identical to the unfused path), off
    elsewhere; non-eligible mixers/ffns fall through to the unfused form.
    """
    aux = jnp.float32(0.0)
    rope = rope or {}
    if fused is None:
        fused = mode == "packed"
    if page_table is not None and kind.mixer != "attn":
        raise NotImplementedError(
            f"paged KV layout is implemented for the attn mixer only, "
            f"not {kind.mixer!r}")
    if kind.mixer == "rwkv":
        st = cache or {
            "wkv": jnp.zeros((x.shape[0], cfg.d_model // cfg.rwkv_head_dim,
                              cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32),
            "x_time": jnp.zeros((x.shape[0], 1, cfg.d_model), x.dtype),
            "x_chan": jnp.zeros((x.shape[0], 1, cfg.d_model), x.dtype),
        }
        h = L.rmsnorm(bp["ln1"], x, eps=cfg.norm_eps)
        if cache is None or x.shape[1] > 1:
            y, x_last, wkv = rwkv_mod.time_mix(
                bp["time"], h, st["x_time"].astype(h.dtype), st["wkv"], cfg, mode=mode
            )
        else:
            y, tstate = rwkv_mod.time_mix_decode(bp["time"], h, {"wkv": st["wkv"],
                                                                 "x_time": st["x_time"]},
                                                 cfg, mode=mode)
            x_last, wkv = tstate["x_time"], tstate["wkv"]
        x = x + y
        h2 = L.rmsnorm(bp["ln2"], x, eps=cfg.norm_eps)
        y2, x_chan = rwkv_mod.channel_mix(bp["channel"], h2, st["x_chan"].astype(h2.dtype),
                                          mode=mode)
        x = x + y2
        return x, {"wkv": wkv, "x_time": x_last, "x_chan": x_chan}, aux

    if cache is not None and x.shape[1] > 1 and kind.mixer != "attn":
        raise NotImplementedError(
            f"prefill_chunk (multi-token step against a cache) is only "
            f"implemented for the attn mixer, not {kind.mixer!r}"
        )
    if fused and mode == "packed" and kind.mixer == "attn" and kind.ffn == "dense":
        # Int8-resident fast path (DESIGN.md §norm-quant): the norm-quant
        # prologue feeds the projections pre-quantized, the o/down matmuls
        # absorb the residual adds, and the SwiGLU hidden never leaves the
        # matmul pipeline as float. Bit-identical to the unfused branch.
        # When the matmul engine resolves to table-lookup for the consuming
        # projections, the prologue also emits the TL group tables in the
        # same VMEM pass (the paper's online precomputation, fused).
        engine = getattr(cfg, "matmul_engine", "auto")
        rows = x.shape[0] * x.shape[1]
        t1 = bitlinear.resolve_engine(bp["attn"]["q"], rows,
                                      use_kernel=engine) == "tl"
        with jax.named_scope("attn/qkv"):
            hq = L.norm_quant(bp["ln1"], x, eps=cfg.norm_eps, tables=t1)
        x, new_cache = _apply_attn(bp["attn"], x, cfg, kind, positions, mode=mode,
                                   cache=cache, pos=pos, attn_impl=attn_impl,
                                   prefix_limit=prefix_limit, aligned=aligned,
                                   rope=rope.get("attn"), xq=hq, residual=x,
                                   use_kernel=engine, page_table=page_table,
                                   layer=layer)
        x = constrain(x, "act_batch", "act_seq", None)
        t2 = bitlinear.resolve_engine(bp["ffn"]["gate"], rows,
                                      use_kernel=engine) == "tl"
        with jax.named_scope("mlp"):
            h2q = L.norm_quant(bp["ln2"], x, eps=cfg.norm_eps, tables=t2)
            x = L.mlp_fused(bp["ffn"], h2q, out_dtype=x.dtype, residual=x,
                            use_kernel=engine)
        x = constrain(x, "act_batch", "act_seq", None)
        return x, new_cache, aux

    h = L.rmsnorm(bp["ln1"], x, eps=cfg.norm_eps)
    if kind.mixer == "attn":
        y, new_cache = _apply_attn(bp["attn"], h, cfg, kind, positions, mode=mode,
                                   cache=cache, pos=pos, attn_impl=attn_impl,
                                   prefix_limit=prefix_limit, aligned=aligned,
                                   rope=rope.get("attn"), page_table=page_table,
                                   layer=layer)
    elif kind.mixer == "mla":
        if cache is None:
            y, new_cache = mla_mod.mla_prefill(bp["attn"], h, cfg, positions, mode=mode,
                                               rope=rope.get("mla"))
        else:
            y, new_cache = mla_mod.mla_decode(bp["attn"], h, cfg, cache, pos, mode=mode,
                                              rope=rope.get("mla"))
    elif kind.mixer == "mamba":
        if cache is None:
            y, new_cache = mamba_mod.mamba_prefill(bp["mamba"], h, cfg, mode=mode)
        else:
            y, new_cache = mamba_mod.mamba_decode(bp["mamba"], h, cfg, cache, mode=mode)
    else:
        raise ValueError(kind.mixer)
    x = x + y
    x = constrain(x, "act_batch", "act_seq", None)
    with jax.named_scope("mlp"):
        h2 = L.rmsnorm(bp["ln2"], x, eps=cfg.norm_eps)
        y2, aux = _apply_ffn(bp["ffn"], h2, cfg, kind, pcfg, mode=mode)
    x = x + y2
    x = constrain(x, "act_batch", "act_seq", None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------


def rope_for(cfg, positions):
    """Per-mixer RoPE tables for one step, computed once and threaded through
    every layer (satellite of DESIGN.md §norm-quant: the tables are loop-
    invariant across the scanned layer stack, so per-layer trig was waste)."""
    prelude, period, _ = block_plan(cfg)
    mixers = {k.mixer for k in prelude + period}
    tables = {}
    if "attn" in mixers:
        tables["attn"] = L.rope_tables(positions, cfg.head_dim, theta=cfg.rope_theta)
    if "mla" in mixers:
        tables["mla"] = L.rope_tables(positions, cfg.qk_rope_head_dim,
                                      theta=cfg.rope_theta)
    return tables


def embed_inputs(params, batch, cfg):
    """tokens [B,S] or embeddings [B,S,Dfe] -> [B,S,d]."""
    if cfg.frontend != "none" and "embeddings" in batch:
        x = bitlinear.dense_apply(params["frontend"], batch["embeddings"].astype(cfg.dtype))
    else:
        x = L.embed(params["embed"], batch["tokens"], dtype=cfg.dtype)
    return constrain(x, "act_batch", "act_seq", None)


def forward(params, batch, cfg, pcfg=None, *, mode="train", collect_cache=False,
            fused=None):
    """Full-sequence pass. Returns (logits [B,S,V], aux, caches|None)."""
    prelude, period, n_periods = block_plan(cfg)
    x = embed_inputs(params, batch, cfg)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    rope = rope_for(cfg, positions)

    caches: dict[str, Any] = {}
    aux_total = jnp.float32(0.0)
    for i, kind in enumerate(prelude):
        x, c, aux = apply_block(kind, params[f"prelude_{i}"], x, cfg, pcfg, positions,
                                mode=mode, rope=rope, fused=fused)
        aux_total += aux
        if collect_cache:
            caches[f"prelude_{i}"] = c

    def body(carry, pparams):
        x = carry
        aux_p = jnp.float32(0.0)
        cs = {}
        for i, kind in enumerate(period):
            x, c, aux = apply_block(kind, pparams[f"b{i}"], x, cfg, pcfg, positions,
                                    mode=mode, rope=rope, fused=fused)
            aux_p += aux
            cs[f"b{i}"] = c
        return x, (aux_p, cs if collect_cache else None)

    if pcfg is not None and pcfg.remat == "full" and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)
    elif pcfg is not None and pcfg.remat == "dots" and mode == "train":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots, prevent_cse=False
        )
    x, (aux_ps, period_caches) = jax.lax.scan(body, x, params["blocks"])
    aux_total += aux_ps.sum()
    if collect_cache:
        caches["blocks"] = period_caches

    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
        logits = L.lm_head(params["lm_head"], x,
                           softcap=cfg.final_logit_softcap)
    logits = constrain(logits, "act_batch", "act_seq", "act_vocab")
    return logits, aux_total, (caches if collect_cache else None)


def loss_fn(params, batch, cfg, pcfg=None, *, mode="train", aux_weight=0.01):
    logits, aux, _ = forward(params, batch, cfg, pcfg, mode=mode)
    ce = L.cross_entropy(logits, batch["labels"])
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


def kv_in_place(attn_impl: str, *, paged: bool = False,
                aligned: bool = True) -> bool:
    """Whether a cached step writes the attention KV cache in place
    (DESIGN.md §decode): the stacked cache rides the layer loop's carry,
    each layer writes only its new rows into it, and the Pallas kernels read
    the layer from the stack through their index maps — nothing the size of
    the cache is sliced out, rewritten or restacked.

    It needs the kernels: the XLA form (the CPU, ``attn_impl="xla"``, the
    GSPMD dry-run lowerings) keeps the scan's per-layer slices and the
    sharding-safe rewrite (``attn_ops.update_kv_cache``), as per-slot
    scatter indices would all-gather a sharded cache. The paged pools and
    the unaligned speculative verify chunks keep the rewrite too."""
    if attn_impl == "auto":
        attn_impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    return attn_impl == "kernel" and not paged and aligned


def _layer_loop(period, n_periods, block, x, params_blocks, caches_blocks,
                in_place):
    """Scan the period over the layer stack; returns (x, new block caches).

    ``block(kind, bp, x, cache, layer) -> (x, cache)`` applies one block.
    Caches ride the scan as ``xs`` -> ``ys``, one layer's slice per step —
    except, ``in_place``, the attention mixers' caches: those ride the carry
    whole and each step gets ``layer``. Their int8 scale leaves take the
    kernels' [L, B, HK, 1, M] form for the loop (one relayout each way per
    step, as the kernels read a (1, bkv) scale block)."""
    carried = {f"b{i}" for i, k in enumerate(period)
               if in_place and k.mixer == "attn"}

    def to_carry(c, fn):
        return {n: fn(a) if n.endswith("_scale") else a for n, a in c.items()}

    kv = {n: to_carry(caches_blocks[n], lambda a: a[..., None, :])
          for n in carried}
    scanned = {n: c for n, c in caches_blocks.items() if n not in carried}

    def body(carry, xs):
        x, kv = carry
        pparams, pcaches, layer = xs
        kv, cs = dict(kv), {}
        for i, kind in enumerate(period):
            n = f"b{i}"
            if n in carried:
                x, kv[n] = block(kind, pparams[n], x, kv[n], layer)
            else:
                x, cs[n] = block(kind, pparams[n], x, pcaches[n], None)
        return (x, kv), cs

    layers = jnp.arange(n_periods, dtype=jnp.int32)
    (x, kv), cs = jax.lax.scan(body, (x, kv), (params_blocks, scanned, layers))
    cs.update({n: to_carry(c, lambda a: a[..., 0, :]) for n, c in kv.items()})
    return x, cs


def decode_step(params, batch, caches, pos, cfg, *, mode="eval", attn_impl="auto",
                fused=None, page_table=None):
    """One autoregressive step. batch {tokens [B,1] | embeddings [B,1,Dfe]};
    caches from ``forward(collect_cache=True)`` (or abstract cache_specs);
    pos [B] write/attend position. Returns (logits [B, V], new caches).

    ``attn_impl`` routes the attention mixers' cache read: ``"kernel"`` is the
    fused Pallas decode-attention path (frontier skipping over the padded
    cache), ``"xla"`` the dense form, ``"auto"`` kernel-on-TPU. ``fused``
    routes the linear path through the int8-resident NQD pipeline (default:
    on for ``mode="packed"``; bit-identical either way). ``page_table``
    ([B, NB] int32) flags the caches as page pools (DESIGN.md §paged-kv) —
    it is constant across the scanned layers, so it threads as a closure
    capture, one table shared by every layer's pool."""
    prelude, period, n_periods = block_plan(cfg)
    x = embed_inputs(params, batch, cfg)
    b = x.shape[0]
    pos = jnp.asarray(pos)  # scalar (synchronized) or [B] (per-slot)
    positions = jnp.broadcast_to(pos, (b,))[:, None]
    rope = rope_for(cfg, positions)

    new_caches: dict[str, Any] = {}
    for i, kind in enumerate(prelude):
        x, c, _ = apply_block(kind, params[f"prelude_{i}"], x, cfg, None, positions,
                              mode=mode, cache=caches[f"prelude_{i}"], pos=pos,
                              attn_impl=attn_impl, rope=rope, fused=fused,
                              page_table=page_table)
        new_caches[f"prelude_{i}"] = c

    def block(kind, bp, x, cache, layer):
        x, c, _ = apply_block(kind, bp, x, cfg, None, positions, mode=mode,
                              cache=cache, pos=pos, attn_impl=attn_impl,
                              rope=rope, fused=fused, page_table=page_table,
                              layer=layer)
        return x, c

    in_place = kv_in_place(attn_impl, paged=page_table is not None)
    x, new_caches["blocks"] = _layer_loop(period, n_periods, block, x,
                                          params["blocks"], caches["blocks"],
                                          in_place)

    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
        logits = L.lm_head(params["lm_head"], x,
                           softcap=cfg.final_logit_softcap)
    return logits[:, 0], new_caches


def prefill_chunk_step(params, batch, caches, offset, cfg, *, mode="eval",
                       attn_impl="auto", last_row=None, prefix_limit=0,
                       aligned=True, fused=None, page_table=None):
    """One chunked-prefill step (``mode="prefill_chunk"``): a C-token chunk per
    slot runs against the batched caches, appending each layer's K/V at the
    slot's ``offset`` and attending to the cache prefix + itself.

    batch {tokens [B, C]}; caches as in ``decode_step`` (seq length M must be
    a multiple of C); offset [B] per-slot cache frontier (``≡ 0 mod C`` — the
    engine's chunk schedule guarantees it). Returns (logits, new caches with
    the chunk's K/V written in place). With ``last_row=None`` logits cover
    every chunk row ([B, C, V]); with ``last_row [B]`` set, each slot's hidden
    state is gathered at that row *before* the LM head, so only [B, V] logits
    are computed — the serving tick needs one row per finishing slot, and the
    full-vocab head over all C rows is the dominant per-tick matmul otherwise.
    ``attn_impl`` routes the chunk attention through the fused Pallas
    ``prefill_append`` kernel ("kernel"), the dense XLA form ("xla"), or
    backend-default ("auto").
    """
    prelude, period, n_periods = block_plan(cfg)
    x = embed_inputs(params, batch, cfg)
    b, c = x.shape[:2]
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    positions = offset[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    rope = rope_for(cfg, positions)

    new_caches: dict[str, Any] = {}
    for i, kind in enumerate(prelude):
        x, cch, _ = apply_block(kind, params[f"prelude_{i}"], x, cfg, None, positions,
                                mode=mode, cache=caches[f"prelude_{i}"], pos=offset,
                                attn_impl=attn_impl, prefix_limit=prefix_limit,
                                aligned=aligned, rope=rope, fused=fused,
                                page_table=page_table)
        new_caches[f"prelude_{i}"] = cch

    def block(kind, bp, x, cache, layer):
        x, c, _ = apply_block(kind, bp, x, cfg, None, positions, mode=mode,
                              cache=cache, pos=offset, attn_impl=attn_impl,
                              prefix_limit=prefix_limit, aligned=aligned,
                              rope=rope, fused=fused, page_table=page_table,
                              layer=layer)
        return x, c

    in_place = kv_in_place(attn_impl, paged=page_table is not None,
                           aligned=aligned)
    x, new_caches["blocks"] = _layer_loop(period, n_periods, block, x,
                                          params["blocks"], caches["blocks"],
                                          in_place)

    with jax.named_scope("head"):
        if last_row is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(last_row, jnp.int32)[:, None, None], axis=1)
        x = L.rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
        logits = L.lm_head(params["lm_head"], x,
                           softcap=cfg.final_logit_softcap)
    if last_row is not None:
        return logits[:, 0], new_caches
    return logits, new_caches


def verify_chunk_step(params, batch, caches, offset, cfg, *, mode="eval",
                      attn_impl="auto", prefix_limit=0, fused=None,
                      page_table=None):
    """Speculative verify step (DESIGN.md §speculative): run a ``γ+1``-token
    chunk — ``[current token, γ drafted tokens]`` — at each slot's cache
    frontier ``offset`` and return logits at *every* chunk row.

    batch {tokens [B, C]}; offset [B] per-slot frontier — **arbitrary**, not
    ``≡ 0 (mod C)`` like the prefill chunk path (a decode frontier lands
    wherever the previous acceptance left it). Returns
    (logits [B, C, V], new caches): row ``j``'s logits are the model's
    distribution after consuming chunk rows ``0..j`` against the cache prefix
    — exactly what ``decode_step`` would have produced token-by-token — so
    acceptance at row ``j`` can compare draft ``j+1`` against the model in
    one pass. Runs on both KV-cache dtypes (bf16 / int8 + scale side arrays,
    quantized at the same append sites) and through the fused norm→quant
    pipeline (``fused``, default on for ``mode="packed"``).

    The chunk's K/V land at ``[offset, offset+C)``; on rejection the engine
    *rewinds its frontier pointer* instead of cleaning those rows — they are
    dead to every subsequent read and overwritten by the next tick's chunk
    (see ``core.ternary.mask_past_frontier`` for the invariant).

    ``attn_impl``: the kernel path of ``prefill_append`` is held to
    chunk-aligned frontiers (the paged kernel's aliased page windows need
    them) — verify offsets are not — so this step threads
    ``aligned=False`` down to ``prefill_append_attention``, which resolves
    ``"auto"`` to the XLA append form even on TPU and rejects an explicit
    ``"kernel"`` rather than mis-writing the cache (a frontier-aligned
    kernel variant is future work, DESIGN.md §speculative).
    """
    return prefill_chunk_step(params, batch, caches, offset, cfg, mode=mode,
                              attn_impl=attn_impl, last_row=None,
                              prefix_limit=prefix_limit, aligned=False,
                              fused=fused, page_table=page_table)


# ---------------------------------------------------------------------------
# Cache declarations (abstract, for the decode dry-run)
# ---------------------------------------------------------------------------


def _kind_cache_spec(cfg, kind: LayerKind, batch: int, seq: int, dtype,
                     kv_pages=None):
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    if kind.mixer == "attn":
        if kv_pages is not None:
            # Paged layout (DESIGN.md §paged-kv): one page *pool* shared by
            # every slot, [P, HK, page_size, D] (+ [P, HK, page_size] f32
            # scales for int8), addressed through the engine's page table.
            # The axes deliberately avoid "act_kv_seq": resize/guard
            # machinery keyed on that name (grow/fit, scale_guard,
            # rollback masking) is frontier arithmetic on contiguous rows
            # and does not apply to a pool — the page allocator owns those
            # invariants instead.
            ps = cfg.kv_page_size
            pool_axes = ("kv_pages", "act_kv_heads", "kv_page_seq", None)
            scale_axes = ("kv_pages", "act_kv_heads", "kv_page_seq")
            if cfg.kv_cache_dtype == "int8":
                return {
                    "k": (jax.ShapeDtypeStruct((kv_pages, hk, ps, hd),
                                               jnp.int8), pool_axes),
                    "k_scale": (jax.ShapeDtypeStruct((kv_pages, hk, ps),
                                                     jnp.float32), scale_axes),
                    "v": (jax.ShapeDtypeStruct((kv_pages, hk, ps, hd),
                                               jnp.int8), pool_axes),
                    "v_scale": (jax.ShapeDtypeStruct((kv_pages, hk, ps),
                                                     jnp.float32), scale_axes),
                }
            return {
                "k": (jax.ShapeDtypeStruct((kv_pages, hk, ps, hd), dtype),
                      pool_axes),
                "v": (jax.ShapeDtypeStruct((kv_pages, hk, ps, hd), dtype),
                      pool_axes),
            }
        if cfg.kv_cache_dtype == "int8":
            # int8 data + per-(slot, head, row) f32 absmax scale side arrays
            # (DESIGN.md §kv-cache). The scale leaves carry act_kv_seq so the
            # path-based grow/fit machinery resizes them with their caches.
            return {
                "k": (jax.ShapeDtypeStruct((batch, hk, seq, hd), jnp.int8),
                      ("act_batch", "act_kv_heads", "act_kv_seq", None)),
                "k_scale": (jax.ShapeDtypeStruct((batch, hk, seq), jnp.float32),
                            ("act_batch", "act_kv_heads", "act_kv_seq")),
                "v": (jax.ShapeDtypeStruct((batch, hk, seq, hd), jnp.int8),
                      ("act_batch", "act_kv_heads", "act_kv_seq", None)),
                "v_scale": (jax.ShapeDtypeStruct((batch, hk, seq), jnp.float32),
                            ("act_batch", "act_kv_heads", "act_kv_seq")),
            }
        return {
            "k": (jax.ShapeDtypeStruct((batch, hk, seq, hd), dtype),
                  ("act_batch", "act_kv_heads", "act_kv_seq", None)),
            "v": (jax.ShapeDtypeStruct((batch, hk, seq, hd), dtype),
                  ("act_batch", "act_kv_heads", "act_kv_seq", None)),
        }
    if kind.mixer == "mla":
        return {
            "c_kv": (jax.ShapeDtypeStruct((batch, seq, cfg.kv_lora_rank), dtype),
                     ("act_batch", "act_kv_seq", None)),
            "k_rope": (jax.ShapeDtypeStruct((batch, seq, cfg.qk_rope_head_dim), dtype),
                       ("act_batch", "act_kv_seq", None)),
        }
    if kind.mixer == "mamba":
        di = cfg.mamba_expand * cfg.d_model
        return {
            "ssm": (jax.ShapeDtypeStruct((batch, di, cfg.mamba_d_state), jnp.float32),
                    ("act_batch", "act_mlp", None)),
            "conv": (jax.ShapeDtypeStruct((batch, cfg.mamba_d_conv - 1, di), dtype),
                     ("act_batch", None, "act_mlp")),
        }
    if kind.mixer == "rwkv":
        h = cfg.d_model // cfg.rwkv_head_dim
        n = cfg.rwkv_head_dim
        return {
            "wkv": (jax.ShapeDtypeStruct((batch, h, n, n), jnp.float32),
                    ("act_batch", "act_heads", None, None)),
            "x_time": (jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dtype),
                       ("act_batch", None, None)),
            "x_chan": (jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dtype),
                       ("act_batch", None, None)),
        }
    raise ValueError(kind.mixer)


def cache_specs(cfg, batch: int, seq: int, dtype=jnp.bfloat16, *,
                kv_pages=None):
    """(ShapeDtypeStruct tree, logical-axes tree) for the KV/state caches.

    ``cfg.kv_cache_dtype == "int8"`` switches attention-mixer caches to the
    int8 + scale-side-array layout (DESIGN.md §kv-cache); non-attention
    state (MLA latents, mamba/rwkv recurrent state) is always dense, so the
    knob is a no-op for archs without an attn mixer.

    ``kv_pages`` (int, DESIGN.md §paged-kv) switches attention-mixer caches
    to the page-pool layout with that many pages. It is an *explicit* opt-in
    rather than keyed on ``cfg.kv_layout``: only the serving engine pages —
    ``generate``/``forward``/training always build contiguous caches, even
    under a paged config.
    """
    if cfg.kv_cache_dtype not in ("bf16", "int8"):
        raise ValueError(f"kv_cache_dtype must be 'bf16' or 'int8', got "
                         f"{cfg.kv_cache_dtype!r}")
    prelude, period, n_periods = block_plan(cfg)

    def split(tree):
        shapes = {k: (split(v) if isinstance(v, dict) else v[0]) for k, v in tree.items()}
        return shapes

    def axes(tree):
        return {k: (axes(v) if isinstance(v, dict) else v[1]) for k, v in tree.items()}

    full: dict[str, Any] = {}
    for i, kind in enumerate(prelude):
        full[f"prelude_{i}"] = _kind_cache_spec(cfg, kind, batch, seq, dtype,
                                                kv_pages=kv_pages)
    blocks = {}
    for i, kind in enumerate(period):
        one = _kind_cache_spec(cfg, kind, batch, seq, dtype,
                               kv_pages=kv_pages)
        blocks[f"b{i}"] = {
            k: (jax.ShapeDtypeStruct((n_periods,) + v[0].shape, v[0].dtype),
                ("layers",) + v[1])
            for k, v in one.items()
        }
    full["blocks"] = blocks
    return split(full), axes(full)
