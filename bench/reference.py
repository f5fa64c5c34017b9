"""Plain float32 reference of the served model, and the comparison that
decides ``correct``.

It imports nothing of the program. From the seed it draws the same float
weights as the served path (``weights.py``), ternarises them with its own
absmean code, and runs the model as the configuration states it: pre-norm
RMSNorm blocks, int8 per-token absmax activations before every ternary
matmul, rotary attention with grouped KV heads, a SiLU-gated MLP, and a
float32 head. Everything else is float32, with attention and the head at
``Precision.HIGHEST``; the ternary matmuls run as exact int8 x int8 -> int32
products. It runs after the window, layer by layer and in blocks of
sequences, teacher-forced over each sampled prompt and its served tokens.

The number compared is the widest gap by which a served token's reference
logit lies below the reference's best logit at that position (0 where the
served token is the reference's greedy choice).

A ``variant`` recomputes the same pass in a lower precision, as a control
that the comparison must fail: ``act_int4`` quantises activations to int4
instead of int8. (The other control, the KV cache one step down, is the
program's own int8-KV path; see ``control.py``.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST


def ternarize(w):
    scale = jnp.maximum(jnp.mean(jnp.abs(w)), 1e-8)
    return jnp.clip(jnp.round(w / scale), -1, 1).astype(jnp.int8), scale


def quant(x, bits: int = 8):
    """Per-row absmax to ``bits``-bit signed integers (held as int8)."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax).astype(jnp.int8), s


def tlinear(xq, w):
    (xi, xs), (wt, ws) = xq, w
    acc = jax.lax.dot_general(xi, wt, (((xi.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    """Rotate-half RoPE; x [B, H, S, D], pos [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_fn(c: dict, variant: str):
    m = W.dims(c)
    h, hk, hd = m["h"], m["hk"], m["hd"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    bits = 4 if variant == "act_int4" else 8

    def layer(x, w):
        b, s, _ = x.shape
        pos = jnp.arange(s)
        hq = quant(rmsnorm(x, eps), bits)
        q = tlinear(hq, w["q"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        k = tlinear(hq, w["k"]).reshape(b, s, hk, hd).transpose(0, 2, 1, 3)
        v = tlinear(hq, w["v"]).reshape(b, s, hk, hd).transpose(0, 2, 1, 3)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HI) / math.sqrt(hd)
        sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HI)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        x = x + tlinear(quant(o, bits), w["o"])
        h2 = quant(rmsnorm(x, eps), bits)
        a = jax.nn.silu(tlinear(h2, w["gate"])) * tlinear(h2, w["up"])
        return x + tlinear(quant(a, bits), w["down"])

    return jax.jit(layer)


def _layer_weights(c: dict):
    return jax.jit(lambda key, i: {n: ternarize(w) for n, w in
                                   W.draw_layer(key, i, c).items()})


ROWS = 512  # logits rows per call: one compiled shape


def run(c: dict, seed: int, seqs: list, *, seq_len: int, variants=("ref",),
        block_bytes: int = 1 << 30) -> dict:
    """Teacher-force each of ``seqs`` (``(prompt, served)`` token lists)
    through the reference, once per variant, and return for each variant
    the verdicts at every served position.

    Every call pads to ``seq_len`` positions and to whole blocks of
    sequences, so one cell compiles the same few programs every run.
    Returns ``{"ref": {"max_gap", "mean_gap", "tokens", "agree",
    "kv_bits"}, <control>: {...}}``, where a control's gap is that of the
    token the control puts first, read on the reference's logits, and
    ``kv_bits`` the precision it holds keys and values at (float32)."""
    m = W.dims(c)
    key = W.seed_key(seed)
    full = [list(p) + list(g[:-1]) for p, g in seqs]
    if max(len(t) for t in full) > seq_len:
        raise ValueError(f"a sequence is longer than seq_len {seq_len}")
    nb = max(1, block_bytes // (m["h"] * seq_len * seq_len * 4 * 3))
    toks = np.zeros((-(-len(full) // nb) * nb, seq_len), np.int32)
    for i, t in enumerate(full):
        toks[i, :len(t)] = t
    embed = jax.jit(lambda k: W.draw_embed(k, c))(key)
    gather = jax.jit(lambda e, t: e[t])
    blocks = [jnp.asarray(toks[i:i + nb]) for i in range(0, len(toks), nb)]
    xs = {v: [gather(embed, b) for b in blocks] for v in variants}
    del embed
    layers = {v: layer_fn(c, v) for v in variants}
    lw = _layer_weights(c)
    for i in range(m["layers"]):
        w = lw(key, jnp.int32(i))
        for v in variants:
            xs[v] = [layers[v](x, w) for x in xs[v]]
        del w
    head = jax.jit(lambda k: W.draw_head(k, c))(key)
    eps = float(c["rms_norm_eps"])
    logits = jax.jit(lambda x, r, w: jnp.matmul(
        rmsnorm(x.reshape(-1, x.shape[-1])[r], eps), w, precision=HI))
    out = {v: [] for v in variants}  # per row: (best, served, control's)
    for bi in range(len(blocks)):
        rows = [((i - bi * nb) * seq_len + len(p) - 1 + j, int(t))
                for i, (p, g) in enumerate(seqs)
                if bi * nb <= i < (bi + 1) * nb for j, t in enumerate(g)]
        for lo in range(0, len(rows), ROWS):
            part = rows[lo:lo + ROWS]
            n = len(part)
            r = np.zeros((ROWS,), np.int32)
            r[:n] = [p for p, _ in part]
            t = np.asarray([t for _, t in part])
            ref = np.asarray(logits(xs["ref"][bi], r, head), np.float64)[:n]
            best = ref.max(-1)
            served = ref[np.arange(n), t]
            for v in variants:
                lv = ref if v == "ref" else np.asarray(
                    logits(xs[v][bi], r, head), np.float64)[:n]
                pick = ref[np.arange(n), lv.argmax(-1)]
                out[v].append(np.stack([best, served, pick], 1))
    res = {}
    for v in variants:
        a = np.concatenate(out[v])
        gap = a[:, 0] - (a[:, 1] if v == "ref" else a[:, 2])
        res[v] = {"max_gap": float(gap.max()), "mean_gap": float(gap.mean()),
                  "tokens": int(len(gap)),
                  "agree": float(np.mean(gap == 0.0)), "kv_bits": 32}
    return res
