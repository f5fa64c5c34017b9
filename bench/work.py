"""The work a tick needs, counted from shapes: operations and HBM bytes.

These count what the algorithm needs for the rows that carry a request,
whatever implements it: a kernel that processes idle slots or padding does
more work than is counted here, so its share of the roofline shows that
waste, and a PR that replaces a kernel does not change the denominator.

A tick record (made by the harness around each engine tick) holds
``dec``, the attended context length of every decoding slot, and ``pre``,
``(offset, tokens)`` of every prompt chunk appended in the tick, and
``emit``, the tokens the tick emitted.

Bytes: ternary weights 2 bits each, int8 activations, bfloat16 outputs
(int8 plus one float32 scale per row for the SwiGLU hidden), bfloat16 query
and output rows, and the live KV rows at the cache's element size.
"""

from __future__ import annotations

import json
import os

import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def kv_bytes(c: dict) -> int:
    return {"bf16": 2, "int8": 1}[c["kv_cache_dtype"]]


def matmul_calls(c: dict, m: int) -> list:
    """``(int8 ops, bytes)`` of each ternary matmul of one layer for ``m``
    rows: q, k, v, o, the fused gate+up SwiGLU, and down."""
    if m <= 0:
        return []
    sh = W.matrix_shapes(c)
    out = []
    for name in ("q", "k", "v", "o", "down"):
        n, k = sh[name]
        out.append((2 * m * n * k, n * k // 4 + m * n + 2 * m * k))
    n, k = sh["gate"]
    out.append((2 * m * n * 2 * k, 2 * n * k // 4 + m * n + m * k + 4 * m))
    return out


def decode_attention_call(c: dict, ctx: int) -> tuple:
    """``(bf16 flops, bytes)`` of one layer's decode attention for one slot
    attending ``ctx`` rows."""
    m = W.dims(c)
    h, hk, hd = m["h"], m["hk"], m["hd"]
    return (4 * h * hd * ctx, 2 * hk * hd * ctx * kv_bytes(c) + 4 * h * hd)


def prefill_append_call(c: dict, off: int, r: int) -> tuple:
    """``(bf16 flops, bytes)`` of one layer's chunk attention for ``r``
    prompt rows appended at ``off``: causal over the prefix and the chunk,
    reading q/k/v of the chunk and the live prefix, writing the appended
    rows and the output."""
    m = W.dims(c)
    h, hk, hd = m["h"], m["hk"], m["hd"]
    kb = kv_bytes(c)
    flops = 4 * h * hd * (r * off + r * (r + 1) // 2)
    byts = (2 * r * h * hd + 2 * 2 * r * hk * hd + 2 * off * hk * hd * kb
            + 2 * r * hk * hd * kb + 2 * r * h * hd)
    return flops, byts


def least_s(ops: float, byts: float, peak_ops: float, bw: float) -> float:
    return max(ops / peak_ops, byts / bw)


def tick_kernel_least_s(c: dict, tick: dict, pk: dict) -> dict:
    """Least seconds each kernel family needs in one tick, at the roofline.
    The weights are read once per tick for all the tick's rows, decode and
    prompt alike."""
    L = c["num_hidden_layers"]
    i8, bf, bw = (pk["int8_ops_per_s"], pk["bf16_flops_per_s"],
                  pk["hbm_bytes_per_s"])
    rows = len(tick["dec"]) + sum(r for _, r in tick["pre"])
    mm = sum(least_s(o, b, i8, bw) for o, b in matmul_calls(c, rows))
    da = sum(least_s(*decode_attention_call(c, x), bf, bw) for x in tick["dec"])
    pa = sum(least_s(*prefill_append_call(c, o, r), bf, bw)
             for o, r in tick["pre"])
    return {"ternary_matmul": L * mm, "decode_attention": L * da,
            "prefill_append": L * pa}


def tick_model_ops(c: dict, tick: dict) -> tuple:
    """``(int8 ops, bf16 flops)`` the tick's useful work needs: ternary
    matmuls for every processed token, attention at each token's live
    context, and the LM head for each emitted token."""
    L = c["num_hidden_layers"]
    m = W.dims(c)
    rows = len(tick["dec"]) + sum(r for _, r in tick["pre"])
    i8 = L * sum(o for o, _ in matmul_calls(c, rows))
    bf = L * (sum(decode_attention_call(c, x)[0] for x in tick["dec"])
              + sum(prefill_append_call(c, o, r)[0] for o, r in tick["pre"]))
    bf += 2 * m["d"] * m["vocab"] * tick["emit"]
    return i8, bf


def tick_least_s(c: dict, tick: dict, pk: dict) -> float:
    """The least time the tick's useful work needs at peak compute."""
    i8, bf = tick_model_ops(c, tick)
    return i8 / pk["int8_ops_per_s"] + bf / pk["bf16_flops_per_s"]
