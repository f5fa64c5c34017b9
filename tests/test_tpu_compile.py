"""Compile rehearsal: the main-path Pallas kernels at ``tellme-0.7b`` widths
for a described (not attached) TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses (8-bit shifts,
bf16 rounding on the vector unit, blocks off the (8, 128) tiling). These
tests compile each kernel for the real target and check that the compiled
program holds the Pallas kernel (``tpu_custom_call``). Nothing runs, so
they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import ops as da
from repro.kernels.fused_norm_quant import ops as nq
from repro.kernels.prefill_append import ops as pa
from repro.kernels.ternary_matmul import ops as tm

CFG = get_config("tellme-0.7b")
D, FF, H, HK, HD = CFG.d_model, CFG.d_ff, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
B, M = 4, 1280           # slots, cache rows (max_len 1024 + a 256-row tail)
PS = CFG.kv_page_size    # 64
P = B * M // PS + 1      # page pool, garbage page included
I8, U8, F32, BF16, I32 = jnp.int8, jnp.uint8, jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off (an entry
    compiled for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_norm_quant(one_chip):
    txt = _compile(lambda x, g: nq.norm_quant(x, g, impl="kernel",
                                              interpret=False),
                   one_chip, ((8, D), BF16), ((D,), F32))
    assert "tpu_custom_call" in txt


def test_ternary_gemv(one_chip):
    txt = _compile(lambda x, s, w, ws, r: tm.ternary_gemv(
        x, s, w, ws, out_dtype=BF16, residual=r, interpret=False),
        one_chip, ((8, FF), I8), ((8, 1), F32), ((FF // 4, D), U8),
        ((), F32), ((8, D), BF16))
    assert "tpu_custom_call" in txt


def test_ternary_matmul(one_chip):
    txt = _compile(lambda x, s, w, ws: tm.ternary_matmul(
        x, s, w, ws, out_dtype=BF16, interpret=False),
        one_chip, ((256, D), I8), ((256, 1), F32), ((D // 4, D), U8),
        ((), F32))
    assert "tpu_custom_call" in txt


def test_ternary_swiglu(one_chip):
    txt = _compile(lambda x, s, wg, gs, wu, us: tm.ternary_swiglu(
        x, s, wg, gs, wu, us, interpret=False),
        one_chip, ((8, D), I8), ((8, 1), F32), ((D // 4, FF), U8),
        ((), F32), ((D // 4, FF), U8), ((), F32))
    assert "tpu_custom_call" in txt


def _kv(kv: str, paged: bool):
    """(cache/pool shape, K/V dtype, scale shapes or ())."""
    lead = (P, HK, PS) if paged else (B, HK, M)
    dt = I8 if kv == "int8" else BF16
    scales = ((lead, F32), (lead, F32)) if kv == "int8" else ()
    return (lead + (HD,), dt), scales


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_attention(one_chip, kv, layout):
    paged = layout == "paged"
    cache, scales = _kv(kv, paged)
    table = (((B, M // PS), I32),) if paged else ()

    def fn(q, k, v, pos, *rest):
        pt = rest[0] if paged else None
        sc = rest[1:] if paged else rest
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        if paged:
            return da.decode_attention_paged(q, k, v, pt, pos, interpret=False,
                                             **kw)
        return da.decode_attention(q, k, v, pos, interpret=False, **kw)

    txt = _compile(fn, one_chip, ((B, H, HD), BF16), cache, cache,
                   ((B,), I32), *table, *scales)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_prefill_append(one_chip, kv, layout):
    """64-row chunks: the engine's narrowest, whose int8 scales are spliced
    into a 128-lane window on the contiguous layout."""
    paged = layout == "paged"
    c = 64
    cache, scales = _kv(kv, paged)
    table = (((B, M // PS), I32),) if paged else ()
    chunk = ((B, H, c, HD), BF16)

    def fn(q, kn, vn, k, v, off, *rest):
        pt = rest[0] if paged else None
        sc = rest[1:] if paged else rest
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        if paged:
            return pa.prefill_append_paged(q, kn, vn, k, v, pt, off,
                                           interpret=False, **kw)
        return pa.prefill_append(q, kn, vn, k, v, off, interpret=False, **kw)

    txt = _compile(fn, one_chip, chunk, chunk, chunk, cache, cache,
                   ((B,), I32), *table, *scales)
    assert "tpu_custom_call" in txt
