"""Operations and bytes from shapes, against counts made by hand, and the
table of peaks."""

import json
import os

import pytest

import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params", [
    # per layer: 4 d^2 (MHA q,k,v,o) + 3 d ff
    ("tellme-0.7b", 4 * 1536 ** 2 + 3 * 1536 * 4096),
    # q and o d*d, k and v d*1024 (8 kv heads x 128), 3 d ff
    ("granite-8b", 2 * 4096 ** 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336),
])
def test_matmul_counts(name, params):
    c = config(name)
    calls = work.matmul_calls(c, 1)
    assert sum(o for o, _ in calls) == 2 * params
    # 2-bit weights dominate the bytes of a one-row call
    assert sum(b for _, b in calls) >= params // 4
    calls = work.matmul_calls(c, 64)
    assert sum(o for o, _ in calls) == 2 * 64 * params
    assert work.matmul_calls(c, 0) == []


def test_matmul_bytes_tellme_q():
    c = config("tellme-0.7b")
    ops, byts = work.matmul_calls(c, 8)[0]  # q: 1536 -> 1536
    assert ops == 2 * 8 * 1536 * 1536
    assert byts == 1536 * 1536 // 4 + 8 * 1536 + 2 * 8 * 1536


@pytest.mark.parametrize("name,per_row", [
    ("tellme-0.7b", 2 * 16 * 96 * 2),  # K and V rows, 16 kv heads, bf16
    ("granite-8b", 2 * 8 * 128 * 2),
])
def test_attention_counts(name, per_row):
    c = config(name)
    h, hd = c["num_attention_heads"], c["head_dim"]
    flops, byts = work.decode_attention_call(c, 1000)
    assert flops == 4 * h * hd * 1000
    assert byts == per_row * 1000 + 4 * h * hd
    f0, _ = work.prefill_append_call(c, 0, 4)
    assert f0 == 4 * h * hd * (1 + 2 + 3 + 4)  # causal within the chunk
    f1, _ = work.prefill_append_call(c, 256, 4)
    assert f1 - f0 == 4 * h * hd * 4 * 256


def test_tick_totals():
    c = config("tellme-0.7b")
    pk = work.peaks("TPU v5 lite")
    tick = {"dec": [100, 300], "pre": [(0, 64)], "emit": 3}
    i8, bf = work.tick_model_ops(c, tick)
    assert i8 == 24 * sum(o for o, _ in work.matmul_calls(c, 66))
    assert bf == (24 * (work.decode_attention_call(c, 100)[0]
                        + work.decode_attention_call(c, 300)[0]
                        + work.prefill_append_call(c, 0, 64)[0])
                  + 2 * 1536 * 32000 * 3)
    assert work.tick_least_s(c, tick, pk) == pytest.approx(
        i8 / 393e12 + bf / 197e12)
    ks = work.tick_kernel_least_s(c, tick, pk)
    assert set(ks) == {"ternary_matmul", "decode_attention", "prefill_append"}
    assert all(v > 0 for v in ks.values())


def test_peaks_refuses_unknown_device():
    pk = work.peaks("TPU v5 lite")
    assert (pk["bf16_flops_per_s"], pk["int8_ops_per_s"],
            pk["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="TPU v4"):
        work.peaks("TPU v4")
