"""The reduction from a trace to busy time, kernel time and idle gaps, on a
hand-built trace."""

import pytest

import devtrace

OPS = [("fusion", 0, 100), ("ternary_matmul_kernel", 50, 150),
       ("decode_attention_kernel", 300, 400), ("copy", 390, 420),
       ("ternary_matmul_kernel", 700, 800), ("while", 0, 420)]


def test_merge_and_busy():
    assert devtrace.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [[0, 4],
                                                                   [5, 10]]
    # union of [0,420] (the loop holds the first ops) and [700,800]
    assert devtrace.busy_ns(OPS, 0, 1000) == 420 + 100
    # clipped to a window that cuts two intervals
    assert devtrace.busy_ns(OPS, 100, 750) == 320 + 50


def test_idle_gaps_longest_first():
    gaps = devtrace.idle_gaps(OPS, 0, 1000)
    assert gaps == [(420, 700), (800, 1000)]
    assert devtrace.idle_gaps(OPS[:5], 0, 1000)[2] == (150, 300)
    assert sum(e - s for s, e in gaps) == 1000 - devtrace.busy_ns(OPS, 0, 1000)


def test_kernel_seconds_and_totals():
    pat = ["ternary_(gemv|matmul|swiglu)_kernel"]
    s = devtrace.kernel_seconds(OPS, 0, 1000, pat)
    assert s == pytest.approx(200e-9)
    assert devtrace.kernel_seconds(OPS, 0, 720, ["decode_attention_kernel"]
                                   + pat) == pytest.approx(220e-9)
    # matched in full: a prefix of a name is not a match
    assert devtrace.kernel_seconds(OPS, 0, 1000, ["ternary"]) == 0
    tot = devtrace.op_totals(OPS, 0, 1000)
    assert tot["ternary_matmul_kernel"] == pytest.approx(200e-9)
    assert tot["decode_attention_kernel"] == pytest.approx(100e-9)
    assert tot["fusion"] == pytest.approx(100e-9)
    assert "while" not in tot


def test_op_name_from_hlo_text():
    assert devtrace.op_name("%ternary_matmul_kernel.44 = bf16[24,1536]{1,0} "
                            "custom-call(s8[24,4096] %x.1)") == \
        "ternary_matmul_kernel"
    assert devtrace.op_name("%copy-start.17 = copy-start("
                            "ternary_matmul_kernel.44)") == "copy-start"
    assert devtrace.op_name("%while.4 = (s32[]) while(%tuple.86)") == "while"


def test_label_gap_takes_innermost_span():
    spans = [("bench.window", 0, 1000), ("bench.step", 400, 760),
             ("bench.emit", 500, 600)]
    assert devtrace.label_gap(spans, 420, 700) == "bench.emit"
    assert devtrace.label_gap(spans, 800, 1000) == "host_other"
    assert devtrace.label_gap(spans, 650, 700) == "bench.step"
