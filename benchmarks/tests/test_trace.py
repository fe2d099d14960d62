"""The reduction from a trace to numbers: the busy union, per-program
and per-kernel sums, self times, and the roofline and MFU arithmetic —
on hand-made events, and on one small trace recorded on the chip (half
a second of smollm2-1.7b.chat on a TPU v5e, PR 24's first chip call)."""

import os

import pytest

from conftest import HERE
from harness import rooflines, trace

RECORDED = os.path.join(HERE, "data", "chat_half_second.xplane.pb")
NAMES = {"programs": {"decode": ["^jit__decode_sample"],
                      "prefill": ["^jit_fused"]},
         "kernels": {"attention": ["^%closed_call[.\\d]* = .* custom-call\\("]}}
KERNEL = "%closed_call.14 = bf16[4]{0} custom-call(s32[4]{0} %x)"
FUSION = "%fusion.3 = bf16[4]{0:T(8,128)(2,1)} fusion(bf16[4]{0} %y), kind=kLoop"
WHILE = "%while.7 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t), body=%b"


def test_union_merges_overlaps():
    total, merged = trace.union_ns([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert total == 23 and merged == [[0, 12], [20, 31]]
    assert trace.union_ns([]) == (0, [])


def test_short_names_and_self_times():
    assert trace.short_name(KERNEL) == "%closed_call.14 custom-call"
    assert trace.short_name(FUSION) == "%fusion.3 fusion"
    assert trace.short_name(WHILE) == "%while.7 while"
    assert trace.short_name("bench.mark") == "bench.mark"
    # a while of 100 holding a kernel of 60 and a fusion of 30
    out = trace.self_times([(WHILE, 0, 100), (KERNEL, 5, 65),
                            (FUSION, 65, 95), (FUSION, 200, 210)])
    assert out == {"%while.7 while": 10, "%closed_call.14 custom-call": 60,
                   "%fusion.3 fusion": 40}


def test_reduce_attributes_kernels_to_the_program_they_run_in():
    dev = {"program_line": [("jit__decode_sample(1)", 0.0, 100.0),
                            ("jit_fused(2)", 150.0, 50.0),
                            ("jit_other(3)", 300.0, 10.0)],
           "op_line": [(WHILE, 0.0, 100.0), (KERNEL, 10.0, 40.0),
                       (KERNEL, 160.0, 20.0), (FUSION, 300.0, 10.0)]}
    r = trace.reduce_device(dev, NAMES, 0.0, 400.0)
    assert r["window_ns"] == 400 and r["busy_ns"] == 100 + 20 + 10
    assert r["programs"]["decode"]["ns"] == 100
    assert r["programs"]["prefill"]["count"] == 1
    assert r["programs"]["other"]["ns"] == 10
    assert r["kernels"] == {"attention": {"decode": 40.0, "prefill": 20.0}}
    gaps = sorted(r["gaps"])
    assert gaps == [(100.0, 60.0), (180.0, 120.0), (310.0, 90.0)]
    # clipped to a window that cuts the first program in half
    r = trace.reduce_device(dev, NAMES, 50.0, 170.0)
    assert r["programs"]["decode"]["ns"] == 50
    assert r["kernels"]["attention"] == {"prefill": 10.0}


def test_an_empty_device_reads_nothing():
    r = trace.reduce_device({"program_line": [], "op_line": []}, NAMES)
    assert r["window_ns"] == 0 and r["busy_ns"] == 0


def test_recorded_trace_reduces():
    s = trace.summarize(RECORDED, span_s=0.5)
    assert s["devices"] == 1 and s["mark_ns"] is not None
    assert s["window_s"] == 0.5 and 0 < s["busy_s"] <= s["window_s"]
    assert set(s["programs"]) == {"decode", "prefill"}
    assert s["programs"]["decode"]["count"] >= 1
    busy_by_program = sum(p["device_s"] for p in s["programs"].values())
    assert abs(busy_by_program - s["busy_s"]) < 0.02
    att = s["kernels"]["attention"]
    assert 0 < att["decode"] < s["programs"]["decode"]["device_s"]
    assert 0 < att["prefill"] < s["programs"]["prefill"]["device_s"]
    assert s["top_ops"][0][0] == "%closed_call.14 custom-call"
    assert len(s["top_ops"]) <= 10 and len(s["gaps"]) <= 10
    # without the span the window runs from the first event to the last
    whole = trace.summarize(RECORDED)
    assert whole["mark_ns"] is None and whole["busy_s"] >= s["busy_s"]


CFG = {"hidden_size": 2048, "intermediate_size": 8192,
       "num_hidden_layers": 24, "num_attention_heads": 32,
       "num_key_value_heads": 32, "head_dim": 64, "vocab_size": 49152}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_roofline_arithmetic_smollm2():
    assert rooflines.kv_row_bytes(CFG) == 192 * 1024
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert rooflines.layer_matmul_params(CFG) == per_layer
    pair = 4 * 24 * 32 * 64
    assert rooflines.attn_pair_flops(CFG) == pair
    dense = 2 * (24 * per_layer + 2048 * 49152)
    assert rooflines.decode_flops(CFG, [100, 300]) == 2 * dense + pair * 400
    n = 1000
    assert rooflines.prefill_flops(CFG, [n]) == pytest.approx(
        2 * 24 * per_layer * n + 2 * 2048 * 49152 + pair * n * (n + 1) / 2)
    # decode attention at context 1000 is bound by the K/V bytes
    flops, nbytes = rooflines.decode_attn_need(CFG, [1000])
    t, bound = rooflines.least_time(flops, nbytes, PEAK)
    assert bound == "bandwidth" and t == pytest.approx(1000 * 196608 / 819e9)
    # causal attention over a 4096-token prompt is bound by compute
    flops, nbytes = rooflines.prefill_attn_need(CFG, [(0, 4096)])
    assert flops == pytest.approx(pair * 4096 * 4097 / 2)
    assert rooflines.least_time(flops, nbytes, PEAK)[1] == "compute"
    # a chunk [1024, 2048) attends 2048 rows of history and itself
    f2, _ = rooflines.prefill_attn_need(CFG, [(1024, 2048)])
    f1, _ = rooflines.prefill_attn_need(CFG, [(0, 1024)])
    f12, _ = rooflines.prefill_attn_need(CFG, [(0, 2048)])
    assert f1 + f2 == pytest.approx(f12)


def test_unknown_device_kind_is_an_error():
    assert rooflines.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        rooflines.peaks("TPU v9 imaginary")
