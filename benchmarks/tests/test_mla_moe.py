"""What PR 28 added for the ``deepseek_v3`` family: the counts of
``harness/rooflines_mla_moe.py`` against the configuration's own
arithmetic, the four per-layer readers on a hand-made trace summary and
flight log (a known number in, a known number out; nothing to read,
``None``), the ``long-doc`` mix through the one generator, and run.py's
rehearsal of the new cell on the CPU at a toy size of the same shape."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, REPO
from harness import rooflines, rooflines_mla_moe as need, serve, spans
from harness import stats, traffic

from gofr_tpu.serving import observability as obs

CELL = "kanana-2-30b-a3b-6l.long-doc"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
MARK_NS, T0, T1 = 5e9, 100.0, 110.0


@pytest.fixture(scope="module")
def cfg():
    return serve.load_config("kanana-2-30b-a3b-6l")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(BENCH, "metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def context(cfg, records, trace, peak=PEAK):
    return {"cfg": cfg, "records": records, "peak": peak, "chips": 1,
            "rooflines": rooflines, "stats": stats,
            "traced": {"t_start": T0, "t_end": T1},
            "trace": {"mark_ns": MARK_NS, "window_s": 10.0, "gaps": [],
                      **trace}}


def record(prompt_len, times):
    return {"prompt": [1] * prompt_len, "token_times": times,
            "tokens": [1] * len(times), "max_tokens": len(times),
            "done": True, "error": None, "dropped": False, "sent": 99.0}


# ------------------------------------------------------------ the counts

def test_parameter_counts_are_the_configurations(cfg):
    """The arithmetic of the configuration's ``deployment``."""
    assert need.attn_params(cfg) == 12_582_912 + 1_179_648 + 4_194_304 \
        + 8_388_608                                         # 26.35 M
    assert need.head_params(cfg) == 2048 * 128256           # 262.7 M
    active_expert_layer = 2048 * 128 + 3 * 2048 * (1536 + 6 * 768)
    assert need.active_matmul_params(cfg) == (
        6 * need.attn_params(cfg) + 3 * 2048 * 6144
        + 5 * active_expert_layer)
    # every expert held: 64.1 + 5 x 640.0 + 525.3 = 3,790 M parameters
    held = (6 * need.attn_params(cfg) + 3 * 2048 * 6144
            + 5 * (2048 * 128 + 3 * 2048 * (1536 + 128 * 768))
            + 2 * need.head_params(cfg))
    assert round(held / 1e6) == 3790
    assert need.latent_row_bytes(cfg) == 6912


def test_a_decode_row_is_one_vector_whatever_the_heads(cfg):
    flops, nbytes = need.decode_attn_need(cfg, [1000, 24])
    assert nbytes == 1024 * 6 * 1152
    assert flops == 1024 * 6 * 32 * 2 * (576 + 512)
    # the materialised pair is the cheaper one where K and V exist anyway
    assert need.prefill_pair_flops(cfg) == 6 * 32 * 2 * (192 + 128)
    assert need.prefill_pair_flops(cfg) < need.decode_pair_flops(cfg)


def test_flops_count_active_parameters_and_the_head_once(cfg):
    one = need.decode_flops(cfg, [1])
    dense = 2 * (need.active_matmul_params(cfg) + need.head_params(cfg))
    assert one == dense + need.decode_pair_flops(cfg)
    whole = need.prefill_flops(cfg, [10])
    assert whole == 2 * need.active_matmul_params(cfg) * 10 \
        + 2 * need.head_params(cfg) + need.prefill_pair_flops(cfg) * 55


# ----------------------------------------------------------- the readers

def test_mla_attn_roofline_reads_the_kernels_time_in_decode(cfg):
    # two tokens inside the span (the first of a request is prefill's)
    recs = [record(1000, [101.0, 102.0, 103.0, 120.0])]
    ctx = context(cfg, recs, {"kernels": {"attention": {"decode": 0.5,
                                                        "prefill": 9.0}},
                              "programs": {}})
    flops, nbytes = need.decode_attn_need(cfg, [1001, 1002])
    least = max(flops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    assert reader("mla_attn_roofline")(ctx) == pytest.approx(
        100 * least / 0.5)
    ctx["trace"]["kernels"] = {}
    assert reader("mla_attn_roofline")(ctx) is None


def test_mfu_readers_count_the_spans_tokens(cfg):
    recs = [record(1000, [101.0, 102.0, 103.0, 120.0]),
            record(3000, [95.0, 96.0])]
    ctx = context(cfg, recs, {"kernels": {}, "programs": {
        "decode": {"count": 2, "device_s": 0.25, "names": {}}}})
    decode = need.decode_flops(cfg, [1001, 1002])
    assert reader("mla_moe_decode_step_mfu")(ctx) == pytest.approx(
        100 * decode / (0.25 * 1e12))
    whole = decode + need.prefill_flops(cfg, [1000])
    assert reader("mla_moe_window_mfu")(ctx) == pytest.approx(
        100 * whole / (10.0 * 1e12))
    ctx["trace"]["programs"] = {}
    assert reader("mla_moe_decode_step_mfu")(ctx) is None
    assert reader("mla_moe_window_mfu")({**ctx, "peak": None}) is None


def test_experts_touched_reads_the_pass_records(cfg):
    rec = obs.FlightRecorder(size=16, request_logs=4)
    rec.log.spans.append(("engine.wait", 99.0, 111.0, None))
    rec.log.passes.extend([
        {"pass_id": 1, "kind": "decode", "t0": 98.0, "t1": 99.5, "steps": 8,
         "experts_touched": 9999},                         # before the span
        {"pass_id": 2, "kind": "decode", "t0": 100.0, "t1": 101.0,
         "steps": 8, "experts_touched": 8 * 5 * 32},
        {"pass_id": 3, "kind": "decode", "t0": 101.0, "t1": 102.0,
         "steps": 8, "experts_touched": 8 * 5 * 96},
        {"pass_id": 4, "kind": "decode", "t0": 102.0, "t1": 103.0,
         "steps": 8},                      # a family that counts nothing
        {"pass_id": 5, "kind": "prefill_chunk", "t0": 103.0, "t1": 104.0}])
    ctx = context(cfg, [], {"kernels": {}, "programs": {}})
    read = reader("moe_experts_touched_pct")
    assert read(ctx) == pytest.approx(100 * (32 + 96) / (2 * 128))
    assert read({**ctx, "trace": {**ctx["trace"], "mark_ns": None}}) is None
    rec.log.passes.clear()
    assert read(ctx) is None


# ------------------------------------------------------------ the traffic

def test_long_doc_through_the_generator():
    mix = traffic.load_mix("long-doc")
    assert mix["loop"] == "open" and mix["sharing"] == "none"
    a = traffic.generate(mix, 2 ** 31 + 11, 50, 128256)
    assert a == traffic.generate(mix, 2 ** 31 + 11, 50, 128256)
    b = traffic.generate(mix, 7, 50, 128256)["requests"]
    reqs = a["requests"]
    assert [len(r["prompt"]) for r in reqs] == [len(r["prompt"]) for r in b]
    assert [r["due_s"] for r in reqs] == [r["due_s"] for r in b]
    assert abs(len(reqs) - mix["rate_per_s"] * 50) <= 1
    assert all(2048 <= len(r["prompt"]) <= 16384 for r in reqs)
    assert all(32 <= r["max_tokens"] <= 512 for r in reqs)
    lens = sorted(len(r["prompt"]) for r in reqs)
    assert abs(lens[len(lens) // 2] - 6144) <= 0.15 * 6144
    # every prompt is wider than the widest bucket: all walk chunks
    cfg = serve.load_config("kanana-2-30b-a3b-6l")
    assert lens[0] > max(cfg["engine"]["prefill_buckets"])
    assert max(len(r["prompt"]) + r["max_tokens"] for r in reqs) \
        < cfg["engine"]["max_seq"] - 512


def test_benchmark_json_lists_the_cell_and_its_metrics(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1 and len(cell[0]["why"]) <= 200
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == [
        "mla_attn_roofline", "mla_moe_decode_step_mfu",
        "mla_moe_window_mfu", "moe_experts_touched_pct"]
    assert all(os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")) for m in mine)
    entry = [c for c in bench["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}


# ---------------------------------------------------------- the rehearsal

def test_rehearsal_of_the_cell_is_not_a_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 9), "--seconds", "3",
         "--trace", "1", "--rehearse",
         os.path.join(HERE, "rehearsal_mla_moe")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and "correct" not in last
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["would_be_correct"] is True
    facts = json.loads(lines[-2])
    assert facts["recompiles_in_window"] == 0
    assert facts["engine"]["preemptions"] == 0
    assert proc.stderr.rstrip().splitlines()[-1] == "correct: True"
