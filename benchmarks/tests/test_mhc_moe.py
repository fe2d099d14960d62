"""What PR 32 added for the ``xing4_0`` family (Xing4.0-29B-A4B): the
counts of ``harness/rooflines_mhc_moe.py`` against the configuration's
own arithmetic, the five per-layer readers on a hand-made trace summary
and flight log (a known number in, a known number out; nothing to read,
``None``), the configuration file against the catalog's published keys,
the ``reasoning`` mix through the one generator, and run.py's rehearsal
of the new cell on the CPU at a toy size of the same shape."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, REPO
from harness import rooflines, rooflines_mhc_moe as need
from harness import rooflines_mla_moe as base
from harness import serve, stats, traffic

from gofr_tpu.serving import observability as obs

CELL = "xing4-29b-a4b-8l.reasoning"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
MARK_NS, T0, T1 = 5e9, 100.0, 110.0
READERS = ["mhc_mla_attn_roofline", "mhc_moe_decode_step_mfu",
           "mhc_moe_experts_touched_pct", "mhc_moe_window_mfu",
           "mhc_sinkhorn_row_err_ppm"]


@pytest.fixture(scope="module")
def cfg():
    return serve.load_config("xing4-29b-a4b-8l")


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
    assert need.attn_params(cfg) == 2_752_512 + 4_718_592 + 2_064_384 \
        + 2 * 2_097_152 + 14_680_064                        # 28.41 M
    assert need.mhc_params(cfg) == 4 * 3584 * 24            # a sublayer
    assert need.head_params(cfg) == 3584 * 131072           # 469.8 M
    active_expert_layer = 3584 * 64 + 3 * 3584 * (1024 + 4 * 1024)
    assert need.active_matmul_params(cfg) == (
        8 * (need.attn_params(cfg) + 2 * need.mhc_params(cfg))
        + 2 * 3 * 3584 * 9216 + 6 * active_expert_layer)
    # every expert held: 256.4 + 6 x 745.0 + 939.5 = 5,666 M parameters
    held = (8 * (need.attn_params(cfg) + 2 * need.mhc_params(cfg))
            + 2 * 3 * 3584 * 9216
            + 6 * (3584 * 64 + 3 * 3584 * (1024 + 64 * 1024))
            + 2 * need.head_params(cfg))
    assert round(held / 1e6) == 5666
    assert base.latent_row_bytes(cfg) == 9216


def test_compressed_queries_and_mixes_are_what_differs_from_the_family(cfg):
    plain = {**cfg, "q_lora_rank": None}
    assert need.attn_params(cfg) - base.attn_params(plain) == \
        768 * (3584 + 32 * 192) - 3584 * 32 * 192
    assert need.mhc_mix_flops(cfg) == 2 * 3584 * (4 + 16 + 4)
    one = need.decode_flops(cfg, [1])
    assert one == (2 * need.active_matmul_params(cfg)
                   + 2 * 8 * need.mhc_mix_flops(cfg)
                   + 2 * need.head_params(cfg) + need.decode_pair_flops(cfg))
    whole = need.prefill_flops(cfg, [10])
    assert whole == 10 * need.token_flops(cfg) + 2 * need.head_params(cfg) \
        + need.prefill_pair_flops(cfg) * 55
    # the cached row and the attention pairs are the family's
    flops, nbytes = base.decode_attn_need(cfg, [1000, 24])
    assert nbytes == 1024 * 8 * 1152
    assert flops == 1024 * 8 * 32 * 2 * (576 + 512)


# ----------------------------------------------------------- the readers

def test_attn_roofline_reads_the_kernels_time_in_decode(cfg):
    recs = [record(1000, [101.0, 102.0, 103.0, 120.0])]
    ctx = context(cfg, recs, {"kernels": {"attention": {"decode": 0.5,
                                                        "prefill": 9.0}},
                              "programs": {}})
    flops, nbytes = base.decode_attn_need(cfg, [1001, 1002])
    least = max(flops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    assert reader("mhc_mla_attn_roofline")(ctx) == pytest.approx(
        100 * least / 0.5)
    ctx["trace"]["kernels"] = {}
    assert reader("mhc_mla_attn_roofline")(ctx) is None


def test_mfu_readers_count_the_spans_tokens(cfg):
    recs = [record(1000, [101.0, 102.0, 103.0, 120.0]),
            record(300, [95.0, 96.0])]
    ctx = context(cfg, recs, {"kernels": {}, "programs": {
        "decode": {"count": 2, "device_s": 0.25, "names": {}}}})
    decode = need.decode_flops(cfg, [1001, 1002])
    assert reader("mhc_moe_decode_step_mfu")(ctx) == pytest.approx(
        100 * decode / (0.25 * 1e12))
    whole = decode + need.prefill_flops(cfg, [1000])
    assert reader("mhc_moe_window_mfu")(ctx) == pytest.approx(
        100 * whole / (10.0 * 1e12))
    ctx["trace"]["programs"] = {}
    assert reader("mhc_moe_decode_step_mfu")(ctx) is None
    assert reader("mhc_moe_window_mfu")({**ctx, "peak": None}) is None


def test_counter_readers_read_the_pass_records(cfg):
    rec = obs.FlightRecorder(size=16, request_logs=4)
    rec.log.spans.append(("engine.wait", 99.0, 111.0, None))
    rec.log.passes.extend([
        {"pass_id": 1, "kind": "decode", "t0": 98.0, "t1": 99.5, "steps": 8,
         "experts_touched": 9999, "mhc_row_err": 0.5},     # before the span
        {"pass_id": 2, "kind": "decode", "t0": 100.0, "t1": 101.0,
         "steps": 8, "experts_touched": 8 * 6 * 16, "mhc_row_err": 2e-4},
        {"pass_id": 3, "kind": "decode", "t0": 101.0, "t1": 102.0,
         "steps": 8, "experts_touched": 8 * 6 * 48, "mhc_row_err": 9e-4},
        {"pass_id": 4, "kind": "decode", "t0": 102.0, "t1": 103.0,
         "steps": 8},          # a program that counts nothing: the parent
        {"pass_id": 5, "kind": "prefill", "t0": 103.0, "t1": 104.0}])
    ctx = context(cfg, [], {"kernels": {}, "programs": {}})
    touched, err = (reader("mhc_moe_experts_touched_pct"),
                    reader("mhc_sinkhorn_row_err_ppm"))
    assert touched(ctx) == pytest.approx(100 * (16 + 48) / (2 * 64))
    assert err(ctx) == pytest.approx(900.0)
    unmarked = {**ctx, "trace": {**ctx["trace"], "mark_ns": None}}
    assert touched(unmarked) is None and err(unmarked) is None
    # a program without the counters: nothing, and no exception
    for p in rec.log.passes:
        p.pop("mhc_row_err", None)
        p.pop("experts_touched", None)
    assert touched(ctx) is None and err(ctx) is None
    rec.log.passes.clear()
    assert touched(ctx) is None and err(ctx) is None


# ------------------------------------------------- the configuration file

def test_configuration_holds_the_catalogs_published_keys(cfg):
    """Every number of the catalog row's ``config`` under the same key,
    but for what ``reduced`` names."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = [json.loads(l) for l in f if "Xing4.0-29B-A4B" in l][0]
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "num_nextn_predict_layers"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["num_hidden_layers"] == 8
    assert cfg["num_nextn_predict_layers"] == 0
    assert "num_nextn_predict_layers" in cfg["left_out"]
    for key in ("hc_alpha", "hc_bias", "hc_phi", "hc_norm_gain",
                "hc_sinkhorn_order", "hc_eps", "e_score_correction_bias",
                "rope_interleave", "torch_dtype"):
        assert key in cfg["assumed"], key
    model = serve.model_config(cfg)
    assert (model.hc_mult, model.q_lora_rank) == (4, 768)
    assert model.softmax_scale == pytest.approx(0.1446788, rel=1e-5)
    # the pool holds every slot at its full length
    eng = cfg["engine"]
    assert eng["kv_pages"] * eng["page_size"] == \
        eng["max_batch"] * eng["max_seq"]


# ------------------------------------------------------------ the traffic

def test_reasoning_through_the_generator(cfg):
    mix = traffic.load_mix("reasoning")
    assert mix["loop"] == "open" and mix["sharing"] == "none"
    a = traffic.generate(mix, 2 ** 31 + 11, 50, 131072)
    assert a == traffic.generate(mix, 2 ** 31 + 11, 50, 131072)
    b = traffic.generate(mix, 7, 50, 131072)["requests"]
    reqs = a["requests"]
    assert [len(r["prompt"]) for r in reqs] == [len(r["prompt"]) for r in b]
    assert [r["due_s"] for r in reqs] == [r["due_s"] for r in b]
    assert abs(len(reqs) - mix["rate_per_s"] * 50) <= 1
    assert all(128 <= len(r["prompt"]) <= 1536 for r in reqs)
    assert all(256 <= r["max_tokens"] <= 2560 for r in reqs)
    lens = sorted(len(r["prompt"]) for r in reqs)
    assert abs(lens[len(lens) // 2] - 384) <= 0.15 * 384
    outs = sorted(r["max_tokens"] for r in reqs)
    assert abs(outs[len(outs) // 2] - 1024) <= 0.15 * 1024
    # most prompts fit a bucket (the materialised bucket prefill), a
    # few walk chunks; none meets the engine's clamp
    widest = max(cfg["engine"]["prefill_buckets"])
    fit = sum(n <= widest for n in lens) / len(lens)
    assert 0.9 <= fit < 1.0
    assert max(len(r["prompt"]) + r["max_tokens"] for r in reqs) \
        < cfg["engine"]["max_seq"]


def test_benchmark_json_lists_the_cell_and_its_metrics(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1 and len(cell[0]["why"]) <= 200
    assert bench["workloads"][-1]["name"] == CELL       # added at the end
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == READERS
    assert [m["name"] for m in bench["per_layer"][-5:]] == \
        [m["name"] for m in mine]
    assert all(os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")) for m in mine)
    assert {m["layer"] for m in mine} <= {
        m["layer"] for m in bench["per_layer"] if m not in mine}
    entry = bench["configs"][-1]
    assert entry["name"] == cfg["name"] and entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


# ---------------------------------------------------------- the rehearsal

def test_rehearsal_of_the_cell_is_not_a_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 9), "--seconds", "3",
         "--trace", "1", "--rehearse",
         os.path.join(HERE, "rehearsal_mhc_moe")],
        capture_output=True, text=True, timeout=1800,
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
