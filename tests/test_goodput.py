"""Goodput observatory: device-time waste attribution with a hard
conservation invariant (useful + sum(waste causes) == busy, per pass
kind and cumulatively), memory watermarks (monotone non-decreasing
within a run), the post-warmup recompile sentinel (fires exactly once
per novel shape, silent on warm shapes), per-tenant waste columns in
the usage ledger, fleet-summary waste fields, and the replay
efficiency-divergence report.

The zero-hot-path invariant itself (transfer guard + greedy
bit-identity with the meter ON) is pinned by test_observability.py —
the meter defaults on, so those tests already run with it.
"""

import json
import math
import time

import pytest

from gofr_tpu.metrics.registry import Manager as MetricsManager
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.observability import (GoodputMeter,
                                            RecompileSentinel,
                                            UsageLedger,
                                            WatermarkTracker)
from gofr_tpu.serving.replay import (efficiency_divergence,
                                     parse_workload, replay_workload)


def _drive(eng, prompts, n, *, tenants=None, timeout=120):
    """Submit + drain on an already-started engine (engines are not
    restartable: tests needing several waves share one session)."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=n)
    reqs = [eng.submit(p, sp,
                       tenant=tenants[i] if tenants else None)
            for i, p in enumerate(prompts)]
    deadline = time.time() + timeout
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return reqs


def _run(eng, prompts, n, *, tenants=None, timeout=120):
    eng.start()
    try:
        return _drive(eng, prompts, n, tenants=tenants,
                      timeout=timeout)
    finally:
        eng.stop()


def _assert_conserved(meter: GoodputMeter) -> None:
    """THE invariant: every accounted busy second is classified."""
    assert meter.busy_s > 0
    total = meter.useful_s + sum(meter.waste_s.values())
    assert math.isclose(total, meter.busy_s, rel_tol=1e-9,
                        abs_tol=1e-9), (total, meter.busy_s)
    for kind, sub in meter.by_kind.items():
        ktotal = sub["useful_s"] + sum(sub[c] for c in meter.CAUSES)
        assert math.isclose(ktotal, sub["busy_s"], rel_tol=1e-9,
                            abs_tol=1e-9), (kind, sub)


# ---------------------------------------------------------- meter unit
def test_meter_decode_padding_split():
    m = GoodputMeter()
    m.add_decode(1.0, 3, 4)
    assert m.useful_s == pytest.approx(0.75)
    assert m.waste_s["padding"] == pytest.approx(0.25)
    _assert_conserved(m)


def test_meter_prefill_recompute_split():
    m = GoodputMeter()
    # group of 4 padded rows: 2 fresh, 1 recompute, 1 dummy pad
    m.add_prefill("prefill", 2.0, 4, 2, 1)
    assert m.useful_s == pytest.approx(1.0)
    assert m.waste_s["preempt_recompute"] == pytest.approx(0.5)
    assert m.waste_s["padding"] == pytest.approx(0.5)
    _assert_conserved(m)


def test_meter_spec_rejected_split():
    m = GoodputMeter()
    # batch 2, one row drafted 4 accepted 1 (bonus always emits), one
    # row with no drafts (pure decode step: fully useful)
    m.add_spec(1.0, 2, [(4, 1), (0, 0)])
    share = 0.5
    assert m.waste_s["spec_rejected"] == pytest.approx(share * 3 / 5)
    assert m.useful_s == pytest.approx(share * 2 / 5 + share)
    assert m.waste_s["padding"] == pytest.approx(0.0)
    _assert_conserved(m)


def test_meter_bubble_requires_backlog():
    m = GoodputMeter()
    m.note_pass_end(10.0, backlog=False)
    m.note_dispatch(10.5)
    assert m.waste_s["bubble"] == 0.0
    m.note_pass_end(11.0, backlog=True)
    m.note_dispatch(11.25)
    assert m.waste_s["bubble"] == pytest.approx(0.25)
    assert m.busy_s == pytest.approx(0.25)
    # the gap is consumed: a second dispatch opens no new bubble
    m.note_dispatch(12.0)
    assert m.waste_s["bubble"] == pytest.approx(0.25)


def test_meter_disabled_accounts_nothing():
    m = GoodputMeter(enabled=False)
    m.add_decode(1.0, 1, 4)
    m.note_pass_end(1.0, True)
    m.note_dispatch(2.0)
    assert m.busy_s == 0.0 and m.summary().get("goodput_ratio") is None


def test_sentinel_fires_once_and_only_after_seal():
    s = RecompileSentinel()
    assert not s.dispatch(("decode", 0))  # pre-seal: cold compile
    s.observe(("prefill", 64, 1))
    s.seal()
    assert not s.dispatch(("decode", 0))       # seen pre-seal
    assert not s.dispatch(("prefill", 64, 1))  # observed in warmup
    assert s.dispatch(("prefill", 128, 1))     # novel: fires
    assert not s.dispatch(("prefill", 128, 1))  # now warm: silent
    assert s.recompiles == 1
    assert s.state()["signatures"] == ["prefill/128/1"]
    off = RecompileSentinel(enabled=False)
    off.seal()
    assert not off.dispatch(("x",)) and off.recompiles == 0


def test_watermark_tracker_monotone():
    wm = WatermarkTracker()
    assert wm.update("kv_pages", 4.0)
    assert not wm.update("kv_pages", 3.0)  # below the mark: ignored
    assert wm.get("kv_pages") == 4.0
    assert wm.update("kv_pages", 9.0)
    state = wm.state()
    assert state["kv_pages"]["value"] == 9.0
    assert "t" in state["kv_pages"]


# ----------------------------------------------- engine: conservation
def test_decode_conservation_and_padding():
    """Plain decode run on a half-empty batch: the invariant holds and
    the empty slots' device time shows up as padding waste."""
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                         seed=3))
    _run(eng, [[1, 2, 3], [4, 5, 6]], 16)
    _assert_conserved(eng.goodput)
    assert eng.goodput.by_kind["decode"]["busy_s"] > 0
    assert eng.goodput.waste_s["padding"] > 0  # 2 of 4 slots empty
    ratio = eng.goodput.summary()["goodput_ratio"]
    assert 0.0 < ratio <= 1.0


def test_chunk_prefill_conservation():
    """A prompt longer than the widest bucket walks the chunked path;
    its passes are classified and conserved too."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=256, seed=5, prefill_buckets=(16,)))
    _run(eng, [list(range(1, 50))], 8)
    _assert_conserved(eng.goodput)
    assert eng.goodput.by_kind["prefill_chunk"]["busy_s"] > 0


def test_preemption_waste_attributed():
    """Pool pressure forces preemption-by-recompute: the re-prefilled
    device time lands in waste_s['preempt_recompute'], on the
    preempted request's waste_recompute_s, and in its tenant's ledger
    column — conservation still exact."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=8,
        kv_layout="paged", page_size=16, kv_pages=8))
    prompts = [list(range(1, 30))] * 4
    reqs = _run(eng, prompts, 24,
                tenants=["acme", "acme", "globex", "globex"])
    assert eng.stats["preemptions"] > 0, "scenario never preempted"
    _assert_conserved(eng.goodput)
    assert eng.goodput.waste_s["preempt_recompute"] > 0
    assert sum(r.waste_recompute_s for r in reqs) > 0
    usage = eng.usage_ledger.rollup()
    total_waste = sum(t["waste_recompute_s"]
                      for t in usage["tenants"].values())
    # rollup rounds each column to 6 decimals — compare at that grain
    assert total_waste == pytest.approx(
        sum(r.waste_recompute_s for r in reqs), abs=1e-5)


def test_spec_verify_conservation():
    """Speculative decoding: verify passes are classified (useful +
    spec_rejected + padding) and conserve."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=256, seed=5, speculative=True,
        spec_ngram=1, decode_steps_per_pass=2))
    pattern = [7, 11, 13, 7, 11, 13, 7, 11]
    _run(eng, [pattern], 24)
    assert eng.stats["spec_passes"] > 0
    _assert_conserved(eng.goodput)
    sub = eng.goodput.by_kind["spec_verify"]
    assert sub["busy_s"] > 0 and sub["useful_s"] > 0


def test_bubble_recorded_under_load():
    """Sequential single-slot decode leaves host gaps between passes
    while the request is mid-generation — the bubble cause must be
    populated (it is the dispatch-overhead number the observatory
    exists to expose)."""
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128,
                                         seed=2))
    _run(eng, [[1, 2, 3]], 32)
    _assert_conserved(eng.goodput)
    assert eng.goodput.waste_s["bubble"] > 0


# --------------------------------------------------- engine: sentinel
def test_engine_recompile_sentinel_fires_once_on_novel_shape():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=256,
                                         seed=1))
    eng.warmup(prompt_lens=(32,))
    assert eng.sentinel.sealed
    # warm shape: a prompt inside the warmed 32-bucket stays silent
    _run(eng, [[1, 2, 3]], 4)
    assert eng.stats["recompiles"] == 0

    # novel shape: a prompt in an unwarmed bucket fires exactly once
    eng2 = demo_llama_engine(EngineConfig(max_batch=2, max_seq=256,
                                          seed=1))
    eng2.warmup(prompt_lens=(32,))

    class SpyLogger:
        def __init__(self):
            self.warns = []

        def warn(self, msg, **kw):
            self.warns.append((str(msg), kw))

        def error(self, msg, **kw):
            pass

        def info(self, msg, **kw):
            pass

    eng2.logger = spy = SpyLogger()
    eng2.start()
    try:
        _drive(eng2, [list(range(1, 60))], 4)  # bucket 64: not warmed
        assert eng2.stats["recompiles"] == 1
        fired = [kw for msg, kw in spy.warns if "recompile" in msg]
        assert len(fired) == 1 \
            and "prefill/64" in fired[0]["signature"]
        # same novel shape again: warm now, stays silent
        _drive(eng2, [list(range(1, 60))], 4)
        assert eng2.stats["recompiles"] == 1
        assert eng2.sentinel.state()["recompiles"] == 1
    finally:
        eng2.stop()


def test_unwarmed_engine_never_seals():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128,
                                         seed=0))
    _run(eng, [[1, 2, 3]], 4)
    assert not eng.sentinel.sealed
    assert eng.stats["recompiles"] == 0


# ------------------------------------------------- engine: watermarks
def test_engine_watermarks_monotone_within_run():
    m = MetricsManager()
    eng = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=0, kv_layout="paged",
        page_size=16, prefix_cache=True))
    eng.attach_metrics(m)
    eng.start()
    try:
        _drive(eng, [[2, 3, 5], [7, 11, 13]], 12)
        first = eng.efficiency_state()["watermarks"]
        assert first["kv_pages"]["value"] > 0
        assert first["host_rss_bytes"]["value"] > 0
        _drive(eng, [list(range(1, 40))], 12)
        second = eng.efficiency_state()["watermarks"]
        for name, mark in first.items():
            assert second[name]["value"] >= mark["value"], (name,
                                                            first,
                                                            second)
        time.sleep(0.3)
        eng._update_gauges()  # past the throttle window
        # the published gauges mirror the marks
        assert m.get("app_engine_kv_pages_watermark").get() \
            == second["kv_pages"]["value"]
    finally:
        eng.stop()


# ---------------------------------------------------- metrics surface
def test_waste_counters_and_ratio_published():
    m = MetricsManager()
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                         seed=3))
    eng.attach_metrics(m)
    eng.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=24)
    reqs = [eng.submit([1 + i, 2, 3], sp) for i in range(2)]
    deadline = time.time() + 60
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    time.sleep(0.3)  # one throttled gauge refresh past the retires
    eng._update_gauges()
    eng.stop()
    ratio = m.get("app_engine_goodput_ratio").get()
    assert 0.0 < ratio <= 1.0
    waste = m.get("app_engine_waste_seconds")
    published = sum(waste.get(cause=c) for c in GoodputMeter.CAUSES)
    # deltas lag the meter by at most one throttle window: published
    # totals can never exceed the busy time they conserve against
    assert 0.0 < published <= eng.goodput.busy_s + 1e-9


def test_ledger_waste_columns_in_rollup():
    ledger = UsageLedger()
    ledger.record(tenant="acme", status="ok", prompt_tokens=10,
                  completion_tokens=5, device_s=1.0,
                  waste_recompute_s=0.25, waste_spec_s=0.1)
    ledger.record(tenant="acme", status="ok", prompt_tokens=10,
                  completion_tokens=5, device_s=0.5,
                  waste_recompute_s=0.05)
    tot = ledger.rollup()["tenants"]["acme"]
    assert tot["waste_recompute_s"] == pytest.approx(0.3)
    assert tot["waste_spec_s"] == pytest.approx(0.1)
    windowed = ledger.rollup(window_s=3600)["tenants"]["acme"]
    assert windowed["waste_recompute_s"] == pytest.approx(0.3)


def test_fleet_summary_carries_goodput_fields():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128,
                                         seed=0))
    _run(eng, [[1, 2, 3]], 8)
    summary = eng.recorder.fleet_summary()
    assert 0.0 < summary["goodput_ratio"] <= 1.0
    assert summary["busy_s"] > 0
    assert set(GoodputMeter.CAUSES) == set(summary["waste_s"])


def test_leader_names_straggler_waste_cause():
    """The straggler WARN and /debug/fleet digest carry the slow
    host's dominant waste cause from its heartbeat summary."""
    from gofr_tpu.serving.control_plane import ControlPlaneLeader

    class SpyLogger:
        def __init__(self):
            self.warns = []

        def warn(self, msg, **kw):
            self.warns.append((str(msg), kw))

        def info(self, msg, **kw):
            pass

        def error(self, msg, **kw):
            pass

    leader = ControlPlaneLeader(logger=(spy := SpyLogger()))
    # three hosts: with only two, max/median can never clear the 2x
    # straggler threshold (the median of two IS their mean)
    for host in ("fast-a", "fast-b", "slow"):
        leader.join(host, f"{host}:1", 1)
    for host in ("fast-a", "fast-b"):
        leader.heartbeat(host, leader.generation, {"status": "UP"},
                         {"pass_p50_s": 0.01, "pass_p95_s": 0.01,
                          "busy_s": 10.0, "useful_s": 9.0,
                          "waste_s": {"padding": 0.5, "bubble": 0.5}})
    leader.heartbeat(
        "slow", leader.generation, {"status": "UP"},
        {"pass_p50_s": 0.5, "pass_p95_s": 0.5,
         "busy_s": 10.0, "useful_s": 4.0,
         "waste_s": {"padding": 1.0, "preempt_recompute": 5.0}})
    digest = leader._recompute_skew()
    assert digest["stragglers"] == ["slow"]
    assert digest["straggler_causes"]["slow"] == "preempt_recompute"
    fleet_gp = digest["goodput"]
    assert fleet_gp["busy_s"] == pytest.approx(30.0)
    assert fleet_gp["goodput_ratio"] == pytest.approx(22.0 / 30.0)
    named = [kw for msg, kw in spy.warns if "straggler" in msg]
    assert named and named[0]["dominant_waste"] == "preempt_recompute"


# -------------------------------------------------- replay divergence
def test_efficiency_divergence_rule():
    rec = {"busy_s": 10.0, "waste_s": {"padding": 1.0,
                                       "preempt_recompute": 0.5}}
    bad = {"busy_s": 10.0, "waste_s": {"padding": 1.1,
                                       "preempt_recompute": 2.0}}
    out = efficiency_divergence(rec, bad)
    assert [d["cause"] for d in out] == ["preempt_recompute"]
    assert out[0]["recorded_share"] == pytest.approx(0.05)
    assert out[0]["replayed_share"] == pytest.approx(0.2)
    assert efficiency_divergence(rec, rec) == []
    assert efficiency_divergence(None, bad) == []
    assert efficiency_divergence(rec, {"busy_s": 0.0}) == []


def test_capture_header_and_replay_report_carry_goodput(tmp_path):
    cfg = dict(max_batch=4, max_seq=128, seed=17,
               workload_capture=True)
    eng = demo_llama_engine(EngineConfig(**cfg))
    _run(eng, [[3 + i, 5, 9] for i in range(3)], 10)
    text = eng.workload.to_jsonl()
    header = json.loads(text.splitlines()[0])
    assert header["goodput"]["busy_s"] > 0
    assert "waste_s" in header["goodput"]

    workload = parse_workload(text)
    replayer = demo_llama_engine(
        EngineConfig(max_batch=4, max_seq=128, seed=17))
    try:
        report = replay_workload(replayer, workload, closed_loop=3,
                                 timeout_s=120)
    finally:
        replayer.stop()
    assert report["bit_identical"], report["divergences"]
    assert report["recorded_goodput"]["busy_s"] > 0
    assert report["replayed_goodput"]["busy_s"] > 0
    assert isinstance(report["efficiency_divergence"], list)


# ------------------------------------------------- capacity estimator
def test_capacity_pick_max_sustainable():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "capacity", os.path.join(os.path.dirname(__file__), "..",
                                 "scripts", "capacity.py"))
    capacity = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(capacity)
    levels = [{"concurrency": 1, "qps": 10, "tripped": False},
              {"concurrency": 2, "qps": 18, "tripped": False},
              {"concurrency": 4, "qps": 19, "tripped": True},
              {"concurrency": 8, "qps": 12, "tripped": False}]
    best = capacity.pick_max_sustainable(levels)
    assert best["concurrency"] == 2  # nothing past the first trip
    assert capacity.pick_max_sustainable(
        [{"concurrency": 1, "qps": 1, "tripped": True}]) is None


def test_capacity_sweep_reports_goodput_curve():
    """Two lenient-SLO levels over a tiny captured workload: each
    level carries qps + goodput + burn state, and the sweep names the
    max sustainable level."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "capacity", os.path.join(os.path.dirname(__file__), "..",
                                 "scripts", "capacity.py"))
    capacity = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(capacity)
    from gofr_tpu.serving.observability import SLOConfig

    cap = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                         seed=9, workload_capture=True))
    _run(cap, [[2 + i, 4, 6] for i in range(4)], 8)
    workload = parse_workload(cap.workload.to_jsonl())

    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                         seed=9))
    try:
        result = capacity.sweep(
            eng, workload, [1, 2],
            SLOConfig(ttft_s=60.0, tpot_s=60.0, e2e_s=120.0),
            timeout_s=120, log=lambda _m: None)
    finally:
        eng.stop()
    assert [e["concurrency"] for e in result["levels"]] == [1, 2]
    for entry in result["levels"]:
        assert entry["qps"] > 0
        assert 0.0 < entry["goodput_ratio"] <= 1.0
        assert not entry["tripped"]
    assert result["max_sustainable_concurrency"] == 2
    assert result["tripped_at"] is None
