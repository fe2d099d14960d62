"""Serving-path observability: engine tracing, flight recorder, the
full Prometheus engine surface, and the profiler-capture endpoints.

The hard invariant under test: observability fully enabled (tracer +
flight recorder + metrics) adds ZERO host->device transfers to the
steady-state decode path and does not change a single generated token.
Everything is assembled host-side from timestamps the engine already
collects (serving/observability.py).
"""

import json
import re
import time
from pathlib import Path

import jax
import pytest

from gofr_tpu.container.container import Container
from gofr_tpu.metrics.registry import Manager as MetricsManager
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.observability import FlightRecorder, ProfilerCapture
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.tracing.tracer import InMemoryExporter, Tracer

from .apputil import AppRunner

SERVING_DIR = Path(__file__).resolve().parent.parent / "gofr_tpu" / "serving"

# first string-literal argument of any metrics write call
_WRITE_RE = re.compile(
    r"(?:record_histogram|set_gauge|increment_counter|add_counter|"
    r"delta_up_down_counter)\(\s*['\"]([A-Za-z0-9_]+)['\"]")


def _run(eng, prompts, n, *, tracer=None, timeout=120):
    eng.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=n)
    if tracer is not None:
        with tracer.start_span("parent"):
            reqs = [eng.submit(p, sp) for p in prompts]
    else:
        reqs = [eng.submit(p, sp) for p in prompts]
    deadline = time.time() + timeout
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return reqs


# ------------------------------------------------------ registry coverage
def test_every_serving_metric_write_is_registered():
    """Every metric name written anywhere under gofr_tpu/serving/ must
    be registered by attach_metrics or the container's framework set —
    an unregistered write is a silent log-and-drop."""
    written = set()
    for path in SERVING_DIR.glob("*.py"):
        written.update(_WRITE_RE.findall(path.read_text()))
    assert written, "no metric writes found — the scan regex broke"

    container = Container()
    container.register_framework_metrics()
    # tenant metering + SLO + fleet/router + event-ledger series must
    # live in the CONTAINER framework set (not only attach_metrics):
    # federation merges them across hosts and leaders/aggregators
    # never call attach_metrics
    framework_missing = sorted(
        n for n in written
        if n.startswith(("app_tenant_", "app_slo_", "app_fleet_",
                         "app_router_", "app_events_"))
        and container.metrics.get(n) is None)
    assert not framework_missing, (
        f"tenant/SLO metric(s) written in serving/ but absent from the "
        f"container framework set: {framework_missing}")
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64))
    eng.attach_metrics(container.metrics)
    missing = sorted(n for n in written
                     if container.metrics.get(n) is None)
    assert not missing, (
        f"metric(s) written in serving/ but never registered: {missing}")


def test_render_federated_merges_tenant_counters_across_hosts():
    """The per-tenant counters ride the PR 4 federation path: identical
    tenant labelsets SUM across hosts in merge_snapshots, and the
    federated exposition carries each host's series under its host
    label."""
    from gofr_tpu.metrics.registry import merge_snapshots, render_federated
    managers = {}
    for host, tokens in (("host-a", 10), ("host-b", 32)):
        m = MetricsManager()
        m.new_counter("app_tenant_completion_tokens",
                      "generated tokens by tenant")
        m.add_counter("app_tenant_completion_tokens", float(tokens),
                      tenant="acme")
        managers[host] = m
    snaps = {h: m.snapshot() for h, m in managers.items()}
    merged = merge_snapshots(snaps)
    fam = merged["metrics"]["app_tenant_completion_tokens"]
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in fam["series"]}
    assert series[(("tenant", "acme"),)] == 42.0  # summed, one labelset
    text = render_federated(snaps)
    assert 'app_tenant_completion_tokens{host="host-a",tenant="acme"} 10' \
        in text
    assert 'app_tenant_completion_tokens{host="host-b",tenant="acme"} 32' \
        in text


def test_attach_metrics_registers_on_bare_manager():
    """An engine attached to a fresh Manager (no container) registers
    its full surface itself — serve_model-less embedding works."""
    m = MetricsManager()
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64))
    eng.attach_metrics(m)
    for name in ("app_engine_batch_occupancy", "app_chat_queue_seconds",
                 "app_chat_tpot_seconds", "app_chat_e2e_seconds",
                 "app_engine_kv_pool_utilization", "app_engine_mfu",
                 "app_engine_preemptions", "app_engine_spec_drafted"):
        assert m.get(name) is not None, name


# -------------------------------------------- zero-perturbation invariant
def test_steady_state_zero_h2d_with_observability_enabled():
    """The transfer-guard contract of test_decode_state, with tracing +
    flight recorder + metrics ALL on: steady-state decode still uploads
    nothing."""
    container = Container()
    container.register_framework_metrics()
    tracer = Tracer(exporter=InMemoryExporter())
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=256,
                                         seed=0), tracer=tracer)
    eng.attach_metrics(container.metrics)
    params = SamplingParams(temperature=0.0, max_new_tokens=200)
    with tracer.start_span("parent"):
        reqs = [eng.submit([1 + i, 2, 3], params) for i in range(3)]
    batch = eng.waiting.pop_batch(len(reqs), first_wait_s=0.5)
    assert batch and len(batch) == len(reqs)
    eng._admit_batch(batch)
    eng._collect_prefills()
    # two unguarded passes: admission upload, then the use_prev flip
    for _ in range(2):
        eng._decode_step()
        eng._drain_pending()
    transfers = eng.stats["h2d_transfers"]
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            eng._decode_step()
            eng._drain_pending()
    assert eng.stats["h2d_transfers"] == transfers
    # ...and the observability layer actually observed those passes
    kinds = [p["kind"] for p in eng.recorder.snapshot()["passes"]]
    assert kinds.count("decode") >= 5
    assert container.metrics.get_histogram_count(
        "app_engine_batch_occupancy") >= 5
    last = eng.recorder.snapshot()["passes"][-1]
    assert last["h2d"] == 0 and last["occupancy"] == 3
    assert last["tokens"] > 0


@pytest.mark.parametrize("layout_kw", [
    {},      # the default: the pool through the dense view off the TPU
    {"page_size": 16, "paged_attention": "xla"},    # the native path
])
def test_greedy_bit_identical_with_observability_enabled(layout_kw):
    """Greedy token streams with tracer+recorder+metrics enabled are
    bit-identical to the bare engine (both attention paths)."""
    prompts = [[5 + i, 2, 9] for i in range(3)]

    def cfg():
        return EngineConfig(max_batch=4, max_seq=128, seed=11,
                            **layout_kw)

    bare = demo_llama_engine(cfg())
    want = [r.generated for r in _run(bare, prompts, 24)]

    container = Container()
    container.register_framework_metrics()
    tracer = Tracer(exporter=InMemoryExporter())
    obs = demo_llama_engine(cfg(), tracer=tracer)
    obs.attach_metrics(container.metrics)
    got_reqs = _run(obs, prompts, 24, tracer=tracer)
    assert [r.generated for r in got_reqs] == want
    # the observed run produced spans for every request
    names = [s.name for s in tracer.exporter.spans]
    assert names.count("engine.request") == len(prompts)


# --------------------------------------------------------- flight recorder
def test_flight_recorder_ring_and_request_logs():
    rec = FlightRecorder(size=4, request_logs=2)
    for i in range(10):
        rec.record_pass("decode", tokens=i)
    snap = rec.snapshot()
    assert len(snap["passes"]) == 4                    # ring bounded
    assert [p["tokens"] for p in snap["passes"]] == [6, 7, 8, 9]
    assert snap["passes_recorded"] == 10
    assert rec.snapshot(2)["passes"][-1]["pass_id"] == 10  # last-N works
    assert rec.summary()["by_kind"] == {"decode": 10}
    disabled = FlightRecorder(size=0)
    disabled.record_pass("decode")
    assert disabled.snapshot()["passes"] == []


# ------------------------------------------- span log and pass records
SPAN_TABLE = {
    "engine.wait", "engine.admit", "engine.prefill_dispatch",
    "engine.chunk_walk", "engine.chunk_wait", "engine.decode_dispatch",
    "engine.decode_wait", "engine.emit", "engine.finalize",
    "engine.planes", "engine.prefill_collect", "engine.prefill_wait",
    "engine.gauges"}


@pytest.fixture(scope="module")
def served():
    """A tiny paged engine that has served two bucket prompts and one
    long enough to walk chunks (29 tokens through width-8 chunks), and
    the (rid, lengths-after-the-pass) the test saw each decode pass
    enqueue, to hold the records' ``ctx`` against."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, prefill_buckets=(8,), seed=1,
        kv_layout="paged", page_size=16, paged_attention="view"))
    seen = {}
    enqueue = eng._enqueue_decode

    def watching(pass_id):
        rec = enqueue(pass_id)
        if rec is not None:
            rows = [i for i, on in enumerate(rec["mask"]) if on]
            seen[pass_id] = ([eng.active[i].rid for i in rows],
                             [int(eng.lengths[i]) for i in rows])
        return rec

    eng._enqueue_decode = watching
    reqs = _run(eng, [[1, 2, 3], [4, 5, 6, 7], list(range(1, 30))], 10)
    return eng, reqs, seen


def test_every_pass_record_says_when_and_what(served):
    eng, reqs, seen = served
    passes = eng.recorder.snapshot()["passes"]
    assert {p["kind"] for p in passes} == {"prefill", "prefill_chunk",
                                           "decode"}
    rids = {r.rid for r in reqs}
    assert rids == {1, 2, 3}
    for p in passes:
        assert isinstance(p["t0"], float) and p["pass_id"] >= 1
        assert p["t1"] is None or p["t0"] <= p["t1"]
        assert p["rids"] and set(p["rids"]) <= rids
    assert len({p["pass_id"] for p in passes}) == len(passes)
    decode = [p for p in passes if p["kind"] == "decode"]
    assert decode and all(p["t1"] is not None for p in decode)
    for p in decode:  # the rows' lengths after the pass, as enqueued
        assert (p["rids"], p["ctx"]) == seen[p["pass_id"]]
        assert p["steps"] == eng._tokens_per_pass
    chunks = [p for p in passes if p["kind"] == "prefill_chunk"]
    walked = sorted((p["offsets"][0], p["lens"][0]) for p in chunks)
    assert walked == [(0, 8), (8, 8), (16, 8), (24, 5)]
    # only the walk's last chunk is waited for: its first token is read
    assert [p["t1"] is not None for p in
            sorted(chunks, key=lambda p: p["offsets"][0])] \
        == [False, False, False, True]
    bucket = [p for p in passes if p["kind"] == "prefill"]
    assert sorted(n for p in bucket for n in p["lens"]) == [3, 4]
    assert all(p["bucket"] == 8 and p["group"] >= len(p["rids"])
               for p in bucket)


def test_request_log_carries_rid_and_a_prompt_digest(served):
    eng, reqs, _ = served
    from gofr_tpu.serving.observability import (PROMPT_HASH_SALT,
                                                salted_token_hash)
    by_rid = {e["rid"]: e for e in eng.recorder.snapshot()["requests"]}
    for r in reqs:
        assert by_rid[r.rid]["prompt_hash"] == salted_token_hash(
            r.prompt_tokens, PROMPT_HASH_SALT)
    walker = by_rid[reqs[2].rid]
    # a chunk's request event times the enqueue and says so
    assert {e["name"] for e in walker["events"]} == {"prefill_dispatch"}
    assert all(e["pass_id"] >= 1 for e in walker["events"])


def test_every_phase_of_the_loop_is_a_span(served):
    eng, _, _ = served
    spans = list(eng.recorder.log.spans)
    assert {s[0] for s in spans} == SPAN_TABLE
    assert all(t0 <= t1 for _, t0, t1, _ in spans)
    # one thread wrote them: taken outermost first, a span lies inside
    # the one open before it or after its end — never across an edge
    open_spans: list = []
    top = []
    for span in sorted(spans, key=lambda s: (s[1], -s[2])):
        while open_spans and open_spans[-1][2] <= span[1]:
            open_spans.pop()
        if open_spans:
            assert span[2] <= open_spans[-1][2], (span, open_spans[-1])
        else:
            top.append(span)
        open_spans.append(span)
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    # a pass's spans carry its id, and a child with none its parent's
    passes = {p["pass_id"]: p["kind"]
              for p in eng.recorder.snapshot()["passes"]}
    kinds = {"engine.prefill_dispatch": "prefill",
             "engine.prefill_wait": "prefill",
             "engine.chunk_walk": "prefill_chunk",
             "engine.chunk_wait": "prefill_chunk",
             "engine.decode_wait": "decode", "engine.emit": "decode",
             "engine.planes": "decode"}
    for name, _, _, pass_id in spans:
        if name in kinds:
            assert passes[pass_id] == kinds[name], (name, pass_id)
    by_pass: dict = {}
    for name, _, _, pass_id in spans:
        by_pass.setdefault(pass_id, set()).add(name)
    for pass_id, kind in passes.items():
        if kind == "decode":
            assert {"engine.decode_dispatch", "engine.decode_wait",
                    "engine.emit", "engine.planes"} <= by_pass[pass_id]
    # the decode record's host times are its spans' durations
    dur = {(n, pid): t1 - t0 for n, t0, t1, pid in spans}
    for p in eng.recorder.snapshot()["passes"]:
        if p["kind"] == "decode":
            assert p["dispatch_s"] == round(
                dur["engine.decode_dispatch", p["pass_id"]], 6)
            assert p["collect_s"] >= round(
                dur["engine.emit", p["pass_id"]], 6)


def test_flight_log_outlives_its_engine():
    import gc
    import weakref

    from gofr_tpu.serving.observability import flight_logs
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64, seed=4))
    _run(eng, [[1, 2, 3]], 6)
    log = eng.recorder.log
    assert flight_logs()[-1] is log
    n_spans, n_passes = len(log.spans), len(log.passes)
    assert n_spans and n_passes and len(log.requests) == 1
    # wall-clock request stamps and monotonic spans share one axis
    entry = log.requests[0]
    first_wait = min(t0 for name, t0, _, _ in log.spans)
    assert abs(log.mono(entry["submitted_at"]) - first_wait) < 60.0
    dead = weakref.ref(eng)
    del eng
    gc.collect()
    assert dead() is None, "the kept log holds its engine alive"
    assert flight_logs()[-1] is log
    assert (len(log.spans), len(log.passes)) == (n_spans, n_passes)
    assert len(flight_logs()) <= 8


def test_flight_recorder_size_zero_records_nothing():
    from gofr_tpu.serving.observability import flight_logs
    kept = flight_logs()
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, seed=4, flight_recorder_size=0))
    reqs = _run(eng, [[1, 2, 3]], 6)
    assert len(reqs[0].generated) == 6
    log = eng.recorder.log
    assert not log.spans and not log.passes and not log.requests
    assert flight_logs() == kept           # and is not kept
    with eng.recorder.span("engine.admit") as sp:
        pass
    assert sp.t0 <= sp.t1 and not log.spans
    assert eng.stats["dispatch_s"] > 0.0   # the engine's own timings hold


def test_spans_land_in_a_profile_of_the_process(tmp_path):
    """The same spans are in any trace taken of the process: the
    recorder enters a TraceAnnotation beside each ring entry."""
    from jax.profiler import ProfileData
    rec = FlightRecorder(size=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("engine.decode_dispatch", rec.new_pass()):
            with rec.span("engine.finalize"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert {"engine.decode_dispatch", "engine.finalize"} <= names
    assert [(s[0], s[3]) for s in rec.log.spans] == [
        ("engine.finalize", 1), ("engine.decode_dispatch", 1)]


def test_engine_health_and_crash_dump_carry_flight_summary():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64, seed=3))

    class SpyLogger:
        lines: list = []

        def error(self, msg, **kw):
            self.lines.append(str(msg))

        def warn(self, msg, **kw):
            pass

        def info(self, msg, **kw):
            pass

    eng.logger = SpyLogger()
    _run(eng, [[1, 2, 3]], 6)
    health = eng.health_check()
    assert health["flight"]["passes_recorded"] >= 1
    eng._crash(RuntimeError("boom"))
    assert any("flight recorder" in ln for ln in SpyLogger.lines)
    assert eng.health_check()["status"] == "DOWN"


def test_spec_verify_recorded_in_ring_and_counters():
    m = MetricsManager()
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=256, seed=5, speculative=True,
        spec_ngram=1, decode_steps_per_pass=2))
    eng.attach_metrics(m)
    pattern = [7, 11, 13, 7, 11, 13, 7, 11]
    _run(eng, [pattern], 24)
    assert eng.stats["spec_passes"] > 0
    kinds = {p["kind"] for p in eng.recorder.snapshot()["passes"]}
    assert "spec_verify" in kinds
    assert m.get("app_engine_spec_drafted").get() > 0
    assert m.get("app_engine_spec_accepted").get() >= 0


# -------------------------------------------------------------- profiler
def test_profiler_capture_single_flight(tmp_path):
    cap = ProfilerCapture(base_dir=str(tmp_path))
    out = cap.start()
    assert out["ok"], out
    again = cap.start()
    assert not again["ok"] and "already" in again["error"]
    assert cap.status()["running"]
    stopped = cap.stop()
    assert stopped["ok"] and stopped["dir"] == out["dir"]
    assert not cap.status()["running"]
    assert not cap.stop()["ok"]  # idempotent-safe


# ------------------------------------------------------------------- e2e
@pytest.fixture(scope="module")
def obs_app():
    engine = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=0, kv_layout="paged",
        page_size=16, prefix_cache=True, paged_attention="view"))

    def build(app):
        app.serve_model("llm", engine, ByteTokenizer())

    runner = AppRunner(build=build,
                       config={"TRACE_EXPORTER": "memory",
                               "PROFILER_ENABLED": "true"})
    with runner as app:
        yield app


def test_e2e_traceparent_links_engine_spans(obs_app):
    """A chat request with a W3C traceparent produces linked engine.*
    child spans in the in-memory exporter: HTTP span -> engine.request
    -> queue/prefill/decode/retire, one trace end to end."""
    trace_id = "ab" * 16
    status, _, data = obs_app.request(
        "POST", "/chat",
        {"prompt": "trace me end to end", "max_tokens": 8,
         "temperature": 0.0},
        headers={"traceparent": f"00-{trace_id}-{'cd' * 8}-01"})
    assert status == 201
    body = json.loads(data)["data"]
    assert body["usage"]["tpot_ms"] is not None
    spans = obs_app.app.container.tracer.exporter.spans
    mine = [s for s in spans if s.trace_id == trace_id]
    http_span = next(s for s in mine if s.name == "POST /chat")
    assert http_span.parent_id == "cd" * 8
    by_name = {s.name: s for s in mine}
    root = by_name["engine.request"]
    assert root.parent_id == http_span.span_id
    for name in ("engine.queue", "engine.prefill", "engine.decode",
                 "engine.retire"):
        assert by_name[name].parent_id == root.span_id, name
    assert by_name["engine.decode"].attributes["tokens"] == 8
    assert by_name["engine.queue"].end_time >= by_name[
        "engine.queue"].start_time


def test_e2e_debug_engine_returns_pass_records(obs_app):
    status, body = obs_app.get_json("/debug/engine?n=8")
    assert status == 200
    llm = body["data"]["llm"]
    assert llm["health"]["status"] == "UP"
    assert llm["flight"]["passes"], "no pass records served"
    assert len(llm["flight"]["passes"]) <= 8
    last = llm["flight"]["passes"][-1]
    assert {"pass_id", "kind", "t", "t0"} <= set(last)
    spans = llm["flight"]["spans"]
    assert 0 < len(spans) <= 8                         # ?n= limits them
    assert {"name", "t0", "t1", "pass_id"} == set(spans[-1])
    assert spans[-1]["name"].startswith("engine.")
    assert {"wall", "monotonic"} == set(llm["flight"]["anchor"])


def test_e2e_metrics_expose_engine_surface(obs_app):
    # a second request makes sure samples exist regardless of ordering,
    # then give the throttled gauges one refresh window
    status, _, _ = obs_app.request(
        "POST", "/chat", {"prompt": "trace me end to end",
                          "max_tokens": 8, "temperature": 0.0})
    assert status == 201
    time.sleep(0.6)
    _, _, data = obs_app.request("GET", "/metrics",
                                 port=obs_app.metrics_port)
    text = data.decode()
    series = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name_part, _, value = line.rpartition(" ")
        series[name_part.split("{", 1)[0]] = float(value)
    for name in ("app_chat_queue_seconds_count",
                 "app_chat_tpot_seconds_count",
                 "app_chat_e2e_seconds_count",
                 "app_engine_batch_occupancy_count",
                 "app_engine_kv_pool_utilization"):
        assert series.get(name, 0.0) > 0.0, (name, series.get(name))
    # present even when zero-valued on CPU
    for name in ("app_engine_mfu", "app_engine_tokens_per_second",
                 "app_engine_kv_pool_fragmentation",
                 "app_engine_prefix_cache_pages"):
        assert name in series, name


def test_e2e_debug_efficiency_conserves(obs_app):
    """GET /debug/efficiency serves the goodput classification with
    the conservation invariant intact, watermarks with timestamps,
    and the recompile-sentinel state."""
    status, _, _ = obs_app.request(
        "POST", "/chat", {"prompt": "efficiency probe",
                          "max_tokens": 8, "temperature": 0.0})
    assert status == 201
    status, body = obs_app.get_json("/debug/efficiency")
    assert status == 200
    eff = body["data"]["llm"]
    gp = eff["goodput"]
    assert gp["busy_s"] > 0
    total = gp["useful_s"] + sum(gp["waste_s"].values())
    # each JSON field is rounded to 6 decimals, so the serialized sum
    # may be off by a few ulps of the rounding grain; the raw-float
    # invariant is exact (conservation_error_s, and test_goodput.py)
    assert abs(total - gp["busy_s"]) < 5e-6, gp
    assert abs(gp["conservation_error_s"]) < 1e-9, gp
    assert 0.0 < gp["goodput_ratio"] <= 1.0
    assert set(gp["waste_s"]) == {"padding", "preempt_recompute",
                                 "spec_rejected", "bubble",
                                 "integrity_probe"}
    assert eff["watermarks"]["kv_pages"]["value"] > 0
    assert "t" in eff["watermarks"]["kv_pages"]
    assert "recompiles" in eff["recompiles"]


def test_e2e_debug_engine_exposes_trace_drops(obs_app):
    """The bounded span exporter's eviction counter is surfaced in
    /debug/engine — a truncated trace capture must say so."""
    status, body = obs_app.get_json("/debug/engine?n=1")
    assert status == 200
    traces = body["data"]["traces"]
    assert traces["dropped_spans"] == 0
    assert traces["buffered_spans"] >= 1
    assert traces["max_spans"] == 8192
    # scrape refreshes the gauge from the exporter
    _, _, data = obs_app.request("GET", "/metrics",
                                 port=obs_app.metrics_port)
    assert "app_traces_dropped_spans 0" in data.decode()


def test_e2e_profiler_endpoints(obs_app, tmp_path_factory):
    target = str(tmp_path_factory.mktemp("xprof"))
    status, _, data = obs_app.request("POST", "/debug/profile/start",
                                      {"dir": target})
    assert status in (200, 201)
    out = json.loads(data)["data"]
    assert out["ok"], out
    # double-start is refused, not crashed
    status, _, data = obs_app.request("POST", "/debug/profile/start", {})
    assert not json.loads(data)["data"]["ok"]
    status, _, data = obs_app.request("POST", "/debug/profile/stop", {})
    stopped = json.loads(data)["data"]
    assert stopped["ok"] and stopped["dir"] == target
