"""Fleet observability plane: metrics federation over heartbeats,
cross-host trace stitching, straggler detection, stall escalation.

The contract under test extends PR 3's invariant across hosts: every
fleet surface is host-side assembly of data the engine already records
— snapshots read on heartbeat threads, skew computed on the leader,
the watchdog polling ``health_check()`` — so the transfer-guard and
greedy bit-identity tests pass with ALL of it enabled.
"""

import json
import time

import jax
import pytest

from gofr_tpu.container.container import Container
from gofr_tpu.logging.logger import (MockLogger, clear_fleet_context,
                                     current_fleet_context,
                                     set_fleet_context)
from gofr_tpu.metrics.registry import (Manager, merge_snapshots,
                                       render_federated)
from gofr_tpu.serving.control_plane import (ControlPlaneLeader,
                                            FleetConfig, WorkerAgent,
                                            engine_fleet_sources)
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.observability import FlightRecorder, StallWatchdog
from gofr_tpu.tracing.tracer import InMemoryExporter, Tracer

from .apputil import AppRunner


@pytest.fixture(autouse=True)
def _clean_fleet_context():
    """The fleet context is process-global by design — never let one
    test's host identity leak into another's log records."""
    clear_fleet_context()
    yield
    clear_fleet_context()


def make_leader(**kw):
    leader = ControlPlaneLeader(coordinator="10.0.0.1:8476", **kw)

    def build(app):
        leader.install(app)
    return leader, build


def parse_prom(text: str) -> dict[str, float]:
    """{'name{a="b"}': value} — labels kept verbatim."""
    out = {}
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        out[name] = float(value)
    return out


# ------------------------------------------------------ registry snapshot
def test_manager_snapshot_round_trips_all_kinds():
    m = Manager()
    m.new_counter("jobs_total", "jobs")
    m.new_gauge("temp", "temperature")
    m.new_histogram("lat", "latency", buckets=(0.1, 1.0))
    m.add_counter("jobs_total", 3, queue="a")
    m.set_gauge("temp", 21.5)
    m.record_histogram("lat", 0.05)
    m.record_histogram("lat", 2.0)
    snap = m.snapshot()
    fams = snap["metrics"]
    assert fams["jobs_total"]["kind"] == "counter"
    assert fams["jobs_total"]["series"] == [
        {"labels": {"queue": "a"}, "value": 3.0}]
    assert fams["temp"]["series"][0]["value"] == 21.5
    lat = fams["lat"]
    assert lat["buckets"] == [0.1, 1.0]
    assert lat["series"][0]["counts"] == [1, 1]
    assert lat["series"][0]["count"] == 2
    json.dumps(snap)  # must be wire-safe as-is


def test_merge_snapshots_counters_sum_gauges_keep_histograms_merge():
    def host_snap(jobs, temp, lat_counts, lat_sum, lat_n):
        return {"metrics": {
            "jobs_total": {"kind": "counter", "help": "j", "series": [
                {"labels": {}, "value": jobs}]},
            "temp": {"kind": "gauge", "help": "t", "series": [
                {"labels": {}, "value": temp}]},
            "lat": {"kind": "histogram", "help": "l",
                    "buckets": [0.1, 1.0],
                    "series": [{"labels": {}, "counts": lat_counts,
                                "sum": lat_sum, "count": lat_n}]},
        }}

    merged = merge_snapshots({
        "a": host_snap(3.0, 20.0, [1, 2], 1.5, 3),
        "b": host_snap(4.0, 30.0, [2, 2], 2.5, 4)})["metrics"]
    assert merged["jobs_total"]["series"] == [{"labels": {}, "value": 7.0}]
    # up/down counters render as gauges but SUM across hosts
    updown = {"metrics": {"inflight": {
        "kind": "gauge", "help": "i", "updown": True,
        "series": [{"labels": {}, "value": 2.0}]}}}
    updown2 = {"metrics": {"inflight": {
        "kind": "gauge", "help": "i", "updown": True,
        "series": [{"labels": {}, "value": 5.0}]}}}
    m2 = merge_snapshots({"a": updown, "b": updown2})["metrics"]
    assert m2["inflight"]["series"] == [{"labels": {}, "value": 7.0}]
    temps = {s["labels"]["host"]: s["value"]
             for s in merged["temp"]["series"]}
    assert temps == {"a": 20.0, "b": 30.0}
    lat = merged["lat"]["series"][0]
    assert lat["counts"] == [3, 4] and lat["count"] == 7
    assert lat["sum"] == pytest.approx(4.0)


def test_render_federated_labels_every_sample_one_family_header():
    snap = {"metrics": {"jobs_total": {
        "kind": "counter", "help": "j",
        "series": [{"labels": {}, "value": 5.0}]}}}
    snap2 = {"metrics": {"jobs_total": {
        "kind": "counter", "help": "j",
        "series": [{"labels": {}, "value": 7.0}]}}}
    text = render_federated(
        {"h1": snap, "h2": snap2},
        {"h1": {"host": "h1", "rank": "0"},
         "h2": {"host": "h2", "rank": "1"}})
    assert text.count("# TYPE jobs_total counter") == 1
    series = parse_prom(text)
    assert series['jobs_total{host="h1",rank="0"}'] == 5.0
    assert series['jobs_total{host="h2",rank="1"}'] == 7.0
    assert sum(series.values()) == 12.0


# ------------------------------------------------- bounded span exporter
def test_inmemory_exporter_bounded_with_drop_counter():
    exp = InMemoryExporter(max_spans=4)
    tracer = Tracer(exporter=exp)
    for i in range(10):
        tracer.start_span(f"s{i}").end()
    assert len(exp.spans) == 4
    assert exp.dropped == 6
    assert [s.name for s in exp.spans] == ["s6", "s7", "s8", "s9"]


# ------------------------------------------------- flight fleet summary
def test_flight_recorder_fleet_summary_percentiles():
    rec = FlightRecorder(size=64)
    t0 = time.time()
    for i in range(20):
        rec.record_pass("decode", dur=0.01 * (i + 1), occupancy=4,
                        queue_depth=i, tokens=8)
    s = rec.fleet_summary()
    assert s["pass_p50_s"] == pytest.approx(0.10, abs=0.02)
    assert s["pass_p95_s"] == pytest.approx(0.19, abs=0.02)
    assert s["occupancy_mean"] == 4
    assert s["queue_depth"] == 19
    assert s["passes_recorded"] == 20
    # tokens_per_s appears once the ring spans real wall time
    assert "by_kind" in s and s["by_kind"]["decode"] == 20
    assert time.time() - t0 < 5


# --------------------------------------------------- federation over HTTP
def test_heartbeat_carries_summary_and_metrics_to_fleet_views():
    leader, build = make_leader()
    with AppRunner(build=build) as runner:
        managers = {}
        agents = {}
        for host in ("host-a", "host-b"):
            m = Manager()
            m.new_counter("app_engine_preemptions", "p")
            m.add_counter("app_engine_preemptions",
                          3.0 if host == "host-a" else 4.0)
            m.new_gauge("app_engine_tokens_per_second", "tps")
            m.set_gauge("app_engine_tokens_per_second", 100.0)
            managers[host] = m
            agents[host] = WorkerAgent(
                f"http://127.0.0.1:{runner.port}", host_id=host,
                n_devices=1, heartbeat_interval_s=0.1,
                metrics_source=m.snapshot,
                summary_source=lambda h=host: {
                    "pass_p50_s": 0.01, "pass_p95_s": 0.02,
                    "occupancy_mean": 3.0, "queue_depth": 1,
                    "tokens_per_s": 120.0})
        for agent in agents.values():
            agent.join()
        for agent in agents.values():
            agent._heartbeat_once()

        # consolidated JSON view
        status, body = runner.get_json("/debug/fleet")
        assert status == 200
        fleet = body["data"]
        assert fleet["world_size"] == 2
        assert fleet["generation"] == 2
        assert fleet["hosts"]["host-a"]["rank"] == 0
        assert fleet["hosts"]["host-b"]["summary"]["pass_p95_s"] == 0.02
        assert fleet["hosts"]["host-a"]["federated"]
        assert fleet["fleet"]["pass_skew"] >= 1.0
        assert fleet["counter_totals"]["app_engine_preemptions"] == 7.0

        # federated Prometheus text: host/rank labels, counters sum
        status, _, data = runner.request("GET", "/control/fleet/metrics")
        assert status == 200
        text = data.decode()
        series = parse_prom(text)
        a = series['app_engine_preemptions{host="host-a",rank="0"}']
        b = series['app_engine_preemptions{host="host-b",rank="1"}']
        assert (a, b) == (3.0, 4.0)
        assert text.count("# TYPE app_engine_preemptions counter") == 1
        # per-host gauges stay per-host
        assert series[
            'app_engine_tokens_per_second{host="host-a",rank="0"}'] == 100.0
        # leader-computed fleet families ride the same scrape
        assert series.get("app_fleet_generation") == 2.0
        assert series.get("app_fleet_world_size") == 2.0
        assert "app_fleet_pass_skew" in series


def test_federation_off_keeps_heartbeats_lean():
    leader, build = make_leader(fleet=FleetConfig(federation=False))
    with AppRunner(build=build) as runner:
        m = Manager()
        m.new_counter("c", "c")
        agent = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="w", heartbeat_interval_s=0.1,
                            metrics_source=m.snapshot,
                            fleet=FleetConfig(federation=False))
        agent.join()
        agent._heartbeat_once()
        status, body = runner.get_json("/debug/fleet")
        assert not body["data"]["hosts"]["w"]["federated"]
        status, _, data = runner.request("GET", "/control/fleet/metrics")
        assert status == 200
        text = data.decode()
        # no federated worker series (the leader's own app_fleet_*
        # families, e.g. host-labeled heartbeat counts, still render)
        assert "# TYPE c counter" not in text
        assert 'host="w",rank=' not in text


# ------------------------------------------------------------ stragglers
def test_straggler_detection_flags_skewed_host_and_warns():
    log = MockLogger()
    leader, build = make_leader(logger=log,
                                fleet=FleetConfig(straggler_ratio=1.5))
    with AppRunner(build=build) as runner:
        p95 = {"fast-1": 0.010, "fast-2": 0.011, "slow": 0.200}
        agents = {}
        for host, v in p95.items():
            agents[host] = WorkerAgent(
                f"http://127.0.0.1:{runner.port}", host_id=host,
                heartbeat_interval_s=0.1,
                summary_source=lambda v=v: {"pass_p95_s": v,
                                            "occupancy_mean": 2.0})
            agents[host].join()
        for agent in agents.values():
            agent._heartbeat_once()
        status, body = runner.get_json("/debug/fleet")
        fleet = body["data"]["fleet"]
        assert fleet["stragglers"] == ["slow"]
        assert fleet["worst_host"] == "slow"
        assert fleet["pass_skew"] == pytest.approx(0.2 / 0.011, rel=0.01)
        assert fleet["straggler_ratio"] == pytest.approx(1 / 3, abs=0.01)
        # gauges on the leader's own metrics port
        metrics = leader.metrics
        assert metrics.get("app_fleet_pass_skew").get() > 1.5
        assert metrics.get("app_fleet_straggler_ratio").get() > 0
        warns = [ln for ln in log.lines
                 if "straggler" in str(ln.get("message", ""))]
        assert warns and warns[0]["host"] == "slow"
        # WARN fires once per episode, not on every heartbeat
        agents["slow"]._heartbeat_once()
        warns2 = [ln for ln in log.lines
                  if "straggler" in str(ln.get("message", ""))]
        assert len(warns2) == len(warns)


def test_signature_normalized_straggler_names_the_kernel():
    """With federated cost tables the leader compares hosts on the
    SAME dispatch signature: a host that is genuinely slow on a shared
    kernel is flagged (and the signature named), while a host whose
    p95 is fat only because it serves a heavier shape mix is NOT — the
    exact confusion the raw max/median-p95 heuristic can't avoid."""
    log = MockLogger()
    leader, build = make_leader(logger=log,
                                fleet=FleetConfig(straggler_ratio=1.5))
    summaries = {
        # the reference host: normal mix, normal costs
        "fast": {"pass_p95_s": 0.010, "occupancy_mean": 2.0,
                 "costs": {
                     "decode/0": {"kind": "decode", "n": 50,
                                  "mean_s": 0.010},
                     "prefill/8/1": {"kind": "prefill", "n": 9,
                                     "mean_s": 0.040}}},
        # fattest p95 in the fleet — but only because it serves the
        # long-context window; its SHARED signature costs are normal
        "heavy-mix": {"pass_p95_s": 0.200, "occupancy_mean": 2.0,
                      "costs": {
                          "decode/0": {"kind": "decode", "n": 50,
                                       "mean_s": 0.011},
                          "decode/2048": {"kind": "decode", "n": 40,
                                          "mean_s": 0.190}}},
        # modest p95, but 3x the fleet median on the shared decode
        # kernel — the actual straggler
        "slow-kernel": {"pass_p95_s": 0.033, "occupancy_mean": 2.0,
                        "costs": {
                            "decode/0": {"kind": "decode", "n": 50,
                                         "mean_s": 0.033},
                            "prefill/8/1": {"kind": "prefill", "n": 9,
                                            "mean_s": 0.041}}},
    }
    with AppRunner(build=build) as runner:
        agents = {}
        for host, summary in summaries.items():
            agents[host] = WorkerAgent(
                f"http://127.0.0.1:{runner.port}", host_id=host,
                heartbeat_interval_s=0.1,
                summary_source=lambda s=summary: s)
            agents[host].join()
        for agent in agents.values():
            agent._heartbeat_once()
        status, body = runner.get_json("/debug/fleet")
        fleet = body["data"]["fleet"]
        assert fleet["straggler_mode"] == "signature"
        assert fleet["stragglers"] == ["slow-kernel"]
        assert fleet["straggler_signatures"] == {
            "slow-kernel": "decode/0"}
        # decode/2048 has one reporter, so it never enters the compare
        assert "decode/2048" not in fleet["costs"]["signatures"]
        assert fleet["costs"]["signatures"]["decode/0"] == \
            pytest.approx(0.011)
        assert sorted(fleet["costs"]["hosts"]) == \
            ["fast", "heavy-mix", "slow-kernel"]
        # the WARN names the kernel, not just the host
        warns = [ln for ln in log.lines
                 if "straggler" in str(ln.get("message", ""))]
        assert warns and warns[0]["host"] == "slow-kernel"
        assert warns[0]["signature"] == "decode/0"


# ------------------------------------------------------- trace stitching
def test_control_rpcs_stitch_one_trace_across_hosts():
    leader, build = make_leader()
    worker_exp = InMemoryExporter()
    worker_tracer = Tracer(service_name="worker", exporter=worker_exp)
    runner = AppRunner(build=build,
                       config={"TRACE_EXPORTER": "memory"})
    with runner:
        agent = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="w0", heartbeat_interval_s=0.1,
                            tracer=worker_tracer)
        agent.join()
        agent._heartbeat_once()
        client_spans = [s for s in worker_exp.spans
                        if s.name.startswith("control.")]
        assert {s.name for s in client_spans} >= {"control.join",
                                                  "control.heartbeat"}
        leader_spans = runner.app.container.tracer.exporter.spans
        for client in client_spans:
            server = [s for s in leader_spans
                      if s.trace_id == client.trace_id]
            assert server, f"no leader span on trace of {client.name}"
            assert any(s.parent_id == client.span_id for s in server)


def test_fleet_context_enriches_spans_and_logs_after_join():
    leader, build = make_leader()
    with AppRunner(build=build) as runner:
        agent = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="ctx-host",
                            heartbeat_interval_s=0.1)
        agent.join()
        ctx = current_fleet_context()
        assert ctx["host_id"] == "ctx-host"
        assert ctx["rank"] == 0 and ctx["generation"] == 1
        # every span now carries the host identity as resource attrs
        exp = InMemoryExporter()
        tracer = Tracer(exporter=exp)
        tracer.start_span("anything").end()
        attrs = exp.spans[0].attributes
        assert attrs["host_id"] == "ctx-host" and attrs["rank"] == 0
        # explicit attributes win over the resource context
        tracer.start_span("x", attributes={"rank": 9}).end()
        assert exp.spans[1].attributes["rank"] == 9
        # ...and every log record next to trace_id/span_id
        log = MockLogger()
        log.info("hello")
        rec = log.lines[0]
        assert rec["host_id"] == "ctx-host"
        assert rec["rank"] == 0 and rec["generation"] == 1


# ------------------------------------------------------ stall escalation
def _stalled_engine():
    """An engine whose stall flag IS set: work waiting, loop silent."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, seed=0, stall_threshold_s=0.05,
        watchdog_interval_s=0))  # watchdog driven by hand in tests
    eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    eng._running = True            # loop "alive"...
    eng._last_beat = time.time() - 10.0  # ...but no pass for 10 s
    return eng


def test_watchdog_escalates_stall_once_per_episode():
    eng = _stalled_engine()
    log = MockLogger()
    eng.logger = log
    exp = InMemoryExporter()
    eng.tracer = Tracer(exporter=exp)
    m = Manager()
    eng.attach_metrics(m)
    dog = StallWatchdog(eng, interval_s=0.05)
    assert eng.health_check()["status"] == "DEGRADED"
    assert dog.check_once() is True
    assert dog.check_once() is False          # same episode: no re-fire
    assert eng.stats["stalls"] == 1
    assert m.get("app_engine_stalls").get() == 1.0
    assert any(s.name == "engine.stall" for s in exp.spans)
    dumped = [ln for ln in log.lines
              if "flight recorder" in str(ln.get("message", ""))]
    assert dumped, "flight recorder was not dumped on stall"
    # recovery re-arms the watchdog
    eng._last_beat = time.time()
    assert dog.check_once() is False
    eng._last_beat = time.time() - 10.0
    assert dog.check_once() is True
    assert eng.stats["stalls"] == 2
    eng._running = False


def test_degraded_heartbeat_evicts_and_survivors_rerank():
    leader, build = make_leader()
    with AppRunner(build=build) as runner:
        eng = _stalled_engine()
        health, summary, _ = engine_fleet_sources(eng)
        sick = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                           host_id="a-sick", heartbeat_interval_s=0.1,
                           health_source=health, summary_source=summary)
        survivor = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                               host_id="b-ok", heartbeat_interval_s=0.1)
        sick.join()
        survivor.join()
        assert survivor.assignment.rank == 1
        generation = leader.generation
        assert leader.metrics.get("app_fleet_world_size").get() == 2.0

        sick._heartbeat_once()   # gossips DEGRADED -> evicted NOW
        assert sick.assignment is None
        topo = leader.topology()
        assert topo["world_size"] == 1
        assert "a-sick" not in topo["members"]
        assert leader.generation == generation + 1
        # fleet counters moved through the transition
        assert leader.metrics.get("app_fleet_evictions").get(
            reason="degraded") == 1.0
        assert leader.metrics.get("app_fleet_generation").get() \
            == leader.generation
        assert leader.metrics.get("app_fleet_world_size").get() == 1.0
        # survivor re-ranks to 0 at its next heartbeat (elastic regen)
        survivor._heartbeat_once()
        assert survivor.assignment.rank == 0
        assert survivor.assignment.world_size == 1
        # the degraded agent does NOT thrash back in while unhealthy
        assert not sick._healthy()
        sick._running = True
        assert sick.assignment is None
        # ...but a recovered engine rejoins through the normal path
        eng._last_beat = time.time()
        assert sick._healthy()
        sick.join()
        assert leader.topology()["world_size"] == 2
        eng._running = False


def test_stalled_worker_end_to_end_watchdog_to_eviction():
    """The full escalation: watchdog flips health, the next heartbeat
    gossips DEGRADED, the leader evicts and re-ranks — no heartbeat
    silence involved."""
    leader, build = make_leader()
    with AppRunner(build=build) as runner:
        eng = _stalled_engine()
        log = MockLogger()
        eng.logger = log
        health, summary, _ = engine_fleet_sources(eng)
        agent = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="w-stall",
                            heartbeat_interval_s=0.1,
                            health_source=health,
                            summary_source=summary)
        other = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="w-live", heartbeat_interval_s=0.1)
        agent.join()
        other.join()
        dog = StallWatchdog(eng, interval_s=0.05)
        assert dog.check_once()          # dump + counter + span
        agent._heartbeat_once()          # DEGRADED rides the heartbeat
        assert agent.assignment is None  # evicted
        other._heartbeat_once()
        assert other.assignment.rank == 0
        assert other.assignment.world_size == 1
        assert any("flight recorder" in str(ln.get("message", ""))
                   for ln in log.lines)
        eng._running = False


# --------------------------------------- zero-perturbation, fleet edition
def test_steady_state_zero_h2d_with_full_fleet_plane_enabled():
    """The transfer-guard contract with the ENTIRE fleet plane on:
    federation heartbeats, fleet context, watchdog, summaries. Decode
    steady state still uploads nothing host->device."""
    leader, build = make_leader()
    with AppRunner(build=build) as runner:
        container = Container()
        container.register_framework_metrics()
        tracer = Tracer(exporter=InMemoryExporter())
        eng = demo_llama_engine(
            EngineConfig(max_batch=4, max_seq=256, seed=0,
                         watchdog_interval_s=0.05), tracer=tracer)
        eng.attach_metrics(container.metrics)
        health, summary, metrics_src = engine_fleet_sources(eng)
        agent = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="perturb-0",
                            heartbeat_interval_s=0.05,
                            health_source=health,
                            summary_source=summary,
                            metrics_source=metrics_src,
                            tracer=tracer)
        agent.start()                # heartbeats + federation on a thread
        dog = StallWatchdog(eng, interval_s=0.05)
        dog.start()                  # watchdog polling health
        try:
            params = SamplingParams(temperature=0.0, max_new_tokens=200)
            with tracer.start_span("parent"):
                reqs = [eng.submit([1 + i, 2, 3], params)
                        for i in range(3)]
            batch = eng.waiting.pop_batch(len(reqs), first_wait_s=0.5)
            assert batch and len(batch) == len(reqs)
            eng._admit_batch(batch)
            eng._collect_prefills()
            for _ in range(2):       # admission upload + use_prev flip
                eng._decode_step()
                eng._drain_pending()
            transfers = eng.stats["h2d_transfers"]
            with jax.transfer_guard_host_to_device("disallow"):
                for _ in range(3):
                    eng._decode_step()
                    eng._drain_pending()
                time.sleep(0.15)     # heartbeats + watchdog fire inside
            assert eng.stats["h2d_transfers"] == transfers
            assert agent.assignment is not None  # fleet plane was live
        finally:
            dog.stop()
            agent.stop()


@pytest.mark.parametrize("layout_kw", [
    {},      # the default: the pool through the dense view off the TPU
    {"page_size": 16, "paged_attention": "xla"},    # the native path
])
def test_greedy_bit_identical_with_fleet_plane_enabled(layout_kw):
    prompts = [[5 + i, 2, 9] for i in range(3)]

    def run(eng, tracer=None):
        eng.start()
        sp = SamplingParams(temperature=0.0, max_new_tokens=24)
        reqs = [eng.submit(p, sp) for p in prompts]
        deadline = time.time() + 120
        while time.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            time.sleep(0.005)
        eng.stop()
        assert all(r.error is None for r in reqs)
        return [r.generated for r in reqs]

    bare = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=11, watchdog_interval_s=0,
        **layout_kw))
    want = run(bare)

    leader, build = make_leader()
    with AppRunner(build=build) as runner:
        container = Container()
        container.register_framework_metrics()
        tracer = Tracer(exporter=InMemoryExporter())
        eng = demo_llama_engine(EngineConfig(
            max_batch=4, max_seq=128, seed=11,
            watchdog_interval_s=0.05, **layout_kw), tracer=tracer)
        eng.attach_metrics(container.metrics)
        health, summary, metrics_src = engine_fleet_sources(eng)
        agent = WorkerAgent(f"http://127.0.0.1:{runner.port}",
                            host_id="bits-0", heartbeat_interval_s=0.05,
                            health_source=health,
                            summary_source=summary,
                            metrics_source=metrics_src, tracer=tracer)
        agent.start()
        try:
            got = run(eng, tracer)
        finally:
            agent.stop()
        assert got == want


# ---------------------------------------------------- app-level wiring
def test_app_serve_fleet_leader_and_join_fleet():
    from gofr_tpu.app import App
    from gofr_tpu.config import DictConfig
    from gofr_tpu.serving.tokenizer import ByteTokenizer

    leader_holder = {}

    def build(app):
        leader_holder["leader"] = app.serve_fleet_leader(
            coordinator="127.0.0.1:9999", host_id="the-leader")

    with AppRunner(build=build) as runner:
        worker_app = App(config=DictConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0",
            "APP_NAME": "fleet-worker", "TRACE_EXPORTER": "memory",
            "GOFR_TELEMETRY": "false"}))
        eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                             seed=0))
        worker_app.serve_model("llm", eng, ByteTokenizer())
        agent = worker_app.join_fleet(
            f"http://127.0.0.1:{runner.port}", host_id="app-worker",
            heartbeat_interval_s=0.1)
        # the app hooks start/stop engine+agent; drive both by hand here
        eng.start()
        try:
            agent.join()
            agent._heartbeat_once()
        finally:
            eng.stop()
        status, body = runner.get_json("/debug/fleet")
        host = body["data"]["hosts"]["app-worker"]
        assert host["status"] == "UP"
        assert "active_slots" in host["summary"]
        assert host["federated"]  # container manager snapshot attached
        status, _, data = runner.request("GET", "/control/fleet/metrics")
        assert 'host="app-worker"' in data.decode()
