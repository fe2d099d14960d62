"""CI smoke: the flight data recorder reconstructs a leader failover.

Boots two leader candidates (rank 0 active, rank 1 standby) with a
tuned event-ledger config (short incident window, no debounce) plus an
engine worker, then drills the observability story the ledger exists
for — a 3am incident an operator reconstructs from ONE endpoint:

1. **Kill the leader mid-traffic.** The worker's missed-ack walk
   elects the standby (epoch 2); the new leader's ``IncidentDetector``
   opens EXACTLY ONE ``failover`` bundle, and the bundle's
   ``trace_id`` resolves to a real span in the leader's in-memory
   exporter — the takeover join RPC that elected it.
2. **A stale epoch is fenced.** ``stale_epoch_replay`` is injected on
   the new leader: its next heartbeat ack carries ``epoch - 1``, the
   worker-side fence refuses it (``fleet.fence_reject``), re-discovers
   and rejoins — and the reject event rides the worker's next
   heartbeat digest into the leader's merged timeline.
3. **A crashing worker recovers.** A late-joining worker with an
   injected pass crash and a restart budget serves one request:
   ``engine.restart``/``engine.recovery`` land on its local ledger and
   federate the same way.
4. **One endpoint tells the whole story.** ``GET /debug/fleet/events``
   on the surviving leader yields a merged timeline spanning >= 3
   hosts with ``fleet.failover`` < ``fleet.fence_reject`` <
   ``engine.recovery`` in causal order; ``GET /debug/fleet/incidents``
   lists the single sealed bundle, complete with timeline, state
   snapshots and config/git digests.

Exits nonzero on any failure; one line per check on success.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from gofr_tpu.serving.control_plane import FleetConfig
from gofr_tpu.serving.engine import EngineConfig, RestartPolicy
from gofr_tpu.serving.events import EventLedgerConfig, parse_events
from gofr_tpu.serving.faults import FaultPlan
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.router import RouterConfig
from gofr_tpu.serving.tokenizer import ByteTokenizer
from router_smoke import AppThread, chat, make_app, request

SYSTEM = "You are the gofr-tpu events smoke. Answer in one line. "
HEARTBEAT = 0.5
LEDGER = dict(incident_window_s=3.0, incident_debounce_s=0.0)


def boot_leader(name, rank):
    app = make_app(name)
    leader = app.serve_fleet_leader(
        host_id=name, rank=rank,
        fleet=FleetConfig(),
        router=RouterConfig(max_retries=2, affinity_size=64),
        heartbeat_interval_s=HEARTBEAT,
        events=EventLedgerConfig(**LEDGER))
    return app, leader, AppThread(app).start()


def boot_worker(name, urls, *, engine_kw=None):
    app = make_app(name)
    engine = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=256, page_size=8,
        prefill_buckets=(8,), seed=5, **(engine_kw or {})))
    app.serve_model("llm", engine, ByteTokenizer())
    app.join_fleet(urls[0], host_id=name,
                   heartbeat_interval_s=HEARTBEAT,
                   fleet=FleetConfig(leader_candidates=urls,
                                     missed_acks_before_failover=1))
    return app, engine, AppThread(app).start()


def fleet_timeline(port, **params):
    query = "&".join(f"{k}={v}" for k, v in params.items())
    path = "/debug/fleet/events" + (f"?{query}" if query else "")
    status, _, data = request(port, "GET", path)
    assert status == 200, (status, data[:200])
    _header, events = parse_events(data.decode())
    return events


def wait_for(predicate, what, deadline_s=30, interval=0.1):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


def main() -> int:
    app0, leader0, thread0 = boot_leader("ev-leader0", 0)
    app1, leader1, thread1 = boot_leader("ev-leader1", 1)
    urls = (f"http://127.0.0.1:{thread0.port}",
            f"http://127.0.0.1:{thread1.port}")
    for lead in (leader0, leader1):
        lead.fleet.leader_candidates = urls

    _w0app, _w0eng, w0thread = boot_worker("ev-w0", urls)
    w1thread = None
    try:
        wait_for(lambda: len(leader0.routing_view()) == 1
                 and all(m["address"] for m in leader0.routing_view()),
                 "worker to become routable")
        print("ok: rank-0 leader active at epoch 1, worker routable")

        # --------------------- phase 1: kill the leader mid-traffic
        stream_result = {}

        def run_stream():
            try:
                stream_result["response"] = chat(
                    thread0.port, SYSTEM + "ev stream", max_tokens=48,
                    stream=True)
            except Exception as exc:  # died with the leader
                stream_result["error"] = exc

        stream_thread = threading.Thread(target=run_stream)
        stream_thread.start()
        time.sleep(0.05)
        thread0.stop()
        t_down = time.time()
        wait_for(lambda: leader1.leadership()["active"],
                 "standby takeover")
        assert leader1.epoch == 2, leader1.epoch
        stream_thread.join(30)
        print(f"ok: standby took over in {time.time() - t_down:.2f}s "
              "at epoch 2")

        # exactly ONE incident bundle, reason=failover, on the
        # survivor's fleet surface
        status, _, data = request(thread1.port, "GET",
                                  "/debug/fleet/incidents")
        assert status == 200, (status, data[:200])
        incidents = json.loads(data)["data"]["incidents"]
        assert len(incidents) == 1, incidents
        meta = incidents[0]
        assert meta["reason"] == "failover", meta
        print("ok: exactly one incident bundle, reason=failover")

        # ...whose trace_id resolves to a span the new leader actually
        # exported — the takeover join RPC that elected it
        trace_id = meta["trace_id"]
        assert trace_id, f"failover bundle carries no trace_id: {meta}"
        exporter = app1.container.tracer.exporter
        wait_for(lambda: any(s.trace_id == trace_id
                             for s in exporter.spans),
                 "the failover trace to appear in the span exporter")
        span_names = sorted({s.name for s in exporter.spans
                             if s.trace_id == trace_id})
        print(f"ok: bundle trace_id {trace_id[:8]}... resolves to "
              f"exported spans {span_names}")

        # ------------------- phase 2: stale epoch ack gets fenced
        wait_for(lambda: len(leader1.routing_view()) == 1,
                 "worker to rejoin the new leader")
        leader1.faults = FaultPlan.parse("stale_epoch_replay:at=1")
        wait_for(lambda: any(e["kind"] == "fleet.fence_reject"
                             for e in fleet_timeline(thread1.port)),
                 "fence_reject to federate into the fleet timeline")
        print("ok: injected stale ack fenced by the worker; "
              "fleet.fence_reject federated over heartbeats")

        # ------------- phase 3: crashing worker restarts + recovers
        _w1app, w1eng, w1thread = boot_worker(
            "ev-w1", (urls[1],),
            engine_kw=dict(
                faults="pass_raise:at=3",
                restart_policy=RestartPolicy(max_restarts=3,
                                             backoff_s=0.02)))
        status, _, data = chat(w1thread.port, SYSTEM + "ev crash",
                               max_tokens=12)
        assert status == 201, (status, data[:200])
        assert w1eng.events.snapshot(kind="engine.recovery"), \
            "crash did not leave an engine.recovery event"
        wait_for(lambda: any(e["kind"] == "engine.recovery"
                             for e in fleet_timeline(thread1.port)),
                 "engine.recovery to federate into the fleet timeline")
        print("ok: injected pass crash salvaged within the restart "
              "budget; engine.restart/recovery federated")

        # ---------------- phase 4: one endpoint, the whole story
        timeline = fleet_timeline(thread1.port)
        hosts = {e["host"] for e in timeline if e.get("host")}
        assert len(hosts) >= 3, f"timeline spans only {sorted(hosts)}"
        firsts = {}
        for event in timeline:  # already skew-corrected + sorted
            firsts.setdefault(event["kind"], event["ts"])
        order = ("fleet.failover", "fleet.fence_reject",
                 "engine.recovery")
        for kind in order:
            assert kind in firsts, (kind, sorted(firsts))
        assert firsts[order[0]] < firsts[order[1]] < firsts[order[2]], \
            {k: firsts[k] for k in order}
        print(f"ok: merged timeline spans {len(hosts)} hosts and "
              "orders failover < fence_reject < recovery")

        # the bundle sealed itself once its window passed, and it is
        # complete: merged timeline, state snapshots, config + git
        wait_for(lambda: time.time() >
                 meta["ts"] + LEDGER["incident_window_s"] + 0.1,
                 "the incident window to pass", interval=0.05)
        status, _, data = request(
            thread1.port, "GET",
            f"/debug/fleet/incidents?id={meta['id']}")
        assert status == 200, (status, data[:200])
        bundle = json.loads(data)["data"]
        assert bundle["sealed"] is True, bundle["id"]
        assert any(e["kind"] == "fleet.failover"
                   for e in bundle["timeline"]), "timeline lost the " \
            "failover that opened the bundle"
        for key in ("state", "git", "ledger"):
            assert bundle.get(key), f"bundle missing {key}"
        print(f"ok: bundle {bundle['id']} sealed with "
              f"{len(bundle['timeline'])} timeline events, "
              f"{len(bundle['state'])} state snapshots, git digest")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        w0thread.stop()
        if w1thread is not None:
            w1thread.stop()
        thread1.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
