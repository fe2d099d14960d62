"""CI smoke: the pass-cost observatory end to end on a live app.

Boots one served model with the whole cost plane ON (cost model,
drift sentinel, auto-profiler, events, incidents) and drills the
tentpole story — "p95 regressed, which kernel?" answered from one
endpoint with the trace already captured:

1. **Baselines seal from serving traffic.** Greedy requests run until
   ``GET /debug/costs`` shows a sealed baseline for the decode
   signature; conservation holds: the cost table's ``total_s`` equals
   the goodput meter's busy seconds net of bubble waste.
2. **Induced drift is deterministic and bit-identical.** A
   ``cost_skew`` fault scoped to the decode signature inflates the
   OBSERVED duration only (no sleep, no token change): the re-run of
   the same greedy prompt produces byte-identical text while the
   sentinel opens EXACTLY ONE drift episode — one ``obs.cost_drift``
   event, one ``cost_drift`` incident bundle.
3. **The anomaly arms the profiler once.** The drift arms a bounded
   auto-capture whose artifact directory exists on disk, is referenced
   from exactly one incident bundle (``attrs.autoprof_dir``), and
   matches ``/debug/costs``' ``last_artifact``; the bundle's state
   snapshots carry the cost table that named the kernel.

Exits nonzero on any failure; one line per check on success.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.events import EventLedgerConfig, parse_events
from gofr_tpu.serving.faults import FaultPlan
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.tokenizer import ByteTokenizer
from router_smoke import AppThread, make_app, request

PROMPT = list(b"observe!")  # 8 tokens == the compiled prefill bucket
BASELINE_PASSES = 6
SKEW_S = 0.5  # >> any CPU pass; one skewed pass trips a 2.0x ratio


def get_json(port, path):
    status, _, data = request(port, "GET", path)
    assert status == 200, (path, status, data[:200])
    return json.loads(data)["data"]


def run_greedy(engine, max_new_tokens=24):
    req = engine.submit(PROMPT, SamplingParams(
        temperature=0.0, max_new_tokens=max_new_tokens))
    deadline = time.time() + 60
    while req.finished_at is None and req.error is None:
        assert time.time() < deadline, "greedy request stalled"
        time.sleep(0.002)
    assert req.error is None, req.error
    return list(req.generated)


def main() -> int:
    autoprof_dir = f"/tmp/gofr_cost_smoke_{os.getpid()}"
    app = make_app("cost-smoke")
    engine = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=256, page_size=8,
        prefill_buckets=(8,), seed=5,
        cost_baseline_passes=BASELINE_PASSES,
        cost_drift_ratio=2.0, cost_drift_sigma=6.0,
        autoprof_passes=4, autoprof_debounce_s=0.0,
        autoprof_dir=autoprof_dir,
        events=EventLedgerConfig(incident_window_s=0.0,
                                 incident_debounce_s=0.0)))
    # compile ahead of traffic so serving-path baselines measure warm
    # passes (the model never folds warmup timings — they'd be
    # compile-laden — so an unwarmed engine's first collects would
    # inflate the baseline std instead)
    engine.warmup(prompt_lens=(8,))
    app.serve_model("llm", engine, ByteTokenizer())
    thread = AppThread(app).start()
    port = thread.port
    try:
        # ----------------- phase 1: baselines seal, busy_s conserves
        baseline = run_greedy(engine)
        # fused decode emits several tokens per pass, so one request
        # is a few passes — keep serving until the baseline seals
        for _ in range(12):
            costs = get_json(port, "/debug/costs")["llm"]["costs"]
            sigs = costs["signatures"]
            decode_sig = next(s for s, rec in sigs.items()
                              if rec["kind"] == "decode")
            if "baseline_s" in sigs[decode_sig]:
                break
            assert run_greedy(engine) == baseline, "greedy diverged"
        assert "baseline_s" in sigs[decode_sig], \
            f"decode baseline did not seal after " \
            f"{sigs[decode_sig]['n']} passes: {sigs[decode_sig]}"
        assert any(rec["kind"] == "prefill" for rec in sigs.values()), \
            f"no prefill signature observed: {sorted(sigs)}"
        gp = engine.goodput
        accounted = gp.busy_s - gp.waste_s.get("bubble", 0.0)
        drift_off = costs["total_s"] - costs["synthetic_s"]
        assert abs(drift_off - accounted) < 1e-6, \
            (costs["total_s"], costs["synthetic_s"], gp.busy_s)
        assert costs["synthetic_s"] == 0.0
        print(f"ok: baseline sealed for {decode_sig} after "
              f"{sigs[decode_sig]['n']} passes; cost total "
              f"{costs['total_s']:.4f}s conserves against busy "
              f"seconds net of bubbles")

        # ------------- phase 2: induced drift, bit-identical outputs
        engine.faults = FaultPlan.parse(
            f"cost_skew:at=1,times=0,seconds={SKEW_S},"
            f"request={decode_sig}")
        rerun = run_greedy(engine)
        assert rerun == baseline, \
            "cost_skew perturbed greedy tokens: " \
            f"{baseline[:8]} vs {rerun[:8]}"
        print("ok: greedy rerun is bit-identical with the whole cost "
              "plane ON and the cost_skew fault firing")

        state = get_json(port, "/debug/costs")["llm"]
        costs, autoprof = state["costs"], state["autoprof"]
        assert costs["drift_episodes"] == 1, costs["drift_episodes"]
        assert costs["signatures"][decode_sig]["drifting"]
        assert costs["synthetic_s"] > 0
        gp = engine.goodput
        accounted = gp.busy_s - gp.waste_s.get("bubble", 0.0)
        assert abs(costs["total_s"] - costs["synthetic_s"]
                   - accounted) < 1e-6, \
            (costs["total_s"], costs["synthetic_s"], gp.busy_s)
        status, _, data = request(
            port, "GET", "/debug/events?kind=obs.cost_drift")
        assert status == 200, (status, data[:200])
        _, drift_events = parse_events(data.decode())
        assert len(drift_events) == 1, drift_events
        ev_attrs = drift_events[0].get("attrs") or {}
        assert ev_attrs["signature"] == decode_sig, drift_events[0]
        assert ev_attrs["ratio"] > 2.0, drift_events[0]
        print(f"ok: exactly one drift episode and one obs.cost_drift "
              f"event naming {decode_sig} (ratio {ev_attrs['ratio']})")

        # --------------- phase 3: one capture, one bundle, on disk
        deadline = time.time() + 30
        while autoprof.get("last_artifact") is None \
                and time.time() < deadline:
            run_greedy(engine, max_new_tokens=8)  # drain pass budget
            autoprof = get_json(port, "/debug/costs")["llm"]["autoprof"]
        artifact = autoprof["last_artifact"]
        assert artifact and artifact["ok"], autoprof
        assert artifact["reason"] == "cost_drift", artifact
        assert autoprof["captures"] == 1, autoprof
        files = [os.path.join(root, f)
                 for root, _, names in os.walk(artifact["dir"])
                 for f in names]
        assert files, f"capture dir {artifact['dir']} is empty"

        incidents = get_json(port, "/debug/incidents")["llm"]["incidents"]
        drifts = [m for m in incidents if m["reason"] == "cost_drift"]
        assert len(drifts) == 1, incidents
        bundle = get_json(port,
                          f"/debug/incidents?id={drifts[0]['id']}")
        assert bundle["attrs"]["autoprof_dir"] == artifact["dir"], \
            (bundle["attrs"], artifact)
        assert bundle["attrs"]["signature"] == decode_sig
        bundle_sigs = bundle["state"]["costs"]["costs"]["signatures"]
        assert decode_sig in bundle_sigs, sorted(bundle_sigs)
        print(f"ok: one auto-capture ({len(files)} artifact files) "
              f"referenced from exactly one cost_drift bundle "
              f"{bundle['id']}, which carries the cost table")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        thread.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
