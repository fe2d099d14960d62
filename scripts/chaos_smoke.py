"""CI smoke: chaos — deterministic fault injection end to end.

Three acts against the real stack, every fault fired by invocation
count (serving/faults.py — no wall clock, no RNG, reproducible under
bisect):

1. **Crash recovery**: a pass exception mid-traffic restarts the
   engine within its ``RestartPolicy`` budget; requests salvaged
   before their first token replay BIT-IDENTICALLY to a fault-free
   run, mid-stream casualties draw the typed retryable
   ``engine_restart`` reject and land bit-identically on retry; the
   goodput ledger still conserves (useful + sum(waste) == busy).
2. **Stall -> evict -> heal -> rejoin**: a wedged pass drives
   health to DEGRADED, the leader evicts on the gossip, and the
   worker rejoins on its own once the stall clears.
3. **Page exhaustion over HTTP**: an injected KV-pool exhaustion is a
   typed 503 with ``Retry-After`` + ``details.code`` on /chat and the
   OpenAI surface — never a crash; the next request serves 201.

Exits nonzero on any failure; one line per check on success.
"""

import asyncio
import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from gofr_tpu.app import App
from gofr_tpu.config import DictConfig
from gofr_tpu.serving.engine import (EngineConfig, RestartPolicy,
                                     SamplingParams)
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.tokenizer import ByteTokenizer

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=6)


def request(port: int, method: str, path: str, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    headers = dict(headers or {})
    if isinstance(body, dict):
        body = json.dumps(body)
        headers.setdefault("Content-Type", "application/json")
    try:
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def run_app(app):
    """Boot ``app`` on a background loop; returns (loop, thread)."""
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def runner():
        asyncio.set_event_loop(loop)

        async def main_coro():
            await app.start()
            started.set()
            await app._stop_event.wait()

        loop.run_until_complete(main_coro())

    thread = threading.Thread(target=runner, daemon=True)
    thread.start()
    if not started.wait(60):
        raise AssertionError("app did not start")
    return loop, thread


def stop_app(app, loop, thread):
    asyncio.run_coroutine_threadsafe(app.stop(), loop).result(30)
    thread.join(10)


def wait_all(reqs, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(r.finished_at is not None or r.error is not None
               for r in reqs):
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------- act 1: crash recovery
def act_crash_recovery() -> None:
    # 20 tokens = several fused decode passes per request, so decode
    # collects exist for nan_logits to corrupt mid-stream
    sp = SamplingParams(temperature=0.0, max_new_tokens=20)
    prompts = [[1 + i, 2, 3] for i in range(6)]
    ref = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64, seed=0))
    ref.start()
    want = [ref.submit_sync(p, sp).generated for p in prompts]
    ref.stop()
    assert all(len(w) == 20 for w in want), "fault-free reference broken?"

    # pass_raise crashes before any token is in flight (replay path);
    # nan_logits crashes at decode collect (mid-stream typed-reject
    # path) — one run covers both recovery branches deterministically
    budget = RestartPolicy(max_restarts=3, backoff_s=0.02)
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, seed=0,
        faults="pass_raise:at=3;nan_logits:at=4",
        restart_policy=budget))
    eng.start()
    t0 = time.time()
    reqs = [eng.submit(p, sp) for p in prompts]
    assert wait_all(reqs), "chaos traffic never settled"
    retried = 0
    for i, (prompt, req) in enumerate(zip(prompts, reqs)):
        if req.error is not None:
            rej = req.reject
            assert rej is not None and rej.code == "engine_restart", \
                (i, req.error)
            assert rej.retry_after_s > 0, rej
            retried += 1
            req = eng.submit(prompt, sp)
            assert wait_all([req]) and req.error is None, req.error
        assert req.generated == want[i], \
            f"recovered output diverged on prompt {i}"
    assert retried >= 1, "nan_logits never drew a mid-stream reject"
    health = eng.health_check()
    assert health["status"] == "UP", health
    assert 2 <= health["restarts"] <= budget.max_restarts, health
    assert "injected fault" in health["last_crash"], health
    elapsed = time.time() - t0
    assert elapsed < 60, f"recovery blew the budget: {elapsed:.1f}s"
    print(f"ok: crash -> restart {health['restarts']}/"
          f"{budget.max_restarts} in {elapsed:.1f}s; {len(prompts)} "
          f"outputs bit-identical ({retried} via typed retry)")

    gp = eng.goodput.state()
    waste_sum = sum(gp["waste_s"].values())
    assert gp["busy_s"] > 0, gp
    assert abs(gp["useful_s"] + waste_sum - gp["busy_s"]) < 5e-6, gp
    assert abs(gp["conservation_error_s"]) < 1e-9, gp
    eng.stop()
    print(f"ok: goodput conserves across the restart "
          f"(busy={gp['busy_s']}s, waste={round(waste_sum, 6)}s)")


# ------------------------------------ act 2: stall -> evict -> rejoin
def act_stall_evict_rejoin() -> None:
    from gofr_tpu.serving.control_plane import (ControlPlaneLeader,
                                                WorkerAgent,
                                                engine_fleet_sources)
    leader = ControlPlaneLeader(coordinator="127.0.0.1:8476")
    leader_app = App(config=DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0",
        "APP_NAME": "chaos-leader", "GOFR_TELEMETRY": "false"}))
    leader.install(leader_app)
    loop, thread = run_app(leader_app)
    eng = None
    agent = None
    try:
        port = leader_app.http_server.bound_port
        eng = demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=128, stall_threshold_s=0.3,
            faults="pass_stall:at=4,seconds=2.5"))
        health_src, summary_src, metrics_src = engine_fleet_sources(eng)
        agent = WorkerAgent(f"http://127.0.0.1:{port}", host_id="chaos-w",
                            heartbeat_interval_s=0.1,
                            health_source=health_src,
                            summary_source=summary_src)
        eng.start()
        agent.start()
        assert agent.assignment is not None, "initial join failed"
        req = eng.submit(list(range(2, 10)), SamplingParams(
            temperature=0.0, max_new_tokens=30))
        # the 4th pass wedges 2.5s >> the 0.3s stall threshold: the
        # DEGRADED gossip must get this host evicted
        deadline = time.time() + 20
        while time.time() < deadline \
                and leader.topology()["world_size"] != 0:
            time.sleep(0.05)
        assert leader.topology()["world_size"] == 0, \
            "stalled host never evicted"
        assert leader.metrics.get("app_fleet_evictions").get(
            reason="degraded") == 1.0
        print("ok: pass_stall -> DEGRADED gossip -> leader evicted "
              "the wedged host")
        # the stall clears, the request completes, health heals, and
        # the agent's own loop rejoins without operator action
        deadline = time.time() + 30
        while time.time() < deadline and agent.assignment is None:
            time.sleep(0.05)
        assert agent.assignment is not None, "healed host never rejoined"
        assert leader.topology()["world_size"] == 1
        assert wait_all([req], timeout=30)
        assert req.error is None and len(req.generated) == 30, req.error
        print("ok: stall cleared -> health UP -> worker rejoined; the "
              "in-flight stream survived untouched")
    finally:
        if agent is not None:
            agent.stop()
        if eng is not None:
            eng.stop()
        stop_app(leader_app, loop, thread)


# ------------------------------------- act 3: page exhaustion over HTTP
def act_page_exhaustion_http() -> None:
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, page_size=16,
        faults="page_exhaustion:at=1,times=2"))
    app = App(config=DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0",
        "APP_NAME": "chaos-smoke", "GOFR_TELEMETRY": "false"}))
    app.serve_model("llm", eng, ByteTokenizer())
    from gofr_tpu.serving.openai_compat import install_openai_routes
    install_openai_routes(app, eng, ByteTokenizer(), model="chaos")
    loop, thread = run_app(app)
    try:
        port = app.http_server.bound_port
        body = {"prompt": "kv pressure", "max_tokens": 4,
                "temperature": 0.0}
        status, headers, data = request(port, "POST", "/chat", body)
        assert status == 503, (status, data[:200])
        assert headers.get("Retry-After"), headers
        err = json.loads(data)["error"]
        details = err.get("details") or {}
        assert details.get("code") == "kv_exhausted", err
        print("ok: injected page exhaustion -> typed 503 on /chat "
              "(Retry-After + details.code=kv_exhausted)")
        status, headers, data = request(
            port, "POST", "/v1/completions",
            {"model": "chaos", "prompt": "kv pressure",
             "max_tokens": 4})
        assert status == 503, (status, data[:200])
        assert headers.get("Retry-After"), headers
        oa_err = json.loads(data)["error"]
        assert (oa_err.get("details") or {}).get("type") \
            == "server_error", oa_err
        print("ok: same fault maps to a 503 server_error on the "
              "OpenAI surface, Retry-After intact")
        # the plan window (times=2) is spent: the engine never crashed
        status, _, data = request(port, "POST", "/chat", body)
        assert status == 201, (status, data[:200])
        assert eng.health_check()["status"] == "UP"
        print("ok: engine survived — next /chat is 201, health UP")
    finally:
        stop_app(app, loop, thread)


def main() -> int:
    try:
        act_crash_recovery()
        act_stall_evict_rejoin()
        act_page_exhaustion_http()
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
