"""CI smoke: the fleet front door routes by prefix cache and absorbs
a mid-traffic drain.

Boots a LEADER App with the data-plane router installed
(``serve_fleet_leader(router=RouterConfig())``) and TWO workers, each
serving a tiny paged-KV engine with the prefix cache on, joined to
the leader. Proves both halves of the router story:

1. **Prefix-aware beats round-robin.** A shared-system-prompt workload
   driven through the leader concentrates on the host whose heartbeat
   digest covers the prompt — its ``prefix_hits`` rise once per
   request, while round-robin on the same workload washes half the
   hits away across hosts.
2. **Typed-retry failover, bit-identical.** One worker drains
   mid-traffic (in-flight stream still running): new requests pinned
   to it draw typed ``draining``/``engine_down`` 503s, the router
   retries them on the survivor, every greedy output is bit-identical
   to its pre-drain reference with zero duplicated stream tokens, and
   the in-flight stream finishes with its terminal event.

Also asserts ``app_router_*`` series on the leader's ``/metrics`` and
the router block in ``/debug/fleet``. Exits nonzero on any failure;
one line per check on success.
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
from gofr_tpu.serving.engine import EngineConfig
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.router import RouterConfig, prefix_hash
from gofr_tpu.serving.tokenizer import ByteTokenizer

WORKERS = ("router-w0", "router-w1")
SYSTEM = ("You are the gofr-tpu router smoke. Answer in one short "
          "line. ")  # shared system prompt: the prefix every request bears
PAGE = 8


def request(port: int, method: str, path: str, body=None, headers=None,
            timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
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


def chat(port, prompt, *, max_tokens=4, session=None, stream=False):
    body = {"prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "stream": stream}
    if session:
        body["session"] = session
    return request(port, "POST", "/chat", body)


def sse_tokens(payload: bytes):
    """-> (token ids, saw_done) out of an SSE body."""
    tokens, done = [], False
    for line in payload.decode().splitlines():
        if not line.startswith("data: "):
            continue
        data = line[len("data: "):]
        if data == "[DONE]":
            done = True
        else:
            doc = json.loads(data)
            if "token" in doc:
                tokens.append(doc["token"])
    return tokens, done


class AppThread:
    """Boot an App on its own event loop thread (ephemeral ports)."""

    def __init__(self, app: App) -> None:
        self.app = app
        self.loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)

        async def main_coro():
            await self.app.start()
            self._started.set()
            await self.app._stop_event.wait()

        self.loop.run_until_complete(main_coro())

    def start(self) -> "AppThread":
        self._thread.start()
        if not self._started.wait(60):
            raise TimeoutError("app did not start")
        return self

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(
            self.app.stop(), self.loop).result(30)
        self._thread.join(10)

    @property
    def port(self) -> int:
        return self.app.http_server.bound_port

    @property
    def metrics_port(self) -> int:
        return self.app.metrics_server.bound_port


def make_app(name: str) -> App:
    return App(config=DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": name,
        "TRACE_EXPORTER": "memory", "GOFR_TELEMETRY": "false"}))


def main() -> int:
    leader_app = make_app("router-leader")
    leader = leader_app.serve_fleet_leader(
        host_id="leader",
        router=RouterConfig(max_retries=2, affinity_size=64))
    router = leader.router
    leader_thread = AppThread(leader_app).start()
    leader_url = f"http://127.0.0.1:{leader_thread.port}"
    lport = leader_thread.port

    workers, engines = [], {}
    for host in WORKERS:
        app = make_app(host)
        engine = demo_llama_engine(EngineConfig(
            max_batch=4, max_seq=256,
            page_size=PAGE, prefill_buckets=(8,), seed=5))
        app.serve_model("llm", engine, ByteTokenizer())
        app.join_fleet(leader_url, host_id=host,
                       heartbeat_interval_s=0.2)
        workers.append((host, AppThread(app).start()))
        engines[host] = engine

    try:
        # workers advertise their ephemeral ports via heartbeat — wait
        # until the leader's routing view can dial both
        deadline = time.time() + 30
        while time.time() < deadline:
            view = leader.routing_view()
            if len(view) == 2 and all(m["address"] for m in view):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("workers never became routable")
        print("ok: both workers advertised routable addresses")

        # ---------------------------------------- phase A: prefix routing
        # the warm prompt and the workload prompts differ only in the
        # LAST character: the divergence lands inside the final
        # (unregistered) page, so every workload request shares the
        # warm request's page-aligned cache key
        status, _, data = chat(lport, SYSTEM + "prefix w")
        assert status == 201, (status, data[:200])
        deadline = time.time() + 10
        owner = None
        while owner is None and time.time() < deadline:
            owner = next((h for h, e in engines.items()
                          if len(e._prefix_cache)), None)
            if owner is None:
                time.sleep(0.02)
        assert owner is not None, "warm request registered no prefix"
        other = next(h for h in WORKERS if h != owner)
        # wait until the owner's digest (with the aligned system-prefix
        # hash) rides a heartbeat into the leader's routing view
        tokens = ByteTokenizer().encode(SYSTEM + "prefix w")
        aligned = ((len(tokens) - 1) // PAGE) * PAGE
        expect = prefix_hash(tokens[:aligned])
        deadline = time.time() + 30
        while time.time() < deadline:
            view = {m["host_id"]: m for m in leader.routing_view()}
            digest = view.get(owner, {}).get("summary", {}) \
                .get("prefix_digest") or {}
            if expect in (digest.get("hashes") or []):
                break
            time.sleep(0.1)
        else:
            raise AssertionError(
                f"{owner}'s prefix digest never reached the leader")
        print(f"ok: {owner} published its prefix digest via heartbeat")

        hits_before = {h: engines[h].stats["prefix_hits"]
                       for h in WORKERS}
        routed_before = dict(router.debug_state()["routed"])
        for i in range(6):
            status, _, data = chat(lport, SYSTEM + f"prefix {i}")
            assert status == 201, (status, data[:200])
        routed = router.debug_state()["routed"]
        assert routed.get(owner, 0) - routed_before.get(owner, 0) == 6, \
            (routed, routed_before)
        prefix_gain = engines[owner].stats["prefix_hits"] \
            - hits_before[owner]
        assert prefix_gain == 6, prefix_gain
        assert engines[other].stats["prefix_hits"] \
            == hits_before[other], "non-owner saw prefix traffic"
        print(f"ok: prefix policy sent 6/6 to {owner} "
              f"(+{prefix_gain} prefix_hits, 0 on {other})")

        # round-robin baseline over the same workload: hits wash out
        router.config.policy = "round_robin"
        rr_before = {h: engines[h].stats["prefix_hits"]
                     for h in WORKERS}
        for i in range(6):
            status, _, data = chat(lport, SYSTEM + f"rrobin {i}")
            assert status == 201, (status, data[:200])
        rr_owner_gain = engines[owner].stats["prefix_hits"] \
            - rr_before[owner]
        assert rr_owner_gain <= 3, rr_owner_gain
        assert prefix_gain > rr_owner_gain, (prefix_gain, rr_owner_gain)
        router.config.policy = "prefix"
        print(f"ok: round-robin washed the owner down to "
              f"+{rr_owner_gain} hits — prefix routing measurably wins")

        state = router.debug_state()
        assert state["cache_hit_ratio"] > 0, state
        print(f"ok: routed cache-hit ratio "
              f"{state['cache_hit_ratio']} on /debug/fleet")

        # -------------------------------- phase B: drain-driven failover
        # greedy references while both hosts serve (the engines are
        # identical, so a reference is host-independent)
        prompts = [SYSTEM + f"failover {i}" for i in range(4)]
        stream_prompt = SYSTEM + "failover stream"
        refs = {}
        for p, n in [(p, 12) for p in prompts] + [(stream_prompt, 96)]:
            status, _, data = chat(lport, p, max_tokens=n)
            assert status == 201, (status, data[:200])
            refs[p] = json.loads(data)["data"]["tokens"]
            assert refs[p], p

        # a long stream pinned to the owner, running when drain begins
        router.affinity.put("s-stream", owner)
        stream_result = {}

        def run_stream():
            status, _, payload = chat(
                lport, stream_prompt, max_tokens=96,
                session="s-stream", stream=True)
            stream_result["status"] = status
            stream_result["payload"] = payload

        stream_thread = threading.Thread(target=run_stream)
        stream_thread.start()
        deadline = time.time() + 30
        owner_engine = engines[owner]
        while time.time() < deadline:
            if any(r is not None for r in owner_engine.active):
                break
            time.sleep(0.01)
        else:
            raise AssertionError("stream never became active on owner")

        drain_result = {}
        drain_thread = threading.Thread(
            target=lambda: drain_result.update(
                ok=owner_engine.drain(timeout_s=60)))
        drain_thread.start()

        # mid-drain traffic pinned at the draining host: typed rejects
        # fail over to the survivor, outputs stay bit-identical
        for i, p in enumerate(prompts):
            router.affinity.put(f"s-{i}", owner)
            status, _, payload = chat(lport, p, max_tokens=12,
                                      session=f"s-{i}", stream=True)
            assert status == 200, (status, payload[:200])
            got, done = sse_tokens(payload)
            assert done, f"stream truncated for {p!r}"
            assert got == refs[p][:len(got)] and len(got) == len(refs[p]), \
                (p, got, refs[p])  # bit-identical, zero duplicates

        drain_thread.join(90)
        stream_thread.join(30)
        assert not drain_thread.is_alive() and drain_result.get("ok"), \
            "drain did not complete cleanly"
        assert stream_result["status"] == 200
        got, done = sse_tokens(stream_result["payload"])
        assert done, "in-flight stream lost its terminal event"
        assert got == refs[stream_prompt][:len(got)] \
            and len(got) == len(refs[stream_prompt]), \
            "in-flight stream tokens diverged"
        state = router.debug_state()
        assert state["retries"] >= 1, state
        assert router.affinity.get("s-0") == other, \
            "failed-over session did not re-pin to the survivor"
        print(f"ok: drain absorbed — {state['retries']} typed "
              f"retries, 5/5 greedy outputs bit-identical, in-flight "
              f"stream finished")

        # ------------------------------------------ observability surface
        status, _, data = request(lport, "GET", "/debug/fleet")
        assert status == 200, status
        fleet = json.loads(data)["data"]
        assert fleet["router"]["routed_total"] >= 17, fleet["router"]
        assert fleet["router"]["policy"] == "prefix"
        print("ok: router block on /debug/fleet")

        status, _, data = request(leader_thread.metrics_port, "GET",
                                  "/metrics")
        assert status == 200, status
        text = data.decode()
        for name in ("app_router_routed", "app_router_retries",
                     "app_router_routed_share",
                     "app_router_cache_hit_ratio"):
            assert name in text, f"{name} missing from leader /metrics"
        print("ok: app_router_* series on the leader's /metrics")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        for _host, thread in workers:
            thread.stop()
        leader_thread.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
