"""CI smoke: scrape a LIVE app's /metrics and assert the engine series.

Boots a real App with a tiny serving engine on ephemeral ports, drives
one chat request with a traceparent, scrapes the Prometheus text off
the metrics port, parses it, and asserts the engine observability
surface is present with samples — the end-to-end check that the
registry, the engine write sites and the exposition format agree.
Also hits /debug/engine for the flight-recorder ring. Exits nonzero on
any failure; one line per check on success.
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
from gofr_tpu.serving.tokenizer import ByteTokenizer

REQUIRED_SERIES = (
    "app_chat_ttft_seconds_count",
    "app_chat_queue_seconds_count",
    "app_chat_tpot_seconds_count",
    "app_chat_e2e_seconds_count",
    "app_engine_batch_occupancy_count",
    "app_engine_kv_pool_utilization",
    "app_engine_active_slots",
    "app_engine_tokens_per_second",
)


def parse_prometheus(text: str) -> dict[str, float]:
    """name{labels} value -> {name: value} (labels dropped, last wins)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        name = name_part.split("{", 1)[0]
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def request(port: int, method: str, path: str, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    headers = dict(headers or {})
    if isinstance(body, dict):
        body = json.dumps(body)
        headers.setdefault("Content-Type", "application/json")
    try:
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def main() -> int:
    engine = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=0,
        page_size=16, prefix_cache=True, paged_attention="view"))
    app = App(config=DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0",
        "APP_NAME": "metrics-smoke", "TRACE_EXPORTER": "memory",
        "GOFR_TELEMETRY": "false"}))
    app.serve_model("llm", engine, ByteTokenizer())

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
        print("FAIL: app did not start", file=sys.stderr)
        return 1
    try:
        port = app.http_server.bound_port
        mport = app.metrics_server.bound_port
        traceparent = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        status, data = request(
            port, "POST", "/chat",
            {"prompt": "observability smoke prompt", "max_tokens": 8,
             "temperature": 0.0},
            headers={"traceparent": traceparent})
        assert status == 201, (status, data[:200])
        print("ok: /chat 201")
        time.sleep(0.6)  # let the throttled gauges refresh post-retire

        status, data = request(port, "GET", "/debug/engine?n=16")
        assert status == 200, (status, data[:200])
        flight = json.loads(data)["data"]["llm"]["flight"]
        assert flight["passes"], "flight recorder ring is empty"
        print(f"ok: /debug/engine ({len(flight['passes'])} pass records)")

        status, data = request(mport, "GET", "/metrics")
        assert status == 200, status
        series = parse_prometheus(data.decode())
        missing = [s for s in REQUIRED_SERIES if s not in series]
        assert not missing, f"missing series: {missing}"
        zero = [s for s in ("app_chat_queue_seconds_count",
                            "app_chat_tpot_seconds_count",
                            "app_engine_batch_occupancy_count",
                            "app_engine_kv_pool_utilization")
                if series.get(s, 0.0) <= 0.0]
        assert not zero, f"series present but zero: {zero}"
        print(f"ok: /metrics ({len(series)} series, engine surface live)")

        spans = app.container.tracer.exporter.spans
        engine_spans = [s for s in spans if s.name.startswith("engine.")
                        and s.trace_id == "ab" * 16]
        assert engine_spans, "no engine.* spans linked to the traceparent"
        print(f"ok: {len(engine_spans)} engine.* spans on the inbound trace")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(30)
        thread.join(10)
    return 0


if __name__ == "__main__":
    sys.exit(main())
