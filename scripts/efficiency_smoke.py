"""CI smoke: drive traffic and assert the goodput observatory is live.

Boots a real App with a tiny serving engine, warms it (sealing the
recompile sentinel), drives chat traffic, and asserts:

- ``GET /debug/efficiency`` serves the goodput classification and the
  conservation invariant holds there: useful + sum(waste causes) ==
  busy (to float epsilon);
- ``app_engine_goodput_ratio`` is scraped off /metrics and is in
  (0, 1], and the ``app_engine_waste_seconds{cause}`` counters never
  exceed the busy total they conserve against;
- memory watermarks are present and monotone across two reads — the
  ``kv_bytes`` watermark (``app_engine_kv_bytes_watermark``) included;
- the recompile sentinel is sealed with zero recompiles (the smoke's
  traffic only uses warmed shapes);
- an int8 KV pool (``kv_dtype="int8"``) at the SAME byte budget
  admits at least 1.8x the resident sessions of the native pool.

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
from gofr_tpu.serving.engine import EngineConfig
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.tokenizer import ByteTokenizer


def parse_prometheus(text: str) -> dict:
    """name{labels} value -> {(name, labels-frag): value}."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        try:
            out[name_part] = float(value)
        except ValueError:
            continue
    return out


def series(parsed: dict, name: str) -> dict:
    return {k: v for k, v in parsed.items()
            if k == name or k.startswith(name + "{")}


def request(port, method, path, body=None, headers=None):
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


def check_kv_capacity() -> None:
    """int8 KV pages at a fixed ``kv_pool_bytes`` budget must hold
    >= 1.8x the resident sessions of the native pool: per-row bytes
    drop from itemsize*head_dim to head_dim+4 (codes + f32 scale),
    and the engine sizes the pool in bytes, not rows."""
    budget = 1 << 20
    sess_len, page = 64, 16
    pages_per_sess = -(-sess_len // page)

    def sessions(kv_dtype: str) -> int:
        eng = demo_llama_engine(EngineConfig(
            max_batch=4, max_seq=128, seed=0,
            page_size=page, kv_dtype=kv_dtype, kv_pool_bytes=budget))
        return eng._n_pages // pages_per_sess

    native, int8 = sessions("bf16"), sessions("int8")
    assert int8 >= 1.8 * native > 0, (native, int8)
    print(f"ok: int8 KV pool admits {int8} resident sessions vs "
          f"{native} native at the same {budget}-byte budget "
          f"({int8 / native:.2f}x >= 1.8x)")


def main() -> int:
    check_kv_capacity()
    engine = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=0,
        page_size=16, prefix_cache=True, paged_attention="view"))
    # warm + seal: post-warmup novel shapes would now count as
    # recompiles — the smoke's prompts stay inside the warmed bucket.
    # chunked=True matters: with the prefix cache on, repeat prompts
    # reattach through the chunk-with-history walk, and an unwarmed
    # chunk graph is a REAL serving-path recompile the sentinel
    # (correctly) flags
    engine.warmup(prompt_lens=(32,), chunked=True)
    app = App(config=DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0",
        "APP_NAME": "efficiency-smoke", "TRACE_EXPORTER": "memory",
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
        for i in range(4):
            status, data = request(
                port, "POST", "/chat",
                {"prompt": f"efficiency smoke {i}", "max_tokens": 8,
                 "temperature": 0.0})
            assert status == 201, (status, data[:200])
        print("ok: 4x /chat 201")
        time.sleep(0.6)  # throttled gauge refresh window

        status, data = request(port, "GET", "/debug/efficiency")
        assert status == 200, (status, data[:200])
        eff = json.loads(data)["data"]["llm"]
        gp = eff["goodput"]
        busy = gp["busy_s"]
        waste_sum = sum(gp["waste_s"].values())
        assert busy > 0, gp
        # THE invariant: every busy device-second is classified (the
        # serialized fields are rounded to 6 decimals, hence the 5e-6
        # grain; the raw-float residual must be exact)
        assert abs(gp["useful_s"] + waste_sum - busy) < 5e-6, gp
        assert abs(gp["conservation_error_s"]) < 1e-9, gp
        assert 0.0 < gp["goodput_ratio"] <= 1.0, gp
        assert gp["dominant_waste"] in (None, *gp["waste_s"]), gp
        print(f"ok: /debug/efficiency conserves "
              f"(busy={busy}s, ratio={gp['goodput_ratio']})")

        marks1 = eff["watermarks"]
        assert marks1.get("kv_pages", {}).get("value", 0) > 0, marks1
        assert marks1.get("kv_bytes", {}).get("value", 0) > 0, marks1
        assert marks1.get("host_rss_bytes", {}).get("value", 0) > 0, \
            marks1
        # pool accounting rides the same payload: total HBM bytes and
        # the per-token cost the byte-budget sizing is stated in
        assert eff["kv_bytes"] > 0, eff
        assert eff["kv_bytes_per_token"] > 0, eff
        sent = eff["recompiles"]
        assert sent["sealed"], sent
        assert sent["recompiles"] == 0, \
            f"warm-shape traffic tripped the sentinel: {sent}"
        print(f"ok: watermarks present, sentinel sealed with "
              f"{sent['recompiles']} recompiles")

        status, data = request(mport, "GET", "/metrics")
        assert status == 200, status
        parsed = parse_prometheus(data.decode())
        ratio = parsed.get("app_engine_goodput_ratio")
        assert ratio is not None, "app_engine_goodput_ratio not scraped"
        assert 0.0 < ratio <= 1.0, ratio
        waste = series(parsed, "app_engine_waste_seconds")
        assert waste, "no app_engine_waste_seconds{cause} series"
        # published counters lag the meter by at most one throttle
        # window, so they can never exceed the busy total they
        # conserve against
        assert sum(waste.values()) <= busy + 1e-6, (waste, busy)
        for key in ("app_engine_kv_pages_watermark",
                    "app_engine_kv_bytes_watermark",
                    "app_engine_host_rss_bytes_watermark"):
            assert parsed.get(key, 0.0) > 0.0, key
        print(f"ok: /metrics goodput ratio {ratio} in (0,1], "
              f"{len(waste)} waste cause series conserve")

        # one more request, then watermarks must be monotone
        status, _ = request(port, "POST", "/chat",
                            {"prompt": "efficiency smoke again",
                             "max_tokens": 8, "temperature": 0.0})
        assert status == 201
        time.sleep(0.6)
        status, data = request(port, "GET", "/debug/efficiency")
        marks2 = json.loads(data)["data"]["llm"]["watermarks"]
        for name, mark in marks1.items():
            assert marks2[name]["value"] >= mark["value"], (name,
                                                            marks1,
                                                            marks2)
        print("ok: watermarks monotone non-decreasing across reads")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(30)
        thread.join(10)
    return 0


if __name__ == "__main__":
    sys.exit(main())
