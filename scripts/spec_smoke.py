"""CI smoke: adaptive speculative decoding end to end.

Asserts the four claims the speculation stack makes:

- **Greedy bit-identity**: a speculative engine's greedy output is
  token-identical to plain decode — checked on the int8 paged pool
  (KV compaction moves raw codes+scales, so acceptance must be exact)
  and on the dense slot layout (the gather/scatter fallback path);
- the ``app_engine_spec_accept_rate`` gauge is scraped off /metrics
  and sits in [0, 1], and ``/debug/efficiency`` serves the
  controller's state (fitted costs, per-slot EWMAs, lifetime ledger);
- the goodput conservation invariant ``useful + sum(waste) == busy``
  holds with the speculation controller active (rejected drafts are
  billed to ``spec_rejected``, never dropped on the floor);
- the recompile sentinel stays sealed with ZERO post-warmup
  recompiles — verify widths are pow-2 bucketed and every bucket is
  compiled during warmup, so adaptive depth changes never retrace.

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
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.tokenizer import ByteTokenizer

# repetitive pattern prompt: its n-grams recur, so prompt-lookup
# drafting engages deterministically
PATTERN = [7, 11, 13, 17, 19, 23, 29, 31] * 8


def parse_prometheus(text: str) -> dict:
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


def run_engine(cfg: EngineConfig, n_tokens: int = 24):
    engine = demo_llama_engine(cfg)
    engine.warmup(prompt_lens=(64,), chunked=True)
    engine.start()
    try:
        req = engine.submit_sync(PATTERN[:61], SamplingParams(
            temperature=0.0, max_new_tokens=n_tokens))
        assert req.error is None, req.error
        return list(req.generated), dict(engine.stats)
    finally:
        engine.stop()


def check_greedy_identity() -> None:
    """Spec ON == spec OFF, greedy, on an int8 and a bf16 pool (off
    the TPU both verify on the gathered dense view; the int8 pool
    requantizes the accepted rows on the scatter back)."""
    layouts = (
        ("int8 pool", dict(page_size=16, kv_dtype="int8")),
        ("bf16 pool", {}),
    )
    for name, extra in layouts:
        base = dict(max_batch=2, max_seq=128, seed=0,
                    prefill_buckets=(64,), decode_steps_per_pass=1,
                    spec_ngram=2, **extra)
        plain, _ = run_engine(EngineConfig(**base))
        spec, stats = run_engine(EngineConfig(speculative=True, **base))
        assert spec == plain, (
            f"{name}: speculative greedy output diverged from plain "
            f"decode:\n  spec : {spec}\n  plain: {plain}")
        assert stats["spec_passes"] > 0, (
            f"{name}: speculation never engaged: {stats}")
        assert stats["recompiles"] == 0, (
            f"{name}: post-warmup recompile: {stats}")
        print(f"ok: {name} greedy bit-identical over "
              f"{len(plain)} tokens ({stats['spec_passes']} verify "
              f"passes, {stats['spec_accepted']}/"
              f"{stats['spec_drafted']} drafts accepted, "
              f"0 recompiles)")


def main() -> int:
    check_greedy_identity()

    engine = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, seed=0,
        page_size=16, speculative=True, spec_ngram=2,
        decode_steps_per_pass=1))
    engine.warmup(prompt_lens=(32,), chunked=True)
    app = App(config=DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0",
        "APP_NAME": "spec-smoke", "TRACE_EXPORTER": "memory",
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
        # repetitive text so byte-level n-grams recur and drafting
        # engages inside the warmed 32-byte bucket
        for i in range(4):
            status, data = request(
                port := app.http_server.bound_port, "POST", "/chat",
                {"prompt": "abcabcabcabcabcabc", "max_tokens": 16,
                 "temperature": 0.0})
            assert status == 201, (status, data[:200])
        print("ok: 4x /chat 201")
        assert engine.stats["spec_passes"] > 0, dict(engine.stats)
        time.sleep(0.6)  # throttled gauge refresh window

        status, data = request(port, "GET", "/debug/efficiency")
        assert status == 200, (status, data[:200])
        eff = json.loads(data)["data"]["llm"]
        gp = eff["goodput"]
        busy = gp["busy_s"]
        waste_sum = sum(gp["waste_s"].values())
        assert busy > 0, gp
        # conservation with the controller ACTIVE: rejected-draft
        # device time lands in waste_s.spec_rejected, and every busy
        # second stays classified
        assert abs(gp["useful_s"] + waste_sum - busy) < 5e-6, gp
        assert "spec_rejected" in gp["waste_s"], gp
        print(f"ok: goodput conserves with controller active "
              f"(busy={busy}s, spec_rejected="
              f"{gp['waste_s']['spec_rejected']}s)")

        spec = eff["spec"]
        assert spec["adaptive"] is True, spec
        assert spec["drafted"] >= spec["accepted"] >= 0, spec
        assert 0.0 <= spec["accept_rate"] <= 1.0, spec
        assert len(spec["slots"]) == engine.config.max_batch, spec
        for slot in spec["slots"]:
            assert 0.0 <= slot["accept_ewma"] <= 1.0, spec
        print(f"ok: /debug/efficiency controller state "
              f"(accept_rate={spec['accept_rate']}, "
              f"drafted={spec['drafted']}, "
              f"sec_per_token={spec['sec_per_token']})")

        sent = eff["recompiles"]
        assert sent["sealed"], sent
        assert sent["recompiles"] == 0, (
            f"adaptive speculation tripped the sentinel: {sent}")
        print("ok: sentinel sealed, 0 post-warmup recompiles")

        status, data = request(app.metrics_server.bound_port, "GET",
                               "/metrics")
        assert status == 200, status
        parsed = parse_prometheus(data.decode())
        rate = parsed.get("app_engine_spec_accept_rate")
        assert rate is not None, \
            "app_engine_spec_accept_rate not scraped"
        assert 0.0 <= rate <= 1.0, rate
        print(f"ok: /metrics accept-rate gauge {rate} in [0, 1]")
    except AssertionError as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 1
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(30)
        thread.join(10)
    return 0


if __name__ == "__main__":
    sys.exit(main())
