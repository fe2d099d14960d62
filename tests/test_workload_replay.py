"""Workload capture + deterministic replay (serving/observability.py
WorkloadRecorder + serving/replay.py).

The contract under test: capture adds ZERO perturbation to the hot
path (transfer-guard + greedy bit-identity hold with capture ON), the
captured JSONL round-trips through the replay driver, and greedy
replay through a fresh engine with the same model/config/seed is
**bit-identical** to the recorded completions — with divergences
detected, located (first divergent token) and counted when it is not.
"""

import json
import time

import jax
import pytest

from gofr_tpu.container.container import Container
from gofr_tpu.metrics.registry import Manager as MetricsManager
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.observability import (WORKLOAD_VERSION,
                                            WorkloadRecorder)
from gofr_tpu.serving.replay import (load_workload, parse_workload,
                                     replay_workload)
from gofr_tpu.serving.tokenizer import ByteTokenizer

from .apputil import AppRunner


class _FakeReq:
    def __init__(self, i, generated=(1, 2, 3)):
        self.prompt_tokens = [10 + i, 5, 7]
        self.params = SamplingParams(temperature=0.0, max_new_tokens=8)
        self.submitted_at = 100.0 + i
        self.first_token_at = 100.5 + i
        self.finished_at = 101.0 + i
        self.generated = list(generated)
        self.tenant = f"t{i % 2}"
        self.error = None
        self.cancelled = False

    @property
    def ttft_ms(self):
        return (self.first_token_at - self.submitted_at) * 1000.0


def _run(eng, prompts, n, *, tenants=None, timeout=120):
    eng.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=n)
    reqs = [eng.submit(p, sp,
                       tenant=tenants[i] if tenants else None)
            for i, p in enumerate(prompts)]
    deadline = time.time() + timeout
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return reqs


# ---------------------------------------------------------- recorder unit
def test_recorder_ring_bounds_under_overflow():
    rec = WorkloadRecorder(4, engine_seed=3)
    rec.start()
    for i in range(10):
        rec.record(_FakeReq(i))
    snap = rec.snapshot()
    assert len(snap["records"]) == 4                    # ring bounded
    assert snap["header"]["recorded"] == 10
    assert snap["header"]["dropped"] == 6
    assert [r["prompt_tokens"][0] for r in snap["records"]] == \
        [16, 17, 18, 19]                                # oldest dropped
    assert rec.snapshot(2)["records"][-1]["prompt_tokens"][0] == 19
    # size 0 disables entirely; start() is a no-op
    off = WorkloadRecorder(0)
    off.start()
    off.record(_FakeReq(0))
    assert off.snapshot()["records"] == [] and not off.capturing


def test_recorder_not_capturing_until_started_and_start_clears():
    rec = WorkloadRecorder(8, engine_seed=1)
    rec.record(_FakeReq(0))
    assert rec.snapshot()["records"] == []              # disarmed
    rec.start()
    rec.record(_FakeReq(1))
    assert len(rec.snapshot()["records"]) == 1
    rec.stop()
    rec.record(_FakeReq(2))
    assert len(rec.snapshot()["records"]) == 1          # disarmed again
    rec.start()                                         # fresh capture
    assert rec.snapshot()["records"] == []


def test_redaction_never_emits_raw_tokens():
    rec = WorkloadRecorder(8, redact=True, engine_seed=1)
    rec.start()
    req = _FakeReq(0, generated=(42, 43, 44))
    rec.record(req)
    text = rec.to_jsonl()
    header, record = [json.loads(ln) for ln in text.splitlines()]
    assert header["redacted"] is True
    assert "prompt_tokens" not in record
    assert "completion_tokens" not in record
    assert record["prompt_len"] == 3 and record["completion_len"] == 3
    assert len(record["prompt_hash"]) == 24
    # no raw id sequence anywhere in the serialized file
    assert "42" not in json.dumps(record.get("prompt_hash", "")) or True
    for needle in ("[10, 5, 7]", "[42, 43, 44]", '"42,'):
        assert needle not in text
    # identical token streams collide (what divergence checks need);
    # different streams don't
    rec.record(_FakeReq(0, generated=(42, 43, 44)))
    rec.record(_FakeReq(0, generated=(42, 43, 99)))
    recs = rec.snapshot()["records"]
    assert recs[0]["completion_hash"] == recs[1]["completion_hash"]
    assert recs[0]["completion_hash"] != recs[2]["completion_hash"]


def test_workload_format_validation():
    with pytest.raises(ValueError, match="empty"):
        parse_workload("")
    with pytest.raises(ValueError, match="not a gofr-workload"):
        parse_workload('{"format": "something-else"}')
    with pytest.raises(ValueError, match="version"):
        parse_workload(json.dumps(
            {"format": "gofr-workload", "version": WORKLOAD_VERSION + 1}))
    with pytest.raises(ValueError, match="not JSON"):
        parse_workload('{"format": "gofr-workload", "version": %d}\n'
                       "garbage" % WORKLOAD_VERSION)
    ok = parse_workload(json.dumps(
        {"format": "gofr-workload", "version": WORKLOAD_VERSION})
        + '\n{"t": 1.0}')
    assert len(ok["records"]) == 1


def test_replay_refuses_redacted_workloads():
    workload = {"header": {"redacted": True}, "records": []}
    with pytest.raises(ValueError, match="redacted"):
        replay_workload(object(), workload)


# ----------------------------------------- zero-perturbation with capture
def test_steady_state_zero_h2d_with_capture_on():
    """The transfer-guard contract with workload capture armed:
    steady-state decode still uploads nothing."""
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=256,
                                         seed=0, workload_capture=True))
    assert eng.workload.capturing
    params = SamplingParams(temperature=0.0, max_new_tokens=200)
    reqs = [eng.submit([1 + i, 2, 3], params, tenant=f"t{i}")
            for i in range(3)]
    batch = eng.waiting.pop_batch(len(reqs), first_wait_s=0.5)
    assert batch and len(batch) == len(reqs)
    eng._admit_batch(batch)
    eng._collect_prefills()
    for _ in range(2):  # admission upload, then the use_prev flip
        eng._decode_step()
        eng._drain_pending()
    transfers = eng.stats["h2d_transfers"]
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            eng._decode_step()
            eng._drain_pending()
    assert eng.stats["h2d_transfers"] == transfers


@pytest.mark.parametrize("layout_kw", [
    {},      # the default: the pool through the dense view off the TPU
    {"page_size": 16, "paged_attention": "xla"},    # the native path
])
def test_greedy_bit_identical_with_capture_on(layout_kw):
    """Capture ON changes no generated token, and the captured
    completions ARE the emitted streams (both attention paths)."""
    prompts = [[5 + i, 2, 9] for i in range(3)]

    def cfg(**kw):
        return EngineConfig(max_batch=4, max_seq=128, seed=11,
                            **layout_kw, **kw)

    bare = demo_llama_engine(cfg())
    want = [r.generated for r in _run(bare, prompts, 16)]

    cap = demo_llama_engine(cfg(workload_capture=True))
    got = _run(cap, prompts, 16,
               tenants=[f"tenant-{i}" for i in range(3)])
    assert [r.generated for r in got] == want
    records = cap.workload.snapshot()["records"]
    assert len(records) == 3
    by_prompt = {tuple(r["prompt_tokens"]): r for r in records}
    for req in got:
        rec = by_prompt[tuple(req.prompt_tokens)]
        assert rec["completion_tokens"] == req.generated
        assert rec["status"] == "ok"
        assert rec["seed"] == 11 and rec["ttft_ms"] is not None


# ------------------------------------------------------------ replay e2e
def _capture_workload(seed=17, n_reqs=5, gen=12):
    cfg = EngineConfig(max_batch=4, max_seq=128, seed=seed,
                       workload_capture=True)
    eng = demo_llama_engine(cfg)
    prompts = [[3 + i, 8, 1, 9] for i in range(n_reqs)]
    _run(eng, prompts, gen,
         tenants=[f"team-{i % 2}" for i in range(n_reqs)])
    return eng.workload.to_jsonl(), cfg


def test_capture_then_replay_is_bit_identical(tmp_path):
    text, cfg = _capture_workload()
    path = tmp_path / "w.jsonl"
    path.write_text(text)
    workload = load_workload(str(path))
    assert workload["header"]["engine_seed"] == 17

    fresh = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128,
        seed=workload["header"]["engine_seed"]))
    try:
        report = replay_workload(fresh, workload, speed=1000.0,
                                 timeout_s=120.0)
    finally:
        fresh.stop()
    assert report["compared"] == 5
    assert report["divergent"] == 0
    assert report["bit_identical"] is True
    assert report["replay_errors"] == 0
    # tenants rode the replay into the fresh engine's accounting
    assert set(fresh.usage_ledger.rollup()["tenants"]) == \
        {"team-0", "team-1"}
    # both latency views populated
    assert report["recorded_latency"]["p50_ttft_ms"] is not None
    assert report["replayed_latency"]["p50_ttft_ms"] is not None


def test_replay_detects_and_locates_divergence(tmp_path):
    text, _ = _capture_workload(seed=19, n_reqs=3, gen=10)
    workload = parse_workload(text)
    # tamper: flip the 4th token of one recorded completion
    victim = workload["records"][1]
    victim["completion_tokens"] = list(victim["completion_tokens"])
    victim["completion_tokens"][3] ^= 1
    fresh = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                           seed=19))
    m = MetricsManager()
    fresh.attach_metrics(m)
    try:
        report = replay_workload(fresh, workload, speed=1000.0)
    finally:
        fresh.stop()
    assert report["divergent"] == 1
    assert report["bit_identical"] is False
    div = report["divergences"][0]
    assert div["kind"] == "token"
    assert div["first_divergent_token"] == 3
    assert m.get("app_replay_divergence").get() == 1.0


def test_replay_closed_loop_mode():
    text, _ = _capture_workload(seed=23, n_reqs=4, gen=8)
    workload = parse_workload(text)
    fresh = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128,
                                           seed=23))
    try:
        report = replay_workload(fresh, workload, closed_loop=2,
                                 timeout_s=120.0)
    finally:
        fresh.stop()
    assert report["mode"] == "closed-loop-2"
    assert report["divergent"] == 0 and report["compared"] == 4


# --------------------------------------------------------- HTTP surface
@pytest.fixture(scope="module")
def workload_app():
    engine = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                            seed=0))

    def build(app):
        app.enable_api_key_auth(key_names={"alpha-key": "team-alpha",
                                           "beta-key": "team-beta"})
        app.serve_model("llm", engine, ByteTokenizer())

    with AppRunner(build=build) as app:
        yield app


AUTH = {"X-Api-Key": "alpha-key"}


def _chat(app, key, prompt, n=4):
    status, _, data = app.request(
        "POST", "/chat",
        {"prompt": prompt, "max_tokens": n, "temperature": 0.0},
        headers={"X-Api-Key": key})
    assert status == 201, (status, data[:200])
    return json.loads(data)["data"]


def test_workload_endpoints_e2e(workload_app):
    app = workload_app
    # arm capture, drive traffic from two tenants, stop, download
    status, _, data = app.request("POST", "/debug/workload/start",
                                  headers=AUTH)
    assert status in (200, 201), (status, data[:200])
    _chat(app, "alpha-key", "workload alpha one")
    _chat(app, "beta-key", "workload beta one")
    status, _, data = app.request("POST", "/debug/workload/stop",
                                  headers=AUTH)
    assert status in (200, 201), status
    assert json.loads(data)["data"]["workload"]["records"] == 2

    status, headers, data = app.request("GET", "/debug/workload",
                                        headers=AUTH)
    assert status == 200, status
    assert "application/jsonl" in headers.get("Content-Type", "")
    lines = [json.loads(ln) for ln in data.decode().splitlines()]
    header, records = lines[0], lines[1:]
    assert header["format"] == "gofr-workload"
    assert header["version"] == WORKLOAD_VERSION
    assert len(records) == 2
    assert {r["tenant"] for r in records} == {"team-alpha", "team-beta"}
    assert all(r["status"] == "ok" and r["completion_tokens"]
               for r in records)

    # ?n= keeps the last n records
    status, _, data = app.request("GET", "/debug/workload?n=1",
                                  headers=AUTH)
    assert len(data.decode().strip().splitlines()) == 2  # header + 1

    # the downloaded file replays through the driver end to end
    workload = parse_workload(data.decode())
    assert len(workload["records"]) == 1


def test_workload_endpoint_input_hardening(workload_app):
    app = workload_app
    # garbage n -> 400 on BOTH debug surfaces
    for path in ("/debug/workload?n=zzz", "/debug/engine?n=zzz",
                 "/debug/workload?n=1.5", "/debug/engine?n=%20"):
        status, _, data = app.request("GET", path, headers=AUTH)
        assert status == 400, (path, status, data[:200])
    # negative and absurd values clamp instead of erroring
    for path in ("/debug/workload?n=-5", "/debug/engine?n=-1",
                 "/debug/workload?n=999999999999",
                 "/debug/engine?n=999999999999"):
        status, _, _ = app.request("GET", path, headers=AUTH)
        assert status == 200, (path, status)
    # unknown model -> 404
    status, _, _ = app.request("GET", "/debug/workload?model=nope",
                               headers=AUTH)
    assert status == 404
    status, _, _ = app.request("POST", "/debug/workload/start",
                               body={"redact": True},
                               headers={**AUTH,
                                        "Content-Type":
                                        "application/json"})
    assert status in (200, 201)
    # leave capture disarmed for other tests
    app.request("POST", "/debug/workload/stop", headers=AUTH)


def test_workload_endpoints_respect_app_auth(workload_app):
    app = workload_app
    for method, path in (("GET", "/debug/workload"),
                         ("POST", "/debug/workload/start"),
                         ("POST", "/debug/workload/stop"),
                         ("GET", "/debug/engine")):
        status, _, _ = app.request(method, path)
        assert status == 401, (method, path, status)
