"""Tenant usage metering, SLO burn-rate tracking, exemplar-linked
metrics (PR 5).

The invariant carried over from PRs 3-4: metering + SLO + exemplars
fully enabled add ZERO host->device transfers to steady-state decode
and change no generated token — everything is host arithmetic over
data the engine already collects at collect/retire.
"""

import json
import time

import jax
import pytest

from gofr_tpu.container.container import Container
from gofr_tpu.http.auth import (
    APIKeyAuthProvider,
    TenantResolver,
    credential_fingerprint,
    jwt_sign_hs256,
)
from gofr_tpu.logging import MockLogger
from gofr_tpu.metrics.registry import Manager as MetricsManager
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.observability import (
    SLOConfig,
    SLOTracker,
    UsageLedger,
    parse_window,
)
from gofr_tpu.serving.tokenizer import ByteTokenizer
from gofr_tpu.tracing.tracer import InMemoryExporter, Tracer

from .apputil import AppRunner


def _run(eng, submits, n, *, timeout=120):
    """submits: list of (prompt, tenant). Returns the requests."""
    eng.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=n)
    reqs = [eng.submit(p, sp, tenant=t) for p, t in submits]
    deadline = time.time() + timeout
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return reqs


# ------------------------------------------------------ tenant resolution
class TestTenantResolver:
    def test_each_principal_shape(self):
        r = TenantResolver()
        assert r.resolve(None) == "anonymous"
        assert r.resolve({}) == "anonymous"
        assert r.resolve({"username": "alice"}) == "alice"
        assert r.resolve({"claims": {"org": "acme", "sub": "u1"}}) == "acme"
        assert r.resolve({"claims": {"sub": "u1"}}) == "u1"
        assert r.resolve({"api_key": "deadbeef0123"}) == "key-deadbeef0123"
        assert r.resolve({"tenant": "team-blue"}) == "team-blue"
        # unknown shape: a hashed bucket, never the raw repr
        label = r.resolve({"auth": "s3cr3t-token"})
        assert label.startswith("t-") and "s3cr3t" not in label

    def test_cardinality_hard_bound(self):
        r = TenantResolver(max_tenants=3)
        seen = {r.resolve({"username": f"u{i}"}) for i in range(3)}
        assert seen == {"u0", "u1", "u2"}
        # the 4th (and every later) new label collapses
        assert r.resolve({"username": "u3"}) == "other"
        assert r.resolve({"username": "u99"}) == "other"
        # already-seen labels keep resolving to themselves
        assert r.resolve({"username": "u1"}) == "u1"

    def test_labels_sanitized(self):
        r = TenantResolver()
        assert r.resolve({"username": 'ev"il\nname{x}'}) == "ev_il_name_x_"
        assert len(r.resolve({"username": "x" * 300})) == 64

    def test_api_key_provider_hashes_and_maps(self):
        provider = APIKeyAuthProvider(
            keys=["legacy-key"], key_names={"named-key": "team-blue"})

        class Req:
            def __init__(self, key):
                self._key = key

            def header(self, k):
                return self._key if k == "x-api-key" else ""

        named = provider.authenticate(Req("named-key"))
        assert named["tenant"] == "team-blue"
        assert named["api_key"] == credential_fingerprint("named-key")
        assert "named-key" not in json.dumps(named)
        legacy = provider.authenticate(Req("legacy-key"))
        assert legacy == {"api_key": credential_fingerprint("legacy-key")}
        assert provider.authenticate(Req("wrong")) is None


# --------------------------------------------------------- usage ledger
def test_ledger_device_time_shares_sum_to_busy_time():
    """Each pass's busy span splits across its occupied rows; summed
    back over the retired requests it reproduces the recorded pass
    time — device-time attribution conserves the total."""
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                         seed=7))
    reqs = _run(eng, [([1 + i, 2, 3], f"t{i % 2}") for i in range(4)], 16)
    share_total = sum(r.device_s for r in reqs)
    pass_total = sum(p.get("dur", 0.0)
                     for p in eng.recorder.snapshot()["passes"])
    assert share_total > 0
    assert share_total <= pass_total * 1.05
    assert share_total >= pass_total * 0.75, (share_total, pass_total)
    # and the ledger accounted exactly what the requests accumulated
    roll = eng.usage_ledger.rollup()
    ledger_total = sum(t["device_s"] for t in roll["tenants"].values())
    assert ledger_total == pytest.approx(share_total, rel=1e-4)
    assert set(roll["tenants"]) == {"t0", "t1"}


def test_ledger_rollup_windows_and_status():
    ledger = UsageLedger()
    now = time.time()
    ledger.record(tenant="acme", status="ok", prompt_tokens=10,
                  completion_tokens=20, t=now - 600)
    ledger.record(tenant="acme", status="ok", prompt_tokens=1,
                  completion_tokens=2, t=now - 10)
    ledger.record(tenant="acme", status="error", prompt_tokens=3,
                  completion_tokens=0, t=now - 5)
    ledger.record(tenant="globex", status="ok", prompt_tokens=7,
                  completion_tokens=9, t=now - 5)
    total = ledger.rollup()
    assert total["tenants"]["acme"]["prompt_tokens"] == 14
    assert total["tenants"]["acme"]["requests"] == {"ok": 2, "error": 1}
    # 5-minute window drops the 10-minute-old event
    recent = ledger.rollup(window_s=300.0)
    assert recent["tenants"]["acme"]["prompt_tokens"] == 4
    assert recent["tenants"]["acme"]["requests"] == {"ok": 1, "error": 1}
    # tenant filter
    only = ledger.rollup(tenant="globex")
    assert set(only["tenants"]) == {"globex"}
    assert parse_window("5m") == 300.0
    with pytest.raises(ValueError):
        parse_window("soon")


def test_failed_submission_is_metered_as_error():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64))
    eng.stop()  # closes the waiting queue
    req = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4),
                     tenant="acme")
    assert req.error is not None
    roll = eng.usage_ledger.rollup(tenant="acme")
    assert roll["tenants"]["acme"]["requests"] == {"error": 1}
    assert roll["tenants"]["acme"]["completion_tokens"] == 0


# ------------------------------------------------------------------- SLO
class TestSLO:
    def test_burn_rate_math_on_synthetic_stream(self):
        cfg = SLOConfig(availability=0.99, windows=(60.0, 3600.0),
                        fast_burn=0.0, budget_window_s=3600.0)
        t0 = time.time()
        tracker = SLOTracker(cfg)
        # 40 old requests (2 bad) land only in the 1h window; 10 recent
        # (2 bad) land in both
        for i in range(40):
            tracker.record(good=i % 20 != 0, t=t0 - 600)
        for i in range(10):
            tracker.record(good=i % 5 != 0, t=t0 - 1)
        state = tracker.state()
        one_m, one_h = state["windows"]["1m"], state["windows"]["1h"]
        assert one_m["total"] == 10 and one_m["bad"] == 2
        assert one_m["error_rate"] == pytest.approx(0.2)
        assert one_m["burn_rate"] == pytest.approx(0.2 / 0.01)  # 20x
        assert one_h["total"] == 50 and one_h["bad"] == 4
        assert one_h["burn_rate"] == pytest.approx(0.08 / 0.01)
        # budget: 50 requests allow 0.5 errors, 4 burned -> deep red
        assert state["budget"]["remaining"] == -1.0  # clamped
        good_only = SLOTracker(cfg)
        for _ in range(100):
            good_only.record(good=True)
        assert good_only.state()["budget"]["remaining"] == 1.0

    def test_judge_thresholds(self):
        tracker = SLOTracker(SLOConfig(ttft_s=0.1, tpot_s=0.01,
                                       e2e_s=1.0))
        judge = tracker.judge
        assert judge(error=None, ttft_s=0.05, tpot_s=0.005, e2e_s=0.5)
        assert not judge(error="boom", ttft_s=0.05, tpot_s=0.005,
                         e2e_s=0.5)
        assert not judge(error=None, ttft_s=0.2, tpot_s=0.005, e2e_s=0.5)
        assert not judge(error=None, ttft_s=0.05, tpot_s=0.02, e2e_s=0.5)
        assert not judge(error=None, ttft_s=0.05, tpot_s=0.005, e2e_s=2.0)
        # None metrics (no tokens) never violate; None limits disable
        assert judge(error=None, ttft_s=None, tpot_s=None, e2e_s=0.5)
        lax = SLOTracker(SLOConfig(ttft_s=None, tpot_s=None, e2e_s=None))
        assert lax.judge(error=None, ttft_s=99, tpot_s=99, e2e_s=99)

    def test_fast_burn_warns_once_per_episode(self):
        logger = MockLogger()
        m = MetricsManager()
        m.new_gauge("app_slo_burn_rate", "x")
        m.new_gauge("app_slo_error_budget_remaining", "x")
        tracker = SLOTracker(
            SLOConfig(availability=0.9, windows=(0.5, 60.0),
                      fast_burn=5.0), metrics=m, logger=logger)
        for _ in range(5):
            tracker.record(good=False)  # burn 10x >= 5 -> trip
        warns = [ln for ln in logger.lines if ln["level"] == "WARN"]
        assert len(warns) == 1, "one WARN per episode, not per request"
        assert "fast burn" in warns[0]["message"]
        # gauges published
        assert m.get("app_slo_burn_rate").get(window="1m") > 0
        # episode ends (fast window empties), re-arms, trips again
        time.sleep(0.6)
        for _ in range(20):
            tracker.record(good=True)
        for _ in range(20):
            tracker.record(good=False)
        warns = [ln for ln in logger.lines if ln["level"] == "WARN"]
        assert len(warns) == 2


# -------------------------------------------------------------- exemplars
def test_exemplar_rendering_parity_and_capture():
    """Plain Prometheus output is byte-identical with exemplars stored
    or not; the OpenMetrics rendering carries them and terminates with
    # EOF."""
    bare = MetricsManager()
    bare.new_histogram("app_chat_e2e_seconds", "e2e", buckets=(0.1, 1))
    bare.record_histogram("app_chat_e2e_seconds", 0.05)

    with_ex = MetricsManager()
    with_ex.new_histogram("app_chat_e2e_seconds", "e2e", buckets=(0.1, 1))
    with_ex.record_histogram("app_chat_e2e_seconds", 0.05,
                             exemplar_trace_id="ab" * 16)
    assert bare.render_prometheus() == with_ex.render_prometheus()
    assert "trace_id" not in with_ex.render_prometheus()

    om = with_ex.render_openmetrics()
    assert f'# {{trace_id="{"ab" * 16}"}} 0.05' in om
    assert om.rstrip().endswith("# EOF")
    # the exemplar sits on the bucket the observation fell into
    line = next(ln for ln in om.splitlines() if "trace_id" in ln)
    assert 'le="0.1"' in line
    # no-exemplar managers still render valid OpenMetrics
    assert bare.render_openmetrics().rstrip().endswith("# EOF")


def test_exemplar_captured_from_active_span():
    """Histogram.record with no explicit trace id picks up the active
    request's trace (the contextvar the tracer middleware sets)."""
    tracer = Tracer(exporter=InMemoryExporter())
    m = MetricsManager()
    m.new_histogram("app_http_response", "h")
    with tracer.start_span("GET /x") as span:
        m.record_histogram("app_http_response", 0.02)
    om = m.render_openmetrics()
    assert f'trace_id="{span.trace_id}"' in om


# ---------------------------------------- zero-perturbation, all features
def test_steady_state_zero_h2d_with_metering_slo_exemplars_on():
    container = Container()
    container.register_framework_metrics()
    tracer = Tracer(exporter=InMemoryExporter())
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=256,
                                         seed=0), tracer=tracer)
    eng.attach_metrics(container.metrics)
    eng.slo = SLOTracker(SLOConfig(), metrics=container.metrics)
    params = SamplingParams(temperature=0.0, max_new_tokens=200)
    with tracer.start_span("parent"):
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
    # the metering plane observed those passes (device shares accrued)
    assert all(r.device_s > 0 for r in reqs)


@pytest.mark.parametrize("layout_kw", [
    {},      # the default: the pool through the dense view off the TPU
    {"page_size": 16, "paged_attention": "xla"},    # the native path
])
def test_greedy_bit_identical_with_metering_slo_exemplars_on(layout_kw):
    prompts = [[5 + i, 2, 9] for i in range(3)]

    def cfg():
        return EngineConfig(max_batch=4, max_seq=128, seed=11,
                            **layout_kw)

    bare = demo_llama_engine(cfg())
    bare.usage_ledger = None  # truly bare: no metering at all
    want = [r.generated
            for r in _run(bare, [(p, None) for p in prompts], 24)]

    container = Container()
    container.register_framework_metrics()
    tracer = Tracer(exporter=InMemoryExporter())
    obs = demo_llama_engine(cfg(), tracer=tracer)
    obs.attach_metrics(container.metrics)
    obs.slo = SLOTracker(SLOConfig(), metrics=container.metrics)
    got = _run(obs, [(p, f"tenant-{i}") for i, p in enumerate(prompts)],
               24)
    assert [r.generated for r in got] == want
    # every tenant accounted, SLO fed, exemplar-capable series present
    assert set(obs.usage_ledger.rollup()["tenants"]) == \
        {f"tenant-{i}" for i in range(3)}
    assert obs.slo.state()["lifetime"]["total"] == 3
    assert container.metrics.get_histogram_count(
        "app_tenant_e2e_seconds", tenant="tenant-0") == 1


# ------------------------------------------------------------------- e2e
@pytest.fixture(scope="module")
def tenant_app():
    engine = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128,
                                            seed=0))

    def build(app):
        app.enable_api_key_auth(key_names={"alpha-key": "team-alpha",
                                           "beta-key": "team-beta"})
        app.serve_model("llm", engine, ByteTokenizer())

    runner = AppRunner(build=build,
                       config={"TRACE_EXPORTER": "memory"})
    with runner as app:
        yield app


def _chat(app, key, prompt, n=6):
    status, _, data = app.request(
        "POST", "/chat",
        {"prompt": prompt, "max_tokens": n, "temperature": 0.0},
        headers={"X-Api-Key": key})
    assert status == 201, (status, data[:200])
    return json.loads(data)["data"]


def test_e2e_tenant_attribution_usage_and_slo(tenant_app):
    usages = [_chat(tenant_app, "alpha-key", "hello from alpha")["usage"],
              _chat(tenant_app, "alpha-key", "more alpha")["usage"],
              _chat(tenant_app, "beta-key", "hello from beta")["usage"]]
    assert [u["tenant"] for u in usages] == \
        ["team-alpha", "team-alpha", "team-beta"]
    # unauthenticated requests bounce (auth still enforced)
    status, _, _ = tenant_app.request(
        "POST", "/chat", {"prompt": "x", "max_tokens": 2})
    assert status == 401

    # /debug/usage totals == the sum of the chat responses' usage
    status, body = tenant_app.get_json("/debug/usage",
                                       headers={"X-Api-Key": "alpha-key"})
    assert status == 200
    tenants = body["data"]["llm"]["tenants"]
    for label in ("team-alpha", "team-beta"):
        want_prompt = sum(u["prompt_tokens"] for u in usages
                          if u["tenant"] == label)
        want_completion = sum(u["completion_tokens"] for u in usages
                              if u["tenant"] == label)
        assert tenants[label]["prompt_tokens"] == want_prompt, label
        assert tenants[label]["completion_tokens"] == want_completion
        assert tenants[label]["device_s"] > 0
    # tenant + window filters work
    status, body = tenant_app.get_json(
        "/debug/usage?tenant=team-beta&window=5m",
        headers={"X-Api-Key": "alpha-key"})
    assert status == 200
    assert set(body["data"]["llm"]["tenants"]) == {"team-beta"}

    # /debug/slo reports the tracked stream
    status, body = tenant_app.get_json("/debug/slo",
                                       headers={"X-Api-Key": "alpha-key"})
    assert status == 200
    slo = body["data"]["llm"]
    assert slo["lifetime"]["total"] >= 3
    assert "5m" in slo["windows"] and "1h" in slo["windows"]
    assert slo["budget"]["remaining"] == 1.0  # nothing failed

    # tenant-labeled series on /metrics; raw keys nowhere in sight
    _, _, data = tenant_app.request("GET", "/metrics",
                                    port=tenant_app.metrics_port)
    text = data.decode()
    assert 'app_tenant_requests{status="ok",tenant="team-alpha"} 2' in text
    assert 'tenant="team-beta"' in text
    assert "alpha-key" not in text and "beta-key" not in text


def test_e2e_openmetrics_exemplars_resolve_to_engine_traces(tenant_app):
    trace_id = "fe" * 16
    status, _, _ = tenant_app.request(
        "POST", "/chat",
        {"prompt": "exemplar probe", "max_tokens": 6, "temperature": 0.0},
        headers={"X-Api-Key": "alpha-key",
                 "traceparent": f"00-{trace_id}-{'cd' * 8}-01"})
    assert status == 201
    # plain scrape: classic text format, no exemplars
    _, headers, data = tenant_app.request("GET", "/metrics",
                                          port=tenant_app.metrics_port)
    assert "openmetrics" not in headers.get("Content-Type", "")
    assert "trace_id" not in data.decode()
    # negotiated scrape: exemplars + # EOF, same series
    _, headers, data = tenant_app.request(
        "GET", "/metrics", port=tenant_app.metrics_port,
        headers={"Accept": "application/openmetrics-text"})
    assert "application/openmetrics-text" in headers.get("Content-Type", "")
    om = data.decode()
    assert om.rstrip().endswith("# EOF")
    exemplar_ids = {seg.split('"')[1] for line in om.splitlines()
                    if "trace_id" in line
                    for seg in [line.split("trace_id=", 1)[1]]}
    assert trace_id in exemplar_ids
    # ...and that trace id resolves to a real engine.request span
    spans = tenant_app.app.container.tracer.exporter.spans
    assert any(s.name == "engine.request" and s.trace_id == trace_id
               for s in spans)
    # the engine.request span names the tenant
    span = next(s for s in spans if s.name == "engine.request"
                and s.trace_id == trace_id)
    assert span.attributes["tenant"] == "team-alpha"


def test_e2e_request_log_carries_tenant(tenant_app):
    """The logging middleware stamps the resolved tenant into the
    request log record (auth runs inside it, so the principal is on
    the request by the time the log line is built)."""
    from gofr_tpu.http.middleware import RequestLog, logging_middleware
    import asyncio

    resolver = tenant_app.app.container.tenant_resolver
    logger = MockLogger()

    class FakeReq:
        method, path, client_addr = "POST", "/chat", "1.2.3.4"
        auth_info = {"tenant": "team-alpha"}

    async def handler(request):
        from gofr_tpu.http.responder import ResponseData
        return ResponseData(status=200, body=b"{}")

    wrapped = logging_middleware(logger, tenant_resolver=resolver)(handler)
    asyncio.run(wrapped(FakeReq()))
    record = logger.lines[0]["message"]
    assert record["tenant"] == "team-alpha"
