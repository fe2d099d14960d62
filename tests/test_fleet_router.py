"""Fleet front-door router tests: digest hashing + plan scoring,
session affinity, typed-retry failover against live upstreams, the
``/control/leave`` interaction (draining host stops receiving routes
immediately, in-flight streams finish, affinity entries drop), the
engine's prefix-digest export, and autoscale decisions — deterministic
clocks throughout, no sleeps around race windows.

The live-proxy tests boot a REAL leader app (``serve_fleet_leader``
with a ``RouterConfig``) in front of real worker apps whose handlers
are scripted (echo / stream / typed-503) — the full HTTP proxy path
without engine weight.
"""

import asyncio
import http.client
import json
import threading

import pytest

from gofr_tpu.http.responder import ResponseData
from gofr_tpu.serving.router import (Autoscaler, FleetRouter,
                                     RouterConfig, SessionAffinity,
                                     aligned_prefix_hashes, prefix_hash)

from .apputil import AppRunner


# ------------------------------------------------------- digest helpers
class TestDigestHelpers:
    def test_prefix_hash_is_stable_and_content_keyed(self):
        assert prefix_hash((1, 2, 3)) == prefix_hash([1, 2, 3])
        assert prefix_hash((1, 2, 3)) != prefix_hash((1, 2, 4))
        assert len(prefix_hash(range(100))) == 16

    def test_aligned_hashes_longest_first_and_leave_a_suffix(self):
        prompt = list(range(9))  # page 4: aligned prefixes 4 and 8
        got = aligned_prefix_hashes(prompt, 4, 64)
        assert [c for c, _ in got] == [8, 4]
        assert got[0][1] == prefix_hash(prompt[:8])
        # exactly page-aligned length: the full prompt may NOT be a
        # candidate (the engine always leaves >= 1 suffix token)
        got = aligned_prefix_hashes(list(range(8)), 4, 64)
        assert [c for c, _ in got] == [4]

    def test_max_pages_bounds_the_probe(self):
        got = aligned_prefix_hashes(list(range(100)), 4, 2)
        assert [c for c, _ in got] == [8, 4]

    def test_short_prompt_has_no_candidates(self):
        assert aligned_prefix_hashes([1, 2], 4, 64) == []


# ------------------------------------------------------ session affinity
class TestSessionAffinity:
    def test_lru_bound_evicts_oldest(self):
        aff = SessionAffinity(2)
        aff.put("a", "h1")
        aff.put("b", "h2")
        aff.get("a")          # touch: b becomes LRU
        aff.put("c", "h3")
        assert aff.get("a") == "h1"
        assert aff.get("b") is None
        assert aff.get("c") == "h3"

    def test_drop_host_sweeps_only_that_host(self):
        aff = SessionAffinity(8)
        for s, h in (("a", "h1"), ("b", "h2"), ("c", "h1")):
            aff.put(s, h)
        assert aff.drop_host("h1") == 2
        assert aff.get("a") is None and aff.get("c") is None
        assert aff.get("b") == "h2"

    def test_zero_size_disables(self):
        aff = SessionAffinity(0)
        aff.put("a", "h1")
        assert aff.get("a") is None


# ----------------------------------------------------------- plan scoring
class FakeLeader:
    """routing_view/evict surface of ControlPlaneLeader, no threads."""

    def __init__(self, members):
        self.members = members
        self.evict_listeners = []
        self.status_sources = {}
        self.evicted = []

    def routing_view(self):
        return [dict(m, summary=dict(m["summary"]))
                for m in self.members]

    def add_evict_listener(self, fn):
        self.evict_listeners.append(fn)

    def evict(self, host_id, reason="manual"):
        self.evicted.append((host_id, reason))
        self.members = [m for m in self.members
                        if m["host_id"] != host_id]
        for fn in self.evict_listeners:
            fn(host_id, reason)


def member(host, *, hashes=(), page=4, active=0, waiting=0,
           pass_p50=0.01, status="UP"):
    return {"host_id": host, "address": f"127.0.0.1:1{host[-1]}",
            "status": status,
            "summary": {"active_slots": active, "waiting": waiting,
                        "pass_p50_s": pass_p50,
                        "prefix_digest": {"page": page,
                                          "hashes": list(hashes)}}}


PROMPT = list(range(20))  # page 4: candidates 16, 12, 8, 4


class TestPlan:
    def test_longest_prefix_match_wins_over_load(self):
        owner = member("w1", hashes=[prefix_hash(PROMPT[:8])],
                       active=3, waiting=4)
        idle = member("w2")
        router = FleetRouter(FakeLeader([idle, owner]))
        plan = router.plan(PROMPT)
        assert [c["host_id"] for c in plan] == ["w1", "w2"]
        assert plan[0]["covered"] == 8

    def test_longer_coverage_beats_shorter(self):
        short = member("w1", hashes=[prefix_hash(PROMPT[:4])])
        long = member("w2", hashes=[prefix_hash(PROMPT[:16])])
        router = FleetRouter(FakeLeader([short, long]))
        plan = router.plan(PROMPT)
        assert plan[0]["host_id"] == "w2" and plan[0]["covered"] == 16

    def test_load_tiebreak_uses_depth_times_sec_per_token(self):
        # w1: 6 in flight at 10ms/token = 0.06; w2: 2 at 20ms = 0.04
        busy_fast = member("w1", active=4, waiting=2, pass_p50=0.01)
        calm_slow = member("w2", active=1, waiting=1, pass_p50=0.02)
        router = FleetRouter(FakeLeader([busy_fast, calm_slow]))
        assert router.plan(PROMPT)[0]["host_id"] == "w2"

    def test_affinity_moves_its_host_to_front(self):
        owner = member("w1", hashes=[prefix_hash(PROMPT[:8])])
        other = member("w2")
        router = FleetRouter(FakeLeader([owner, other]))
        router.affinity.put("s1", "w2")
        plan = router.plan(PROMPT, session="s1")
        assert plan[0]["host_id"] == "w2" and plan[0]["affinity"]
        assert plan[1]["host_id"] == "w1"

    def test_evict_drops_affinity_and_the_member(self):
        leader = FakeLeader([member("w1"), member("w2")])
        router = FleetRouter(leader)
        router.affinity.put("s1", "w1")
        leader.evict("w1", reason="leave")
        assert router.affinity.get("s1") is None
        assert [c["host_id"] for c in router.plan(PROMPT)] == ["w2"]

    def test_non_up_members_are_never_candidates(self):
        leader = FakeLeader([member("w1", status="DOWN"), member("w2")])
        router = FleetRouter(leader)
        assert [c["host_id"] for c in router.plan(PROMPT)] == ["w2"]

    def test_round_robin_rotates(self):
        leader = FakeLeader([member("w1"), member("w2")])
        router = FleetRouter(leader,
                             RouterConfig(policy="round_robin"))
        first = [router.plan(PROMPT)[0]["host_id"] for _ in range(4)]
        assert first == ["w1", "w2", "w1", "w2"]


# -------------------------------------------------- engine digest export
@pytest.fixture(scope="module")
def paged_engine():
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import demo_llama_engine
    engine = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, kv_layout="paged", page_size=4,
        prefix_digest_hashes=2, seed=0))
    yield engine
    engine.stop()


class TestEngineDigest:
    def _pin(self, engine, key):
        engine._prefix_cache[tuple(key)] = []
        engine._prefix_digest_dirty = True

    def test_digest_reflects_cache_and_rides_fleet_summary(self,
                                                           paged_engine):
        e = paged_engine
        e._prefix_cache.clear()
        key = tuple(range(8))
        self._pin(e, key)
        e._refresh_prefix_digest()
        d = e.prefix_digest()
        assert d["page"] == 4 and d["entries"] == 1
        assert d["hashes"] == [prefix_hash(key)]
        assert e.recorder.fleet_summary()["prefix_digest"] == d

    def test_bound_keeps_the_newest_lru_entries(self, paged_engine):
        e = paged_engine
        e._prefix_cache.clear()
        keys = [tuple(range(n)) for n in (4, 8, 12)]
        for k in keys:
            self._pin(e, k)
        e._refresh_prefix_digest()
        d = e.prefix_digest()
        # prefix_digest_hashes=2: only the two newest keys are hashed,
        # but entries still reports the real cache size
        assert d["entries"] == 3
        assert d["hashes"] == [prefix_hash(k) for k in keys[-2:]]

    def test_clean_flag_skips_reassembly(self, paged_engine):
        e = paged_engine
        e._prefix_cache.clear()
        self._pin(e, range(4))
        e._refresh_prefix_digest()
        before = e.prefix_digest()
        e._prefix_cache[tuple(range(20, 28))] = []  # no dirty mark
        e._refresh_prefix_digest()
        assert e.prefix_digest() is before  # same object: no rebuild

    def test_reset_clears_and_marks_dirty(self, paged_engine):
        e = paged_engine
        self._pin(e, range(4))
        e._refresh_prefix_digest()
        e._reset_runtime_state()
        assert e._prefix_digest_dirty
        e._refresh_prefix_digest()
        assert e.prefix_digest()["hashes"] == []

    def test_digest_boundary_is_declared(self):
        from gofr_tpu.serving.engine import Engine
        reason = getattr(Engine._refresh_prefix_digest,
                         "__gofr_hot_path_boundary__", "")
        assert isinstance(reason, str) and reason.strip()


# ------------------------------------------------------------ autoscaler
class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


def load_view(*loads, occ=0.5):
    return [{"host_id": f"w{i}",
             "summary": {"active_slots": load, "waiting": 0,
                         "occupancy_mean": occ}}
            for i, load in enumerate(loads)]


class TestAutoscaler:
    def cfg(self, **kw):
        kw.setdefault("autoscale", True)
        kw.setdefault("setpoint_concurrency", 4)
        kw.setdefault("sustain_s", 10.0)
        kw.setdefault("cooldown_s", 30.0)
        return RouterConfig(**kw)

    def test_sustained_pressure_scales_up(self):
        clock = FakeClock()
        scaler = Autoscaler(self.cfg(), clock=clock)
        assert scaler.observe(load_view(6, 6)) is None  # arming tick
        clock.advance(9.9)
        assert scaler.observe(load_view(6, 6)) is None  # not sustained
        clock.advance(0.2)
        decision = scaler.observe(load_view(6, 6))
        assert decision and decision["action"] == "scale_up"

    def test_blip_rearms_the_sustain_window(self):
        clock = FakeClock()
        scaler = Autoscaler(self.cfg(), clock=clock)
        scaler.observe(load_view(6, 6))
        clock.advance(8)
        scaler.observe(load_view(1, 1))        # pressure lapsed
        clock.advance(4)
        assert scaler.observe(load_view(6, 6)) is None  # re-armed

    def test_sustained_idle_scales_down_least_loaded(self):
        clock = FakeClock()
        scaler = Autoscaler(self.cfg(), clock=clock)
        view = load_view(2, 1, occ=0.01)
        scaler.observe(view)
        clock.advance(11)
        decision = scaler.observe(view)
        assert decision["action"] == "scale_down"
        assert decision["victim"] == "w1"

    def test_single_host_never_scales_down(self):
        clock = FakeClock()
        scaler = Autoscaler(self.cfg(), clock=clock)
        scaler.observe(load_view(0, occ=0.0))
        clock.advance(60)
        assert scaler.observe(load_view(0, occ=0.0)) is None

    def test_cooldown_spaces_decisions(self):
        clock = FakeClock()
        scaler = Autoscaler(self.cfg(), clock=clock)
        scaler.observe(load_view(6, 6))
        clock.advance(11)
        assert scaler.observe(load_view(6, 6))["action"] == "scale_up"
        clock.advance(11)
        assert scaler.observe(load_view(6, 6)) is None  # cooling down
        clock.advance(31)
        assert scaler.observe(load_view(6, 6))["action"] == "scale_up"

    def test_act_mode_routes_scale_down_through_leader_evict(self):
        clock = FakeClock()
        leader = FakeLeader([member("w0"), member("w1")])
        router = FleetRouter(
            leader, self.cfg(autoscale_act=True, idle_occupancy=0.10),
            clock=clock)
        router.autoscaler.observe(load_view(1, 2, occ=0.01))
        clock.advance(11)
        decision = router.autoscaler.observe(load_view(1, 2, occ=0.01))
        assert decision["action"] == "scale_down"
        assert leader.evicted == [("w0", "scale_down")]

    def test_setpoint_file_read(self, tmp_path):
        path = tmp_path / "setpoint.json"
        path.write_text(json.dumps({"max_concurrency": 7, "qps": 3.2}))
        scaler = Autoscaler(self.cfg(setpoint_concurrency=0))
        scaler.load_setpoint_file(str(path))
        assert scaler.setpoint == 7
        scaler.load_setpoint_file(str(tmp_path / "missing.json"))
        assert scaler.setpoint == 7  # unreadable file keeps the old


# ------------------------------------------------------ live proxy tests
def build_worker(app):
    """A scripted worker: echo /chat (with the host name), a gated SSE
    stream, and typed-503 / bare-503 / 429 modes."""
    state = {"name": "?", "hits": 0, "mode": "ok",
             "started": threading.Event(),
             "release": threading.Event()}
    app._test_state = state

    @app.post("/chat")
    async def chat(ctx):
        state["hits"] += 1
        if state["mode"] == "draining":
            return ResponseData(
                status=503, headers={"Retry-After": "1"},
                body=json.dumps({"error": {
                    "message": "draining",
                    "details": {"code": "draining"}}}).encode())
        if state["mode"] == "plain_503":
            return ResponseData(status=503, body=json.dumps(
                {"error": {"message": "wedged"}}).encode())
        if state["mode"] == "rate_limited":
            return ResponseData(
                status=429, headers={"Retry-After": "2"},
                body=json.dumps({"error": {
                    "message": "slow down",
                    "details": {"code": "rate_limited"}}}).encode())
        body = ctx.bind() or {}
        if body.get("stream"):
            async def sse():
                state["started"].set()
                yield "data: first\n\n"
                while not state["release"].is_set():
                    await asyncio.sleep(0.005)
                yield "data: second\n\n"
                yield "data: [DONE]\n\n"
            return ResponseData(content_type="text/event-stream",
                                stream=sse())
        return {"host": state["name"],
                "echo": body.get("prompt", "")}


def build_leader(app):
    app._leader = app.serve_fleet_leader(
        router=RouterConfig(max_retries=2, affinity_size=16))


@pytest.fixture()
def fleet():
    with AppRunner(build=build_leader) as leader, \
            AppRunner(build=build_worker) as w1, \
            AppRunner(build=build_worker) as w2:
        w1.app._test_state["name"] = "w1"
        w2.app._test_state["name"] = "w2"
        control = leader.app._leader
        control.join("w1", f"127.0.0.1:{w1.port}", 1)
        control.join("w2", f"127.0.0.1:{w2.port}", 1)
        yield leader, w1, w2


def post_chat(runner, body, headers=None):
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    return runner.request("POST", "/chat", body=json.dumps(body),
                          headers=hdrs)


class TestLiveProxy:
    def test_proxies_and_pins_session(self, fleet):
        leader, w1, w2 = fleet
        status, _, body = post_chat(
            leader, {"prompt": "hello", "session": "s1"})
        assert status == 201, body
        first_host = json.loads(body)["data"]["host"]
        runner = {"w1": w1, "w2": w2}[first_host]
        for _ in range(3):
            status, _, body = post_chat(
                leader, {"prompt": "again", "session": "s1"})
            assert status == 201
            assert json.loads(body)["data"]["host"] == first_host
        assert runner.app._test_state["hits"] == 4
        router = leader.app._leader.router
        state = router.debug_state()
        assert state["affinity"]["hits"] >= 3
        assert state["routed_total"] == 4

    def test_session_header_works_like_the_body_field(self, fleet):
        leader, w1, w2 = fleet
        status, _, body = post_chat(leader, {"prompt": "x"},
                                    headers={"X-Session-Id": "hdr"})
        assert status == 201
        host = json.loads(body)["data"]["host"]
        assert leader.app._leader.router.affinity.get("hdr") == host

    def test_typed_503_fails_over_to_the_survivor(self, fleet):
        leader, w1, w2 = fleet
        w1.app._test_state["mode"] = "draining"
        w2.app._test_state["mode"] = "draining"
        # pin the session to w1 so the draining host is first choice
        leader.app._leader.router.affinity.put("s", "w1")
        w2.app._test_state["mode"] = "ok"
        status, _, body = post_chat(
            leader, {"prompt": "failover", "session": "s"})
        assert status == 201, body
        assert json.loads(body)["data"]["host"] == "w2"
        assert w1.app._test_state["hits"] == 1  # refused once
        state = leader.app._leader.router.debug_state()
        assert state["retries"] >= 1
        # the session re-pins to the host that actually served
        assert leader.app._leader.router.affinity.get("s") == "w2"

    def test_429_mirrors_immediately_with_retry_after(self, fleet):
        leader, w1, w2 = fleet
        for w in (w1, w2):
            w.app._test_state["mode"] = "rate_limited"
        status, headers, body = post_chat(leader, {"prompt": "x"})
        assert status == 429
        assert headers.get("Retry-After") == "2"
        assert w1.app._test_state["hits"] \
            + w2.app._test_state["hits"] == 1  # no failover on 429

    def test_untyped_503_is_not_retried(self, fleet):
        leader, w1, w2 = fleet
        for w in (w1, w2):
            w.app._test_state["mode"] = "plain_503"
        status, _, _ = post_chat(leader, {"prompt": "x"})
        assert status == 503
        assert w1.app._test_state["hits"] \
            + w2.app._test_state["hits"] == 1

    def test_all_hosts_draining_mirrors_the_last_503(self, fleet):
        leader, w1, w2 = fleet
        for w in (w1, w2):
            w.app._test_state["mode"] = "draining"
        status, headers, body = post_chat(leader, {"prompt": "x"})
        assert status == 503
        assert headers.get("Retry-After") == "1"
        assert json.loads(body)["error"]["details"]["code"] == "draining"

    def test_leave_mid_stream_finishes_and_drops_routes(self, fleet):
        """Satellite: /control/leave x router. The in-flight stream
        runs to completion while the departed host stops receiving
        new routes the moment the leave lands — no sleeps, the gate
        is event-driven."""
        leader, w1, w2 = fleet
        leader.app._leader.router.affinity.put("s", "w1")
        result = {}

        def streaming_request():
            conn = http.client.HTTPConnection("127.0.0.1", leader.port,
                                              timeout=30)
            try:
                conn.request(
                    "POST", "/chat",
                    body=json.dumps({"prompt": "x", "stream": True,
                                     "session": "s"}),
                    headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                result["status"] = resp.status
                result["body"] = resp.read().decode()
            finally:
                conn.close()

        t = threading.Thread(target=streaming_request)
        t.start()
        assert w1.app._test_state["started"].wait(10), \
            "stream never reached w1"
        # leave lands while the stream is mid-flight
        status, _, _ = leader.request(
            "POST", "/control/leave",
            body=json.dumps({"host_id": "w1"}),
            headers={"Content-Type": "application/json"})
        assert status == 201
        # new routes skip w1 immediately — even for the pinned session
        assert leader.app._leader.router.affinity.get("s") is None
        s2, _, body2 = post_chat(leader,
                                 {"prompt": "after", "session": "s"})
        assert s2 == 201 and json.loads(body2)["data"]["host"] == "w2"
        hits_before = w1.app._test_state["hits"]
        # the in-flight stream still finishes with its terminal chunk
        w1.app._test_state["release"].set()
        t.join(10)
        assert not t.is_alive()
        assert result["status"] == 200
        assert result["body"].count("data:") == 3
        assert result["body"].rstrip().endswith("data: [DONE]")
        assert w1.app._test_state["hits"] == hits_before

    def test_client_abort_cancels_upstream_and_counts(self, fleet):
        """Satellite: client-abort propagation. The downstream client
        half-closes its socket mid-stream; the next chunk write fails,
        the router closes the proxied upstream instead of draining it,
        and ``app_router_client_aborts`` counts the abort. Event-gated
        and deadline-polled — no fixed sleeps."""
        leader, w1, w2 = fleet
        leader.app._leader.router.affinity.put("s", "w1")
        conn = http.client.HTTPConnection("127.0.0.1", leader.port,
                                          timeout=30)
        conn.request("POST", "/chat",
                     body=json.dumps({"prompt": "x", "stream": True,
                                      "session": "s"}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert w1.app._test_state["started"].wait(10)
        # the client walks away after the first chunk
        resp.read(1)  # ensure the first write landed (through the
        #               response's own buffer: the first chunk may have
        #               arrived with the headers, and a read of the bare
        #               socket would then wait for the second)
        conn.close()
        # unblock the worker: the router's NEXT chunk write hits the
        # dead client socket and must cancel the upstream
        w1.app._test_state["release"].set()
        router = leader.app._leader.router
        deadline = threading.Event()
        for _ in range(1000):
            if router.debug_state()["client_aborts"] >= 1:
                break
            deadline.wait(0.01)
        assert router.debug_state()["client_aborts"] == 1
        # the abort rode the metrics surface too
        status, _, text = leader.request("GET", "/metrics",
                                         port=leader.metrics_port)
        assert status == 200
        assert "app_router_client_aborts 1" in text.decode()
        # the fleet is healthy: the released slot serves new traffic
        s2, _, body2 = post_chat(leader, {"prompt": "after"})
        assert s2 == 201, body2

    def test_no_members_is_a_typed_503(self):
        with AppRunner(build=build_leader) as leader:
            status, _, body = post_chat(leader, {"prompt": "x"})
            assert status == 503, body

    def test_router_metrics_and_debug_fleet(self, fleet):
        leader, w1, w2 = fleet
        assert post_chat(leader, {"prompt": "x"})[0] == 201
        status, _, body = leader.request("GET", "/debug/fleet")
        assert status == 200
        doc = json.loads(body)["data"]
        assert doc["router"]["routed_total"] >= 1
        assert doc["router"]["policy"] == "prefix"
        status, _, text = leader.request("GET", "/metrics",
                                         port=leader.metrics_port)
        assert status == 200
        assert "app_router_routed" in text.decode()
        assert "app_router_cache_hit_ratio" in text.decode()


# ------------------------------------------------------- routing text
class TestRoutingText:
    def test_openai_chat_path_matches_the_worker_template(self):
        from gofr_tpu.serving.openai_compat import _render_messages
        messages = [{"role": "system", "content": "be terse"},
                    {"role": "user", "content": "hi"}]
        assert FleetRouter.routing_text(
            "/v1/chat/completions", {"messages": messages}) \
            == _render_messages(messages)

    def test_chat_path_joins_message_contents(self):
        body = {"messages": [{"content": "a"}, {"content": "b"}]}
        assert FleetRouter.routing_text("/chat", body) == "a\nb"
        assert FleetRouter.routing_text("/chat", {"prompt": "p"}) == "p"

    def test_malformed_bodies_route_by_load_alone(self):
        assert FleetRouter.routing_text("/chat", {}) == ""
        assert FleetRouter.routing_text(
            "/v1/chat/completions", {"messages": "nope"}) == ""
