"""Deterministic fault injection, crash recovery, and the restartable
engine lifecycle (serving/faults.py + the Engine supervisor).

The chaos contract under test: a plan fires at exact invocation counts
(never wall clock, never RNG); a crashed loop restarts within the
``RestartPolicy`` budget; requests that never emitted a token replay
bit-identically; mid-stream requests fail with a typed *retryable*
reject (no duplicate-token risk); and a stopped engine ``start()``s
again on its resident weights and compile cache."""

import threading
import time

import pytest

from gofr_tpu.serving.engine import (EngineConfig, GenRequest,
                                     RestartPolicy, SamplingParams)
from gofr_tpu.serving.faults import (NO_FAULTS, FaultPlan, FaultSpec,
                                     InjectedFault, plan_from_env,
                                     resolve_plan)
from gofr_tpu.serving.glue import demo_llama_engine

GREEDY = SamplingParams(temperature=0.0, max_new_tokens=6)


def wait_all(reqs, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(r.finished_at is not None or r.error is not None
               for r in reqs):
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------------------ the plan
class TestFaultPlan:
    def test_parse_full_syntax(self):
        plan = FaultPlan.parse(
            "pass_stall:at=5,seconds=2.5;heartbeat_drop:at=2,times=4;"
            "page_exhaustion:request=tenant-a")
        sites = [s.site for s in plan.specs]
        assert sites == ["pass_stall", "heartbeat_drop", "page_exhaustion"]
        stall, drop, pool = plan.specs
        assert (stall.at, stall.seconds) == (5, 2.5)
        assert (drop.at, drop.times) == (2, 4)
        assert pool.request == "tenant-a"
        # unparameterised defaults: fire once, on the first invocation
        spec = FaultPlan.parse("pass_raise").specs[0]
        assert (spec.at, spec.times) == (1, 1)

    def test_blank_parses_to_the_disabled_singleton(self):
        # identity matters: every call site guards with `is not NO_FAULTS`
        assert FaultPlan.parse("") is NO_FAULTS
        assert FaultPlan.parse("  ") is NO_FAULTS
        assert resolve_plan(FaultPlan(())) is NO_FAULTS

    def test_bad_plans_fail_loudly(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            FaultPlan.parse("meteor_strike")
        with pytest.raises(ValueError, match="key=value"):
            FaultPlan.parse("pass_raise:after=3")
        with pytest.raises(ValueError, match="at >= 1"):
            FaultPlan.parse("pass_raise:at=0")
        with pytest.raises(TypeError):
            resolve_plan(42)

    def test_unknown_site_error_names_token_and_valid_sites(self):
        # a typo'd GOFR_FAULTS silently arming nothing would make a
        # chaos drill vacuously green — the message must hand the
        # operator the bad token AND the menu
        with pytest.raises(ValueError) as err:
            FaultPlan.parse("pass_raise:at=2;leeder_down")
        msg = str(err.value)
        assert "'leeder_down'" in msg
        assert "leader_down" in msg          # the valid-site list
        assert "pass_raise" in msg

    def test_stray_semicolon_is_rejected(self):
        with pytest.raises(ValueError, match="stray ';'"):
            FaultPlan.parse("pass_raise:at=2;")
        with pytest.raises(ValueError, match="stray ';'"):
            FaultPlan.parse(";pass_raise")
        with pytest.raises(ValueError, match="stray ';'"):
            FaultPlan.parse("pass_raise;;heartbeat_drop")

    def test_missing_site_name_is_rejected(self):
        with pytest.raises(ValueError, match="missing site name"):
            FaultPlan.parse(":at=2")

    def test_bad_pair_errors_name_the_offending_token(self):
        # not key=value at all
        with pytest.raises(ValueError, match=r"'at'.*key=value"):
            FaultPlan.parse("pass_raise:at")
        # unknown key, named in the clause
        with pytest.raises(ValueError, match=r"'when=3'"):
            FaultPlan.parse("pass_raise:when=3")
        # non-numeric payloads name the value they choked on
        with pytest.raises(ValueError, match=r"integer.*'soon'"):
            FaultPlan.parse("pass_raise:at=soon")
        with pytest.raises(ValueError, match=r"number.*'fast'"):
            FaultPlan.parse("pass_stall:seconds=fast")

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setenv("GOFR_FAULTS", "pass_raise:at=7")
        plan = plan_from_env()
        assert plan.specs[0].at == 7
        assert resolve_plan(None).specs[0].at == 7
        monkeypatch.delenv("GOFR_FAULTS")
        assert resolve_plan(None) is NO_FAULTS

    def test_trip_fires_by_invocation_count_only(self):
        plan = FaultPlan([FaultSpec(site="pass_raise", at=3, times=2)])
        assert plan.trip("pass_raise") is False       # invocation 1
        assert plan.trip("pass_raise") is False       # invocation 2
        for _ in range(2):                            # 3 and 4: armed
            with pytest.raises(InjectedFault, match="pass_raise"):
                plan.trip("pass_raise")
        assert plan.trip("pass_raise") is False       # 5: window closed
        assert plan.fired == {"pass_raise": 2}
        plan.reset()                                  # rewind: same movie
        assert plan.trip("pass_raise") is False
        assert plan.fired == {}

    def test_times_zero_fires_forever(self):
        plan = FaultPlan([FaultSpec(site="heartbeat_drop", at=2, times=0)])
        got = [plan.trip("heartbeat_drop") for _ in range(5)]
        assert got == [False, True, True, True, True]

    def test_request_tag_gates_the_counter(self):
        # untagged invocations must not advance a tagged spec's trigger
        plan = FaultPlan([FaultSpec(site="page_exhaustion", at=2,
                                    request="tenant-a")])
        assert plan.trip("page_exhaustion") is False              # untagged
        assert plan.trip("page_exhaustion",
                         request_id="tenant-b") is False          # other tag
        assert plan.trip("page_exhaustion",
                         request_id="tenant-a") is False          # count 1
        assert plan.trip("page_exhaustion",
                         request_id="tenant-a") is True           # count 2
        assert plan.trip("page_exhaustion") is False


def test_restart_policy_backoff_is_exponential_and_capped():
    policy = RestartPolicy(backoff_s=0.1, backoff_mult=2.0,
                           max_backoff_s=0.5)
    assert [policy.backoff_for(n) for n in (1, 2, 3, 4, 5)] \
        == [0.1, 0.2, 0.4, 0.5, 0.5]


# -------------------------------------------------- engine fault sites
def test_page_exhaustion_is_a_typed_503_not_a_crash():
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, faults="page_exhaustion:at=1"))
    eng.start()
    try:
        hit = eng.submit_sync([1, 2, 3], GREEDY)
        assert hit.error and "kv page pool exhausted" in hit.error
        assert hit.reject is not None
        assert hit.reject.code == "kv_exhausted"
        assert hit.reject.retry_after_s > 0
        # the engine did NOT crash: the next submit serves normally
        ok = eng.submit_sync([1, 2, 3], GREEDY)
        assert ok.error is None and len(ok.generated) == 6
        assert eng.health_check()["status"] == "UP"
    finally:
        eng.stop()


def test_pass_raise_restarts_within_budget_and_replays_bit_identical():
    """The headline chaos invariant: with a crash injected mid-traffic,
    every request either completes bit-identically to the fault-free
    run or fails with the typed retryable ``engine_restart`` reject —
    and a client-side retry of those lands bit-identically too."""
    prompts = [[1 + i, 2, 3] for i in range(4)]
    ref = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64, seed=11))
    ref.start()
    want = [ref.submit_sync(p, GREEDY).generated for p in prompts]
    ref.stop()

    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, seed=11, faults="pass_raise:at=2",
        restart_policy=RestartPolicy(max_restarts=3, backoff_s=0.01)))
    eng.start()
    try:
        reqs = [eng.submit(p, GREEDY) for p in prompts]
        assert wait_all(reqs)
        for prompt, req, expect in zip(prompts, reqs, want):
            if req.error is not None:
                # mid-stream at the crash: must be the typed reject
                assert req.reject is not None \
                    and req.reject.code == "engine_restart", req.error
                req = eng.submit(prompt, GREEDY)
                assert wait_all([req]) and req.error is None
            assert req.generated == expect
        health = eng.health_check()
        assert health["status"] == "UP"
        assert health["restarts"] == 1
        assert "injected fault: pass_raise" in health["last_crash"]
    finally:
        eng.stop()


def test_restart_budget_exhaustion_is_terminal():
    # every pass raises: the supervisor burns its budget, then _crash
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, faults="pass_raise:times=0",
        restart_policy=RestartPolicy(max_restarts=2, backoff_s=0.01)))
    eng.start()
    try:
        req = eng.submit([1, 2, 3], GREEDY)
        assert wait_all([req], timeout=30)
        deadline = time.time() + 10
        while time.time() < deadline \
                and eng.health_check()["status"] != "DOWN":
            time.sleep(0.01)
        health = eng.health_check()
        assert health["status"] == "DOWN"
        assert health["restarts"] == 2
        assert "injected fault" in health["error"]
    finally:
        eng.stop()


def test_nan_logits_rejects_mid_stream_as_retryable():
    """The fault fires at decode *collect* — tokens already emitted —
    so recovery must take the typed-reject branch, never silently
    replay (the no-duplicate-token invariant)."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, faults="nan_logits:at=3",
        restart_policy=RestartPolicy(max_restarts=3, backoff_s=0.01)))
    eng.start()
    try:
        req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                   max_new_tokens=20))
        assert wait_all([req])
        assert req.error is not None
        assert req.reject is not None
        assert req.reject.code == "engine_restart"
        assert "retry" in req.reject.message
        # partial output stopped mid-stream; the engine itself healed
        assert 0 < len(req.generated) < 20
        ok = eng.submit_sync([1, 2, 3], GREEDY)
        assert ok.error is None and len(ok.generated) == 6
    finally:
        eng.stop()


def test_recover_salvage_rules_whitebox():
    """The discriminator, pinned: ``first_token_at is None`` goes to
    the recovery buffer flagged ``recovered`` (re-prefill priced as
    preempt_recompute); anything mid-stream gets the typed reject."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64,
        restart_policy=RestartPolicy(max_restarts=1, backoff_s=0.01)))
    fresh = GenRequest(prompt_tokens=[1, 2, 3], params=GREEDY)
    fresh.slot = 0
    mid = GenRequest(prompt_tokens=[4, 5, 6], params=GREEDY)
    mid.slot = 1
    mid.first_token_at = time.time()
    mid.generated.append(42)
    eng.active[0], eng.active[1] = fresh, mid
    eng._running = True          # supervisor only runs on a live engine
    try:
        assert eng._recover(RuntimeError("boom")) is True
    finally:
        eng._running = False
    assert fresh in eng._requeued and fresh.recovered
    assert fresh.slot == -1 and fresh.error is None
    assert mid.error is not None and mid.reject.code == "engine_restart"
    assert eng._restarts == 1 and "boom" in eng._last_crash
    # budget exhausted -> terminal
    eng._running = True
    eng.active[0] = None
    try:
        assert eng._recover(RuntimeError("again")) is False
    finally:
        eng._running = False


# ------------------------------------------------ restartable lifecycle
@pytest.mark.parametrize("layout", [
    {"page_size": 16},
    {"page_size": 16, "paged_attention": "xla"},
], ids=["view", "native"])
def test_stop_start_stop_cycle_serves_identically(layout):
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                         seed=3, **layout))
    eng.start()
    first = eng.submit_sync([1, 2, 3], GREEDY)
    assert first.error is None
    eng.stop()
    # the stopped window: submissions get the typed engine_down 503
    down = eng.submit([1, 2, 3], GREEDY)
    assert down.error is not None
    assert down.reject is not None and down.reject.code == "engine_down"
    # restart in place: resident weights + compile cache, clean KV
    eng.start()
    second = eng.submit_sync([1, 2, 3], GREEDY)
    assert second.error is None
    assert second.generated == first.generated
    eng.stop()
    assert eng.health_check()["status"] == "DOWN"


def test_concurrent_stop_callers_are_safe():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64))
    eng.start()
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                               max_new_tokens=100))
    while req.first_token_at is None and req.error is None:
        time.sleep(0.01)
    errors = []

    def stopper():
        try:
            eng.stop()
        except Exception as exc:  # pragma: no cover - the failure mode
            errors.append(exc)

    threads = [threading.Thread(target=stopper) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errors
    assert req.finished_at is not None and req.error == "engine stopped"
    # and the pile-up did not wedge the lifecycle: restart still works
    eng.start()
    ok = eng.submit_sync([1, 2, 3], GREEDY)
    assert ok.error is None
    eng.stop()


def test_drain_completes_inflight_and_refuses_new():
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128))
    eng.start()
    inflight = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                    max_new_tokens=40))
    while inflight.first_token_at is None and inflight.error is None:
        time.sleep(0.01)
    result = {}

    def drainer():
        result["drained"] = eng.drain(timeout_s=60.0)

    t = threading.Thread(target=drainer)
    t.start()
    # inside the drain window: new work is refused with a typed 503
    deadline = time.time() + 5
    refused = None
    while time.time() < deadline and not eng._draining:
        time.sleep(0.002)
    if eng._draining:  # the in-flight request is still running
        refused = eng.submit([7, 8, 9], GREEDY)
    t.join(90)
    assert result["drained"] is True
    assert inflight.error is None and len(inflight.generated) == 40
    if refused is not None:
        assert refused.reject is not None
        assert refused.reject.code == "draining"
    # drained engines restart like stopped ones
    eng.start()
    ok = eng.submit_sync([1, 2, 3], GREEDY)
    assert ok.error is None
    eng.stop()


def test_timed_out_stop_counts_stranded_slots():
    """pass_stall wedges the loop past stop()'s join budget: the timed
    -out path must count the stranded slots into health_check and keep
    the thread handle so start() refuses until the pass retires."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, faults="pass_stall:at=2,seconds=1.5"))
    # queue the request BEFORE start: pass 1 admits it, pass 2 stalls
    req = eng.submit([1, 2, 3], GREEDY)
    eng.start()
    deadline = time.time() + 10
    while time.time() < deadline \
            and not any(r is not None for r in eng.active):
        time.sleep(0.01)
    assert any(r is not None for r in eng.active)
    eng.stop(join_timeout_s=0.1)          # far below the 1.5s stall
    health = eng.health_check()
    assert health["stranded_slots"] == 1
    # start() during the wedged pass must refuse, not corrupt caches
    with pytest.raises(RuntimeError, match="still in a device call"):
        eng.start()
    # the pass completes; the thread retires the stream itself
    deadline = time.time() + 30
    while time.time() < deadline and eng._thread.is_alive():
        time.sleep(0.05)
    assert not eng._thread.is_alive()
    assert req.finished_at is not None
    # and now the engine restarts cleanly, stranded count cleared
    eng.start()
    ok = eng.submit_sync([1, 2, 3], GREEDY)
    assert ok.error is None
    assert "stranded_slots" not in eng.health_check()
    eng.stop()


def test_restart_counters_reach_the_registry():
    from gofr_tpu.metrics.registry import Manager
    metrics = Manager()
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, faults="pass_raise:at=2",
        restart_policy=RestartPolicy(max_restarts=2, backoff_s=0.01)),
        metrics=metrics)
    eng.start()
    try:
        reqs = [eng.submit([1 + i, 2, 3], GREEDY) for i in range(3)]
        assert wait_all(reqs)
        deadline = time.time() + 10
        while time.time() < deadline \
                and metrics.get("app_engine_restarts").get() < 1.0:
            time.sleep(0.01)
        assert metrics.get("app_engine_restarts").get() == 1.0
        scrape = metrics.render_prometheus()
        assert "app_engine_requests_recovered" in scrape
    finally:
        eng.stop()


def test_sigterm_drain_completes_inflight_requests():
    """The app's signal path must DRAIN served engines — the in-flight
    stream finishes (no "engine stopped" cut-off) before the hard-stop
    hooks run — and still complete shutdown."""
    from .apputil import AppRunner
    from gofr_tpu.serving.tokenizer import ByteTokenizer
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128))

    def build(app):
        app.serve_model("llm", eng, ByteTokenizer())

    with AppRunner(build=build) as runner:
        req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                   max_new_tokens=40))
        while req.first_token_at is None and req.error is None:
            time.sleep(0.01)
        runner._loop.call_soon_threadsafe(runner.app._signal_stop)
        deadline = time.time() + 60
        while time.time() < deadline \
                and not runner.app._stop_event.is_set():
            time.sleep(0.05)
        assert runner.app._stop_event.is_set()
        assert req.error is None and len(req.generated) == 40
        assert not eng._running


# --------------------------------------------- control-plane fault sites
def _leader(**kw):
    from gofr_tpu.serving.control_plane import ControlPlaneLeader
    leader = ControlPlaneLeader(coordinator="10.0.0.1:8476", **kw)

    def build(app):
        leader.install(app)
    return leader, build


def _agent(runner, host_id, **kw):
    from gofr_tpu.serving.control_plane import WorkerAgent
    return WorkerAgent(f"http://127.0.0.1:{runner.port}",
                       host_id=host_id, n_devices=4,
                       heartbeat_interval_s=0.05, **kw)


def test_join_retries_back_off_with_jitter(monkeypatch):
    """With the leader refusing every join, retry delays must grow
    exponentially from the heartbeat interval to the cap, jittered —
    never a fixed-cadence thundering herd."""
    import time as real_time

    from gofr_tpu.serving import control_plane

    class FakeTime:
        def __init__(self):
            self.delays = []

        def sleep(self, d):
            self.delays.append(d)
            real_time.sleep(0.001)  # yield without waiting the delay out

        def __getattr__(self, name):
            return getattr(real_time, name)

    fake = FakeTime()
    monkeypatch.setattr(control_plane, "time", fake)
    plan = FaultPlan.parse("join_refused:times=0")  # refuse forever
    agent = control_plane.WorkerAgent(
        "http://127.0.0.1:1", host_id="unwanted",
        heartbeat_interval_s=0.1, join_backoff_max_s=0.8, faults=plan)
    agent.start()
    try:
        deadline = real_time.time() + 10
        while real_time.time() < deadline \
                and plan.fired.get("join_refused", 0) < 8:
            real_time.sleep(0.01)
        assert plan.fired.get("join_refused", 0) >= 8
    finally:
        agent.stop()
    delays = fake.delays
    # first retry: one heartbeat interval, jittered x0.5-1.5
    assert 0.05 <= delays[0] <= 0.15
    # the ramp reached well past the base (0.15 is the base ceiling)
    assert max(delays) >= 0.4
    # and respected cap x max-jitter
    assert max(delays) <= 0.8 * 1.5 + 1e-9
    assert agent.assignment is None


def test_join_refused_then_recovers():
    """A leader refusing the first joins (rolling restart) is survived:
    the retry loop lands the join once the refusal window closes."""
    from .apputil import AppRunner
    leader, build = _leader()
    with AppRunner(build=build) as runner:
        plan = FaultPlan.parse("join_refused:times=2")
        agent = _agent(runner, "w", faults=plan)
        agent.start()      # initial join trips 1; loop retries 2, 3...
        try:
            deadline = time.time() + 10
            while time.time() < deadline and agent.assignment is None:
                time.sleep(0.02)
            assert agent.assignment is not None
            assert plan.fired["join_refused"] == 2
            assert leader.topology()["world_size"] == 1
        finally:
            agent.stop()


def test_heartbeat_drop_leads_to_timeout_eviction():
    """Dropping every heartbeat (lossy control network) must look to
    the leader exactly like a dead host: sweeper eviction with
    reason=heartbeat_timeout."""
    from .apputil import AppRunner
    leader, build = _leader(heartbeat_interval_s=0.1, eviction_misses=2)
    with AppRunner(build=build) as runner:
        agent = _agent(runner, "mute",
                       faults="heartbeat_drop:times=0")
        agent.start()
        try:
            deadline = time.time() + 10
            while time.time() < deadline \
                    and leader.topology()["world_size"] != 0:
                time.sleep(0.05)
            assert leader.topology()["world_size"] == 0
            assert leader.metrics.get("app_fleet_evictions").get(
                reason="heartbeat_timeout") == 1.0
        finally:
            agent.stop()


def test_deregister_leaves_immediately_and_suppresses_rejoin():
    """The SIGTERM drain path: deregister() tells the leader NOW (no
    heartbeat-silence wait), survivors re-rank, and the agent's own
    retry loop must not quietly rejoin afterwards."""
    from .apputil import AppRunner
    leader, build = _leader()
    with AppRunner(build=build) as runner:
        leaving = _agent(runner, "leaving")
        staying = _agent(runner, "staying")
        leaving.start()
        staying.start()
        try:
            deadline = time.time() + 10
            while time.time() < deadline and (
                    leaving.assignment is None
                    or staying.assignment is None):
                time.sleep(0.02)
            assert leader.topology()["world_size"] == 2
            leaving.deregister()
            topo = leader.topology()
            assert topo["world_size"] == 1
            assert "leaving" not in topo["members"]
            assert leader.metrics.get("app_fleet_evictions").get(
                reason="leave") == 1.0
            # several heartbeat intervals later: still out (no rejoin)
            time.sleep(0.4)
            assert leaving.assignment is None
            assert leader.topology()["world_size"] == 1
        finally:
            leaving.stop()
            staying.stop()


