"""gofrlint unit tests: per-rule fixtures (flagged + clean twins),
suppression parsing, the CLI contract, and the meta-test pinning the
static metric extraction to the dynamic registry-coverage scan on the
live repo."""

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from gofr_tpu.analysis import run_analysis
from gofr_tpu.analysis.rules import metric_hygiene

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "analysis_fixtures"


def lint(*names, rules=None):
    findings, _ = run_analysis([FIXTURES / n for n in names],
                               rules=rules, root=REPO)
    return findings


def violations(findings, rule=None):
    out = [f for f in findings if not f.suppressed]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ------------------------------------------------------------ hot path
class TestHotPathPurity:
    def test_bad_fixture_flags_every_seeded_violation(self):
        got = violations(lint("hot_path_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        # the nine direct violations in dispatch() ...
        assert {14, 15, 16, 17, 18, 19, 20, 21, 22} <= lines
        # ... and the closure-reached one in the undecorated helper
        assert 32 in lines

    def test_closure_finding_names_the_root_chain(self):
        got = violations(lint("hot_path_bad.py"), "hot-path-purity")
        via = [f for f in got if f.line == 32]
        assert via and "Engine.step" in via[0].message

    def test_clean_twin_is_silent(self):
        assert violations(lint("hot_path_good.py"), "hot-path-purity") == []

    def test_boundary_stops_traversal_but_cold_code_is_ignored(self):
        # _retire (boundary) and cold_path (unreachable) both contain
        # would-be violations; neither may fire
        got = lint("hot_path_good.py")
        assert violations(got, "hot-path-purity") == []


# ---------------------------------------------------- scheduler contract
class TestSchedulerHotPathContract:
    """The serving/scheduler.py contract, lint-enforced: admission/
    retire bookkeeping (clocks, metrics, logging, burn-rate reads) is
    legal ONLY behind @hot_path_boundary entry points — inline in a
    hot root, or in an undecorated helper the closure reaches, it
    must flag."""

    def test_inline_scheduler_bookkeeping_flags(self):
        got = violations(lint("sched_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        # the three direct violations in admit_pass() ...
        assert {15, 16, 17} <= lines
        # ... and the closure-reached fair-share helper
        assert {23, 24} <= lines

    def test_boundary_entry_points_are_clean(self):
        assert violations(lint("sched_good.py"), "hot-path-purity") == []

    def test_live_scheduler_entry_points_declare_boundaries(self):
        # the real module, not a fixture: the entry points that touch
        # admission/retire paths carry the boundary annotation with a
        # non-empty reason, so the contract survives refactors
        from gofr_tpu.serving.scheduler import Scheduler
        for entry in (Scheduler.put, Scheduler.note_retire):
            reason = getattr(entry, "__gofr_hot_path_boundary__", "")
            assert isinstance(reason, str) and reason.strip(), entry

    def test_live_repo_hot_closure_excludes_scheduler(self):
        # with the scheduler ON by default, the engine's hot closure
        # must not grow into scheduler.py (the zero-hot-path invariant)
        from gofr_tpu.analysis.callgraph import CallGraph
        from gofr_tpu.analysis.core import load_project
        project = load_project([REPO / "gofr_tpu" / "serving"], root=REPO)
        closure = CallGraph(project).hot_closure()
        offenders = [str(k) for k in closure
                     if k.module.endswith("scheduler.py")]
        assert not offenders, offenders


# ---------------------------------------------------- fault-site contract
class TestFaultInjectionSites:
    """The serving/faults.py contract, lint-enforced: chaos compiled
    into the hot loop is legal ONLY as a guarded call into a
    @hot_path_boundary trip — inlined clocks/metrics/logging flag."""

    def test_inline_chaos_flags(self):
        got = violations(lint("faults_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        assert {14, 15, 16} <= lines          # inline trigger + telemetry
        assert 22 in lines                    # closure-reached helper

    def test_boundary_guarded_sites_are_clean(self):
        assert violations(lint("faults_good.py"), "hot-path-purity") == []

    def test_live_trip_declares_a_boundary(self):
        # the real module, not a fixture: FaultPlan.trip must keep its
        # boundary (with a reason) or every compiled-in site would
        # drag sleeps and counters into the engine's hot closure
        from gofr_tpu.serving.faults import FaultPlan
        reason = getattr(FaultPlan.trip, "__gofr_hot_path_boundary__", "")
        assert isinstance(reason, str) and reason.strip()


# ----------------------------------------------- event-ledger contract
class TestEventLedgerContract:
    """The serving/events.py contract, lint-enforced: flight-recorder
    emission is legal ONLY through the @hot_path_boundary
    ``EventLedger.emit`` — inline ring appends, wall-clock stamps or
    counters in a hot root (or a closure-reached helper) must flag."""

    def test_inline_event_recording_flags(self):
        got = violations(lint("events_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        assert {14, 15, 16} <= lines          # inline stamp + telemetry
        assert 21 in lines                    # closure-reached helper

    def test_boundary_emission_is_clean(self):
        assert violations(lint("events_good.py"), "hot-path-purity") == []

    def test_live_emit_declares_a_boundary(self):
        # the real module, not a fixture: EventLedger.emit must keep
        # its boundary (with a reason) or every emission site would
        # drag clocks, locks and counters into the hot closure
        from gofr_tpu.serving.events import EventLedger
        reason = getattr(EventLedger.emit,
                         "__gofr_hot_path_boundary__", "")
        assert isinstance(reason, str) and reason.strip()

    def test_live_repo_hot_closure_excludes_events(self):
        # with the ledger wired on by default, the engine's hot
        # closure must not grow into events.py: emission is only
        # reachable through already-declared boundary sites
        from gofr_tpu.analysis.callgraph import CallGraph
        from gofr_tpu.analysis.core import load_project
        project = load_project([REPO / "gofr_tpu" / "serving"], root=REPO)
        closure = CallGraph(project).hot_closure()
        offenders = [str(k) for k in closure
                     if k.module.endswith("events.py")]
        assert not offenders, offenders


# -------------------------------------------------- cost-model contract
class TestCostModelContract:
    """The serving/costmodel.py contract, lint-enforced: pass-cost
    accounting is legal ONLY through @hot_path_boundary folds
    (``CostModel.observe`` / ``Engine._note_pass_cost``) — inline EWMA
    updates, wall-clock reads or drift counters in a hot root (or a
    closure-reached helper) must flag."""

    def test_inline_cost_accounting_flags(self):
        got = violations(lint("costmodel_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        assert {14, 15, 16} <= lines          # inline price + telemetry
        assert 21 in lines                    # closure-reached helper

    def test_boundary_fold_is_clean(self):
        assert violations(lint("costmodel_good.py"),
                          "hot-path-purity") == []

    def test_live_folds_declare_boundaries(self):
        # the real modules, not fixtures: both the model's fold and
        # the engine's per-pass feed must keep their boundaries (with
        # reasons) or every collect site would drag the EWMA math,
        # drift counters and WARNs into the hot closure
        from gofr_tpu.serving.costmodel import CostModel
        from gofr_tpu.serving.engine import Engine
        for entry in (CostModel.observe, Engine._note_pass_cost):
            reason = getattr(entry, "__gofr_hot_path_boundary__", "")
            assert isinstance(reason, str) and reason.strip(), entry

    def test_live_repo_hot_closure_excludes_costmodel(self):
        # with the cost model ON by default, the engine's hot closure
        # must not grow into costmodel.py: observation is only
        # reachable through already-declared boundary sites
        from gofr_tpu.analysis.callgraph import CallGraph
        from gofr_tpu.analysis.core import load_project
        project = load_project([REPO / "gofr_tpu" / "serving"], root=REPO)
        closure = CallGraph(project).hot_closure()
        offenders = [str(k) for k in closure
                     if k.module.endswith("costmodel.py")]
        assert not offenders, offenders


# -------------------------------------------------- integrity contract
class TestIntegrityContract:
    """The serving/integrity.py contract, lint-enforced: output
    fingerprinting is legal ONLY through @hot_path_boundary folds
    (``IntegrityPlane.fold`` / ``Engine._note_integrity``) — inline
    digest downloads, mismatch counters or WARNs in a hot root (or a
    closure-reached helper) must flag."""

    def test_inline_fingerprinting_flags(self):
        got = violations(lint("integrity_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        assert {14, 18, 19} <= lines          # download + telemetry
        assert 24 in lines                    # closure-reached helper

    def test_boundary_fold_is_clean(self):
        assert violations(lint("integrity_good.py"),
                          "hot-path-purity") == []

    def test_live_folds_declare_boundaries(self):
        # the real modules, not fixtures: both the plane's fold and
        # the engine's per-request feed must keep their boundaries
        # (with reasons) or every retire site would drag the digest,
        # probe pricing and mismatch telemetry into the hot closure
        from gofr_tpu.serving.engine import Engine
        from gofr_tpu.serving.integrity import IntegrityPlane
        for entry in (IntegrityPlane.fold, Engine._note_integrity):
            reason = getattr(entry, "__gofr_hot_path_boundary__", "")
            assert isinstance(reason, str) and reason.strip(), entry

    def test_live_repo_hot_closure_excludes_integrity(self):
        # with the plane ON by default, the engine's hot closure must
        # not grow into integrity.py: folding is only reachable
        # through already-declared boundary sites
        from gofr_tpu.analysis.callgraph import CallGraph
        from gofr_tpu.analysis.core import load_project
        project = load_project([REPO / "gofr_tpu" / "serving"], root=REPO)
        closure = CallGraph(project).hot_closure()
        offenders = [str(k) for k in closure
                     if k.module.endswith("integrity.py")]
        assert not offenders, offenders


# ------------------------------------------------ speculation contract
class TestSpeculationContract:
    """The drafting/controller contract, lint-enforced: n-gram index
    maintenance, controller pricing and the verify collect's device
    reads are legal ONLY behind the engine's @hot_path_boundary entry
    points (``_draft_proposals``, ``_spec_pass``) — inline in a hot
    root, or in an undecorated helper the closure reaches, they must
    flag."""

    def test_inline_drafting_flags(self):
        got = violations(lint("spec_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        assert {14, 15, 16} <= lines    # clock + counter + log inline
        assert {23, 24} <= lines        # closure-reached draft helper

    def test_boundary_drafting_is_clean(self):
        assert violations(lint("spec_good.py"), "hot-path-purity") == []

    def test_live_spec_entry_points_declare_boundaries(self):
        # the real module, not a fixture: drafting and the verify
        # collect must keep their boundaries (with reasons) or the
        # n-gram index, controller EWMAs and accept/path downloads
        # would drag host syncs into the engine's hot closure
        from gofr_tpu.serving.engine import Engine
        for entry in (Engine._draft_proposals, Engine._spec_pass):
            reason = getattr(entry, "__gofr_hot_path_boundary__", "")
            assert isinstance(reason, str) and reason.strip(), entry

    def test_live_repo_hot_closure_excludes_spec(self):
        # the drafting/controller module stays out of the hot closure:
        # it is only reachable through the declared boundary sites
        from gofr_tpu.analysis.callgraph import CallGraph
        from gofr_tpu.analysis.core import load_project
        project = load_project([REPO / "gofr_tpu" / "serving"],
                               root=REPO)
        closure = CallGraph(project).hot_closure()
        offenders = [str(k) for k in closure
                     if k.module.endswith("spec.py")]
        assert not offenders, offenders


# ----------------------------------------------------- router contract
class TestRouterContract:
    """The serving/router.py contract, lint-enforced: the async proxy
    path must never block the event loop (every stream the leader
    proxies rides it), and prefix-digest assembly is legal ONLY behind
    a declared @hot_path_boundary — inline in a hot root or in a
    closure-reached helper it must flag."""

    def test_blocking_proxy_path_flags(self):
        got = violations(lint("router_bad.py"), "blocking-in-async")
        # sleep, sync HTTP probe, setpoint-file read — all inline in
        # the async proxy
        assert {f.line for f in got} == {16, 17, 18}

    def test_inline_digest_assembly_flags(self):
        got = violations(lint("router_bad.py"), "hot-path-purity")
        lines = {f.line for f in got}
        assert {29, 30} <= lines        # clock + gauge in the hot root
        assert 37 in lines              # closure-reached digest helper

    def test_clean_twin_is_silent_on_both_rules(self):
        got = lint("router_good.py")
        assert violations(got, "blocking-in-async") == []
        assert violations(got, "hot-path-purity") == []

    def test_live_digest_refresh_declares_a_boundary(self):
        # the real module, not a fixture: the engine's digest refresh
        # runs off the gauge pass inside the hot loop, so losing its
        # boundary would drag hashing into the hot closure
        from gofr_tpu.serving.engine import Engine
        reason = getattr(Engine._refresh_prefix_digest,
                         "__gofr_hot_path_boundary__", "")
        assert isinstance(reason, str) and reason.strip()

    def test_live_proxy_path_is_async_clean(self):
        # the real router module must pass the blocking-in-async rule
        # it exists to model
        findings, _ = run_analysis(
            [REPO / "gofr_tpu" / "serving" / "router.py"], root=REPO)
        assert [f for f in findings
                if not f.suppressed
                and f.rule == "blocking-in-async"] == []


# ---------------------------------------------------------------- locks
class TestLockDiscipline:
    def test_bad_fixture(self):
        got = violations(lint("locks_bad.py"), "lock-discipline")
        assert {f.line for f in got} == {17, 20, 23}
        assert any("_items" in f.message for f in got)
        assert any("_count" in f.message for f in got)

    def test_clean_twin(self):
        assert violations(lint("locks_good.py"), "lock-discipline") == []


# ------------------------------------------------------------- election
class TestElectionContract:
    """Leader-HA determinism contract (docs/operations.md "Losing the
    leader"): lease state (epoch/active) mutates only under the lock,
    and election/fencing decisions are pure functions of counts and
    epochs — no wall clock, no RNG — so every failover drill
    reproduces under bisect."""

    #: the election/fencing decision functions in the live module
    ELECTION_FNS = ("ensure_active", "_fence", "_choose_candidate",
                    "_adopt_epoch")

    def test_bad_fixture_lease_races_are_flagged(self):
        got = violations(lint("election_bad.py"), "lock-discipline")
        assert {f.line for f in got} == {20, 21}
        assert any("active" in f.message for f in got)
        assert any("epoch" in f.message for f in got)

    def test_bad_fixture_election_reads_clock_and_rng(self):
        # what the contract bans, demonstrated: the bad twin's choose()
        # references time and random
        names = self._referenced_modules(
            FIXTURES / "election_bad.py", ("choose",))
        assert {"time", "random"} <= names

    def test_clean_twin_is_silent_and_pure(self):
        assert violations(lint("election_good.py"),
                          "lock-discipline") == []
        names = self._referenced_modules(
            FIXTURES / "election_good.py", ("choose",))
        assert not names & {"time", "random"}

    def test_live_election_functions_are_clock_and_rng_free(self):
        src = REPO / "gofr_tpu" / "serving" / "control_plane.py"
        names = self._referenced_modules(src, self.ELECTION_FNS)
        assert not names & {"time", "random"}, (
            f"election/fencing logic reads a clock or RNG: {names}")

    def test_live_module_lints_clean(self):
        src = REPO / "gofr_tpu" / "serving" / "control_plane.py"
        findings, _ = run_analysis([src], root=REPO)
        assert violations(findings, "lock-discipline") == []

    @staticmethod
    def _referenced_modules(path, fn_names):
        """Module names used as ``mod.attr(...)`` inside the named
        functions of ``path`` (any nesting depth)."""
        import ast
        tree = ast.parse(path.read_text())
        out: set = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in fn_names:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Attribute) \
                            and isinstance(sub.value, ast.Name):
                        out.add(sub.value.id)
        return out


# ---------------------------------------------------------------- async
class TestBlockingInAsync:
    def test_bad_fixture(self):
        got = violations(lint("async_bad.py"), "blocking-in-async")
        assert {f.line for f in got} == {9, 10, 11, 12, 13}

    def test_clean_twin(self):
        assert violations(lint("async_good.py"), "blocking-in-async") == []


# -------------------------------------------------------------- metrics
class TestMetricHygiene:
    def test_bad_fixture(self):
        got = violations(lint("metrics_bad.py"), "metric-hygiene")
        msgs = {f.line: f.message for f in got}
        assert "app_orphan_total" in msgs[6]      # orphan registration
        assert "app_never_registered" in msgs[13]
        assert "not a string literal" in msgs[14]
        assert len(got) == 3

    def test_clean_twin_including_loop_unroll(self):
        assert violations(lint("metrics_good.py"), "metric-hygiene") == []

    def test_cross_file_resolution(self):
        # registration in one file, write in the other: both clean when
        # linted together
        got = violations(lint("metrics_good.py", "metrics_bad.py"),
                         "metric-hygiene")
        # bad file's findings survive; good file contributes none
        assert all(f.path.endswith("metrics_bad.py") for f in got)


# ------------------------------------------------------------ recompile
class TestRecompileHazard:
    def test_bad_fixture(self):
        got = violations(lint("recompile_bad.py"), "recompile-hazard")
        assert {f.line for f in got} == {17, 18, 19, 29}

    def test_clean_twin(self):
        assert violations(lint("recompile_good.py"), "recompile-hazard") == []


# ------------------------------------------------------------- kv quant
class TestKvQuantBoundary:
    """Quantize-on-write contract (ops/paged_kv.py): the jitted
    scatters own the pool representation — hot closures pass raw rows
    and never cast or host-read the pool."""

    def test_bad_fixture_flags_every_seeded_violation(self):
        got = violations(lint("kvquant_bad.py"), "kv-quant-boundary")
        assert {f.line for f in got} == {11, 14, 22, 24, 29, 30, 31}
        assert any("quantizes/casts on write" in f.message for f in got)
        assert any("host-side readback" in f.message for f in got)

    def test_clean_twin_is_silent(self):
        assert violations(lint("kvquant_good.py"),
                          "kv-quant-boundary") == []

    def test_live_serving_and_models_respect_the_boundary(self):
        """The contract test the rule exists for: the LIVE engine/glue/
        model hot closures quantize inside the jitted scatters — no
        caller-side .astype at a scatter boundary, no host-side pool
        dequant crept back in."""
        findings, _ = run_analysis(
            [REPO / "gofr_tpu" / "serving", REPO / "gofr_tpu" / "models",
             REPO / "gofr_tpu" / "ops"], root=REPO)
        assert [f for f in findings if not f.suppressed
                and f.rule == "kv-quant-boundary"] == []


# ---------------------------------------------------------- suppression
class TestSuppressions:
    def test_missing_reason_is_an_error(self):
        got = lint("suppression_bad.py")
        bad = violations(got, "bad-suppression")
        assert any("missing its mandatory" in f.message and f.line == 9
                   for f in bad)

    def test_reasonless_allow_does_not_suppress(self):
        got = lint("suppression_bad.py")
        assert any(f.line == 9 for f in
                   violations(got, "hot-path-purity"))

    def test_stale_allow_is_an_error(self):
        got = lint("suppression_bad.py")
        assert any(f.line == 12 and "suppresses nothing" in f.message
                   for f in violations(got, "bad-suppression"))

    def test_typoed_rule_neither_suppresses_nor_passes(self):
        got = lint("suppression_bad.py")
        assert any(f.line == 17 for f in violations(got, "hot-path-purity"))
        assert any(f.line == 17 for f in violations(got, "bad-suppression"))

    def test_valid_allow_suppresses_and_keeps_reason(self):
        got = lint("suppression_good.py")
        assert violations(got) == []
        sup = [f for f in got if f.suppressed]
        assert sup and all(f.allow_reason for f in sup)

    def test_one_allow_may_cover_multiple_rules(self):
        got = lint("suppression_good.py")
        rules = {f.rule for f in got if f.suppressed and f.line == 14}
        assert "hot-path-purity" in rules


# ------------------------------------------------------------------ CLI
class TestCLI:
    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, str(REPO / "scripts" / "lint.py"), *args],
            capture_output=True, text=True, cwd=REPO)

    def test_bad_fixture_exits_nonzero_with_file_line(self):
        r = self.run_cli(str(FIXTURES / "async_bad.py"))
        assert r.returncode == 1
        assert re.search(r"async_bad\.py:9:\d+: \[blocking-in-async\]",
                         r.stdout)

    def test_json_format_is_machine_readable(self):
        r = self.run_cli("--format=json", str(FIXTURES / "async_bad.py"))
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["counts"]["blocking-in-async"] == 5
        assert all({"rule", "path", "line", "col", "message"}
                   <= set(v) for v in doc["violations"])

    def test_clean_fixture_exits_zero(self):
        r = self.run_cli(str(FIXTURES / "async_good.py"))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_self_test_passes(self):
        r = self.run_cli("--self-test")
        assert r.returncode == 0, r.stdout + r.stderr

    def test_unknown_rule_is_usage_error(self):
        r = self.run_cli("--rule", "no-such-rule", ".")
        assert r.returncode == 2

    def test_repo_lints_clean(self):
        # the acceptance gate itself: the live tree must stay clean
        r = self.run_cli("gofr_tpu/", "scripts/", "chip_smoke.py")
        assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------------------------------- meta-test
class TestStaticDynamicAgreement:
    """gofrlint's static metric extraction and the dynamic
    registry-coverage test (test_observability.py) must agree on the
    live repo — if they drift, one of them has a blind spot."""

    def test_static_extraction_covers_the_dynamic_scan(self):
        from gofr_tpu.analysis.core import load_project
        from .test_observability import _WRITE_RE, SERVING_DIR

        regex_names = set()
        for path in SERVING_DIR.glob("*.py"):
            regex_names.update(_WRITE_RE.findall(path.read_text()))

        project = load_project([SERVING_DIR], root=REPO)
        static_names = metric_hygiene.written_names(project)

        # everything the regex sees, the AST walk must see ...
        assert regex_names <= static_names, (
            f"static extraction missed: {sorted(regex_names - static_names)}")
        # ... and anything extra the AST walk finds (multi-line calls,
        # loop-unrolled names the regex can't follow) must still be a
        # registered metric, or the dynamic test has a blind spot
        extra = static_names - regex_names
        whole_tree = load_project([REPO / "gofr_tpu"], root=REPO)
        registered = metric_hygiene.registered_names(whole_tree)
        assert extra <= registered, (
            f"statically-found writes the dynamic test cannot see AND "
            f"nobody registers: {sorted(extra - registered)}")

    def test_every_serving_write_is_statically_registered(self):
        """The static twin of the dynamic coverage test's main assert."""
        from gofr_tpu.analysis.core import load_project
        serving = load_project([REPO / "gofr_tpu" / "serving"], root=REPO)
        whole_tree = load_project([REPO / "gofr_tpu"], root=REPO)
        written = metric_hygiene.written_names(serving)
        registered = metric_hygiene.registered_names(whole_tree)
        assert written, "no writes found — the extraction broke"
        missing = sorted(n for n in written if n not in registered)
        assert not missing, f"written in serving/ but never registered: {missing}"
