"""Serving-path observability: flight recorder, workload capture,
engine trace assembly, tenant usage metering, SLO burn-rate tracking,
on-demand profiler capture, MFU derivation.

Everything in this module is HOST-side bookkeeping over timestamps and
counters the engine already collects. The hard invariant is **zero
perturbation of the hot path**: no device syncs, no host->device
transfers, no blocking work on the decode dispatch/collect path. The
pass ring is an append-only ``deque`` (CPython appends are atomic under
the GIL — no lock on the writer side), spans are assembled *after* a
request retires from timestamps recorded along the way, and the MFU
gauge is derived once at compile time from the decode graph's
``cost_analysis()`` FLOPs — serve-time updates are pure host
arithmetic. The transfer-guard test (zero steady-state h2d) and the
greedy bit-identity tests run with all of this enabled.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any

from .events import NO_EVENTS


#: logs of the engines built in this process, newest last (see
#: :func:`flight_logs`); bounded so a test suite's hundreds of engines
#: leave at most this many rings behind
_KEPT_LOGS = 8
_logs: deque = deque(maxlen=_KEPT_LOGS)

#: salt of ``request_summary``'s ``prompt_hash``: fixed, so a reader
#: that holds the prompt ids (a load generator's own record) can
#: recompute the digest and join its record to the engine's
PROMPT_HASH_SALT = "gofr-flight-v1"


class FlightLog:
    """What a :class:`FlightRecorder` writes, and all that outlives its
    engine: the pass ring, the retired-request ring, the span ring and
    one clock anchor. Plain data — it holds no callback and no
    reference to the engine, so keeping it keeps no weights and no KV
    pool alive.

    ``passes`` and ``requests`` hold dicts; ``spans`` holds
    ``(name, t0, t1, pass_id)`` tuples on ``time.perf_counter()``.
    ``anchor`` is ``(time.time(), time.perf_counter())`` read together
    at creation: request timestamps are wall clock, spans and pass
    ``t0``/``t1`` monotonic, and the anchor puts both on one axis."""

    def __init__(self, passes: int, requests: int, spans: int) -> None:
        self.anchor = (time.time(), time.perf_counter())
        self.passes: deque = deque(maxlen=max(1, passes))
        self.requests: deque = deque(maxlen=max(1, requests))
        self.spans: deque = deque(maxlen=max(1, spans))

    def mono(self, t: float) -> float:
        """A ``time.time()`` reading on the ``perf_counter`` axis."""
        return self.anchor[1] + (t - self.anchor[0])


def flight_logs() -> list[FlightLog]:
    """The flight logs of the engines built in this process, newest
    last, at most ``_KEPT_LOGS`` of them. A log stays readable here
    after its engine is freed (a benchmark frees the engine to make
    room on the device and reads the spans afterwards)."""
    return list(_logs)


class _Span:
    """One open span of :meth:`FlightRecorder.span`. ``t0``/``t1`` are
    the ``perf_counter`` readings at its edges, for the caller that
    needs the same instants (so the clock is read once, not twice)."""

    __slots__ = ("_rec", "name", "pass_id", "t0", "t1", "_ann")

    def __init__(self, rec: "FlightRecorder", name: str,
                 pass_id: int | None) -> None:
        self._rec = rec
        self.name = name
        self.pass_id = pass_id

    def __enter__(self) -> "_Span":
        rec = self._rec
        if rec.enabled:
            if self.pass_id is None and rec._open:
                self.pass_id = rec._open[-1].pass_id  # the parent's
            rec._open.append(self)
            # a flag test unless a profile is being taken; then the
            # span lands in the trace, on the device events' clock
            self._ann = (rec._annotation(self.name)
                         if self.pass_id is None else
                         rec._annotation(self.name, pass_id=self.pass_id))
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.t1 = time.perf_counter()
        rec = self._rec
        if rec.enabled:
            self._ann.__exit__(*exc)
            rec._open.pop()
            rec.log.spans.append((self.name, self.t0, self.t1,
                                  self.pass_id))


class FlightRecorder:
    """The engine's black box and its span log: a fixed ring of
    per-pass records (what each pass worked on, when it was enqueued
    and when its result was on the host), a ring of the engine loop's
    phase spans, and a short log of retired requests' event trails.
    Served as JSON at ``/debug/engine``, summarized in
    ``Engine.health_check()``, dumped through the logger when the hot
    loop crashes. The three rings live in ``self.log``
    (:class:`FlightLog`), which :func:`flight_logs` keeps reachable
    after the engine is gone.

    Writer side (the engine thread) only ever appends plain dicts and
    tuples to bounded deques; reader side (``snapshot``) copies under
    the GIL. ``size <= 0`` disables recording entirely.
    """

    SPAN_RING = 32768

    def __init__(self, size: int = 4096, request_logs: int = 512) -> None:
        self.enabled = size > 0
        self.size = max(0, int(size))
        self.log = FlightLog(self.size, int(request_logs), self.SPAN_RING)
        self._seq = 0          # pass ids handed out
        self._recorded = 0     # pass records written
        self._by_kind: dict[str, int] = {}
        self._open: list[_Span] = []   # the engine thread's open spans
        if self.enabled:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
            _logs.append(self.log)
        #: optional () -> goodput summary (GoodputMeter.summary); the
        #: engine wires its meter here so fleet_summary carries the
        #: waste breakdown and the leader can say WHY a host is slow
        self.goodput_source: Any = None
        #: optional () -> prefix-cache digest (Engine.prefix_digest);
        #: rides fleet_summary so the leader's router can score hosts
        #: by longest resident prefix without any new protocol
        self.prefix_digest_source: Any = None
        #: optional () -> per-signature cost table (CostModel.table);
        #: rides fleet_summary so the leader can compare hosts on the
        #: SAME compiled graph (signature-normalized straggler math)
        #: instead of the workload-mix-confounded p95
        self.cost_source: Any = None
        #: optional () -> integrity digest block
        #: (IntegrityPlane.summary); rides fleet_summary so the leader
        #: can majority-vote golden-probe digests across hosts and
        #: quarantine the outlier (serving/integrity.py)
        self.integrity_source: Any = None

    # ------------------------------------------------------------ writers
    def new_pass(self) -> int:
        """The id of a pass about to be enqueued: its spans carry it
        from here on, its record takes it at :meth:`record_pass`."""
        self._seq += 1
        return self._seq

    def span(self, name: str, pass_id: int | None = None) -> _Span:
        """Context manager around one phase of the engine loop: on exit
        ``(name, t0, t1, pass_id)`` joins the span ring, and while a
        JAX profile is being taken the same span is in that trace. A
        span opened inside another inherits its ``pass_id`` unless
        given one. Disabled, it records and annotates nothing and only
        reads the clock for its caller."""
        return _Span(self, name, pass_id)

    def record_pass(self, kind: str, pass_id: int | None = None,
                    **fields: Any) -> None:
        """One device pass: the engine names the rows and contexts it
        served (docs/observability.md has the fields). A decode pass of
        a sparse-expert family also carries ``experts_touched``,
        ``assignments`` (counted on the device in the model's step,
        read with the sampled tokens) and ``kv_row_bytes`` (the pool's
        stored bytes a token, from the model's stated row); one with a
        multi-stream residual also ``streams`` and ``mhc_row_err``."""
        if not self.enabled:
            return
        rec = {"pass_id": self.new_pass() if pass_id is None else pass_id,
               "kind": kind, "t": time.time()}
        rec.update(fields)
        self.log.passes.append(rec)
        self._recorded += 1
        self._by_kind[kind] = self._by_kind.get(kind, 0) + 1

    def record_request(self, summary: dict) -> None:
        if self.enabled:
            self.log.requests.append(summary)

    # ------------------------------------------------------------ readers
    def snapshot(self, n: int | None = None) -> dict:
        passes = list(self.log.passes)
        spans = list(self.log.spans) if self.enabled else []
        if n is not None and n > 0:
            passes, spans = passes[-n:], spans[-n:]
        return {"enabled": self.enabled, "ring_size": self.size,
                "passes_recorded": self._recorded,
                "passes": passes,
                "requests": list(self.log.requests),
                "anchor": {"wall": self.log.anchor[0],
                           "monotonic": self.log.anchor[1]},
                "spans": [{"name": name, "t0": t0, "t1": t1,
                           "pass_id": pid}
                          for name, t0, t1, pid in spans]}

    def summary(self) -> dict:
        passes = self.log.passes
        last = passes[-1] if passes else None
        out = {"passes_recorded": self._recorded,
               "by_kind": dict(self._by_kind)}
        if last is not None:
            out["last_pass_kind"] = last["kind"]
            out["last_pass_age_s"] = round(time.time() - last["t"], 3)
        return out

    def fleet_summary(self) -> dict:
        """Compact per-host digest attached to control-plane heartbeats
        (serving/control_plane.py): p50/p95 pass duration, mean
        occupancy, last queue depth, tokens/s — computed over the pass
        ring, on the heartbeat thread, from fields already recorded.
        The leader derives fleet skew and straggler gauges from these."""
        passes = list(self.log.passes)
        out: dict = {"passes_recorded": self._recorded,
                     "by_kind": dict(self._by_kind)}
        durs = sorted(p["dur"] for p in passes
                      if isinstance(p.get("dur"), (int, float)))
        if durs:
            out["pass_p50_s"] = round(durs[int(0.5 * (len(durs) - 1))], 6)
            out["pass_p95_s"] = round(durs[int(0.95 * (len(durs) - 1))], 6)
        occ = [p["occupancy"] for p in passes
               if isinstance(p.get("occupancy"), (int, float))]
        if occ:
            out["occupancy_mean"] = round(sum(occ) / len(occ), 3)
        depths = [p["queue_depth"] for p in passes
                  if isinstance(p.get("queue_depth"), (int, float))]
        if depths:
            out["queue_depth"] = depths[-1]
        timed = [p for p in passes if "tokens" in p]
        if len(timed) >= 2:
            span = timed[-1]["t"] - timed[0]["t"]
            if span > 0:
                out["tokens_per_s"] = round(
                    sum(p["tokens"] for p in timed[1:]) / span, 2)
        if self.goodput_source is not None:
            try:
                g = self.goodput_source() or {}
            except Exception:
                g = {}
            for key in ("goodput_ratio", "busy_s", "useful_s",
                        "waste_s"):
                if g.get(key) is not None:
                    out[key] = g[key]
        if self.prefix_digest_source is not None:
            try:
                digest = self.prefix_digest_source()
            except Exception:
                digest = None
            if digest:
                out["prefix_digest"] = digest
        if self.cost_source is not None:
            try:
                costs = self.cost_source()
            except Exception:
                costs = None
            if costs:
                out["costs"] = costs
        if self.integrity_source is not None:
            try:
                integ = self.integrity_source()
            except Exception:
                integ = None
            if integ:
                out["integrity"] = integ
        return out

    def dump(self, logger: Any, reason: str = "") -> None:
        """Post-mortem: the ring is exactly what you want to see after
        a crash — the last N passes before the loop died."""
        if logger is None or not self.enabled:
            return
        try:
            # the newest passes and spans: the text is cut below, and
            # what led up to the crash is at the rings' ends
            text = json.dumps(self.snapshot(32), default=str)
            logger.error(f"engine flight recorder ({reason or 'dump'}): "
                         f"{text[:16384]}")
        except Exception:
            pass


def request_summary(req: Any) -> dict:
    """Flight-recorder entry for a retired request — plain host fields."""
    return {
        "rid": getattr(req, "rid", None),
        "prompt_tokens": len(req.prompt_tokens),
        # joins this entry to a client's own record of the same prompt
        "prompt_hash": salted_token_hash(req.prompt_tokens,
                                         PROMPT_HASH_SALT),
        "generated": len(req.generated),
        "slot": req.slot,
        "tenant": getattr(req, "tenant", None),
        "device_s": round(getattr(req, "device_s", 0.0), 6),
        "submitted_at": req.submitted_at,
        "admitted_at": req.admitted_at,
        "first_token_at": req.first_token_at,
        "finished_at": req.finished_at,
        "ttft_ms": round(req.ttft_ms, 3) if req.ttft_ms is not None else None,
        "error": req.error,
        "cancelled": req.cancelled,
        "digest": getattr(req, "digest", None),
        "events": [{"name": name, "t0": t0, "t1": t1, **(attrs or {})}
                   for name, t0, t1, attrs in req.events],
    }


# ------------------------------------------------- goodput accounting
class GoodputMeter:
    """Device-time waste attribution with a hard conservation
    invariant: every accounted device-second is classified as
    ``useful`` or one of the waste causes, and

        ``useful_s + sum(waste_s.values()) == busy_s``

    holds at all times (useful is computed as the residual of each
    pass's classification, so the identity is structural, not
    statistical — tests pin it across every pass kind).

    Causes (the set ``/debug/efficiency`` and
    ``app_engine_waste_seconds{cause}`` expose):

    - ``padding`` — inactive/pad rows in a dispatched fixed-shape
      batch: empty decode slots, dummy prefill-group rows, verify rows
      discarded before collect. The kernels tolerate them by design;
      the meter prices them.
    - ``preempt_recompute`` — prefill time spent re-computing KV a
      preempted request already produced once (vLLM-style
      preemption-by-recompute), plus batch-prefill rows orphaned by a
      preemption mid-flight.
    - ``spec_rejected`` — the drafted-minus-accepted fraction of each
      speculative verify row: positions computed and thrown away.
    - ``bubble`` — wall-clock gaps between a collect completing with
      NOTHING left in flight and the next dispatch, while work was
      waiting (queued, requeued or active). Host scheduling overhead
      the device spends idle — the dispatch-bound regime the round-5
      chip run showed, now a named number.
    - ``integrity_probe`` — device time spent serving golden canary
      probes (serving/integrity.py): correct-by-design synthetic
      traffic, re-priced out of ``useful`` at the probe's retire
      (:meth:`reprice_probe`) so correctness verification is never
      mistaken for serving goodput.

    Everything is engine-thread float arithmetic at dispatch/collect —
    the same single-writer discipline as the FlightRecorder; no locks,
    no device syncs, zero hot-path perturbation (the transfer-guard
    and greedy bit-identity tests run with the meter ON). ``busy_s``
    sums per-pass durations, so with pipelining it may exceed wall
    time — it is an attribution base, not a wall clock.
    """

    CAUSES = ("padding", "preempt_recompute", "spec_rejected", "bubble",
              "integrity_probe")

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = bool(enabled)
        self.reset()

    def reset(self) -> None:
        self.busy_s = 0.0
        self.useful_s = 0.0
        self.waste_s = {c: 0.0 for c in self.CAUSES}
        self.passes = 0
        #: per-pass-kind sub-ledger for the /debug/efficiency rollup
        self.by_kind: dict[str, dict] = {}
        self._free_at: float | None = None
        self._backlog = False

    # ------------------------------------------------------------ feeds
    def _account(self, kind: str, busy: float, useful: float,
                 **wastes: float) -> None:
        self.busy_s += busy
        self.useful_s += useful
        sub = self.by_kind.setdefault(
            kind, {"busy_s": 0.0, "useful_s": 0.0,
                   **{c: 0.0 for c in self.CAUSES}})
        sub["busy_s"] += busy
        sub["useful_s"] += useful
        for cause, amount in wastes.items():
            if amount:
                self.waste_s[cause] += amount
                sub[cause] += amount
        self.passes += 1

    def add_decode(self, busy: float, served_rows: int,
                   batch: int) -> None:
        """A decode pass: the graph always runs the full ``batch``
        shape; rows that emitted no kept tokens (empty slots,
        pending-prefill sentinels, retired requests riding out a
        pipelined pass) are padding."""
        if not self.enabled or busy <= 0 or batch <= 0:
            return
        served = max(0, min(int(served_rows), batch))
        useful = busy * served / batch
        self._account("decode", busy, useful, padding=busy - useful)

    def add_prefill(self, kind: str, busy: float, group: int,
                    fresh_rows: int, recompute_rows: int) -> None:
        """A (batch or chunk) prefill dispatch of ``group`` padded
        rows: ``fresh_rows`` computed new KV, ``recompute_rows``
        re-prefilled a preempted request's history (or were orphaned
        by one), the rest were dummy pad rows."""
        if not self.enabled or busy <= 0 or group <= 0:
            return
        share = busy / group
        fresh = max(0, min(int(fresh_rows), group))
        recomp = max(0, min(int(recompute_rows), group - fresh))
        self._account(kind, busy, fresh * share,
                      preempt_recompute=recomp * share,
                      padding=(group - fresh - recomp) * share)

    def add_spec(self, busy: float, batch: int,
                 rows: list[tuple[int, int]]) -> None:
        """A speculative verify pass over a full-``batch`` graph.
        ``rows`` carries one ``(drafted, accepted)`` pair per row that
        survived to collect; each row's useful fraction is the emitted
        tokens (accepted + bonus) over its fed positions
        (1 + drafted), the rejected remainder is ``spec_rejected``,
        and rows not fed (or discarded by a mid-pass preemption) are
        padding."""
        if not self.enabled or busy <= 0 or batch <= 0:
            return
        share = busy / batch
        useful = rejected = 0.0
        for drafted, accepted in rows:
            drafted = max(0, int(drafted))
            accepted = max(0, min(int(accepted), drafted))
            useful += share * (1 + accepted) / (1 + drafted)
            rejected += share * (drafted - accepted) / (1 + drafted)
        self._account("spec_verify", busy, useful,
                      spec_rejected=rejected,
                      padding=max(0, batch - len(rows)) * share)

    def reprice_probe(self, device_s: float) -> None:
        """Re-price a retired golden probe's attributed device time
        from ``useful`` to the ``integrity_probe`` waste cause —
        ``busy_s`` unchanged, so the conservation identity stays
        structural. The transfer lands in ``by_kind`` as a dedicated
        ``integrity_probe`` journal row (zero busy, negative useful)
        so per-kind sums still reconcile against the totals."""
        if not self.enabled or device_s <= 0:
            return
        moved = min(float(device_s), self.useful_s)
        if moved <= 0:
            return
        self.useful_s -= moved
        self.waste_s["integrity_probe"] += moved
        sub = self.by_kind.setdefault(
            "integrity_probe", {"busy_s": 0.0, "useful_s": 0.0,
                                **{c: 0.0 for c in self.CAUSES}})
        sub["useful_s"] -= moved
        sub["integrity_probe"] += moved

    def note_pass_end(self, t: float, backlog: bool) -> None:
        """The device went idle at host time ``t`` (a collect finished
        with nothing left in flight). ``backlog`` records whether work
        was waiting — only then does the gap to the next dispatch
        count as a bubble."""
        if self.enabled:
            self._free_at = t
            self._backlog = bool(backlog)

    def note_dispatch(self, t: float) -> None:
        """A device dispatch at host time ``t`` closes any open idle
        gap; with backlog pending, the gap was a bubble: device-time
        lost to host-side scheduling while requests waited."""
        if not self.enabled or self._free_at is None:
            return
        gap = t - self._free_at
        self._free_at = None
        if self._backlog and gap > 0:
            self.busy_s += gap
            self.waste_s["bubble"] += gap

    # ---------------------------------------------------------- readers
    def summary(self) -> dict:
        """The compact digest: heartbeat summaries, workload headers,
        the bench payload."""
        busy = self.busy_s
        out = {"busy_s": round(busy, 6),
               "useful_s": round(self.useful_s, 6),
               "waste_s": {c: round(v, 6)
                           for c, v in self.waste_s.items()}}
        if busy > 0:
            out["goodput_ratio"] = round(self.useful_s / busy, 6)
        return out

    def dominant_waste(self) -> str | None:
        worst = max(self.waste_s, key=self.waste_s.get, default=None)
        return worst if worst and self.waste_s[worst] > 0 else None

    def state(self) -> dict:
        """The full ``/debug/efficiency`` payload: totals, per-kind
        breakdown, dominant cause, and the conservation residual (a
        float-epsilon health check on the invariant itself)."""
        out = self.summary()
        out["enabled"] = self.enabled
        out["passes"] = self.passes
        out["dominant_waste"] = self.dominant_waste()
        out["by_kind"] = {k: {kk: round(vv, 6) for kk, vv in sub.items()}
                          for k, sub in self.by_kind.items()}
        out["conservation_error_s"] = round(
            self.busy_s - self.useful_s - sum(self.waste_s.values()), 9)
        return out


class WatermarkTracker:
    """Memory high-water marks with timestamps: KV-pool pages (or rows
    for the slot layout), prefix-cache pages, and host RSS. Updated on
    the engine's throttled gauge cadence — pure host compares, monotone
    non-decreasing within a run by construction. Served in
    ``/debug/efficiency`` and as ``app_engine_*_watermark`` gauges."""

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = bool(enabled)
        self._marks: dict[str, dict] = {}
        #: EventLedger high-water crossings are recorded on (engine
        #: wiring); crossings within 5% of the last recorded one are
        #: not re-recorded, so a slowly creeping mark can't flood the
        #: ring while the ratchet still lands in the timeline
        self.events = NO_EVENTS
        self._event_marks: dict[str, float] = {}

    def update(self, name: str, value: float,
               t: float | None = None) -> bool:
        """Record ``value`` if it is a new high-water mark; returns
        True when the mark advanced."""
        if not self.enabled:
            return False
        mark = self._marks.get(name)
        if mark is not None and value <= mark["value"]:
            return False
        self._marks[name] = {"value": value,
                             "t": time.time() if t is None else t}
        last = self._event_marks.get(name)
        if last is None or value >= last * 1.05:
            self._event_marks[name] = value
            self.events.emit("obs.watermark", cause=name, value=value)
        return True

    def update_rss(self) -> None:
        """Host RSS high-water mark from the kernel's own accounting
        (``ru_maxrss`` is already a max — one cheap syscall)."""
        if not self.enabled:
            return
        try:
            import resource
            kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            self.update("host_rss_bytes", float(kb) * 1024.0)
        except Exception:
            pass

    def get(self, name: str) -> float | None:
        mark = self._marks.get(name)
        return mark["value"] if mark is not None else None

    def state(self) -> dict:
        return {name: dict(mark) for name, mark in self._marks.items()}


class RecompileSentinel:
    """Detects unexpected post-warmup XLA recompiles from dispatch
    shape signatures.

    The engine's graphs are keyed by static shape tuples — prefill
    (bucket, group), chunk (width, group, window), decode (window),
    verify (draft width). ``warmup()`` observes every signature it
    compiles, then ``seal()``s the sentinel; after that, the first
    dispatch of a NOVEL signature is, by construction, a lowering the
    warmup did not cover — a serving-path recompile. The engine bumps
    ``app_engine_recompiles`` and WARNs once per signature with the
    offending shape, so a shape-induced recompile storm names itself
    instead of surfacing as an unexplained p99 explosion.

    Host-side set lookups at dispatch time — O(1), no device work.
    Engines that never warm up never seal, so the sentinel stays
    silent (everything is an expected cold compile then)."""

    MAX_SIGNATURES = 32

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = bool(enabled)
        self.sealed = False
        self.recompiles = 0
        self.signatures: list[str] = []
        self._seen: set = set()

    def observe(self, sig: tuple) -> None:
        """Seed an expected signature (warmup-time compiles)."""
        if self.enabled:
            self._seen.add(sig)

    def seal(self) -> None:
        """Warmup is done: novel signatures are recompiles from now."""
        self.sealed = True

    def dispatch(self, sig: tuple) -> bool:
        """Note a dispatch; True when it is a novel POST-warmup shape
        (fires exactly once per signature — the repeat dispatch hits a
        warm graph and stays silent)."""
        if not self.enabled or sig in self._seen:
            return False
        self._seen.add(sig)
        if not self.sealed:
            return False
        self.recompiles += 1
        if len(self.signatures) < self.MAX_SIGNATURES:
            self.signatures.append("/".join(str(p) for p in sig))
        return True

    def state(self) -> dict:
        return {"enabled": self.enabled, "sealed": self.sealed,
                "recompiles": self.recompiles,
                "signatures": list(self.signatures),
                "known_shapes": len(self._seen)}


# ------------------------------------------------- workload capture
#
# Versioned workload-file format (JSONL): the first line is a header
# object, every following line one retired request. The replay driver
# (serving/replay.py) refuses unknown formats/versions, so the header
# is the compatibility contract — bump WORKLOAD_VERSION on any
# incompatible record change.
WORKLOAD_FORMAT = "gofr-workload"
WORKLOAD_VERSION = 1


def salted_token_hash(tokens: Any, salt: str) -> str:
    """Stable redaction digest of a token-id sequence. The salt is
    drawn per recorder (never serialized), so captured hashes cannot
    be dictionary-attacked against a known tokenizer — but two
    requests with the same prompt in one capture still collide, which
    is exactly what replay-divergence comparison needs."""
    body = ",".join(str(int(t)) for t in tokens)
    return hashlib.sha256(f"{salt}:{body}".encode()).hexdigest()[:24]


class WorkloadRecorder:
    """Bounded ring of per-request workload records — the capturable,
    replayable twin of the :class:`FlightRecorder` (which keeps pass
    telemetry; this keeps the *traffic*). Served as a versioned JSONL
    file at ``GET /debug/workload``, armed/disarmed by
    ``POST /debug/workload/start|stop`` or ``EngineConfig.workload_capture``.

    Records are host-assembled ONCE per request at retire
    (``Engine._finalize_obs``), from fields the engine already carries:
    arrival timestamp, prompt token ids, sampling params, the engine's
    resolved sampling seed, tenant label, and the outcome
    (completion ids, TTFT/TPOT/e2e, finish reason). The hot loop never
    touches this — the zero-perturbation invariant of the module holds
    with capture ON (tested).

    ``redact=True`` swaps prompt/completion token ids for salted
    hashes (lengths preserved): safe to ship off-box, still good for
    load-shape replay and hash-level divergence checks, but NOT for
    bit-identity replay (the prompts are gone — ``replay_workload``
    refuses).
    """

    def __init__(self, size: int = 4096, *, redact: bool = False,
                 engine_seed: int | None = None) -> None:
        self.enabled = size > 0
        self.size = max(0, int(size))
        self.redact = bool(redact)
        self.engine_seed = engine_seed
        self.capturing = False
        self.started_at: float | None = None
        self._salt = os.urandom(8).hex()
        self._records: deque = deque(maxlen=max(1, self.size))
        self._seq = 0
        self._dropped = 0
        #: optional () -> GoodputMeter.summary, wired by the engine:
        #: the header then carries the capture-side efficiency digest
        #: so a replay can compare waste breakdowns, not just tokens
        self.goodput_source: Any = None
        #: optional () -> CostModel.table, wired by the engine: the
        #: header then carries the capture-side per-signature cost
        #: table so a replay can report per-kernel-class divergence
        self.cost_source: Any = None

    # ------------------------------------------------------------ control
    def start(self, redact: bool | None = None) -> dict:
        """Arm capture with a FRESH ring (a capture is one workload —
        stale records from an earlier session never bleed in)."""
        if not self.enabled:
            return self.status()
        if redact is not None:
            self.redact = bool(redact)
        self._records.clear()
        self._seq = 0
        self._dropped = 0
        self.started_at = time.time()
        self.capturing = True
        return self.status()

    def stop(self) -> dict:
        self.capturing = False
        return self.status()

    def status(self) -> dict:
        return {"enabled": self.enabled, "capturing": self.capturing,
                "redact": self.redact, "size": self.size,
                "records": len(self._records), "recorded": self._seq,
                "dropped": self._dropped, "started_at": self.started_at}

    # ------------------------------------------------------------ writer
    def record(self, req: Any) -> None:
        """One retired request -> one record. Engine-thread append of a
        plain dict onto a bounded deque — same writer discipline as the
        flight recorder."""
        if not (self.enabled and self.capturing):
            return
        self._seq += 1
        if len(self._records) == self._records.maxlen:
            self._dropped += 1
        p = req.params
        status = ("cancelled" if req.cancelled
                  else "error" if req.error is not None else "ok")
        end = req.finished_at
        n = len(req.generated)
        tpot_ms = None
        if req.first_token_at is not None and end is not None and n > 1:
            tpot_ms = (end - req.first_token_at) * 1000.0 / (n - 1)
        rec: dict = {
            "t": req.submitted_at,
            "tenant": getattr(req, "tenant", None),
            # per-request seed: today every request shares the engine's
            # resolved sampling seed (rng keys ride the graphs as
            # arguments, folded by a global step) — recorded per request
            # so the format survives a future per-request rng
            "seed": self.engine_seed,
            "params": {"temperature": p.temperature, "top_p": p.top_p,
                       "top_k": p.top_k,
                       "max_new_tokens": p.max_new_tokens},
            "status": status,
        }
        if self.redact:
            rec["prompt_hash"] = salted_token_hash(req.prompt_tokens,
                                                   self._salt)
            rec["prompt_len"] = len(req.prompt_tokens)
            rec["completion_hash"] = salted_token_hash(req.generated,
                                                       self._salt)
            rec["completion_len"] = n
        else:
            rec["prompt_tokens"] = list(req.prompt_tokens)
            rec["completion_tokens"] = list(req.generated)
        if getattr(req, "digest", None):
            # the output fingerprint (serving/integrity.py): additive
            # record field so replay can diff recorded vs replayed
            # digests (the digest_divergence report key)
            rec["digest"] = req.digest
        if req.error is not None:
            rec["error"] = str(req.error)[:200]
        if req.ttft_ms is not None:
            rec["ttft_ms"] = round(req.ttft_ms, 3)
        if tpot_ms is not None:
            rec["tpot_ms"] = round(tpot_ms, 3)
        if end is not None:
            rec["e2e_ms"] = round((end - req.submitted_at) * 1000.0, 3)
        self._records.append(rec)

    # ------------------------------------------------------------ readers
    def header(self) -> dict:
        out = {"format": WORKLOAD_FORMAT, "version": WORKLOAD_VERSION,
               "redacted": self.redact, "engine_seed": self.engine_seed,
               "started_at": self.started_at, "recorded": self._seq,
               "dropped": self._dropped}
        if self.goodput_source is not None:
            # additive field (same WORKLOAD_VERSION): readers that
            # predate it simply ignore the key
            try:
                g = self.goodput_source()
                if g and g.get("busy_s"):
                    out["goodput"] = g
            except Exception:
                pass
        if self.cost_source is not None:
            # additive field, same contract as the goodput block
            try:
                costs = self.cost_source()
                if costs:
                    out["costs"] = costs
            except Exception:
                pass
        return out

    def snapshot(self, n: int | None = None) -> dict:
        records = list(self._records)
        if n is not None and n > 0:
            records = records[-n:]
        return {"header": self.header(), "records": records}

    def to_jsonl(self, n: int | None = None) -> str:
        """The ``GET /debug/workload`` body: header line, then one
        line per record in arrival order (the ring holds retire order;
        replay sorts by ``t`` anyway)."""
        snap = self.snapshot(n)
        lines = [json.dumps(snap["header"])]
        lines.extend(json.dumps(rec) for rec in snap["records"])
        return "\n".join(lines) + "\n"


def emit_engine_spans(tracer: Any, req: Any) -> None:
    """Assemble the ``engine.*`` child spans for a retired request and
    export them through the tracer. Called once at retire, entirely from
    host timestamps recorded along the lifecycle — the hot loop never
    creates spans. ``req.trace`` carries (trace_id, parent_span_id)
    captured at submit from the caller's active span (the HTTP/gRPC
    middleware span) or the inbound ``traceparent``, so one distributed
    trace runs HTTP -> engine -> retire."""
    trace = getattr(req, "trace", None)
    if tracer is None or trace is None:
        return
    trace_id, parent_id = trace
    end = req.finished_at or time.time()
    status = "OK" if req.error is None else f"ERROR: {req.error}"
    attrs = {"rid": getattr(req, "rid", None),
             "prompt_tokens": len(req.prompt_tokens),
             "generated_tokens": len(req.generated),
             "slot": req.slot, "cancelled": req.cancelled}
    if getattr(req, "tenant", None):
        # the accounting identity: a trace found through an exemplar
        # names who it was served for without a ledger lookup
        attrs["tenant"] = req.tenant
    root = tracer.emit_span(
        "engine.request", trace_id=trace_id, parent_id=parent_id,
        start_time=req.submitted_at, end_time=end, status=status,
        attributes=attrs)
    admit = req.admitted_at or req.first_token_at or end
    tracer.emit_span("engine.queue", trace_id=trace_id,
                     parent_id=root.span_id, start_time=req.submitted_at,
                     end_time=admit)
    for name, t0, t1, attrs in req.events:
        tracer.emit_span(f"engine.{name}", trace_id=trace_id,
                         parent_id=root.span_id, start_time=t0,
                         end_time=t1, attributes=attrs)
    if req.first_token_at is not None:
        n = len(req.generated)
        tpot = ((end - req.first_token_at) / (n - 1)) if n > 1 else None
        tracer.emit_span(
            "engine.decode", trace_id=trace_id, parent_id=root.span_id,
            start_time=req.first_token_at, end_time=end,
            attributes={"tokens": n,
                        "tpot_s": round(tpot, 6) if tpot else None})
    tracer.emit_span("engine.retire", trace_id=trace_id,
                     parent_id=root.span_id, start_time=end, end_time=end,
                     attributes={"error": req.error or ""})


# ----------------------------------------------------- usage metering
def parse_window(spec: str | None) -> float | None:
    """``"5m"``/``"1h"``/``"30s"``/``"300"`` -> seconds; None/'' -> None
    (cumulative totals). Raises ValueError on garbage."""
    if not spec:
        return None
    spec = spec.strip().lower()
    mult = {"s": 1.0, "m": 60.0, "h": 3600.0, "d": 86400.0}.get(spec[-1])
    if mult is not None:
        return float(spec[:-1]) * mult
    return float(spec)


def _fmt_window(seconds: float) -> str:
    if seconds % 3600 == 0:
        return f"{int(seconds // 3600)}h"
    if seconds % 60 == 0:
        return f"{int(seconds // 60)}m"
    return f"{int(seconds)}s"


class UsageLedger:
    """Per-tenant usage accounting, fed once per retired request from
    the engine's ``_finalize_obs`` — the metering plane behind
    ``app_tenant_*`` metrics, ``GET /debug/usage`` and the federated
    fleet rollup.

    Everything is host arithmetic over numbers the engine already
    collected (token counts, lifecycle timestamps, the per-pass
    device-time shares accumulated during collects), recorded at
    retire on the engine thread — the hot loop never touches this.
    Cumulative totals live per tenant; a bounded event ring
    (``window_records``) answers windowed queries, so
    ``?window=5m`` rollups degrade gracefully (oldest events drop)
    instead of growing without bound.
    """

    def __init__(self, metrics: Any = None,
                 window_records: int = 4096) -> None:
        self.metrics = metrics
        self._lock = threading.Lock()
        self._totals: dict[str, dict] = {}
        self._events: deque = deque(maxlen=max(1, int(window_records)))

    @staticmethod
    def _blank() -> dict:
        return {"requests": {}, "prompt_tokens": 0,
                "completion_tokens": 0, "device_s": 0.0,
                "queue_s": 0.0, "e2e_s": 0.0,
                # who pays for inefficiency: the slice of this
                # tenant's device_s that was preemption recompute or
                # rejected speculation (padding/bubbles are systemic,
                # not attributable to one principal)
                "waste_recompute_s": 0.0, "waste_spec_s": 0.0}

    def record(self, *, tenant: str, status: str, prompt_tokens: int,
               completion_tokens: int, queue_s: float = 0.0,
               e2e_s: float = 0.0, device_s: float = 0.0,
               waste_recompute_s: float = 0.0,
               waste_spec_s: float = 0.0,
               t: float | None = None) -> None:
        t = time.time() if t is None else t
        with self._lock:
            tot = self._totals.setdefault(tenant, self._blank())
            tot["requests"][status] = tot["requests"].get(status, 0) + 1
            tot["prompt_tokens"] += int(prompt_tokens)
            tot["completion_tokens"] += int(completion_tokens)
            tot["device_s"] += float(device_s)
            tot["queue_s"] += float(queue_s)
            tot["e2e_s"] += float(e2e_s)
            tot["waste_recompute_s"] += float(waste_recompute_s)
            tot["waste_spec_s"] += float(waste_spec_s)
            self._events.append(
                {"t": t, "tenant": tenant, "status": status,
                 "prompt_tokens": int(prompt_tokens),
                 "completion_tokens": int(completion_tokens),
                 "device_s": float(device_s), "queue_s": float(queue_s),
                 "e2e_s": float(e2e_s),
                 "waste_recompute_s": float(waste_recompute_s),
                 "waste_spec_s": float(waste_spec_s)})
        m = self.metrics
        if m is None:
            return
        m.increment_counter("app_tenant_requests", tenant=tenant,
                            status=status)
        if prompt_tokens:
            m.add_counter("app_tenant_prompt_tokens",
                          float(prompt_tokens), tenant=tenant)
        if completion_tokens:
            m.add_counter("app_tenant_completion_tokens",
                          float(completion_tokens), tenant=tenant)
        if device_s > 0:
            m.add_counter("app_tenant_device_seconds", float(device_s),
                          tenant=tenant)
        if waste_recompute_s > 0:
            m.add_counter("app_tenant_waste_seconds",
                          float(waste_recompute_s), tenant=tenant,
                          cause="preempt_recompute")
        if waste_spec_s > 0:
            m.add_counter("app_tenant_waste_seconds",
                          float(waste_spec_s), tenant=tenant,
                          cause="spec_rejected")
        m.record_histogram("app_tenant_queue_seconds", float(queue_s),
                           tenant=tenant)
        m.record_histogram("app_tenant_e2e_seconds", float(e2e_s),
                           tenant=tenant)

    def rollup(self, tenant: str | None = None,
               window_s: float | None = None) -> dict:
        """The ``GET /debug/usage`` JSON: cumulative totals per tenant,
        or windowed sums over the event ring when ``window_s`` is
        given (flagged ``partial`` when the ring has rotated past the
        window start — the caller knows the sum is a floor)."""
        with self._lock:
            if window_s is None:
                per_tenant = {name: {**tot,
                                     "requests": dict(tot["requests"])}
                              for name, tot in self._totals.items()
                              if tenant is None or name == tenant}
                out = {"window": None, "tenants": per_tenant}
            else:
                cutoff = time.time() - window_s
                per_tenant = {}
                for ev in self._events:
                    if ev["t"] < cutoff:
                        continue
                    if tenant is not None and ev["tenant"] != tenant:
                        continue
                    tot = per_tenant.setdefault(ev["tenant"],
                                                self._blank())
                    tot["requests"][ev["status"]] = \
                        tot["requests"].get(ev["status"], 0) + 1
                    for key in ("prompt_tokens", "completion_tokens",
                                "device_s", "queue_s", "e2e_s",
                                "waste_recompute_s", "waste_spec_s"):
                        tot[key] += ev.get(key, 0)
                partial = bool(self._events) and \
                    self._events[0]["t"] > cutoff and \
                    len(self._events) == self._events.maxlen
                out = {"window": _fmt_window(window_s),
                       "tenants": per_tenant, "partial": partial}
        for tot in out["tenants"].values():
            for key in ("device_s", "queue_s", "e2e_s",
                        "waste_recompute_s", "waste_spec_s"):
                tot[key] = round(tot.get(key, 0.0), 6)
        return out


# -------------------------------------------------------------- SLO
@dataclass
class SLOConfig:
    """Service-level objectives for the chat path (docs/configs.md).

    A retired request is GOOD when it finished without error and met
    every configured latency threshold (``None`` disables that
    dimension); cancelled requests are excluded (the client left —
    nothing was violated). The tracker turns good/bad streams into
    multi-window burn rates against the availability target, the
    standard SRE alerting shape: burn rate 1.0 = spending the error
    budget exactly at the sustainable pace.
    """

    #: time-to-first-token threshold (seconds); None = not judged
    ttft_s: float | None = 2.0
    #: mean inter-token latency threshold (seconds); None = not judged
    tpot_s: float | None = 0.5
    #: end-to-end latency threshold (seconds); None = not judged
    e2e_s: float | None = 30.0
    #: availability objective: the target fraction of good requests
    availability: float = 0.999
    #: burn-rate windows (seconds); the SHORTEST is the fast-burn
    #: window the WARN escalation watches
    windows: tuple = (300.0, 3600.0)
    #: WARN once per episode when the fast-window burn rate crosses
    #: this (14.4 = the classic "2% of a 30-day budget in one hour"
    #: page threshold). 0 disables the escalation.
    fast_burn: float = 14.4
    #: horizon the error-budget-remaining gauge is computed over
    budget_window_s: float = 86400.0
    #: per-window event ring bound; beyond it the oldest events drop
    #: (rates stay correct over what is retained)
    max_events: int = 65536


class SLOTracker:
    """Multi-window burn-rate tracking over the retired-request
    stream: ``app_slo_burn_rate{window=...}`` and
    ``app_slo_error_budget_remaining`` gauges, the ``GET /debug/slo``
    state, and a WARN once per fast-burn episode.

    Fed from ``Engine._finalize_obs`` (host arithmetic at retire,
    zero hot-path work). Each window keeps a rolling (deque, total,
    bad) triple — O(1) amortized per request."""

    def __init__(self, config: SLOConfig | None = None,
                 metrics: Any = None, logger: Any = None) -> None:
        self.config = config if config is not None else SLOConfig()
        self.metrics = metrics
        self.logger = logger
        #: EventLedger fast-burn episodes are recorded on (app wiring)
        self.events = NO_EVENTS
        #: optional zero-arg hook fired once per fast-burn episode —
        #: the IncidentDetector's trigger rides here
        self.on_fast_burn = None
        self._lock = threading.Lock()
        horizons = tuple(sorted(set(
            tuple(self.config.windows) + (self.config.budget_window_s,))))
        self._wins = {w: {"events": deque(maxlen=self.config.max_events),
                          "total": 0, "bad": 0} for w in horizons}
        self._total = 0
        self._bad = 0
        self._escalated = False
        #: monotonic high-water mark over fed timestamps: record()
        #: clamps each t up to it so the per-window deques stay sorted
        #: — _evict_locked pops from the head while events age out,
        #: which silently under- or over-counts if a late-arriving
        #: older timestamp lands behind a newer one (replay feeds and
        #: multi-source clocks do this)
        self._last_t = float("-inf")

    # ------------------------------------------------------------ feed
    def judge(self, *, error: str | None, ttft_s: float | None,
              tpot_s: float | None, e2e_s: float | None) -> bool:
        """Good iff no error and every configured threshold held."""
        if error is not None:
            return False
        cfg = self.config
        for value, limit in ((ttft_s, cfg.ttft_s),
                             (tpot_s, cfg.tpot_s),
                             (e2e_s, cfg.e2e_s)):
            if limit is not None and value is not None and value > limit:
                return False
        return True

    def record(self, good: bool, t: float | None = None) -> None:
        t = time.time() if t is None else t
        with self._lock:
            # modest reordering tolerated: clamp to the newest seen
            # timestamp so windows stay sorted and eviction stays exact
            t = max(t, self._last_t)
            self._last_t = t
            self._total += 1
            self._bad += 0 if good else 1
            for w, win in self._wins.items():
                if win["events"].maxlen == len(win["events"]):
                    _, old_bad = win["events"][0]  # about to rotate out
                    win["total"] -= 1
                    win["bad"] -= old_bad
                win["events"].append((t, 0 if good else 1))
                win["total"] += 1
                win["bad"] += 0 if good else 1
                self._evict_locked(w, t)
            state = self._state_locked(t)
        self._publish(state)

    def _evict_locked(self, w: float, now: float) -> None:
        win = self._wins[w]
        events = win["events"]
        cutoff = now - w
        while events and events[0][0] < cutoff:
            _, bad = events.popleft()
            win["total"] -= 1
            win["bad"] -= bad

    # ----------------------------------------------------------- state
    def _burn_locked(self, w: float) -> dict:
        win = self._wins[w]
        total, bad = win["total"], win["bad"]
        err_rate = (bad / total) if total else 0.0
        budget = max(1e-9, 1.0 - self.config.availability)
        return {"total": total, "bad": bad,
                "error_rate": round(err_rate, 6),
                "burn_rate": round(err_rate / budget, 4)}

    def _state_locked(self, now: float) -> dict:
        for w in self._wins:
            self._evict_locked(w, now)
        windows = {_fmt_window(w): self._burn_locked(w)
                   for w in self.config.windows}
        bw = self.config.budget_window_s
        budget_win = self._burn_locked(bw)
        allowed = budget_win["total"] * (1.0 - self.config.availability)
        remaining = 1.0 - (budget_win["bad"] / allowed) if allowed > 0 \
            else (0.0 if budget_win["bad"] else 1.0)
        fast_w = min(self.config.windows)
        fast = windows[_fmt_window(fast_w)]["burn_rate"]
        return {
            "objectives": {"ttft_s": self.config.ttft_s,
                           "tpot_s": self.config.tpot_s,
                           "e2e_s": self.config.e2e_s,
                           "availability": self.config.availability},
            "windows": windows,
            "budget": {"window": _fmt_window(bw),
                       "total": budget_win["total"],
                       "bad": budget_win["bad"],
                       "remaining": round(max(-1.0, min(1.0, remaining)),
                                          6)},
            "fast_burn": {"window": _fmt_window(fast_w),
                          "burn_rate": fast,
                          "threshold": self.config.fast_burn,
                          "tripped": bool(self.config.fast_burn
                                          and fast >= self.config.fast_burn)},
            "lifetime": {"total": self._total, "bad": self._bad},
        }

    def state(self) -> dict:
        """The ``GET /debug/slo`` payload."""
        with self._lock:
            return self._state_locked(time.time())

    def _publish(self, state: dict) -> None:
        m = self.metrics
        if m is not None:
            for label, win in state["windows"].items():
                m.set_gauge("app_slo_burn_rate", win["burn_rate"],
                            window=label)
            m.set_gauge("app_slo_error_budget_remaining",
                        state["budget"]["remaining"])
        tripped = state["fast_burn"]["tripped"]
        if tripped and not self._escalated:
            self._escalated = True
            if self.logger is not None:
                self.logger.warn(
                    "SLO fast burn: error budget burning at "
                    f"{state['fast_burn']['burn_rate']}x over the "
                    f"{state['fast_burn']['window']} window",
                    threshold=state["fast_burn"]["threshold"],
                    budget_remaining=state["budget"]["remaining"])
            self.events.emit(
                "obs.fast_burn", severity="error",
                burn_rate=state["fast_burn"]["burn_rate"],
                window=state["fast_burn"]["window"],
                budget_remaining=state["budget"]["remaining"])
            hook = self.on_fast_burn
            if hook is not None:
                try:
                    hook()
                except Exception:
                    pass  # an incident capture must never fail a retire
        elif not tripped:
            self._escalated = False  # episode over; re-arm


# ----------------------------------------------------------- watchdog
class StallWatchdog:
    """Promotes the engine's PASSIVE stall flag into action.

    ``Engine.health_check()`` flips to DEGRADED when work is in flight
    but no pass has completed for ``stall_threshold_s`` — but nothing
    reads that unless an orchestrator happens to poll. This thread
    polls it on the worker itself and, once per stall episode:

    - dumps the flight recorder through the logger (the last N passes
      before the hang are the post-mortem),
    - emits an ``engine.stall`` span and bumps the
      ``app_engine_stalls`` counter + ``stats["stalls"]``,

    after which the next control-plane heartbeat (whose health source
    is this same ``health_check``) reports DEGRADED and the leader can
    evict + re-rank survivors instead of waiting for heartbeat silence.

    Everything runs on this thread against host-side state — the hot
    loop is never touched (zero-perturbation invariant). Re-arms when
    the engine recovers, so a flapping device reports each episode.
    """

    def __init__(self, engine: Any, interval_s: float = 5.0) -> None:
        self.engine = engine
        self.interval_s = max(0.05, float(interval_s))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._escalated = False

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="engine-watchdog")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(self.interval_s + 1.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.check_once()
            except Exception:  # a broken check must not kill the thread
                pass

    def check_once(self) -> bool:
        """One poll; returns True when a stall was escalated."""
        engine = self.engine
        health = engine.health_check()
        stalled = (health.get("status") == "DEGRADED"
                   and "stalled_for_s" in health)
        if not stalled:
            self._escalated = False
            return False
        if self._escalated:
            return False  # already reported this episode
        self._escalated = True
        stalled_for = health.get("stalled_for_s")
        engine.stats["stalls"] = engine.stats.get("stalls", 0) + 1
        if engine.logger is not None:
            engine.logger.error(
                "engine stalled: work in flight but no pass for "
                f"{stalled_for}s", active=health.get("active_slots"),
                waiting=health.get("waiting"))
        engine.recorder.dump(engine.logger,
                             reason=f"stall: no pass for {stalled_for}s")
        if engine.metrics is not None:
            engine.metrics.increment_counter("app_engine_stalls")
        tracer = getattr(engine, "tracer", None)
        if tracer is not None:
            tracer.start_span("engine.stall", attributes={
                "stalled_for_s": stalled_for,
                "active_slots": health.get("active_slots"),
                "waiting": health.get("waiting")}).end()
        getattr(engine, "events", NO_EVENTS).emit(
            "fleet.stall", severity="error",
            cause="no pass completed",
            stalled_for_s=stalled_for,
            active_slots=health.get("active_slots"),
            waiting=health.get("waiting"))
        return True


# ------------------------------------------------------------------- MFU
#
# Peak dense bf16 FLOPs per chip, keyed by the EXACT ``device_kind``
# JAX reports (v5e is "TPU v5 lite"; Google Cloud TPU documentation,
# per-generation system architecture pages). A kind that is not in the
# table (CPU, a TPU generation nobody measured on) -> None and the MFU
# gauge simply stays 0 — never a prefix match onto a neighbour's peak.
TPU_PEAK_FLOPS = {"TPU v5 lite": 197e12, "TPU v5p": 459e12,
                  "TPU v4": 275e12, "TPU v6 lite": 918e12}


def device_peak_flops() -> float | None:
    import jax
    return TPU_PEAK_FLOPS.get(jax.devices()[0].device_kind)


def jit_cost_flops(jitted: Any, *args: Any) -> float | None:
    """FLOPs of one call of a jitted function, from XLA's own cost
    analysis of the lowered/compiled graph. Runs at compile time (the
    engine calls it from ``warmup``), never on the serving path; every
    failure mode degrades to None."""
    try:
        lowered = jitted.lower(*args)
    except Exception:
        return None
    cost = None
    for source in (lambda: lowered.cost_analysis(),
                   lambda: lowered.compile().cost_analysis()):
        try:
            cost = source()
        except Exception:
            cost = None
        if cost is not None:
            break
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if isinstance(cost, dict) and cost.get("flops"):
        return float(cost["flops"])
    return None


# -------------------------------------------------------------- profiler
class ProfilerCapture:
    """On-demand TPU profiler capture wrapping
    ``jax.profiler.start_trace/stop_trace`` with single-flight
    semantics — the state machine behind ``POST /debug/profile/start``
    and ``/debug/profile/stop``. A second start while a capture runs is
    refused (JAX would raise); stop without a start reports cleanly.

    Hardening: a capture started with ``max_capture_s`` (per-start or
    the constructor default) is auto-stopped by a daemon watchdog timer
    — a forgotten ``stop`` can no longer let xprof buffer events
    forever. ``stop(force=True)`` recovers a crashed/leaked capture:
    it calls ``jax.profiler.stop_trace`` even when this state machine
    thinks nothing is running (a previous failed stop cleared the local
    state while JAX kept tracing) and swallows the stop error, so the
    next ``start`` works again."""

    def __init__(self, base_dir: str = "/tmp/gofr_tpu_profiles",
                 logger: Any = None,
                 max_capture_s: float = 0.0) -> None:
        self.base_dir = base_dir
        self.logger = logger
        #: default auto-stop budget for every capture; 0 = unbounded
        #: (per-start ``max_capture_s`` overrides)
        self.max_capture_s = max(0.0, float(max_capture_s))
        self._lock = threading.Lock()
        self._active_dir: str | None = None
        self._started_at: float | None = None
        self._timer: threading.Timer | None = None
        self.auto_stops = 0

    def start(self, trace_dir: str | None = None, *,
              max_capture_s: float | None = None) -> dict:
        with self._lock:
            if self._active_dir is not None:
                return {"ok": False, "error": "capture already running",
                        "dir": self._active_dir}
            path = trace_dir or os.path.join(
                self.base_dir, time.strftime("%Y%m%d-%H%M%S"))
            try:
                os.makedirs(path, exist_ok=True)
                import jax
                jax.profiler.start_trace(path)
            except Exception as exc:
                return {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
            self._active_dir = path
            self._started_at = time.time()
            cap = self.max_capture_s if max_capture_s is None \
                else max(0.0, float(max_capture_s))
            if cap > 0:
                self._timer = threading.Timer(cap, self._expire, (path,))
                self._timer.daemon = True
                self._timer.start()
            if self.logger:
                self.logger.info(f"profiler capture started: {path}")
            return {"ok": True, "dir": path}

    def _expire(self, path: str) -> None:
        """Watchdog body: stop the capture iff it is still the one the
        timer was armed for (a manual stop + fresh start must not be
        killed by the previous capture's timer)."""
        with self._lock:
            if self._active_dir != path:
                return
            self.auto_stops += 1
        result = self.stop()
        if self.logger and result.get("ok"):
            self.logger.warn(
                f"profiler capture auto-stopped at max_capture_s: {path}")

    def stop(self, force: bool = False) -> dict:
        with self._lock:
            timer, self._timer = self._timer, None
            if timer is not None:
                timer.cancel()
            if self._active_dir is None:
                if not force:
                    return {"ok": False, "error": "no capture running"}
                # leaked capture: a crashed stop cleared our state while
                # JAX kept tracing — stop the underlying trace so the
                # state machine and the profiler agree again
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                if self.logger:
                    self.logger.warn(
                        "profiler force-stop: recovered a leaked capture")
                return {"ok": True, "recovered": True, "dir": None}
            path, self._active_dir = self._active_dir, None
            started, self._started_at = self._started_at, None
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as exc:
                if force:
                    if self.logger:
                        self.logger.warn(
                            f"profiler force-stop swallowed: {exc!r}")
                    return {"ok": True, "recovered": True, "dir": path,
                            "error": f"{type(exc).__name__}: {exc}"}
                return {"ok": False, "dir": path,
                        "error": f"{type(exc).__name__}: {exc}"}
            if self.logger:
                self.logger.info(f"profiler capture stopped: {path}")
            return {"ok": True, "dir": path,
                    "duration_s": round(time.time() - started, 3)
                    if started else None}

    def status(self) -> dict:
        return {"running": self._active_dir is not None,
                "dir": self._active_dir,
                "auto_stops": self.auto_stops}
