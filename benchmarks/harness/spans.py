"""The program's own span log, as the per-layer readers take it: the
newest flight log of the process, the traced span on the log's clock,
self time per phase of the engine loop, and the join between a client
record and the engine's entry for the same request.

The engine thread records every phase of its loop as a span
``(name, t0, t1, pass_id)`` on ``time.perf_counter()`` — the clock the
load generator and ``ctx["traced"]`` read too — and every pass with
the rows it carried (``gofr_tpu/serving/observability.py``). The log
outlives the engine, which ``run.py`` frees before any reader runs.
Spans nest; what a phase cost is its self time, its duration less what
its children cover. Against a program that keeps no such log (the
parent of the PR that added it) every function here returns ``None``
or nothing, and so does every reader built on them.
"""

from __future__ import annotations

#: phases in which the engine thread works on the host ...
HOST_WORK = frozenset({
    "engine.admit", "engine.prefill_dispatch", "engine.chunk_walk",
    "engine.decode_dispatch", "engine.emit", "engine.finalize",
    "engine.planes", "engine.prefill_collect", "engine.gauges"})
#: ... the part of them that is the observability planes' ...
PLANES = frozenset({"engine.finalize", "engine.planes", "engine.gauges"})
#: ... and those in which it waits: for requests, or for the device
WAITING = frozenset({"engine.wait", "engine.chunk_wait",
                     "engine.decode_wait", "engine.prefill_wait"})


def newest_log():
    """The flight log of the engine built last in this process, or
    None: the program has none, or recorded nothing."""
    try:
        from gofr_tpu.serving import observability
    except ImportError:
        return None
    logs = getattr(observability, "flight_logs", lambda: [])()
    return logs[-1] if logs and logs[-1].spans else None


def traced_span(ctx) -> tuple[float, float] | None:
    """The traced span on ``perf_counter``; None without the mark that
    ties the device trace to that clock (nothing below may guess)."""
    traced = ctx["traced"]
    if ctx["trace"].get("mark_ns") is None or "t_start" not in traced:
        return None
    return traced["t_start"], traced["t_end"]


def gap_intervals(ctx) -> list[tuple[float, float]]:
    """The trace's longest idle gaps as (start, end) on
    ``perf_counter``, mapped as ``harness/trace.label_gaps`` maps them:
    the mark's nanosecond is ``traced["t_start"]``."""
    if traced_span(ctx) is None:
        return []
    t_start, mark = ctx["traced"]["t_start"], ctx["trace"]["mark_ns"]
    return [(t_start + (ns - mark) / 1e9,
             t_start + (ns - mark) / 1e9 + seconds)
            for ns, seconds in ctx["trace"]["gaps"]]


def self_segments(spans) -> list[tuple[float, float, str]]:
    """The spans cut into pieces that do not overlap, each named by the
    innermost span open over it: a span's pieces add up to its self
    time. ``spans`` are (name, t0, t1, ...) of one thread, properly
    nested, in any order."""
    out: list = []
    stack: list = []   # [name, end, from where it has run itself]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
            if stack:
                stack[-1][2] = end

    for name, t0, t1, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(t0)
        if stack and t0 > stack[-1][2]:
            out.append((stack[-1][2], t0, stack[-1][0]))
        stack.append([name, t1, t0])
    close(float("inf"))
    return out


def seconds_in(segments, lo: float, hi: float, names=None) -> float:
    """Seconds of [lo, hi] that the segments named in ``names`` (all of
    them if None) cover."""
    return sum(min(b, hi) - max(a, lo) for a, b, name in segments
               if (names is None or name in names)
               and min(b, hi) > max(a, lo))


def decode_passes_in(log, lo: float, hi: float) -> int:
    """Decode passes whose tokens reached the host inside [lo, hi]."""
    return sum(1 for p in log.passes if p["kind"] == "decode"
               and p.get("t1") is not None and lo <= p["t1"] < hi)


def ms_per_decode_pass(ctx, names) -> float | None:
    """Self time of the phases ``names`` inside the traced span, in
    milliseconds, over the decode passes collected in it."""
    log, span = newest_log(), traced_span(ctx)
    if log is None or span is None:
        return None
    passes = decode_passes_in(log, *span)
    if not passes:
        return None
    return 1e3 * seconds_in(self_segments(log.spans), *span, names) / passes


def joined(ctx) -> list[tuple[dict, dict]]:
    """(client record, engine request entry) of every request of the
    window that came back whole, joined by the digest of the prompt's
    ids; of several entries with one digest (the warm-up sends one of
    the window's prompts) the one submitted nearest the client's send.
    A record with no entry (the ring turned over) is left out."""
    log = newest_log()
    if log is None:
        return []
    from gofr_tpu.serving.observability import (PROMPT_HASH_SALT,
                                                salted_token_hash)
    by_hash: dict = {}
    for entry in log.requests:
        if entry.get("prompt_hash") and entry["first_token_at"] is not None:
            by_hash.setdefault(entry["prompt_hash"], []).append(entry)
    out = []
    for rec in ctx["records"]:
        if not ctx["stats"].answered(rec):
            continue
        entries = by_hash.get(
            salted_token_hash(rec["prompt"], PROMPT_HASH_SALT))
        if entries:
            out.append((rec, min(entries, key=lambda e: abs(
                log.mono(e["submitted_at"]) - rec["sent"]))))
    return out
