"""Percentile and rate arithmetic over the client's records.

A record is what the load generator keeps for one request: ``due``,
``sent``, ``token_times`` (one host-clock reading per streamed token),
``tokens``, ``max_tokens``, ``done`` (the stream ended with its
terminator) and ``error``. All times are ``time.perf_counter()``
seconds; ``t0`` is the window's start.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; None of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def answered(rec: dict) -> bool:
    """The request came back whole: terminator seen, no error, exactly
    the number of tokens asked for (``eos_id`` is -1 in every cell)."""
    return (rec["done"] and rec["error"] is None
            and len(rec["tokens"]) == rec["max_tokens"])


def ttft_ms(records, seconds: float) -> list[float]:
    """First streamed token minus the time the request was DUE, for
    every request due in the window; one that failed, was refused or
    never answered counts as the window's whole length."""
    out = []
    for r in records:
        if answered(r):
            out.append((r["token_times"][0] - r["due"]) * 1e3)
        else:
            out.append(seconds * 1e3)
    return out


def tpot_ms(records) -> list[float]:
    """(last token - first token) / (n - 1) of every request that came
    back whole with two tokens or more."""
    return [(r["token_times"][-1] - r["token_times"][0]) * 1e3
            / (len(r["token_times"]) - 1)
            for r in records if answered(r) and len(r["token_times"]) > 1]


def tokens_in(records, start: float, end: float) -> int:
    """Output tokens received in [start, end)."""
    return sum(1 for r in records for t in r["token_times"]
               if start <= t < end)


def late_ms(records) -> list[float]:
    """How late the generator sent each request: sent - due."""
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if r["sent"] is not None and r["due"] is not None]


def end_to_end(records, t0: float, seconds: float, loop: str) -> dict:
    """The end-to-end metrics of one window (setup_s is the caller's)."""
    out = {"tokens_per_s": tokens_in(records, t0, t0 + seconds) / seconds,
           "tpot_p95_ms": percentile(tpot_ms(records), 95)}
    if loop == "open":
        out["ttft_p95_ms"] = percentile(ttft_ms(records, seconds), 95)
    return out


def medians(records, t0: float, seconds: float, loop: str) -> dict:
    """What the facts line says beside the metrics: medians, the median
    time to first token by quarter of the window (a backlog that grows
    shows as a climb: how the knee was found), and how long after the
    close the last answer ended."""
    out = {"tpot_p50_ms": percentile(tpot_ms(records), 50),
           "last_answer_after_close_s": max(
               (r["ended"] or 0.0 for r in records), default=t0 + seconds)
           - t0 - seconds}
    if loop == "open":
        quarter = seconds / 4
        out["ttft_p50_ms"] = percentile(ttft_ms(records, seconds), 50)
        out["ttft_p50_by_quarter_ms"] = [
            percentile(ttft_ms([r for r in records if q * quarter
                                <= r["due"] - t0 < (q + 1) * quarter],
                               seconds), 50) for q in range(4)]
    return out


def counts(records) -> dict:
    """Attempted and failed; a closed loop's requests in flight at the
    close were dropped by the generator and are neither."""
    kept = [r for r in records if not r["dropped"]]
    ok = sum(1 for r in kept if answered(r))
    return {"attempted": len(kept), "failed": len(kept) - ok}
