"""From the profiler's ``.xplane.pb`` to numbers: device busy time,
time per step program, time per kernel inside each kind of program, the
operations that took most time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. Which plane is
a device, which lines hold programs and operations, and how today's
step programs and kernels are named in the trace is data
(``benchmarks/data/trace_names.json``): the program gives its kernels
and step functions no stable names yet, so the mapping is kept where a
later PR can add to it without touching this file.
"""

from __future__ import annotations

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_names() -> dict:
    with open(os.path.join(HERE, "data", "trace_names.json")) as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


MARK = "bench.mark"   # the host annotation run.py drops at the start


def read_events(path: str, names: dict) -> tuple[dict, float | None]:
    """Per device plane, the (name, start_ns, duration_ns) events of the
    program line and of the operation line; and the start of the
    benchmark's own mark on a host line, which ties the trace's clock
    to the load generator's."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = {}
    mark = None
    for plane in data.planes:
        if not re.match(names["device_plane"], plane.name):
            if mark is None and plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == MARK:
                            mark = float(e.start_ns)
                            break
            continue
        lines = {line.name: line for line in plane.lines}
        dev = {}
        for key in ("program_line", "op_line"):
            line = lines.get(names[key])
            dev[key] = [] if line is None else sorted(
                ((e.name, float(e.start_ns), float(e.duration_ns))
                 for e in line.events), key=lambda e: e[1])
        devices[plane.name] = dev
    return devices, mark


def union_ns(intervals) -> tuple[float, list]:
    """Total covered length and the merged intervals of (start, end)."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line; keep the
    instruction's name and its opcode: ``%closed_call.14 custom-call``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    op = re.search(r" ([a-z][a-z0-9\-]*)\(", " " + rest)
    return f"{head} {op.group(1)}" if op else head


def self_times(ops) -> dict:
    """Seconds each operation ran ITSELF: the operation line nests (a
    while holds its body's operations), so a container's time is its
    duration less its children's. ``ops`` are (name, start, end),
    sorted by start; returns short name -> nanoseconds."""
    out: dict = {}
    stack: list = []   # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(0.0, self_ns)

    for name, start, end in ops:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([short_name(name), end, end - start])
    close(float("inf"))
    return out


def classify(name: str, table: dict) -> str | None:
    """The first class of ``table`` (class -> list of regexes) that
    matches ``name``."""
    for cls, patterns in table.items():
        if any(re.search(p, name) for p in patterns):
            return cls
    return None


def reduce_device(dev: dict, names: dict, start_ns: float | None = None,
                  end_ns: float | None = None) -> dict:
    """One device's events within [start_ns, end_ns] (default: from the
    first event's start to the last one's end)."""
    programs, ops = dev["program_line"], dev["op_line"]
    every = programs + ops
    if not every:
        return {"window_ns": 0.0, "busy_ns": 0.0, "programs": {},
                "kernels": {}, "ops": {}, "gaps": []}
    lo = min(e[1] for e in every) if start_ns is None else start_ns
    hi = max(e[1] + e[2] for e in every) if end_ns is None else end_ns

    def clip(e):
        a, b = max(e[1], lo), min(e[1] + e[2], hi)
        return (a, b) if b > a else None

    busy_src = ops if ops else programs
    busy, merged = union_ns(c for c in map(clip, busy_src) if c)
    gaps, cursor = [], lo
    for a, b in merged:
        if a > cursor:
            gaps.append((cursor, a - cursor))
        cursor = b
    if hi > cursor:
        gaps.append((cursor, hi - cursor))

    out_programs: dict = {}
    spans = []   # (start, end, class) of program executions
    for name, start, dur in programs:
        c = clip((name, start, dur))
        if c is None:
            continue
        cls = classify(name, names["programs"]) or "other"
        rec = out_programs.setdefault(cls, {"count": 0, "ns": 0.0,
                                            "names": {}})
        rec["count"] += 1
        rec["ns"] += c[1] - c[0]
        rec["names"][name] = rec["names"].get(name, 0) + 1
        spans.append((start, start + dur, cls))

    # an operation belongs to the program execution it starts inside
    kernels: dict = {}
    clipped = [(name, *c) for name, start, dur in ops
               if (c := clip((name, start, dur)))]
    op_ns = self_times(clipped)
    i = 0
    for name, start, end in clipped:
        kernel = classify(name, names["kernels"])
        if kernel is None:
            continue
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        inside = spans[i][2] if i < len(spans) and spans[i][0] <= start \
            else "other"
        rec = kernels.setdefault(kernel, {})
        rec[inside] = rec.get(inside, 0.0) + (end - start)
    return {"window_ns": hi - lo, "busy_ns": busy, "programs": out_programs,
            "kernels": kernels, "ops": op_ns,
            "gaps": sorted(gaps, key=lambda g: -g[1])[:10]}


def summarize(path: str, span_s: float | None = None,
              chips: int = 1) -> dict:
    """The trace as the metric readers take it, seconds throughout,
    averaged over the ``chips`` devices used (summed where it says).
    With the benchmark's mark in the trace and ``span_s`` given, the
    window is the ``span_s`` seconds after the mark; else it runs from
    the first device event to the last."""
    names = load_names()
    devices, mark = read_events(path, names)
    start_ns = end_ns = None
    if mark is not None and span_s is not None:
        start_ns, end_ns = mark, mark + span_s * 1e9
        inside = any(start_ns <= e[1] < end_ns for dev in devices.values()
                     for e in dev["program_line"] + dev["op_line"])
        if not inside:   # the device's clock is not the host's: fall back
            start_ns = end_ns = None
    used = [reduce_device(dev, names, start_ns, end_ns)
            for _, dev in sorted(devices.items())][:chips]
    used = [d for d in used if d["window_ns"] > 0]
    if not used:
        return {"devices": 0, "window_s": 0.0, "busy_s": 0.0, "mark_ns": mark,
                "programs": {}, "kernels": {}, "top_ops": [], "gaps": []}
    n = len(used)
    programs: dict = {}
    kernels: dict = {}
    ops: dict = {}
    for d in used:
        for cls, rec in d["programs"].items():
            p = programs.setdefault(cls, {"count": 0, "device_s": 0.0,
                                          "names": {}})
            p["count"] += rec["count"]
            p["device_s"] += rec["ns"] / 1e9
            for k, v in rec["names"].items():
                p["names"][k] = p["names"].get(k, 0) + v
        for kernel, by in d["kernels"].items():
            for cls, ns in by.items():
                k = kernels.setdefault(kernel, {})
                k[cls] = k.get(cls, 0.0) + ns / 1e9
        for name, ns in d["ops"].items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"devices": n, "mark_ns": mark if start_ns is not None else None,
            "window_s": sum(d["window_ns"] for d in used) / n / 1e9,
            "busy_s": sum(d["busy_ns"] for d in used) / n / 1e9,
            # summed over devices: a program's or a kernel's chip-seconds
            "programs": programs, "kernels": kernels,
            "top_ops": [[k, v] for k, v in top],
            # the first device's longest gaps, (start_ns, seconds)
            "gaps": [[g[0], g[1] / 1e9] for g in used[0]["gaps"]]}


def label_gaps(summary: dict, records: list, traced: dict) -> list:
    """The longest idle gaps, each named by what the benchmark's own
    threads can say of the host at the gap's start: how many requests
    were in flight, or that the generator had none out. (The program
    has no host spans yet; finer attribution is the tracing issue.)"""
    out = []
    for start_ns, seconds in summary["gaps"]:
        label = "host_unattributed"
        if summary.get("mark_ns") is not None and "t_start" in traced:
            t = traced["t_start"] + (start_ns - summary["mark_ns"]) / 1e9
            flying = sum(1 for r in records if r["sent"] is not None
                         and r["sent"] <= t
                         and (r["ended"] is None or t < r["ended"]))
            label = (f"requests_in_flight_{flying}" if flying
                     else "generator_idle_no_request_out")
        out.append([label, seconds])
    return out
