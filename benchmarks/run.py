"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process: it owns the chip, builds the app (``App`` + the engine the
configuration file names + ``app.serve_model``), serves it on a free
port from a thread, and the load generator in the same process sends
``POST /chat`` with ``"stream": true`` over a real socket. No
accelerator, or fewer chips than the cell asks for: exit 2, no result.

Set-up (weights from the seed in one jitted call, ``engine.warmup`` for
the cell's own buckets, one request through the socket) is timed as
``setup_s``; then the window; then, with the program's state freed, the
output check against the plain reference; then, with ``--trace 1``, the
reduction of the profiler's trace to the per-layer metrics. The last
line of standard output is the result object and nothing else; every
line before it is a JSON object of facts about the run. The program's
own logging goes to standard error.

``--rehearse DIR`` is the sandbox dry run: the same control flow on the
CPU with the tiny configuration and mixes of DIR, kernels interpreted.
Its last line names ``platform: cpu`` and has no ``correct`` key: it
cannot be read as a result.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
TRACE_SHARE = 0.15      # of the window, traced in its middle
SAMPLE_REQUESTS = 6     # requests the reference replays per run


def say(out, **facts) -> None:
    print(json.dumps(facts), file=out, flush=True)


def take_stdout():
    """Keep standard output for the benchmark's own lines and send
    everything else that writes to fd 1 (the program's logger) to
    standard error, so the last line of standard output is ours."""
    sys.stdout.flush()
    ours = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    return ours


def find(entries, name):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmarks/run.py: {name!r} is not in BENCHMARK.json")


def load_reader(metric: str):
    """A per-layer metric's reader: benchmarks/metrics/<name>.py."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def occupancy(engine) -> tuple[float, int]:
    """Sum and count of the engine's batch-occupancy histogram."""
    snap = engine.metrics.snapshot()["metrics"].get(
        "app_engine_batch_occupancy", {})
    series = snap.get("series", [])
    return (sum(s["sum"] for s in series), sum(s["count"] for s in series))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", metavar="DIR", default=None)
    parser.add_argument("--control", action="store_true",
                        help="also read the int8 control's gap (for "
                             "setting limits; not part of a run)")
    parser.add_argument("--rate", type=float, default=None,
                        help="offer this rate in place of the mix's (the "
                             "one-off sweep that finds the knee)")
    parser.add_argument("--keep-trace", metavar="DIR", default=None,
                        help="copy the .xplane.pb there")
    args = parser.parse_args()
    out = take_stdout()

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find(bench["workloads"], args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [REPO, HERE]
    import gofr_tpu  # noqa: F401 -- without the program: stop, no result

    import jax
    want = "cpu" if args.rehearse else "tpu"
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if devices[0].platform != want or len(devices) < cell["chips"]:
        print(f"benchmarks/run.py: need {cell['chips']} {want} device(s), "
              f"JAX has {device}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    device["count"] = len(devices)

    from gofr_tpu.config.env import enable_compile_cache
    from harness import check, client, rooflines, serve, stats, traffic
    from harness import trace as trace_mod
    cache_dir = enable_compile_cache()   # JAX_COMPILATION_CACHE_DIR, else
    #                                      <checkout>/.jax_cache: fixed
    entries_start = cache_entries(cache_dir)

    if args.rehearse:
        with open(os.path.join(args.rehearse, "config.json")) as f:
            cfg = json.load(f)
        with open(os.path.join(args.rehearse,
                               f"{cell['traffic']}.json")) as f:
            mix = json.load(f)
        peak = None
    else:
        cfg = serve.load_config(cell["config"])
        mix = traffic.load_mix(cell["traffic"])
        peak = rooflines.peaks(device["kind"])
    if args.rate is not None:
        mix = {**mix, "rate_per_s": args.rate}
    reference = serve.load_reference(cfg)
    load = traffic.generate(mix, args.seed, args.seconds, cfg["vocab_size"])

    # ---------------------------------------------------------- set-up
    params = reference.init_weights(cfg, args.seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    engine = serve.build_engine(cfg, params, args.seed, cell["chips"])
    widest = max(engine._usable_buckets)
    lens = [len(r["prompt"]) for r in load["requests"]]
    warm_lens = tuple(sorted({engine._bucket_for(n) for n in lens
                              if n <= widest}))
    engine.warmup(prompt_lens=warm_lens,
                  chunked=any(n > widest for n in lens))
    t_warm = time.perf_counter()

    seen = []               # the engine's own request objects
    submit = engine.submit

    def recording_submit(*a, **kw):
        req = submit(*a, **kw)
        seen.append(req)
        return req

    engine.submit = recording_submit
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    traced = {}

    def during(t0: float) -> None:
        """Trace TRACE_SHARE of the window, in its middle."""
        if not args.trace:
            return
        span = max(0.5, min(6.0, TRACE_SHARE * args.seconds))
        start = t0 + (args.seconds - span) / 2
        time.sleep(max(0.0, start - time.perf_counter()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        traced["t_start"] = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.mark"):
            pass
        time.sleep(span)
        traced["t_end"] = time.perf_counter()
        jax.profiler.stop_trace()

    try:
        with serve.AppThread(engine) as app:
            # the host path once, at the mix's shortest prompt, before
            # the clock: imports, first-call paths, the socket
            first = min(load["requests"], key=lambda r: len(r["prompt"]))
            warm, _ = client.run_window(app.port, {
                "loop": "open", "clients": None,
                "requests": [{**first, "max_tokens": 16, "due_s": 0.0}]},
                seconds=0.0)
            seen.clear()
            before = {"stats": dict(engine.stats),
                      "occupancy": occupancy(engine),
                      "entries": cache_entries(cache_dir)}
            setup_s = time.perf_counter() - T_START
            # ------------------------------------------------ the window
            records, t0 = client.run_window(app.port, load, args.seconds,
                                            during=during)
            after = {"stats": dict(engine.stats),
                     "occupancy": occupancy(engine),
                     "entries": cache_entries(cache_dir)}
            requests = [{"submitted_at": r.submitted_at,
                         "admitted_at": r.admitted_at} for r in seen]
            pool = {"pages": getattr(engine, "_n_pages", None),
                    "peak_pages": engine.watermarks.get("kv_pages")}
            peak_bytes = memory_peak(devices)
    finally:
        engine.submit = submit
    # the program's state leaves the device before the reference runs
    del engine, app, submit, recording_submit, seen
    gc.collect()

    counts = stats.counts(records)
    metrics = stats.end_to_end(records, t0, args.seconds, load["loop"])
    metrics["setup_s"] = setup_s
    compiles = {
        "recompiles": after["stats"]["recompiles"]
        - before["stats"]["recompiles"],
        "cache_entries_gained": after["entries"] - before["entries"]}

    # ------------------------------------------------- the output check
    t_check = time.perf_counter()
    sample = check.pick(records, args.seed, SAMPLE_REQUESTS)
    seq_len = check.padded(mix["prompt_tokens"]["max"]
                           + mix["output_tokens"]["max"], reference.Q_BLOCK)
    gaps = check.served_gaps(reference, cfg, params, sample,
                             seq_len=seq_len,
                             n_read=mix["output_tokens"]["max"],
                             control=args.control)
    limits = cfg["check"]
    wrong = sum(1 for r in records if not r["dropped"] and r["done"]
                and r["error"] is None
                and len(r["tokens"]) != r["max_tokens"])
    compared = {
        "served_gap_mean": {
            "value": gaps["served_sum"] / gaps["tokens"]
            if gaps["tokens"] else None,
            "limit": limits["served_gap_mean"]},
        "answers_missing": {"value": counts["failed"] - wrong, "limit": 0},
        "answers_wrong_length": {"value": wrong, "limit": 0},
        "compiles_in_window": {
            "value": compiles["recompiles"]
            + max(0, compiles["cache_entries_gained"]), "limit": 0},
    }
    correct = check.verdict(compared)
    check_s = time.perf_counter() - t_check

    # ------------------------------------------------ per-layer metrics
    breakdown = None
    if args.trace:
        path = trace_mod.find_xplane(trace_dir)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(
                args.keep_trace, f"{args.workload}.xplane.pb"))
        summary = trace_mod.summarize(
            path, span_s=traced["t_end"] - traced["t_start"],
            chips=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"cfg": cfg, "mix": mix, "seconds": args.seconds,
               "chips": cell["chips"], "peak": peak, "records": records,
               "t0": t0, "loop": load["loop"], "trace": summary,
               "traced": traced, "requests": requests, "pool": pool,
               "before": before, "after": after, "rooflines": rooflines,
               "stats": stats}
        layer = {}
        for m in bench["per_layer"]:
            if "workloads" in m and args.workload not in m["workloads"]:
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                layer[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["top_ops"],
                     "idle_gaps": trace_mod.label_gaps(
                         summary, records, traced)}
        say(out, device=device, trace={k: summary[k] for k in
                        ("devices", "window_s", "busy_s", "programs",
                         "kernels")})
        reported = layer
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        reported = {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items() if v is not None}
    device["memory_peak_bytes"] = peak_bytes

    late = stats.late_ms(records)
    say(out, device=device, workload=args.workload, seed=args.seed,
        seconds=args.seconds, loop=load["loop"],
        requests_sent=sum(1 for r in records if r["sent"] is not None),
        requests_completed=counts["attempted"] - counts["failed"],
        requests_failed=counts["failed"],
        requests_dropped_at_close=sum(1 for r in records if r["dropped"]),
        errors=sorted({r["error"] for r in records
                       if r["error"] and not r["dropped"]})[:5],
        generator_late_p50_ms=stats.percentile(late, 50),
        generator_late_max_ms=max(late, default=None),
        offered_rate_per_s=mix.get("rate_per_s"),
        **stats.medians(records, t0, args.seconds, load["loop"]),
        end_to_end=metrics,
        setup={"weights_s": t_weights - T_START,
               "engine_and_warmup_s": t_warm - t_weights,
               "total_s": setup_s, "warm_request_ok":
               bool(warm and stats.answered(warm[0]))},
        compile_cache={"dir": cache_dir, "entries_at_start": entries_start,
                       "entries_before_window": before["entries"],
                       "entries_after_window": after["entries"]},
        recompiles_in_window=compiles["recompiles"],
        engine={k: after["stats"][k] - before["stats"][k]
                for k in ("prefill_calls", "decode_passes", "preemptions",
                          "prefix_hits", "requeues")},
        pool=pool, memory_peak_bytes=peak_bytes,
        check={"requests": len(sample), "served_tokens": gaps["tokens"],
               "seconds": check_s, "served_gaps": gaps["served"],
               "control_gaps": gaps["control"],
               "served_gap_max": max(gaps["served"], default=None),
               "control_gap_mean": gaps["control_sum"]
               / max(1, gaps["tokens"]) if gaps["control"] else None})

    for name, v in compared.items():
        print(f"compared {name}: value {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    if args.rehearse:
        say(out, rehearsal=True, device=device, attempted=counts["attempted"],
            failed=counts["failed"], would_be_correct=correct,
            metrics=reported)
        return 0
    result = {"correct": correct, **counts, "metrics": reported,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    say(out, **result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
