"""Env-gated xprof capture for TPU job entrypoints.

``GOFR_JOB_PROFILE=1`` wraps a job's measured region in
``jax.profiler.start_trace/stop_trace``, landing an xprof trace under
``GOFR_JOB_PROFILE_DIR`` (default ``/tmp/gofr_tpu_profiles``) — the
same capture the serving app exposes at ``POST /debug/profile/start``
(gofr_tpu/serving/observability.py), so a chip run gets
profiler traces for free alongside the jobs' JSON lines.

Usage in a job (after the sys.path/jax setup)::

    from profiling import profile_start, profile_stop
    trace_dir = profile_start("decode_microprof")
    ...  # measured region
    profile_stop(trace_dir)
    out["xprof_trace"] = trace_dir  # None when disabled
"""

import os
import sys
import time


def profile_start(job: str) -> str | None:
    """Start an xprof capture when GOFR_JOB_PROFILE=1; returns the
    trace directory, or None when profiling is off or failed (a broken
    profiler must never take the measurement down with it)."""
    if os.environ.get("GOFR_JOB_PROFILE") != "1":
        return None
    try:
        import jax
        base = os.environ.get("GOFR_JOB_PROFILE_DIR",
                              "/tmp/gofr_tpu_profiles")
        trace_dir = os.path.join(
            base, f"{job}-{time.strftime('%Y%m%d-%H%M%S')}")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        print(f"# xprof capture -> {trace_dir}", file=sys.stderr)
        return trace_dir
    except Exception as exc:
        print(f"# xprof start failed: {exc!r}", file=sys.stderr)
        return None


def profile_stop(trace_dir: str | None) -> None:
    if trace_dir is None:
        return
    try:
        import jax
        jax.profiler.stop_trace()
        print(f"# xprof trace written: {trace_dir}", file=sys.stderr)
    except Exception as exc:
        print(f"# xprof stop failed: {exc!r}", file=sys.stderr)
