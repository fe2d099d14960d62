"""The readers of the program's span log on a hand-made log: known
spans, passes and gaps give a known number; a missing mark or an empty
log gives None. The log is a real ``FlightRecorder``'s, filled by hand,
so the readers are held to the shapes the program writes."""

import importlib.util
import os

import pytest

from conftest import BENCH
from harness import rooflines, spans, stats

from gofr_tpu.serving import observability as obs

CFG = {"vocab_size": 100, "hidden_size": 64, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 128}
PEAK = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
MARK_NS = 5e9          # the mark's place on the trace's clock
T0, T1 = 100.0, 110.0  # the traced span on perf_counter


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(BENCH, "metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def record(prompt, sent, first):
    return {"prompt": prompt, "sent": sent, "token_times": [first, first + 1],
            "tokens": [1, 2], "max_tokens": 2, "done": True, "error": None,
            "dropped": False}


def entry(log, prompt, submitted, admitted, first, rid):
    """A request entry as ``request_summary`` writes it, wall clock."""
    wall, mono = log.anchor
    return {"rid": rid, "prompt_hash": obs.salted_token_hash(
                prompt, obs.PROMPT_HASH_SALT),
            "submitted_at": wall + (submitted - mono),
            "admitted_at": wall + (admitted - mono),
            "first_token_at": wall + (first - mono)}


@pytest.fixture
def log():
    """Two loop iterations inside the traced span, one before it.

    100.0-101.0 admit{ prefill_dispatch 100.2-100.5 }   host 1.0
    101.0-101.4 decode_dispatch                          host 0.4
    101.4-104.0 decode_wait                              waiting
    104.0-105.0 emit{ finalize 104.5-104.7 }             host 1.0
    105.0-105.2 planes                                   host 0.2
    105.2-105.3 gauges                                   host 0.1
    105.3-109.3 wait                                     waiting
    """
    rec = obs.FlightRecorder(size=64, request_logs=8)
    log = rec.log
    log.spans.extend([
        ("engine.decode_wait", 95.0, 99.0, 1),      # before the span
        ("engine.prefill_dispatch", 100.2, 100.5, 2),
        ("engine.admit", 100.0, 101.0, None),
        ("engine.decode_dispatch", 101.0, 101.4, 3),
        ("engine.decode_wait", 101.4, 104.0, 3),
        ("engine.finalize", 104.5, 104.7, 3),
        ("engine.emit", 104.0, 105.0, 3),
        ("engine.planes", 105.0, 105.2, 3),
        ("engine.gauges", 105.2, 105.3, None),
        ("engine.wait", 105.3, 109.3, None)])
    log.passes.extend([
        {"pass_id": 1, "kind": "decode", "t0": 94.0, "t1": 99.0},
        {"pass_id": 2, "kind": "prefill", "t0": 100.2, "t1": 101.2,
         "rids": [7, 8], "lens": [100, 50], "bucket": 128, "group": 2},
        {"pass_id": 3, "kind": "decode", "t0": 101.3, "t1": 104.0,
         "rids": [7, 8], "ctx": [108, 58], "steps": 8},
        {"pass_id": 4, "kind": "prefill_chunk", "t0": 106.0, "t1": None,
         "rids": [9], "offsets": [0], "lens": [64], "width": 64},
        {"pass_id": 5, "kind": "prefill_chunk", "t0": 107.0, "t1": 107.5,
         "rids": [9], "offsets": [64], "lens": [36], "width": 64},
        {"pass_id": 6, "kind": "prefill_chunk", "t0": 111.0, "t1": None,
         "rids": [10], "offsets": [0], "lens": [64], "width": 64}])
    log.requests.extend([
        # the warm-up sent the first prompt too, long before
        entry(log, [1, 2, 3], 50.0, 50.1, 50.2, 1),
        entry(log, [1, 2, 3], 100.05, 100.15, 101.25, 7),
        entry(log, [4, 5], 100.06, 100.16, 101.26, 8)])
    return log


def context(**over):
    ctx = {"cfg": CFG, "peak": PEAK, "rooflines": rooflines, "stats": stats,
           "traced": {"t_start": T0, "t_end": T1},
           "trace": {"mark_ns": MARK_NS,
                     # 100.4-101.4: host work throughout; 103.0-104.0:
                     # waiting for the device; 109.5-110.0: no span
                     "gaps": [[MARK_NS + 0.4e9, 1.0], [MARK_NS + 3.0e9, 1.0],
                              [MARK_NS + 9.5e9, 0.5]],
                     "programs": {"prefill": {"count": 3, "device_s": 2.0}}},
           "records": [record([1, 2, 3], 100.0, 101.30),
                       record([4, 5], 100.0, 101.36),
                       {**record([6], 100.0, 101.0), "error": "cut"}]}
    ctx.update(over)
    return ctx


def test_self_segments_give_each_span_its_own_time(log):
    segments = spans.self_segments(log.spans)
    by_name: dict = {}
    for a, b, name in segments:
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    assert by_name["engine.admit"] == pytest.approx(0.7)
    assert by_name["engine.prefill_dispatch"] == pytest.approx(0.3)
    assert by_name["engine.emit"] == pytest.approx(0.8)
    assert by_name["engine.finalize"] == pytest.approx(0.2)
    # pieces never overlap: their lengths add up to the union
    total = sum(b - a for a, b, _ in segments)
    assert total == pytest.approx(4.0 + 9.3)
    assert spans.seconds_in(segments, T0, T1) == pytest.approx(9.3)


def test_host_and_planes_time_per_decode_pass(log):
    # one decode pass collected in the span; host work 1.0 + 0.4 + 1.0
    # + 0.2 + 0.1 seconds of it, the planes' part 0.2 + 0.2 + 0.1
    assert reader("host_ms_per_decode_pass")(context()) \
        == pytest.approx(2700.0)
    assert reader("planes_ms_per_decode_pass")(context()) \
        == pytest.approx(500.0)


def test_idle_gaps_split_into_host_work_and_waiting(log):
    # of 2.5 idle seconds, 1.0 under host-work spans (the first gap)
    assert reader("idle_gap_host_work_pct")(context()) \
        == pytest.approx(100.0 * 1.0 / 2.5)


def test_requests_join_by_prompt_hash_nearest_in_time(log):
    pairs = spans.joined(context())
    assert [e["rid"] for _, e in pairs] == [7, 8]   # not the warm-up's
    # client 1.30 / 1.36 s against the engine's 1.20 / 1.20 s
    # (a wall-clock reading resolves a quarter of a microsecond)
    assert reader("http_overhead_p50_ms")(context()) \
        == pytest.approx(130.0, abs=0.01)
    assert reader("admit_to_token_p50_ms")(context()) \
        == pytest.approx(1100.0, abs=0.01)


def test_prefill_work_is_what_the_passes_carried(log):
    f = rooflines.prefill_flops
    # the bucket pass's rows at their real lengths, the walk's two
    # chunks enqueued in the span (a whole prompt of 100 between them);
    # not the chunk enqueued after it
    want = f(CFG, [100, 50]) + f(CFG, [100])
    got = reader("prefill_pass_mfu")(context())
    assert got == pytest.approx(100.0 * want / (2.0 * 1e9))


@pytest.mark.parametrize("name", [
    "http_overhead_p50_ms", "admit_to_token_p50_ms",
    "host_ms_per_decode_pass", "planes_ms_per_decode_pass",
    "idle_gap_host_work_pct", "prefill_pass_mfu"])
def test_nothing_to_read_reads_none(name):
    obs.FlightRecorder(size=64)     # the newest log: empty
    assert reader(name)(context()) is None


@pytest.mark.parametrize("name", [
    "host_ms_per_decode_pass", "planes_ms_per_decode_pass",
    "idle_gap_host_work_pct", "prefill_pass_mfu"])
def test_no_mark_reads_none(log, name):
    trace = {**context()["trace"], "mark_ns": None}
    assert reader(name)(context(trace=trace)) is None


def test_a_program_without_the_log_reads_none(log, monkeypatch):
    monkeypatch.delattr(obs, "flight_logs")     # the parent's module
    assert spans.newest_log() is None and spans.joined(context()) == []
    assert reader("host_ms_per_decode_pass")(context()) is None
