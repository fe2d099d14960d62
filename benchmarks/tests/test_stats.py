"""Percentile and rate arithmetic on hand-made samples, a stall among
them: a request that never answers lands in ttft_p95_ms."""

from harness import stats


def rec(due, times, n=None, *, error=None, done=True, dropped=False):
    return {"due": due, "sent": due + 0.001, "ended": None,
            "token_times": list(times), "tokens": [1] * len(times),
            "max_tokens": len(times) if n is None else n, "done": done,
            "error": error, "dropped": dropped, "prompt": [1, 2]}


def test_percentile_interpolates():
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([0, 10], 50) == 5.0
    xs = list(range(101))
    assert stats.percentile(xs, 95) == 95.0
    assert stats.percentile(xs, 0) == 0 and stats.percentile(xs, 100) == 100


def test_ttft_is_from_due_and_a_stall_counts_as_the_window():
    good = [rec(float(i), [i + 0.1, i + 0.2, i + 0.3]) for i in range(18)]
    stalled = [rec(18.0, [], n=3, done=False, error="no answer"),
               rec(19.0, [19.4], n=3, done=False, error="stream cut short")]
    ttft = stats.ttft_ms(good + stalled, seconds=20.0)
    assert all(abs(t - 100.0) < 1e-6 for t in sorted(ttft)[:18])
    assert sorted(ttft)[-2:] == [20000.0, 20000.0]
    # 2 of 20 stalled: the 95th percentile sits on the stall
    assert stats.percentile(ttft, 95) == 20000.0
    assert stats.counts(good + stalled) == {"attempted": 20, "failed": 2}


def test_tpot_and_token_rate():
    a = rec(0.0, [1.0, 1.1, 1.2, 1.3, 1.4])          # 100 ms a token
    b = rec(0.0, [2.0, 2.5])                          # 500 ms
    one = rec(0.0, [3.0])                             # a single token: none
    cut = rec(0.0, [4.0, 4.1], n=5, done=False, error="cut")
    tp = sorted(stats.tpot_ms([a, b, one, cut]))
    assert len(tp) == 2 and abs(tp[0] - 100) < 1e-6 and abs(tp[1] - 500) < 1e-6
    # every token received in the window counts, also a failed request's
    assert stats.tokens_in([a, b, one, cut], 0.0, 4.05) == 5 + 2 + 1 + 1
    m = stats.end_to_end([a, b, one, cut], 0.0, 10.0, "closed")
    assert m["tokens_per_s"] == 1.0 and "ttft_p95_ms" not in m


def test_dropped_requests_count_tokens_only():
    kept = rec(0.0, [0.5, 0.6])
    dropped = rec(0.0, [0.7, 0.8, 0.9], n=50, done=False,
                  error="no answer by the time limit", dropped=True)
    assert stats.counts([kept, dropped]) == {"attempted": 1, "failed": 0}
    assert stats.tokens_in([kept, dropped], 0.0, 1.0) == 5
    assert len(stats.tpot_ms([kept, dropped])) == 1


def test_generator_lateness():
    assert abs(stats.late_ms([rec(1.0, [2.0])])[0] - 1.0) < 1e-6


def test_medians_by_quarter_show_a_backlog():
    recs = [rec(float(i), [i + 0.1 * (1 + i // 5), i + 1.0]) for i in range(20)]
    for r in recs:
        r["ended"] = r["token_times"][-1]
    m = stats.medians(recs, 0.0, 20.0, "open")
    assert [round(x) for x in m["ttft_p50_by_quarter_ms"]] == \
        [100, 200, 300, 400]
    assert abs(m["last_answer_after_close_s"] - 0.0) < 1e-9
    assert "ttft_p50_ms" not in stats.medians(recs, 0.0, 20.0, "closed")
