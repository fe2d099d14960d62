"""The traffic generator is a pure function of (mix, seed, seconds), and
every seed gets the same schedule of sizes and gaps with token ids of
its own."""

import pytest

from harness import traffic

MIXES = ["chat", "long-prompt", "batch"]


@pytest.mark.parametrize("name", MIXES)
def test_same_arguments_same_traffic(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 2 ** 31 + 11, 20, 32768)
    b = traffic.generate(mix, 2 ** 31 + 11, 20, 32768)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_seeds_change_the_ids_not_the_schedule(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 1, 20, 32768)["requests"]
    b = traffic.generate(mix, 2, 20, 32768)["requests"]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_tokens"],
                lambda r: r["due_s"]):
        assert list(map(key, a)) == list(map(key, b))
    # another order_seed is another order of the same multiset
    c = traffic.generate({**mix, "order_seed": 9}, 1, 20, 32768)["requests"]
    assert [len(r["prompt"]) for r in c] != [len(r["prompt"]) for r in a]
    n = len(a) if mix["loop"] == "open" else mix["clients"]
    assert sorted(len(r["prompt"]) for r in c[:n]) == \
        sorted(len(r["prompt"]) for r in a[:n])
    if mix["loop"] == "open":
        assert all(0 <= r["due_s"] < 20 for r in a)
        assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    else:   # every wave of `clients` requests is the same multiset
        assert sorted(r["max_tokens"] for r in a[:n]) == \
            sorted(r["max_tokens"] for r in a[n:2 * n])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_to_the_mix(name):
    mix = traffic.load_mix(name)
    reqs = traffic.generate(mix, 3, 40, 1000)["requests"]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in reqs)
    assert all(o["min"] <= r["max_tokens"] <= o["max"] for r in reqs)
    assert all(0 <= t < 1000 for r in reqs for t in r["prompt"])
    lens = sorted(len(r["prompt"]) for r in reqs)
    assert abs(lens[len(lens) // 2] - p["median"]) <= 0.1 * p["median"]


def test_open_loop_rate_and_seconds():
    mix = traffic.load_mix("chat")
    n20 = len(traffic.generate(mix, 5, 20, 100)["requests"])
    n40 = len(traffic.generate(mix, 5, 40, 100)["requests"])
    assert abs(n20 - mix["rate_per_s"] * 20) <= 1
    assert abs(n40 - mix["rate_per_s"] * 40) <= 1
