"""One general traffic generator: a mix file's parameters, a seed and a
window length in, a list of requests out. A pure function of the three.

Every seed gets the SAME schedule: the quantile grid of the mix's
length and gap distributions, in one order that the mix file fixes
(``order_seed``). The seed draws the token ids (and, in run.py, the
weights and the output check's sample). Measured on the chip (PR 24):
with the same multiset in an order drawn from the seed, 40 requests a
window left the 95th percentile of time to first token 36% apart
between seeds and the tokens received 20% apart — which request meets
which, and which answers the close cuts off, is most of a window this
short. The work and its order are the mix's; what a run measures is the
system.
"""

from __future__ import annotations

import json
import math
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: closed loops draw their next request from a list this long, which no
#: window of the permitted lengths can exhaust
CLOSED_LIST = 4096
_NORMAL = statistics.NormalDist()


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _length_grid(spec: dict, n: int) -> np.ndarray:
    """The n-point quantile grid of a clipped log-normal."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    qs = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(q) for q in qs])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _gap_grid(n: int, rate: float) -> np.ndarray:
    """The n-point quantile grid of exponential gaps of mean 1/rate,
    scaled so that they sum to n/rate exactly."""
    qs = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-qs)
    return gaps * (n / gaps.sum()) / rate


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """The requests of one window.

    Returns ``{"loop", "clients", "requests"}``; a request is
    ``{"due_s", "prompt", "max_tokens"}`` with ``prompt`` a list of
    token ids uniform over the vocabulary (no two prompts share a
    prefix beyond chance) and ``due_s`` the send time from the start
    of the window (open loop; ``None`` in a closed loop, where a client
    sends its next request when the last one has answered).
    """
    order = np.random.default_rng([int(mix.get("order_seed", 0)), 0x6F6672])
    ids_rng = np.random.default_rng([int(seed), 0x696473])
    if mix["loop"] == "open":
        if mix["arrivals"] != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        n = max(1, math.floor(mix["rate_per_s"] * seconds))
        gaps = order.permutation(_gap_grid(n, mix["rate_per_s"]))
        # the first request is due half a gap in; the last before the end
        due = np.cumsum(gaps) - gaps[0] / 2
        due = due[due < seconds]
        n = len(due)
        prompts = order.permutation(_length_grid(mix["prompt_tokens"], n))
        outputs = order.permutation(_length_grid(mix["output_tokens"], n))
    elif mix["loop"] == "closed":
        # one grid point per client, block after block: each wave of
        # requests holds the same multiset of sizes
        due, c = None, int(mix["clients"])
        n = -(-CLOSED_LIST // c) * c
        prompts, outputs = (np.concatenate(
            [order.permutation(_length_grid(mix[key], c))
             for _ in range(n // c)])
            for key in ("prompt_tokens", "output_tokens"))
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    requests = []
    for i in range(n):
        ids = ids_rng.integers(0, vocab, int(prompts[i]))
        requests.append({"due_s": None if due is None else float(due[i]),
                         "prompt": ids.tolist(),
                         "max_tokens": int(outputs[i])})
    return {"loop": mix["loop"], "clients": mix.get("clients"),
            "requests": requests}
