"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished —
drawn from the seed, the longest among them — is run through the
configuration's plain reference: one forward pass over each prompt with
the tokens that were served after it. For every served token the
reference gives the logit of that token and of its own best token at
the same position; the gap between them is how far the served token
lies below the reference's best. A greedy token from a sound bf16
engine lies within rounding of the best — most often it IS the best and
the gap is nought; a token altered where it is produced, or produced
from a wrong cache row, a wrong mask or a lower precision, lies below
it by the order of the logits' own spread. The number compared is the
MEAN gap over the sample's served tokens; the widest gap is reported
beside it (PERF.md section 2 has the readings of both and why the
mean, not the widest, is held to a limit).

``control=True`` reads the control beside it: at the same positions of
the same prompts and tokens, the gap of the token that the reference
computed in int8 puts first.
"""

from __future__ import annotations

import numpy as np


def pick(records, seed: int, n: int) -> list:
    """``n`` of the requests that came back whole, drawn from the seed,
    the longest (prompt + answer) always among them."""
    from .stats import answered
    whole = [r for r in records if answered(r) and not r["dropped"]]
    if not whole:
        return []
    longest = max(range(len(whole)), key=lambda i: (
        len(whole[i]["prompt"]) + len(whole[i]["tokens"])))
    rng = np.random.default_rng([int(seed), 0x636865636B])
    rest = [i for i in rng.permutation(len(whole)) if i != longest]
    return [whole[i] for i in [longest, *rest[:max(0, n - 1)]]]


def padded(n: int, block: int) -> int:
    return -(-n // block) * block


def served_gaps(reference, cfg: dict, params, sample: list, *,
                seq_len: int, n_read: int, control: bool = False) -> dict:
    """Per sampled request the widest gap of its served tokens, and with
    ``control`` the widest gap of the int8 reference's own tokens.
    Every request is padded to ``seq_len`` tokens and ``n_read`` read
    positions, so one compiled program serves the whole cell."""
    out = {"served": [], "control": [], "tokens": 0,
           "served_sum": 0.0, "control_sum": 0.0}
    for rec in sample:
        prompt, served = rec["prompt"], rec["tokens"]
        n = len(served)
        seq = np.zeros(seq_len, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + n] = served
        # served token i was produced from position len(prompt) - 1 + i
        read = np.full(n_read, len(prompt) - 1, np.int32)
        read[:n] = len(prompt) - 1 + np.arange(n)
        logits = np.asarray(reference.forward_logits(
            cfg, params, seq, read, precision="float32"))[:n]
        best = logits.max(-1)
        rows = np.arange(n)
        gap = best - logits[rows, served]
        out["served"].append(float(gap.max()))
        out["served_sum"] += float(gap.sum())
        out["tokens"] += n
        if control:
            low = np.asarray(reference.forward_logits(
                cfg, params, seq, read, precision="int8"))[:n]
            gap = best - logits[rows, low.argmax(-1)]
            out["control"].append(float(gap.max()))
            out["control_sum"] += float(gap.sum())
    return out


def verdict(compared: dict) -> bool:
    """Every number within its limit; a missing reading fails."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in compared.values())
