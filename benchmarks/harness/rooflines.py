"""What the algorithm needs: operations and bytes from shapes, the
table of peaks, and the least time the chip could take.

Counted as the model requires them, whichever kernel or program does
the work: a multiply-add is two operations; causal attention over a
prompt of n tokens scores n(n+1)/2 pairs; decode attention for a token
at context c reads c rows of K and of V once. Padding, recomputation
and the pool copies the compiled programs make are NOT counted — they
are what a share under 100% is made of.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(HERE, "data", "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks known for device kind {device_kind!r} "
                       f"(benchmarks/data/peaks.json)")
    return table[device_kind]


def sizes(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"V": cfg["vocab_size"], "D": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"], "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": hd,
            "F": cfg["intermediate_size"], "bytes": 2}  # bf16 served


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's seven matrices."""
    s = sizes(cfg)
    q, kv = s["Hq"] * s["hd"], s["Hkv"] * s["hd"]
    return s["D"] * (2 * q + 2 * kv) + 3 * s["D"] * s["F"]


def head_params(cfg: dict) -> int:
    s = sizes(cfg)
    return s["D"] * s["V"]


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one token over all layers."""
    s = sizes(cfg)
    return s["L"] * 2 * s["Hkv"] * s["hd"] * s["bytes"]


def attn_pair_flops(cfg: dict) -> int:
    """Operations for one (query token, key token) pair over all layers
    and heads: q.k and p.v, a multiply-add each per head-dim lane."""
    s = sizes(cfg)
    return 4 * s["L"] * s["Hq"] * s["hd"]


def decode_flops(cfg: dict, contexts) -> float:
    """One decode step for each token, at the context (rows attended,
    itself included) it was produced at."""
    dense = 2 * (sizes(cfg)["L"] * layer_matmul_params(cfg)
                 + head_params(cfg))
    pair = attn_pair_flops(cfg)
    return float(sum(dense + pair * c for c in contexts))


def prefill_flops(cfg: dict, prompt_lens) -> float:
    """Prefill of whole prompts: every layer on every token, the head
    once per prompt, causal attention over n(n+1)/2 pairs."""
    s = sizes(cfg)
    layer = 2 * s["L"] * layer_matmul_params(cfg)
    head = 2 * head_params(cfg)
    pair = attn_pair_flops(cfg)
    return float(sum(layer * n + head + pair * n * (n + 1) / 2
                     for n in prompt_lens))


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least seconds the chip could take and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")


def decode_attn_need(cfg: dict, contexts) -> tuple[float, float]:
    """(operations, bytes) decode attention needs for tokens produced at
    these contexts: the K/V rows read once, q.k and p.v."""
    total = float(sum(contexts))
    return attn_pair_flops(cfg) * total, kv_row_bytes(cfg) * total


def prefill_attn_need(cfg: dict, spans) -> tuple[float, float]:
    """(operations, bytes) causal attention needs for token ranges
    (start, end) of prompts: n(n+1)/2 pairs; q and the output once per
    token, K and V rows once per token they belong to and once more per
    later range that attends them."""
    s = sizes(cfg)
    pair = attn_pair_flops(cfg)
    q_row = s["L"] * 2 * s["Hq"] * s["hd"] * s["bytes"]       # q in, o out
    flops = nbytes = 0.0
    for a, b in spans:
        flops += pair * (b * (b + 1) - a * (a + 1)) / 2
        nbytes += q_row * (b - a) + kv_row_bytes(cfg) * b
    return flops, nbytes
