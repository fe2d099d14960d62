"""What the ``xing4_0`` family needs — a four-stream mHC residual around
latent (MLA) attention with compressed queries and sparse experts — in
operations and bytes from the configuration's published keys. It builds
on ``rooflines_mla_moe.py`` (the ``deepseek_v3`` family's counts, left
as they are): attention pairs, the cached row, the experts and the head
are that file's; what differs is counted here.

- Queries pass through a ``q_lora_rank``-wide bottleneck: ``W_qa``
  [D, Q] and ``W_qb`` [Q, H x 192] in place of one ``W_q`` [D, H x 192].
- Every sublayer (attention and FFN of every layer) computes its three
  mappings from the streams: the projection ``phi`` [n x D, n + n +
  n x n] is a matmul a token passes through, and counted as parameters.
- The three mixes are multiply-adds on the streams, counted as
  operations a token and sublayer: the read-in ``H_pre X`` (2 n D), the
  stream mix ``H_res X`` (2 n n D) and the write-out ``H_post^T F``
  (2 n D). The Sinkhorn rounds (20 x 2 x n x n divisions a sublayer)
  and the norm of vec(X) are left out: three orders below the mixes.

Counted as the model requires them, whichever kernel or program does
the work, ACTIVE parameters only. The cached row is the family's: 576
numbers a layer and token, whatever the streams.
"""

from __future__ import annotations

from harness import rooflines_mla_moe as base

prefill_pair_flops = base.prefill_pair_flops
decode_pair_flops = base.decode_pair_flops
head_params = base.head_params


def attn_params(cfg: dict) -> int:
    """One layer's attention matrices: W_qa, W_qb, W_kva, W_kvb, W_o."""
    s = base.sizes(cfg)
    qk = s["nope"] + s["rope"]
    return (base.attn_params(cfg) - s["D"] * s["H"] * qk
            + cfg["q_lora_rank"] * (s["D"] + s["H"] * qk))


def mhc_params(cfg: dict) -> int:
    """One SUBLAYER's mapping projection: phi [n x D, n + n + n x n]."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def mhc_mix_flops(cfg: dict) -> int:
    """One sublayer's three mixes for one token: read-in, stream mix,
    write-out, as multiply-adds on D-wide streams."""
    n = cfg["hc_mult"]
    return 2 * cfg["hidden_size"] * (n + n * n + n)


def active_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied by over all layers, the head
    left out: the family's count with the compressed queries in place
    of W_q, and two mapping projections a layer."""
    s = base.sizes(cfg)
    return (base.active_matmul_params(cfg)
            + s["L"] * (attn_params(cfg) - base.attn_params(cfg)
                        + 2 * mhc_params(cfg)))


def token_flops(cfg: dict) -> int:
    """Everything one token needs but its attention pairs and the
    head: the active matmuls and the mixes of every sublayer."""
    return (2 * active_matmul_params(cfg)
            + 2 * cfg["num_hidden_layers"] * mhc_mix_flops(cfg))


def decode_flops(cfg: dict, contexts) -> float:
    """One decode step for each token, at the context (rows attended,
    itself included) it was produced at; the head once a token."""
    dense = token_flops(cfg) + 2 * head_params(cfg)
    pair = decode_pair_flops(cfg)
    return float(sum(dense + pair * c for c in contexts))


def prefill_flops(cfg: dict, prompt_lens) -> float:
    """Prefill of whole prompts: every layer on every token, the head
    once per prompt, causal attention over n(n+1)/2 materialised
    pairs."""
    layer, head = token_flops(cfg), 2 * head_params(cfg)
    pair = prefill_pair_flops(cfg)
    return float(sum(layer * n + head + pair * n * (n + 1) / 2
                     for n in prompt_lens))
