"""What the ``deepseek_v3`` family needs — latent (MLA) attention and
sparse experts — in operations and bytes from the configuration's
published keys. The family's twin of ``rooflines.py``, which counts the
dense Llama block and is left as it is; ``least_time`` and the peaks
are that file's.

Counted as the model requires them, whichever kernel or program does
the work, and ACTIVE parameters only: a token passes through the
attention matrices, the shared experts and the ``num_experts_per_tok``
routed experts it chose (the router's matrix too), the leading dense
layers' SwiGLU, and — once per sampled token — the head. The other
122 experts' weights are not operations of that token.

Attention has two forms that give the same output, and each side of a
request is counted in the form that needs fewer operations there:

- prefill, *materialised*: per (query, key) pair and head, q.k over
  ``qk_nope + qk_rope`` lanes and p.v over ``v_head_dim`` lanes. (The
  program's chunk path attends in the absorbed form, 3.4 times the
  operations: that is part of what a share under 100% is made of.)
- decode, *absorbed*: per pair and head, q.row over ``kv_lora_rank +
  qk_rope`` lanes and p.row over ``kv_lora_rank`` lanes, and per
  attended row ONE cached vector read — ``(kv_lora_rank + qk_rope) x
  2`` bytes a layer, whatever the heads. Up-projecting the history
  instead would cost ``context x 2 x kv_lora_rank x heads x 256``
  operations a step and layer.

The pad lanes a cached row is stored with (576 numbers in 640 lanes)
are not needed bytes.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    return {"V": cfg["vocab_size"], "D": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"],
            "Ld": cfg["first_k_dense_replace"],
            "H": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "C": cfg["kv_lora_rank"],
            "F": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "bytes": 2}  # bf16 served


def attn_params(cfg: dict) -> int:
    """One layer's attention matrices: W_q, W_kva, W_kvb, W_o."""
    s = sizes(cfg)
    return (s["D"] * s["H"] * (s["nope"] + s["rope"])
            + s["D"] * (s["C"] + s["rope"])
            + s["C"] * s["H"] * (s["nope"] + s["vd"])
            + s["H"] * s["vd"] * s["D"])


def active_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied by over all layers, the head
    left out: attention everywhere, the dense SwiGLU in the leading
    layers, router + shared + K routed experts in the expert layers."""
    s = sizes(cfg)
    dense = 3 * s["D"] * s["F"]
    expert = s["D"] * s["E"] + 3 * s["D"] * (s["Fs"] + s["K"] * s["Fe"])
    return (s["L"] * attn_params(cfg) + s["Ld"] * dense
            + (s["L"] - s["Ld"]) * expert)


def head_params(cfg: dict) -> int:
    s = sizes(cfg)
    return s["D"] * s["V"]


def latent_row_bytes(cfg: dict) -> int:
    """The cached vector of one token over all layers, as needed."""
    s = sizes(cfg)
    return s["L"] * (s["C"] + s["rope"]) * s["bytes"]


def prefill_pair_flops(cfg: dict) -> int:
    """Materialised attention, one (query, key) pair, all layers."""
    s = sizes(cfg)
    return 2 * s["L"] * s["H"] * (s["nope"] + s["rope"] + s["vd"])


def decode_pair_flops(cfg: dict) -> int:
    """Absorbed attention, one (query, cached row) pair, all layers."""
    s = sizes(cfg)
    return 2 * s["L"] * s["H"] * (2 * s["C"] + s["rope"])


def decode_flops(cfg: dict, contexts) -> float:
    """One decode step for each token, at the context (rows attended,
    itself included) it was produced at; the head once a token."""
    dense = 2 * (active_matmul_params(cfg) + head_params(cfg))
    pair = decode_pair_flops(cfg)
    return float(sum(dense + pair * c for c in contexts))


def prefill_flops(cfg: dict, prompt_lens) -> float:
    """Prefill of whole prompts: every layer on every token, the head
    once per prompt, causal attention over n(n+1)/2 pairs."""
    layer = 2 * active_matmul_params(cfg)
    head = 2 * head_params(cfg)
    pair = prefill_pair_flops(cfg)
    return float(sum(layer * n + head + pair * n * (n + 1) / 2
                     for n in prompt_lens))


def decode_attn_need(cfg: dict, contexts) -> tuple[float, float]:
    """(operations, bytes) decode attention needs for tokens produced
    at these contexts: each cached row read once a step and layer."""
    total = float(sum(contexts))
    return decode_pair_flops(cfg) * total, latent_row_bytes(cfg) * total
