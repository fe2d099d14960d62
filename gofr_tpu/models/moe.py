"""Mixtral-style MoE decoder: Llama attention + sparse expert MLP.

The expert dimension is the natural expert-parallel (EP) axis: the
parallel layer shards ``w1/w3/w2`` over experts and turns the combine
into collectives, while this definition stays unchanged (see
gofr_tpu/parallel). Router logits are returned for the load-balancing
aux loss during training.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.moe import moe_layer
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_frequencies
from ..ops.attention import attention, decode_attention
from .llama import LlamaConfig


@dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2

    @classmethod
    def tiny(cls) -> "MoEConfig":
        return cls(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, ffn_dim=96, max_seq=128, n_experts=4,
                   top_k=2, dtype=jnp.float32)

    @classmethod
    def mixtral_8x7b(cls) -> "MoEConfig":
        return cls(vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, ffn_dim=14336, n_experts=8, top_k=2,
                   rope_theta=1e6)


def moe_init(key: jax.Array, config: MoEConfig) -> dict:
    c = config
    hd = c.head_dim
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(c.dtype)

    lk = jax.random.split(k_layers, 9)
    L, E = c.n_layers, c.n_experts
    layers = {
        "attn_norm": jnp.ones((L, c.dim), c.dtype),
        "wq": dense(lk[0], (L, c.dim, c.n_heads * hd), c.dim),
        "wk": dense(lk[1], (L, c.dim, c.n_kv_heads * hd), c.dim),
        "wv": dense(lk[2], (L, c.dim, c.n_kv_heads * hd), c.dim),
        "wo": dense(lk[3], (L, c.n_heads * hd, c.dim), c.n_heads * hd),
        "ffn_norm": jnp.ones((L, c.dim), c.dtype),
        "gate": dense(lk[4], (L, c.dim, E), c.dim),
        "w1": dense(lk[5], (L, E, c.dim, c.ffn_dim), c.dim),
        "w3": dense(lk[6], (L, E, c.dim, c.ffn_dim), c.dim),
        "w2": dense(lk[7], (L, E, c.ffn_dim, c.dim), c.ffn_dim),
    }
    params = {
        "embed": (jax.random.normal(k_embed, (c.vocab_size, c.dim), jnp.float32)
                  * 0.02).astype(c.dtype),
        "layers": layers,
        "final_norm": jnp.ones((c.dim,), c.dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(k_head, (c.dim, c.vocab_size), c.dim)
    return params


def _moe_mlp(x, lp, c: MoEConfig):
    b, s, d = x.shape
    h = rms_norm(x, lp["ffn_norm"], c.norm_eps)
    flat = h.reshape(b * s, d)
    out, router_logits = moe_layer(flat, lp["gate"], lp["w1"], lp["w3"],
                                   lp["w2"], num_selected=c.top_k)
    return out.reshape(b, s, d), router_logits.reshape(b, s, -1)


def _logits(params, c, x):
    # head in the weights' dtype with f32 accumulation (see
    # models/llama.py::_logits for the rationale)
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    head = params["embed"].T if c.tie_embeddings else params["lm_head"]
    return jnp.matmul(x.astype(head.dtype), head,
                      preferred_element_type=jnp.float32)


def _moe_backbone(params, tokens, c: MoEConfig, kv_lengths, implementation):
    """Embedding + all MoE blocks; final hidden [B, S, D] + caches +
    per-layer router logits."""
    b, s = tokens.shape
    hd = c.head_dim
    inv_freq = rope_frequencies(hd, c.rope_theta, c.rope_scaling)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = params["embed"][tokens]

    def layer_fn(x, lp):
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = (h @ lp["wq"]).reshape(b, s, c.n_heads, hd)
        k = (h @ lp["wk"]).reshape(b, s, c.n_kv_heads, hd)
        v = (h @ lp["wv"]).reshape(b, s, c.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        out = attention(q, k, v, causal=True, kv_lengths=kv_lengths,
                        implementation=implementation)
        x = x + (out.reshape(b, s, c.n_heads * hd) @ lp["wo"])
        mlp_out, router_logits = _moe_mlp(x, lp, c)
        return x + mlp_out, ((k, v), router_logits)

    x, ((ks, vs), router) = jax.lax.scan(layer_fn, x, params["layers"])
    return x, (ks, vs), router


def moe_prefill(params: dict, tokens: jnp.ndarray, config: MoEConfig, *,
                kv_lengths: jnp.ndarray | None = None,
                implementation: str = "auto"):
    """tokens [B,S] -> (logits, (k_cache, v_cache), router_logits)."""
    x, caches, router = _moe_backbone(params, tokens, config, kv_lengths,
                                      implementation)
    return _logits(params, config, x), caches, router


def moe_prefill_last(params: dict, tokens: jnp.ndarray, config: MoEConfig, *,
                     kv_lengths: jnp.ndarray, implementation: str = "auto"):
    """Serving prefill: logits only at each row's last prompt position
    (see models/llama.py::llama_prefill_last — the full [S, V] head is
    pure waste for positions never sampled)."""
    x, caches, router = _moe_backbone(params, tokens, config, kv_lengths,
                                      implementation)
    last = jnp.take_along_axis(
        x, jnp.maximum(kv_lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, config, last), caches, router


def moe_decode_step(params: dict, tokens: jnp.ndarray,
                    k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                    lengths: jnp.ndarray, config: MoEConfig):
    c = config
    b = tokens.shape[0]
    hd = c.head_dim
    inv_freq = rope_frequencies(hd, c.rope_theta, c.rope_scaling)
    positions = lengths[:, None]
    x = params["embed"][tokens][:, None, :]
    batch_idx = jnp.arange(b)

    # caches ride the scan carry — ys emission would copy each layer's
    # full [B, Smax, Hkv, hd] slice per step (see llama_decode_step)
    def layer_fn(carry, scanned):
        x, kc_all, vc_all = carry
        lp, li = scanned
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = (h @ lp["wq"]).reshape(b, 1, c.n_heads, hd)
        k = (h @ lp["wk"]).reshape(b, 1, c.n_kv_heads, hd)
        v = (h @ lp["wv"]).reshape(b, 1, c.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        kc_all = kc_all.at[li, batch_idx, lengths].set(k[:, 0])
        vc_all = vc_all.at[li, batch_idx, lengths].set(v[:, 0])
        kc = jax.lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
        out = decode_attention(q, kc, vc, lengths + 1)
        x = x + (out.reshape(b, 1, c.n_heads * hd) @ lp["wo"])
        mlp_out, _ = _moe_mlp(x, lp, c)
        return (x + mlp_out, kc_all, vc_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer_fn, (x, k_cache, v_cache),
        (params["layers"], jnp.arange(c.n_layers)))
    return _logits(params, c, x)[:, 0], new_k, new_v
