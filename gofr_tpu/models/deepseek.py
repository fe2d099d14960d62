"""The ``deepseek_v3`` decoder family (DeepSeek-V3, Kanana-2): latent
(MLA) attention, leading dense layers, then sigmoid-routed sparse
experts beside shared experts — on the engine's paged serving path.

Pure-functional like :mod:`.llama`: parameters are a pytree, the
forward passes plain jittable functions, layers of one kind under
``lax.scan`` (the leading dense layers in one scan, the expert layers in
another, each with its own stacked attention weights, so no weight is
sliced out of a stack).

What the family keeps in the cache is ONE vector per token and layer —
the normed latent ``c`` (``kv_lora_rank`` lanes) followed by the roped
shared key ``k_pe`` (``qk_rope_head_dim`` lanes), zero-padded to the
128-lane tile (:func:`latent_row_width`): 576 numbers, 640 stored, where
the 32 heads' K and V would be 10,240. The engine's ``(k, v)`` pair
carries it on the K side; the V side holds zero bytes
(:func:`make_latent_cache`).

Attention has two forms that give the same output:

- *materialised* (bucket prefill, no history): per-head K (192) and V
  (128) up-projected from the latent, plain causal attention;
- *absorbed* (chunk prefill with history, decode): W_UK folded into the
  query and W_UV into the output, attention in latent space straight
  against the page pool (:mod:`..ops.latent_attention`).

Experts run sparsely (:func:`..ops.moe.sparse_experts`); the decode and
chunk steps also return how many experts received a token, counted on
the device.

RoPE: ``rope_interleave`` is true in the published configs — the rope
lanes are stored as adjacent pairs (2i, 2i+1) and brought to the
half-split order before ``rotate_half``, as the published modelling
code does.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.attention import xla_attention
from ..ops.moe import sigmoid_routing, sparse_experts, swiglu
from ..ops.norms import rms_norm
from ..ops.paged_kv import LANES
from ..ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class DeepseekConfig:
    """The published ``config.json`` keys of the family, by their own
    names (defaults: kanana-2-30b-a3b-instruct-2601)."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    moe_layer_freq: int = 1
    hidden_act: str = "silu"
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 1000000.0
    rope_scaling: dict | None = None
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # what is written here, by name: nothing is guessed
        unsupported = [
            ("q_lora_rank", self.q_lora_rank, None),
            ("scoring_func", self.scoring_func, "sigmoid"),
            ("topk_method", self.topk_method, "noaux_tc"),
            ("n_group", self.n_group, 1), ("topk_group", self.topk_group, 1),
            ("rope_scaling", self.rope_scaling, None),
            ("rope_interleave", self.rope_interleave, True),
            ("moe_layer_freq", self.moe_layer_freq, 1),
            ("hidden_act", self.hidden_act, "silu"),
            ("tie_word_embeddings", self.tie_word_embeddings, False)]
        for name, got, want in unsupported:
            if got != want:
                raise ValueError(
                    f"DeepseekConfig: {name}={got!r} is not implemented "
                    f"(only {want!r}: models/deepseek.py)")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                "DeepseekConfig: needs at least one leading dense layer "
                "and one expert layer (first_k_dense_replace "
                f"{self.first_k_dense_replace} of {self.num_hidden_layers})")

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def tiny(cls) -> "DeepseekConfig":
        """Test config of the same shape: 1 dense + 2 expert layers,
        8 experts top-2 + 1 shared, float32."""
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                   num_attention_heads=4, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                   intermediate_size=128, moe_intermediate_size=32,
                   n_routed_experts=8, n_shared_experts=1,
                   num_experts_per_tok=2, max_position_embeddings=256,
                   dtype=jnp.float32)


def latent_row_width(config: DeepseekConfig) -> int:
    """Lanes of one cached row: ``kv_lora_rank + qk_rope_head_dim``
    rounded up to the 128-lane tile (ops/latent_attention.py has why)."""
    need = config.kv_lora_rank + config.qk_rope_head_dim
    return -(-need // LANES) * LANES


def latent_row_bytes(config: DeepseekConfig) -> tuple[int, int]:
    """(needed, stored) cache bytes of one token over all layers: the
    576 numbers a layer, and the padded row as the pool holds it."""
    item = jnp.dtype(config.dtype).itemsize
    need = config.kv_lora_rank + config.qk_rope_head_dim
    return (config.num_hidden_layers * need * item,
            config.num_hidden_layers * latent_row_width(config) * item)


def make_latent_cache(config: DeepseekConfig, batch: int, max_seq: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The family's statement of its cache row, in the shape every
    cache constructor returns, ``[L, B, S, heads, width]`` a side: K is
    the one latent row a token, V holds nothing."""
    c = config
    lead = (c.num_hidden_layers, batch, max_seq, 1)
    return (jnp.zeros((*lead, latent_row_width(c)), c.dtype),
            jnp.zeros((*lead, 0), c.dtype))


# ---------------------------------------------------------------- params

def deepseek_init(key: jax.Array, config: DeepseekConfig) -> dict:
    """Random-init parameter pytree: ``dense`` stacks the leading dense
    layers and ``moe`` the expert layers (attention weights in each);
    ``w_uk`` / ``w_uv`` are the K and V halves of the checkpoint's
    ``kv_b_proj``."""
    c = config
    d, h, r = c.hidden_size, c.num_attention_heads, c.kv_lora_rank
    fs = c.n_shared_experts * c.moe_intermediate_size
    fe, e = c.moe_intermediate_size, c.n_routed_experts

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(c.dtype)

    def attn(k, n):
        ks = jax.random.split(k, 5)
        return {"attn_norm": jnp.ones((n, d), c.dtype),
                "wq": dense(ks[0], (n, d, h * c.qk_head_dim), d),
                "wkva": dense(ks[1], (n, d, r + c.qk_rope_head_dim), d),
                "kv_norm": jnp.ones((n, r), c.dtype),
                "w_uk": dense(ks[2], (n, r, h * c.qk_nope_head_dim), r),
                "w_uv": dense(ks[3], (n, r, h * c.v_head_dim), r),
                "wo": dense(ks[4], (n, h * c.v_head_dim, d),
                            h * c.v_head_dim),
                "ffn_norm": jnp.ones((n, d), c.dtype)}

    ks = jax.random.split(key, 15)
    ld, lm, f = c.first_k_dense_replace, c.n_moe_layers, c.intermediate_size
    return {
        "embed": (jax.random.normal(ks[0], (c.vocab_size, d), jnp.float32)
                  * 0.02).astype(c.dtype),
        "dense": {**attn(ks[1], ld),
                  "w1": dense(ks[2], (ld, d, f), d),
                  "w3": dense(ks[3], (ld, d, f), d),
                  "w2": dense(ks[4], (ld, f, d), f)},
        "moe": {**attn(ks[5], lm),
                "router": dense(ks[6], (lm, d, e), d),
                # a buffer of the checkpoint; small and non-zero here
                "router_bias": jax.random.normal(
                    ks[7], (lm, e), jnp.float32) * 0.02,
                "w1": dense(ks[8], (lm, e, d, fe), d),
                "w3": dense(ks[9], (lm, e, d, fe), d),
                "w2": dense(ks[10], (lm, e, fe, d), fe),
                "s1": dense(ks[11], (lm, d, fs), d),
                "s3": dense(ks[12], (lm, d, fs), d),
                "s2": dense(ks[13], (lm, fs, d), fs)},
        "final_norm": jnp.ones((d,), c.dtype),
        "lm_head": dense(ks[14], (d, c.vocab_size), d),
    }


# --------------------------------------------------------------- forward

def _rope_interleaved(x, positions, inv_freq):
    """x [B, S, H, d] with lanes as adjacent pairs: de-interleave to
    [evens ‖ odds], then the rotate-half rope of :mod:`..ops.rope`."""
    return apply_rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                      positions, inv_freq)


def _project(h, lp, c: DeepseekConfig, positions, inv_freq):
    """Normed hidden h [B, S, D] -> (q_nope [B, S, H, nope], q_pe
    [B, S, H, rope] roped, c [B, S, C] normed latent, k_pe [B, S, rope]
    roped)."""
    b, s, _ = h.shape
    q = jnp.matmul(h, lp["wq"]).reshape(b, s, c.num_attention_heads,
                                        c.qk_head_dim)
    kva = jnp.matmul(h, lp["wkva"])
    lat = rms_norm(kva[..., :c.kv_lora_rank], lp["kv_norm"], c.rms_norm_eps)
    k_pe = _rope_interleaved(kva[..., None, c.kv_lora_rank:], positions,
                             inv_freq)[:, :, 0]
    q_pe = _rope_interleaved(q[..., c.qk_nope_head_dim:], positions, inv_freq)
    return q[..., :c.qk_nope_head_dim], q_pe, lat, k_pe


def _latent_rows(lat, k_pe, c: DeepseekConfig):
    """The cache rows [B, S, 1, R]: c ‖ k_pe ‖ zero pad."""
    pad = latent_row_width(c) - lat.shape[-1] - k_pe.shape[-1]
    row = jnp.concatenate([lat, k_pe], -1)
    return jnp.pad(row, ((0, 0), (0, 0), (0, pad)))[:, :, None, :]


def _attn_materialised(x, lp, c: DeepseekConfig, positions, inv_freq,
                       kv_lengths):
    """Self-attention over a whole block with no history, per-head K
    and V up-projected from the latent. Returns (out [B, S, D], cache
    rows [B, S, 1, R]). XLA attention: the flash kernel assumes one
    head_dim and this family's q/k are 192 wide, its v 128."""
    b, s, _ = x.shape
    heads = c.num_attention_heads
    h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
    q_nope, q_pe, lat, k_pe = _project(h, lp, c, positions, inv_freq)
    k_nope = jnp.matmul(lat, lp["w_uk"]).reshape(b, s, heads,
                                                 c.qk_nope_head_dim)
    v = jnp.matmul(lat, lp["w_uv"]).reshape(b, s, heads, c.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None, :], (b, s, heads, c.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    out = xla_attention(q, k, v, causal=True, kv_lengths=kv_lengths,
                        scale=c.qk_head_dim ** -0.5)
    out = jnp.matmul(out.reshape(b, s, heads * c.v_head_dim), lp["wo"])
    return out, _latent_rows(lat, k_pe, c)


def absorb_query(q_nope, q_pe, w_uk, c: DeepseekConfig):
    """q_lat ‖ q_pe ‖ zero pad, [B, S, H, R]: W_UK folded into the
    query so that it scores straight against the cached rows."""
    heads = c.num_attention_heads
    q_lat = jnp.einsum(
        "bshd,chd->bshc", q_nope,
        w_uk.reshape(c.kv_lora_rank, heads, c.qk_nope_head_dim),
        preferred_element_type=jnp.float32).astype(q_nope.dtype)
    pad = latent_row_width(c) - c.kv_lora_rank - c.qk_rope_head_dim
    q = jnp.concatenate([q_lat, q_pe], -1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad)))


def unabsorb_output(o_lat, w_uv, c: DeepseekConfig):
    """Latent-space attention output [B, S, H, C] -> [B, S, H * vd]."""
    b, s, heads, _ = o_lat.shape
    out = jnp.einsum(
        "bshc,chd->bshd", o_lat,
        w_uv.reshape(c.kv_lora_rank, heads, c.v_head_dim),
        preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return out.reshape(b, s, heads * c.v_head_dim)


def _attn_absorbed(x, lp, li, pool, tables, starts, counts, c, positions,
                   inv_freq, implementation):
    """Self-attention of S new positions a slot against the page pool:
    the rows are written first, then attended in latent space. Returns
    (out [B, S, D], pool)."""
    from ..ops.latent_attention import latent_chunk_attention
    from ..ops.paged_kv import pool_write
    h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
    q_nope, q_pe, lat, k_pe = _project(h, lp, c, positions, inv_freq)
    pool = pool_write(pool, li, tables, starts, counts,
                      _latent_rows(lat, k_pe, c))
    o_lat = latent_chunk_attention(
        absorb_query(q_nope, q_pe, lp["w_uk"], c), pool, tables, starts,
        counts, value_width=c.kv_lora_rank, scale=c.qk_head_dim ** -0.5,
        layer=li, implementation=implementation)
    return jnp.matmul(unabsorb_output(o_lat, lp["w_uv"], c), lp["wo"]), pool


def _dense_mlp(x, lp, c: DeepseekConfig):
    h = rms_norm(x, lp["ffn_norm"], c.rms_norm_eps)
    return swiglu(h, lp["w1"], lp["w3"], lp["w2"])


#: the routed experts' stacks: kept out of the layer scan's slices
EXPERT_STACKS = ("w1", "w3", "w2")


def _moe_mlp(x, lp, experts, li, c: DeepseekConfig):
    """Routed experts + shared experts of expert layer ``li``; ``lp``
    is the layer's slice of everything but the routed experts, whose
    stacks ``experts`` go to the grouped matmul whole
    (ops/moe.sparse_experts). Returns (y, number of experts that
    received a token)."""
    b, s, d = x.shape
    h = rms_norm(x, lp["ffn_norm"], c.rms_norm_eps).reshape(b * s, d)
    weights, indices = sigmoid_routing(
        h, lp["router"], lp["router_bias"], c.num_experts_per_tok,
        route_scale=c.routed_scaling_factor, normalize=c.norm_topk_prob)
    routed, group_sizes = sparse_experts(h, weights, indices, *experts,
                                         layer=li)
    y = routed + swiglu(h, lp["s1"], lp["s3"], lp["s2"])
    return y.reshape(b, s, d), jnp.sum(group_sizes > 0).astype(jnp.int32)


def _logits(params, c: DeepseekConfig, x):
    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    return jnp.matmul(x, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _run_layers(params, c: DeepseekConfig, x, carry, attn):
    """Both scans. ``attn(x, lp, li, carry) -> (out, carry, ys)``.
    Returns (x, carry, stacked dense ys, stacked expert ys, experts
    touched per expert layer [Lm])."""
    ld = c.first_k_dense_replace

    def dense_layer(state, scanned):
        x, carry = state
        lp, li = scanned
        out, carry, ys = attn(x, lp, li, carry)
        x = x + out
        return (x + _dense_mlp(x, lp, c), carry), ys

    experts = tuple(params["moe"][k] for k in EXPERT_STACKS)

    def moe_layer(state, scanned):
        x, carry = state
        lp, li = scanned
        out, carry, ys = attn(x, lp, ld + li, carry)
        x = x + out
        y, touched = _moe_mlp(x, lp, experts, li, c)
        return (x + y, carry), (ys, touched)

    (x, carry), ys_d = jax.lax.scan(
        dense_layer, (x, carry), (params["dense"], jnp.arange(ld)))
    (x, carry), (ys_m, touched) = jax.lax.scan(
        moe_layer, (x, carry),
        ({k: v for k, v in params["moe"].items()
          if k not in EXPERT_STACKS}, jnp.arange(c.n_moe_layers)))
    return x, carry, ys_d, ys_m, touched


def _routing_facts(c: DeepseekConfig, touched, tokens: int) -> jnp.ndarray:
    """int32 [2]: experts that received a token, summed over the expert
    layers of the step; and the (token, expert) assignments routed."""
    return jnp.stack([jnp.sum(touched), jnp.int32(
        tokens * c.num_experts_per_tok * c.n_moe_layers)])


def deepseek_prefill_last(params: dict, tokens: jnp.ndarray,
                          config: DeepseekConfig, *,
                          kv_lengths: jnp.ndarray
                          ) -> tuple[jnp.ndarray, tuple]:
    """Bucket prefill: tokens [B, S] -> (logits at each row's last
    prompt token [B, V], (latent rows [L, B, S, 1, R], an empty V
    side)). Materialised attention, no history."""
    c = config
    b, s = tokens.shape
    inv_freq = rope_frequencies(c.qk_rope_head_dim, c.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = jnp.take(params["embed"], tokens, axis=0).astype(c.dtype)

    def attn(x, lp, li, carry):
        out, rows = _attn_materialised(x, lp, c, positions, inv_freq,
                                       kv_lengths)
        return out, carry, rows

    x, _, rows_d, rows_m, _ = _run_layers(params, c, x, (), attn)
    rows = jnp.concatenate([rows_d, rows_m], 0)
    last = jnp.take_along_axis(
        x, jnp.maximum(kv_lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, c, last), (rows, rows[..., :0])


def deepseek_prefill_chunk_paged(params: dict, tokens: jnp.ndarray,
                                 pool: jnp.ndarray, v_pool: jnp.ndarray,
                                 tables: jnp.ndarray, offsets: jnp.ndarray,
                                 chunk_lengths: jnp.ndarray,
                                 config: DeepseekConfig, *,
                                 implementation: str = "auto"
                                 ) -> tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """One chunk of a chunked prefill straight against the latent page
    pool: tokens [B, S] start at absolute positions ``offsets``; each
    layer writes the chunk's rows through the block table and attends,
    absorbed, to history + chunk in place. ``v_pool`` is the pair's
    empty side and passes through. Returns (last-position logits
    [B, V], pool, v_pool)."""
    c = config
    b, s = tokens.shape
    inv_freq = rope_frequencies(c.qk_rope_head_dim, c.rope_theta)
    positions = offsets[:, None] + jnp.arange(s)[None, :]
    x = jnp.take(params["embed"], tokens, axis=0).astype(c.dtype)

    def attn(x, lp, li, pool):
        out, pool = _attn_absorbed(x, lp, li, pool, tables, offsets,
                                   chunk_lengths, c, positions, inv_freq,
                                   implementation)
        return out, pool, None

    x, pool, _, _, _ = _run_layers(params, c, x, pool, attn)
    last = jnp.take_along_axis(
        x, jnp.maximum(chunk_lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, c, last), pool, v_pool


def deepseek_decode_step_paged(params: dict, tokens: jnp.ndarray,
                               pool: jnp.ndarray, v_pool: jnp.ndarray,
                               tables: jnp.ndarray, lengths: jnp.ndarray,
                               config: DeepseekConfig, *,
                               implementation: str = "auto"
                               ) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray, jnp.ndarray]:
    """One decode step against the latent page pool: tokens [B], the
    new row lands at position ``lengths`` through the table, attention
    absorbed. Returns (logits [B, V], pool, v_pool, routing facts int32
    [2]: experts touched summed over the expert layers, assignments)."""
    c = config
    inv_freq = rope_frequencies(c.qk_rope_head_dim, c.rope_theta)
    positions = lengths[:, None]
    one = jnp.ones_like(lengths)
    x = jnp.take(params["embed"], tokens, axis=0).astype(c.dtype)[:, None]

    def attn(x, lp, li, pool):
        out, pool = _attn_absorbed(x, lp, li, pool, tables, lengths, one,
                                   c, positions, inv_freq, implementation)
        return out, pool, None

    x, pool, _, _, touched = _run_layers(params, c, x, pool, attn)
    return (_logits(params, c, x)[:, 0], pool, v_pool,
            _routing_facts(c, touched, tokens.shape[0]))
