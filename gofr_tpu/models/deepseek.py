"""The ``deepseek_v3`` decoder family (DeepSeek-V3, Kanana-2, and its
``xing4_0`` descendant Xing4.0): latent (MLA) attention, leading dense
layers, then sigmoid-routed sparse experts beside shared experts — on
the engine's paged serving path.

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
code does. ``rope_scaling`` of type ``yarn`` changes the frequencies
(:func:`..ops.rope.yarn_frequencies`) and multiplies the softmax scale
by ``yarn_mscale(factor, mscale_all_dim)`` squared, in both attention
forms and in the latent kernel's ``scale=``.

Queries: ``q_lora_rank`` None is one matrix ``wq``; an int puts a
normed bottleneck of that width in its place (``wqa``, ``q_norm``,
``wqb``).

The residual path: ``hc_mult`` None is ``x + F(x)``. An int n carries
n residual streams ``[n, B, S, D]`` and puts manifold-constrained
hyper-connections (:mod:`..ops.hyper_connections`) around attention
and around the FFN of every layer, each with its own ``phi``, ``alpha``
and ``bias``; the streams start as n copies of the embedding and are
summed before the final norm. The multi-token-prediction module some
checkpoints of the family carry (``num_nextn_predict_layers``) is not
part of the model's own next-token path and is not here.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import hyper_connections as hc
from ..ops.attention import xla_attention
from ..ops.moe import sigmoid_routing, sparse_experts, swiglu
from ..ops.norms import rms_norm
from ..ops.paged_kv import LANES
from ..ops.rope import (apply_rope, rope_frequencies, yarn_frequencies,
                        yarn_mscale)


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
    # the residual path (xing4_0): None is the plain residual
    hc_mult: int | None = None
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # what is written here, by name: nothing is guessed
        scaling = self.rope_scaling or {}
        unsupported = [
            ("scoring_func", self.scoring_func, "sigmoid"),
            ("topk_method", self.topk_method, "noaux_tc"),
            ("n_group", self.n_group, 1), ("topk_group", self.topk_group, 1),
            ("rope_scaling", scaling.get("type", scaling.get("rope_type"))
             if scaling else "yarn", "yarn"),
            ("rope_interleave", self.rope_interleave, True),
            ("moe_layer_freq", self.moe_layer_freq, 1),
            ("hidden_act", self.hidden_act, "silu"),
            ("tie_word_embeddings", self.tie_word_embeddings, False)]
        for name, got, want in unsupported:
            if got != want:
                raise ValueError(
                    f"DeepseekConfig: {name}={got!r} is not implemented "
                    f"(only {want!r}: models/deepseek.py)")
        missing = [k for k in ("factor", "original_max_position_embeddings")
                   if scaling and k not in scaling]
        if missing:
            raise ValueError(
                f"DeepseekConfig: rope_scaling lacks {missing} (yarn needs "
                "both: models/deepseek.py)")
        cos_sin = self._yarn_cos_sin_factor() if scaling else 1.0
        if abs(cos_sin - 1.0) > 1e-9:
            raise ValueError(
                f"DeepseekConfig: rope_scaling scales cos/sin by {cos_sin} "
                "(mscale against mscale_all_dim); only 1 is implemented")
        for name, least in (("q_lora_rank", 1), ("hc_mult", 2)):
            got = getattr(self, name)
            if got is not None and (not isinstance(got, int) or got < least):
                raise ValueError(
                    f"DeepseekConfig: {name}={got!r} is not implemented "
                    f"(None or an int of at least {least}: "
                    "models/deepseek.py)")
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

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5``, times YaRN's temperature squared
        where the context is stretched (the published attention's
        ``scaling * mscale * mscale`` with ``mscale_all_dim``)."""
        scale = self.qk_head_dim ** -0.5
        s = self.rope_scaling
        if s and s.get("mscale_all_dim"):
            scale *= yarn_mscale(float(s["factor"]),
                                 float(s["mscale_all_dim"])) ** 2
        return scale

    def _yarn_cos_sin_factor(self) -> float:
        """What the published rotary embedding multiplies cos and sin
        by: ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
        (``mscale(factor)`` where either is absent)."""
        s = self.rope_scaling
        factor = float(s["factor"])
        if s.get("mscale") and s.get("mscale_all_dim"):
            return (yarn_mscale(factor, float(s["mscale"]))
                    / yarn_mscale(factor, float(s["mscale_all_dim"])))
        return yarn_mscale(factor)

    @property
    def rope_inv_freq(self) -> jnp.ndarray:
        """The rope lanes' inverse frequencies [qk_rope_head_dim // 2]."""
        if not self.rope_scaling:
            return rope_frequencies(self.qk_rope_head_dim, self.rope_theta)
        return yarn_frequencies(self.qk_rope_head_dim, self.rope_theta,
                                self.rope_scaling)

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

    @classmethod
    def tiny_mhc(cls) -> "DeepseekConfig":
        """:meth:`tiny` with what ``xing4_0`` adds: four residual
        streams, compressed queries, YaRN over 32 original positions.
        Four Sinkhorn rounds, not the published 20: XLA's CPU backend
        takes half a minute to compile a step with 20 unrolled."""
        return replace(
            cls.tiny(), hc_mult=4, hc_sinkhorn_iters=4, q_lora_rank=24,
            rope_theta=10000.0,
            rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 32})


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

    def queries(k, n):
        if c.q_lora_rank is None:
            return {"wq": dense(k, (n, d, h * c.qk_head_dim), d)}
        ka, kb = jax.random.split(k)
        return {"wqa": dense(ka, (n, d, c.q_lora_rank), d),
                "q_norm": jnp.ones((n, c.q_lora_rank), c.dtype),
                "wqb": dense(kb, (n, c.q_lora_rank, h * c.qk_head_dim),
                             c.q_lora_rank)}

    def streams(k, n):
        """Each sublayer's mHC tensors (ops/hyper_connections.py): the
        dynamic term of order one, the stream mix leaning to identity."""
        if c.hc_mult is None:
            return {}
        m = c.hc_mult
        out = {}
        for which, kk in zip(("attn", "ffn"), jax.random.split(k)):
            k1, k2, k3 = jax.random.split(kk, 3)
            bias = jax.random.normal(k3, (n, 2 * m + m * m),
                                     jnp.float32) * 0.5
            out[f"hc_{which}_phi"] = dense(k1, (n, m, d, 2 * m + m * m),
                                           m * d)
            out[f"hc_{which}_alpha"] = 1.0 + 0.1 * jax.random.normal(
                k2, (n, 3), jnp.float32)
            out[f"hc_{which}_bias"] = bias.at[:, 2 * m:].add(
                2.0 * jnp.eye(m, dtype=jnp.float32).reshape(-1))
        return out

    def attn(k, n):
        ks = jax.random.split(k, 5)
        return {"attn_norm": jnp.ones((n, d), c.dtype),
                **queries(ks[0], n),
                **streams(jax.random.fold_in(k, 5), n),
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
    if c.q_lora_rank is None:
        q = jnp.matmul(h, lp["wq"])
    else:
        q = jnp.matmul(rms_norm(jnp.matmul(h, lp["wqa"]), lp["q_norm"],
                                c.rms_norm_eps), lp["wqb"])
    q = q.reshape(b, s, c.num_attention_heads, c.qk_head_dim)
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
                        scale=c.softmax_scale)
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
        counts, value_width=c.kv_lora_rank, scale=c.softmax_scale,
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


def _around(x, lp, which: str, f, c: DeepseekConfig):
    """The residual step around one sublayer, ``f(h [B, S, D]) -> (out
    [B, S, D], aux)``: ``x + out`` on the plain path; with ``hc_mult``
    the sublayer's read-in, stream mix and write-out over the streams
    ``x [n, B, S, D]``. Returns (x, aux, the mix's row error or None)."""
    if c.hc_mult is None:
        out, aux = f(x)
        return x + out, aux, None
    maps = hc.mhc_mappings(
        x, lp[f"hc_{which}_phi"], lp[f"hc_{which}_alpha"],
        lp[f"hc_{which}_bias"], iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
        clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max))
    out, aux = f(hc.read_in(x, maps.pre))
    return hc.write_out(x, out, maps), aux, maps.row_err


def _worse(a, b):
    """The larger of two row errors; None where there are no streams."""
    return None if a is None else jnp.maximum(a, b)


def _run_layers(params, c: DeepseekConfig, x, carry, attn):
    """Both scans over hidden states x [B, S, D], which with ``hc_mult``
    go in as n copies and come out summed. ``attn(h, lp, li, carry) ->
    (out, carry, ys)``. Returns (x, carry, stacked dense ys, stacked
    expert ys, experts touched per expert layer [Lm], the largest row
    error of any stream mix or None)."""
    ld = c.first_k_dense_replace
    if c.hc_mult is not None:
        x = jnp.broadcast_to(x[None], (c.hc_mult, *x.shape))

    def attend(x, lp, li, carry):
        def f(h):
            out, new, ys = attn(h, lp, li, carry)
            return out, (new, ys)

        x, (carry, ys), err = _around(x, lp, "attn", f, c)
        return x, carry, ys, err

    def dense_layer(state, scanned):
        x, carry = state
        lp, li = scanned
        x, carry, ys, e1 = attend(x, lp, li, carry)
        x, _, e2 = _around(x, lp, "ffn",
                           lambda h: (_dense_mlp(h, lp, c), None), c)
        return (x, carry), (ys, _worse(e1, e2))

    experts = tuple(params["moe"][k] for k in EXPERT_STACKS)

    def moe_layer(state, scanned):
        x, carry = state
        lp, li = scanned
        x, carry, ys, e1 = attend(x, lp, ld + li, carry)
        x, touched, e2 = _around(
            x, lp, "ffn", lambda h: _moe_mlp(h, lp, experts, li, c), c)
        return (x, carry), (ys, touched, _worse(e1, e2))

    (x, carry), (ys_d, err_d) = jax.lax.scan(
        dense_layer, (x, carry), (params["dense"], jnp.arange(ld)))
    (x, carry), (ys_m, touched, err_m) = jax.lax.scan(
        moe_layer, (x, carry),
        ({k: v for k, v in params["moe"].items()
          if k not in EXPERT_STACKS}, jnp.arange(c.n_moe_layers)))
    err = None
    if c.hc_mult is not None:
        x = jnp.sum(x.astype(jnp.float32), 0).astype(x.dtype)
        err = jnp.maximum(jnp.max(err_d), jnp.max(err_m))
    return x, carry, ys_d, ys_m, touched, err


def _step_facts(c: DeepseekConfig, touched, tokens: int, row_err
                ) -> jnp.ndarray:
    """int32 [2]: experts that received a token, summed over the expert
    layers of the step; and the (token, expert) assignments routed.
    With ``hc_mult``, int32 [4]: then the number of residual streams
    and the step's largest stream-mix row error, a float32's bits (the
    facts ride an int32 array; a non-negative float's bits order as the
    floats do)."""
    facts = [jnp.sum(touched), jnp.int32(
        tokens * c.num_experts_per_tok * c.n_moe_layers)]
    if row_err is not None:
        facts += [jnp.int32(c.hc_mult), jax.lax.bitcast_convert_type(
            row_err.astype(jnp.float32), jnp.int32)]
    return jnp.stack(facts)


def step_fact_readers(config: DeepseekConfig) -> dict:
    """:func:`_step_facts`' names, in its order: name -> reducer of
    that fact's column over a pass's decode steps (numpy int32 [T]) to
    the number the pass record carries. ``glue.deepseek_engine`` hands
    them to the engine, which knows no fact by its column."""
    readers = {"experts_touched": lambda col: int(col.sum()),
               "assignments": lambda col: int(col.sum())}
    if config.hc_mult is not None:
        readers["streams"] = lambda col: int(col[0])
        # float32 bits, non-negative: the largest int is the largest float
        readers["mhc_row_err"] = lambda col: float(
            col.max().astype(np.int32).view(np.float32))
    return readers


def deepseek_prefill_last(params: dict, tokens: jnp.ndarray,
                          config: DeepseekConfig, *,
                          kv_lengths: jnp.ndarray
                          ) -> tuple[jnp.ndarray, tuple]:
    """Bucket prefill: tokens [B, S] -> (logits at each row's last
    prompt token [B, V], (latent rows [L, B, S, 1, R], an empty V
    side)). Materialised attention, no history."""
    c = config
    b, s = tokens.shape
    inv_freq = c.rope_inv_freq
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = jnp.take(params["embed"], tokens, axis=0).astype(c.dtype)

    def attn(x, lp, li, carry):
        out, rows = _attn_materialised(x, lp, c, positions, inv_freq,
                                       kv_lengths)
        return out, carry, rows

    x, _, rows_d, rows_m, _, _ = _run_layers(params, c, x, (), attn)
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
    inv_freq = c.rope_inv_freq
    positions = offsets[:, None] + jnp.arange(s)[None, :]
    x = jnp.take(params["embed"], tokens, axis=0).astype(c.dtype)

    def attn(x, lp, li, pool):
        out, pool = _attn_absorbed(x, lp, li, pool, tables, offsets,
                                   chunk_lengths, c, positions, inv_freq,
                                   implementation)
        return out, pool, None

    x, pool, _, _, _, _ = _run_layers(params, c, x, pool, attn)
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
    absorbed. Returns (logits [B, V], pool, v_pool, the step's facts
    int32 [2 or 4]: :func:`_step_facts`)."""
    c = config
    inv_freq = c.rope_inv_freq
    positions = lengths[:, None]
    one = jnp.ones_like(lengths)
    x = jnp.take(params["embed"], tokens, axis=0).astype(c.dtype)[:, None]

    def attn(x, lp, li, pool):
        out, pool = _attn_absorbed(x, lp, li, pool, tables, lengths, one,
                                   c, positions, inv_freq, implementation)
        return out, pool, None

    x, pool, _, _, touched, row_err = _run_layers(params, c, x, pool, attn)
    return (_logits(params, c, x)[:, 0], pool, v_pool,
            _step_facts(c, touched, tokens.shape[0], row_err))
