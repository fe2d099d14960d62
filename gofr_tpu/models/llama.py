"""Llama-3-family decoder — the flagship serving model.

Pure-functional: parameters are a pytree of arrays, the forward passes
are plain jittable functions. TPU-first structure:

- **lax.scan over layers** with stacked per-layer weights (leading
  ``L`` axis): one compiled layer body regardless of depth, which keeps
  XLA compile times flat for 32/80-layer configs and gives the pipeline
  parallel path its natural stage structure.
- bf16 params/activations, f32 norms/softmax/logits.
- GQA + RoPE (Llama-3 scaling), SwiGLU MLP, RMSNorm, optional tied
  embeddings.
- Prefill returns the per-layer K/V for cache insertion; decode takes
  cache [L, B, Smax, Hkv, hd] + per-sequence lengths and updates in
  place (donated by the engine under jit).

Capability reference: the serving targets of BASELINE.json (Llama-3-8B
`/chat` on v5e-8, 70B multi-host on v5p-64).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.attention import attention, decode_attention
from ..ops.norms import rms_norm
from ..ops.quant import qgather, qmatmul, qmatmul_t
from ..ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    rope_scaling: dict | None = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    # ---- presets -----------------------------------------------------
    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Test config: runs everywhere in milliseconds."""
        return cls(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, ffn_dim=128, max_seq=128,
                   dtype=jnp.float32)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()  # the defaults are the 8B shape

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                   ffn_dim=28672)

    @classmethod
    def llama3_1b(cls) -> "LlamaConfig":
        """Llama-3.2-1B shape — the single-chip bench model."""
        return cls(vocab_size=128256, dim=2048, n_layers=16, n_heads=32,
                   n_kv_heads=8, ffn_dim=8192, tie_embeddings=True)

    def scaled(self, **kw) -> "LlamaConfig":
        return replace(self, **kw)


# ---------------------------------------------------------------- params

def llama_init(key: jax.Array, config: LlamaConfig) -> dict:
    """Random-init parameter pytree with stacked layer weights."""
    c = config
    hd = c.head_dim
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def norm_init(shape):
        return jnp.ones(shape, c.dtype)

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(c.dtype)

    lk = jax.random.split(k_layers, 7)
    L = c.n_layers
    layers = {
        "attn_norm": norm_init((L, c.dim)),
        "wq": dense_init(lk[0], (L, c.dim, c.n_heads * hd), c.dim),
        "wk": dense_init(lk[1], (L, c.dim, c.n_kv_heads * hd), c.dim),
        "wv": dense_init(lk[2], (L, c.dim, c.n_kv_heads * hd), c.dim),
        "wo": dense_init(lk[3], (L, c.n_heads * hd, c.dim), c.n_heads * hd),
        "ffn_norm": norm_init((L, c.dim)),
        "w1": dense_init(lk[4], (L, c.dim, c.ffn_dim), c.dim),
        "w3": dense_init(lk[5], (L, c.dim, c.ffn_dim), c.dim),
        "w2": dense_init(lk[6], (L, c.ffn_dim, c.dim), c.ffn_dim),
    }
    params = {
        "embed": (jax.random.normal(k_embed, (c.vocab_size, c.dim), jnp.float32)
                  * 0.02).astype(c.dtype),
        "layers": layers,
        "final_norm": norm_init((c.dim,)),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (c.dim, c.vocab_size), c.dim)
    return params


def param_count(params: dict) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


# --------------------------------------------------------------- forward

def _attn_block(x, lp, c: LlamaConfig, inv_freq, positions, kv_lengths,
                implementation):
    """Self-attention over a full (prefill) block. Returns (out, k, v).
    Matrices route through ``qmatmul``: int8-quantized weights (see
    :mod:`..ops.quant`) dequantize inside the matmul read."""
    b, s, _ = x.shape
    hd = c.head_dim
    h = rms_norm(x, lp["attn_norm"], c.norm_eps)
    q = qmatmul(h, lp["wq"]).reshape(b, s, c.n_heads, hd)
    k = qmatmul(h, lp["wk"]).reshape(b, s, c.n_kv_heads, hd)
    v = qmatmul(h, lp["wv"]).reshape(b, s, c.n_kv_heads, hd)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    out = attention(q, k, v, causal=True, kv_lengths=kv_lengths,
                    implementation=implementation)
    out = qmatmul(out.reshape(b, s, c.n_heads * hd), lp["wo"])
    return out, k, v


def _mlp_block(x, lp, c: LlamaConfig):
    h = rms_norm(x, lp["ffn_norm"], c.norm_eps)
    gate = jax.nn.silu(qmatmul(h, lp["w1"]).astype(jnp.float32))
    return qmatmul((gate * qmatmul(h, lp["w3"]).astype(jnp.float32))
                   .astype(x.dtype), lp["w2"])


def _logits(params, c: LlamaConfig, x):
    # LM head runs in the weights' dtype (bf16 in serving; int8 when
    # quantized — half the HBM traffic again) with f32 accumulation:
    # the logits come out f32 for sampling either way.
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    if c.tie_embeddings:
        return qmatmul_t(x, params["embed"], out_dtype=jnp.float32)
    return qmatmul(x, params["lm_head"], out_dtype=jnp.float32)


def _backbone(params: dict, tokens: jnp.ndarray, c: LlamaConfig,
              kv_lengths, implementation, constrain
              ) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Embedding + all transformer blocks; returns final hidden states
    [B, S, D] (pre-final-norm) and the stacked per-layer K/V."""
    b, s = tokens.shape
    inv_freq = rope_frequencies(c.head_dim, c.rope_theta, c.rope_scaling)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = qgather(params["embed"], tokens, c.dtype)
    if constrain is not None:
        x = constrain(x)

    def layer_fn(x, lp):
        attn_out, k, v = _attn_block(x, lp, c, inv_freq, positions,
                                     kv_lengths, implementation)
        x = x + attn_out
        x = x + _mlp_block(x, lp, c)
        if constrain is not None:
            x = constrain(x)
        return x, (k, v)

    return jax.lax.scan(layer_fn, x, params["layers"])


def llama_prefill(params: dict, tokens: jnp.ndarray, config: LlamaConfig, *,
                  kv_lengths: jnp.ndarray | None = None,
                  implementation: str = "auto",
                  constrain=None
                  ) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence forward.

    tokens [B, S] -> (logits [B, S, V], (k_cache, v_cache) each
    [L, B, S, Hkv, hd]). ``kv_lengths`` masks right-padded batches.
    ``constrain``: optional fn applied to residual activations — the
    parallel layer passes a ``with_sharding_constraint`` to pin
    Megatron-style sequence-parallel layouts between blocks.
    """
    x, (ks, vs) = _backbone(params, tokens, config, kv_lengths,
                            implementation, constrain)
    return _logits(params, config, x), (ks, vs)


def llama_prefill_last(params: dict, tokens: jnp.ndarray, config: LlamaConfig,
                       *, kv_lengths: jnp.ndarray,
                       implementation: str = "auto", constrain=None
                       ) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Prefill for serving: logits only at each row's last prompt token.

    The LM head is the single largest matmul in a short-prompt prefill
    (S·D·V vs the backbone's ~S·12·D²); a serving prefill only ever
    samples from the final position, so gather the [B, D] hidden rows
    at ``kv_lengths - 1`` *before* the head. Returns
    (last_logits [B, V], (k_cache, v_cache) each [L, B, S, Hkv, hd]).
    """
    x, (ks, vs) = _backbone(params, tokens, config, kv_lengths,
                            implementation, constrain)
    last = jnp.take_along_axis(
        x, jnp.maximum(kv_lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, config, last), (ks, vs)


def llama_decode_step(params: dict, tokens: jnp.ndarray,
                      k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                      lengths: jnp.ndarray, config: LlamaConfig
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step for a batch of sequences.

    tokens [B] (the latest token per sequence); caches
    [L, B, Smax, Hkv, hd]; lengths [B] = current kv length per sequence
    (the new token is written at that position). Returns
    (logits [B, V], new_k_cache, new_v_cache). The engine donates the
    caches so XLA updates them in place.
    """
    c = config
    b = tokens.shape[0]
    hd = c.head_dim
    inv_freq = rope_frequencies(c.head_dim, c.rope_theta, c.rope_scaling)
    positions = lengths[:, None]  # [B, 1] — absolute position of new token
    x = qgather(params["embed"], tokens, c.dtype)[:, None, :]  # [B, 1, D]
    batch_idx = jnp.arange(b)

    # caches ride the scan CARRY: each layer row-scatters its fresh
    # K/V straight into the full buffer and attention reads a dynamic
    # layer slice. Emitting per-layer caches as scan ys instead (the
    # r4 formulation) forced XLA to write every layer's FULL
    # [B, Smax, Hkv, hd] slice into a fresh stacked output each step —
    # a whole-cache copy per decode step on top of attention's reads
    # (measured 3x step time at max_seq=1024 on the CPU probe).
    def layer_fn(carry, scanned):
        x, kc_all, vc_all = carry
        lp, li = scanned
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = qmatmul(h, lp["wq"]).reshape(b, 1, c.n_heads, hd)
        k = qmatmul(h, lp["wk"]).reshape(b, 1, c.n_kv_heads, hd)
        v = qmatmul(h, lp["wv"]).reshape(b, 1, c.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        kc_all = kc_all.at[li, batch_idx, lengths].set(k[:, 0])
        vc_all = vc_all.at[li, batch_idx, lengths].set(v[:, 0])
        kc = jax.lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
        out = decode_attention(q, kc, vc, lengths + 1)
        x = x + qmatmul(out.reshape(b, 1, c.n_heads * hd), lp["wo"])
        x = x + _mlp_block(x, lp, c)
        return (x, kc_all, vc_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer_fn, (x, k_cache, v_cache),
        (params["layers"], jnp.arange(c.n_layers)))
    logits = _logits(params, c, x)[:, 0]  # [B, V]
    return logits, new_k, new_v


def llama_decode_step_paged(params: dict, tokens: jnp.ndarray,
                            k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                            tables: jnp.ndarray, lengths: jnp.ndarray,
                            config: LlamaConfig, *,
                            implementation: str = "auto"
                            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step straight against the paged KV pool.

    Unlike the engine's generic paged path (gather a dense view, run
    :func:`llama_decode_step`, scatter back — O(full cache) extra HBM
    traffic per pass), this hands each new K/V row and the block table
    to the decode walk, which writes the row and attends
    (:func:`..ops.paged_attention.paged_decode_append_attention`), so
    the pool is only ever touched in place. pools [L, Hkv, Np, pg, hd]
    (head-major — see ops/paged_kv.py); tables [B, Mp]; lengths [B] =
    rows already cached (the new token lands at that position).
    Returns (logits [B, V], new_k_pool, new_v_pool). Quantized pools
    (the ``{"q", "s"}`` pytree from ops/paged_kv.py) ride the same
    scan: their rows quantize inside :func:`..ops.paged_kv.pool_write`
    in front of the walk, which dequantizes per page.
    """
    from ..ops.paged_attention import paged_decode_append_attention
    c = config
    b = tokens.shape[0]
    hd = c.head_dim
    inv_freq = rope_frequencies(c.head_dim, c.rope_theta, c.rope_scaling)
    positions = lengths[:, None]
    x = qgather(params["embed"], tokens, c.dtype)[:, None, :]  # [B, 1, D]

    # pools ride the scan CARRY (see llama_decode_step) and never leave
    # it: the kernel takes the whole pool at ``li`` — a layer's slice
    # taken out of the carry is a copy per layer-step — aliased in and
    # out, and lays a live slot's fresh row at position ``lengths``
    # through the table from inside its walk (a tail page the table
    # does not hold drops). ``pool_write`` in front of the walk moved
    # four page sets of all the compiled slots a layer-step to store
    # one row each (ops/paged_kv.py, "Decode's one row")
    def layer_fn(carry, scanned):
        x, kp_all, vp_all = carry     # [L, Hkv, Np, pg, hd]
        lp, li = scanned
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = qmatmul(h, lp["wq"]).reshape(b, 1, c.n_heads, hd)
        k = qmatmul(h, lp["wk"]).reshape(b, 1, c.n_kv_heads, hd)
        v = qmatmul(h, lp["wv"]).reshape(b, 1, c.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        out, kp_all, vp_all = paged_decode_append_attention(
            q[:, 0], k[:, 0], v[:, 0], kp_all, vp_all, tables, lengths + 1,
            layer=li, implementation=implementation)
        x = x + qmatmul(out.reshape(b, 1, c.n_heads * hd), lp["wo"])
        x = x + _mlp_block(x, lp, c)
        return (x, kp_all, vp_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer_fn, (x, k_pool, v_pool),
        (params["layers"], jnp.arange(c.n_layers)))
    logits = _logits(params, c, x)[:, 0]
    return logits, new_k, new_v


def llama_prefill_chunk(params: dict, tokens: jnp.ndarray,
                        k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                        offsets: jnp.ndarray, chunk_lengths: jnp.ndarray,
                        config: LlamaConfig, *,
                        implementation: str = "auto",
                        return_all_logits: bool = False,
                        tree_depths: jnp.ndarray | None = None,
                        tree_masks: jnp.ndarray | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One chunk of a chunked prefill: process ``tokens`` [B, S] whose
    row b starts at absolute position ``offsets[b]``, attending to the
    cache rows written by earlier chunks plus intra-chunk causal, and
    writing this chunk's K/V into the caches at
    ``[offsets, offsets + chunk_lengths)``.

    This is how prompts longer than the widest prefill bucket run
    without truncation: the engine walks the prompt in bucket-width
    chunks (long-context obligation, SURVEY §5). Returns
    (last-position logits [B, V], new_k_cache, new_v_cache); caches
    are [L, B, Smax, Hkv, hd] and meant to be donated.

    ``tree_depths``/``tree_masks`` [B, S] (both or neither) switch the
    chunk into draft-tree verify mode: row i is tree NODE i (node 0 =
    root, topological order), RoPE runs at ``offsets + tree_depths``
    (siblings share a depth), K/V rows land at node index
    ``offsets + i`` (each node gets its own cache row — the engine
    compacts the accepted path afterwards), and attention masks
    in-chunk visibility by the packed ancestor bits instead of causal
    order. ``None`` (the default) traces the exact historical graph.
    """
    from ..ops.attention import attention, tree_attention
    c = config
    b, s = tokens.shape
    smax = k_cache.shape[2]
    hd = c.head_dim
    inv_freq = rope_frequencies(c.head_dim, c.rope_theta, c.rope_scaling)
    node_pos = offsets[:, None] + jnp.arange(s)[None, :]       # [B, S]
    positions = node_pos if tree_depths is None \
        else offsets[:, None] + tree_depths
    valid = jnp.arange(s)[None, :] < chunk_lengths[:, None]    # [B, S]
    # invalid rows scatter out of bounds and drop — padded tail rows
    # must never overwrite live cache
    write_pos = jnp.where(valid, node_pos, smax)
    batch_idx = jnp.arange(b)
    x = qgather(params["embed"], tokens, c.dtype)

    # caches ride the scan carry (see llama_decode_step): the chunk's
    # rows scatter straight into the full buffer instead of each layer
    # emitting its whole cache slice as a scan output
    def layer_fn(carry, scanned):
        x, kc_all, vc_all = carry
        lp, li = scanned
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = qmatmul(h, lp["wq"]).reshape(b, s, c.n_heads, hd)
        k = qmatmul(h, lp["wk"]).reshape(b, s, c.n_kv_heads, hd)
        v = qmatmul(h, lp["wv"]).reshape(b, s, c.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        kc_all = kc_all.at[li, batch_idx[:, None], write_pos].set(
            k.astype(kc_all.dtype), mode="drop")
        vc_all = vc_all.at[li, batch_idx[:, None], write_pos].set(
            v.astype(vc_all.dtype), mode="drop")
        kc = jax.lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
        # causal against the full history: query row s_i sees cache
        # positions <= offsets + s_i (earlier chunks + intra-chunk).
        # Dispatch follows the rest of the stack; q_offset != 0 routes
        # to the XLA path today, and a future history-aware kernel
        # picks it up here. Tree verify swaps the intra-chunk causal
        # mask for the packed ancestor bits.
        if tree_masks is None:
            out = attention(q, kc, vc, causal=True, q_offset=offsets,
                            implementation=implementation)
        else:
            out = tree_attention(q, kc, vc, history_lens=offsets,
                                 chunk_lens=chunk_lengths,
                                 tree_masks=tree_masks)
        x = x + qmatmul(out.reshape(b, s, c.n_heads * hd), lp["wo"])
        x = x + _mlp_block(x, lp, c)
        return (x, kc_all, vc_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer_fn, (x, k_cache, v_cache),
        (params["layers"], jnp.arange(c.n_layers)))
    if return_all_logits:
        # speculative verification wants every fed position's logits
        # (S is the small draft window there, so the [S, V] head is
        # cheap — unlike prompt prefill, where last-only matters)
        return _logits(params, c, x), new_k, new_v
    last = jnp.take_along_axis(
        x, jnp.maximum(chunk_lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, c, last), new_k, new_v


def llama_prefill_chunk_paged(params: dict, tokens: jnp.ndarray,
                              k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                              tables: jnp.ndarray, offsets: jnp.ndarray,
                              chunk_lengths: jnp.ndarray,
                              config: LlamaConfig, *,
                              implementation: str = "auto",
                              return_all_logits: bool = False,
                              tree_depths: jnp.ndarray | None = None,
                              tree_masks: jnp.ndarray | None = None
                              ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One chunk of a chunked prefill straight against the paged pool.

    The generic paged chunk path gathers a dense per-slot view of the
    WHOLE pool allocation, runs :func:`llama_prefill_chunk` on it and
    scatters back — O(full-cache) HBM traffic per chunk, which
    dominates TTFT for long prompts. This variant writes each layer's
    chunk K/V through the block table (only the pages the chunk spans)
    and attends with the ragged chunk kernel
    (:func:`..ops.paged_attention.paged_chunk_attention`), so the pool
    is only ever touched in place — the prefill-side twin of
    :func:`llama_decode_step_paged`.

    tokens [B, S] start at absolute positions ``offsets`` per row;
    pools [L, Hkv, Np, pg, hd] (head-major); tables [B, Mp]. Rows past
    ``chunk_lengths[b]`` are padding: their writes drop (OOB page id)
    and their logits are garbage the caller discards. Returns
    (last-position logits [B, V] — or all positions [B, S, V] with
    ``return_all_logits`` for speculative verify — new_k_pool,
    new_v_pool); pools are meant to be donated.

    ``tree_depths``/``tree_masks`` [B, S] (both or neither) switch the
    chunk into draft-tree verify mode, exactly as in
    :func:`llama_prefill_chunk`: RoPE at ``offsets + tree_depths``,
    K/V rows at node index ``offsets + i``, attention through
    :func:`..ops.paged_attention.paged_tree_attention`'s packed
    ancestor bitmask. ``None`` traces the historical graph.
    """
    from ..ops.paged_attention import (paged_chunk_attention,
                                       paged_tree_attention)
    from ..ops.paged_kv import pool_write
    c = config
    b, s = tokens.shape
    hd = c.head_dim
    inv_freq = rope_frequencies(c.head_dim, c.rope_theta, c.rope_scaling)
    positions = offsets[:, None] + (
        jnp.arange(s)[None, :] if tree_depths is None else tree_depths)
    x = qgather(params["embed"], tokens, c.dtype)

    # pools ride the scan carry (see llama_decode_step_paged): row i of
    # the chunk — tree NODE i in verify mode — lands at position
    # ``offsets + i``; padding rows past ``chunk_lengths`` and positions
    # past the table drop
    def layer_fn(carry, scanned):
        x, kp_all, vp_all = carry     # [L, Hkv, Np, pg, hd]
        lp, li = scanned
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = qmatmul(h, lp["wq"]).reshape(b, s, c.n_heads, hd)
        k = qmatmul(h, lp["wk"]).reshape(b, s, c.n_kv_heads, hd)
        v = qmatmul(h, lp["wv"]).reshape(b, s, c.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        kp_all = pool_write(kp_all, li, tables, offsets, chunk_lengths, k)
        vp_all = pool_write(vp_all, li, tables, offsets, chunk_lengths, v)
        if tree_masks is None:
            out = paged_chunk_attention(q, kp_all, vp_all, tables, offsets,
                                        chunk_lengths, layer=li,
                                        implementation=implementation)
        else:
            out = paged_tree_attention(q, kp_all, vp_all, tables, offsets,
                                       chunk_lengths, tree_masks, layer=li,
                                       implementation=implementation)
        x = x + qmatmul(out.reshape(b, s, c.n_heads * hd), lp["wo"])
        x = x + _mlp_block(x, lp, c)
        return (x, kp_all, vp_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer_fn, (x, k_pool, v_pool),
        (params["layers"], jnp.arange(c.n_layers)))
    if return_all_logits:
        return _logits(params, c, x), new_k, new_v
    last = jnp.take_along_axis(
        x, jnp.maximum(chunk_lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, c, last), new_k, new_v


def make_empty_cache(config: LlamaConfig, batch: int,
                     max_seq: int | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    c = config
    s = max_seq or c.max_seq
    shape = (c.n_layers, batch, s, c.n_kv_heads, c.head_dim)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)
