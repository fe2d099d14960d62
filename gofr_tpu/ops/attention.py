"""Attention: XLA reference implementation + Pallas flash dispatch.

Layouts follow the serving stack: ``q`` is ``[B, Sq, Hq, D]``, ``k``/``v``
are ``[B, Skv, Hkv, D]`` with grouped-query attention when ``Hq > Hkv``.
Logits and softmax run in float32; inputs/outputs stay bf16.

``attention`` is the prefill path (causal, optional per-sequence kv
lengths for padded batches); ``decode_attention`` is the single-token
decode path against a cache. ``implementation='auto'`` uses the Pallas
flash kernel on TPU and the XLA reference elsewhere (CPU tests run the
kernel in interpret mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def is_tpu() -> bool:
    """What every ``implementation='auto'`` in ops/ asks. A backend
    that fails to initialise raises here — it must never read as "not a
    TPU" and quietly select the XLA path."""
    return jax.default_backend() == "tpu"


def _repeat_kv(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """[B, S, Hkv, D] -> [B, S, Hkv*group, D] for GQA."""
    if group == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, group, d)).reshape(
        b, s, h * group, d)


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True,
                  kv_lengths: jnp.ndarray | None = None,
                  q_offset: jnp.ndarray | int = 0,
                  scale: float | None = None) -> jnp.ndarray:
    """Reference attention. q [B,Sq,Hq,D]; k,v [B,Skv,Hkv,D].

    ``q_offset``: absolute position of q row 0 (scalar or [B]) so chunked
    prefill keeps causal alignment against a longer kv history.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    k = _repeat_kv(k, group)
    v = _repeat_kv(v, group)
    scale = scale if scale is not None else d ** -0.5

    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale

    q_pos = jnp.arange(sq)[None, :]  # [1, Sq]
    if isinstance(q_offset, int):
        q_pos = q_pos + q_offset  # [1, Sq]
    else:
        q_pos = q_pos + q_offset[:, None]  # [B, Sq]
    kv_pos = jnp.arange(skv)  # [Skv]

    mask = jnp.ones((q_pos.shape[0], sq, skv), dtype=bool)
    if causal:
        mask = kv_pos[None, None, :] <= q_pos[:, :, None]
    if kv_lengths is not None:
        mask = mask & (kv_pos[None, None, :] < kv_lengths[:, None, None])
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)

    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32))
    return out.astype(q.dtype)


def tree_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   history_lens: jnp.ndarray,
                   chunk_lens: jnp.ndarray,
                   tree_masks: jnp.ndarray,
                   scale: float | None = None) -> jnp.ndarray:
    """Draft-tree verify attention. q [B,Sq,Hq,D] holds Sq tree nodes
    per slot (topological order, node 0 = root); k/v [B,Skv,Hkv,D] hold
    the history followed by the tree nodes at rows
    ``[history_lens, history_lens + chunk_lens)``. Node i attends every
    history row plus exactly the in-tree rows whose bit is set in
    ``tree_masks[b, i]`` (packed ancestor-or-self bits over the
    in-chunk node index — Sq <= 32). Fully-masked rows return zeros,
    matching the paged kernel's denom-clamp contract."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if sq > 32:
        raise ValueError(f"tree width {sq} exceeds the 32-node packed "
                         f"ancestor bitmask")
    group = hq // hkv
    k = _repeat_kv(k, group)
    v = _repeat_kv(v, group)
    scale = scale if scale is not None else d ** -0.5

    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale

    kv_pos = jnp.arange(skv)[None, None, :]               # [1, 1, Skv]
    rel = kv_pos - history_lens[:, None, None]            # [B, 1, Skv]
    bit = (tree_masks[:, :, None].astype(jnp.int32)
           >> jnp.clip(rel, 0, 31)) & 1                   # [B, Sq, Skv]
    visible = (rel < 0) | ((rel < chunk_lens[:, None, None]) & (bit == 1))
    logits = jnp.where(visible[:, None, :, :], logits, NEG_INF)

    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32))
    # a fully-masked node row (padding with no history) softmaxes to a
    # uniform average of garbage — zero it like the kernel does
    any_visible = visible.any(axis=-1)                    # [B, Sq]
    out = jnp.where(any_visible[:, :, None, None], out,
                    jnp.zeros_like(out))
    return out.astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     kv_lengths: jnp.ndarray,
                     scale: float | None = None) -> jnp.ndarray:
    """Single-step decode: q [B,1,Hq,D] against cache [B,Smax,Hkv,D].

    Every cache row at position < kv_lengths[b] participates. This is
    the XLA path; the engine batches many sequences so the matmuls stay
    MXU-shaped even at Sq=1.
    """
    b, sq, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    # einsums run in the cache dtype (bf16 in serving) with f32
    # accumulation — no materialised f32 copy of the [B,Smax,Hkv,D]
    # cache per layer; only the [.., Smax] logits/weights are f32.
    qr = q.astype(k_cache.dtype).reshape(b, sq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k_cache,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(smax)[None, :] < kv_lengths[:, None]  # [B, Smax]
    logits = jnp.where(mask[:, None, None, None, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True,
              kv_lengths: jnp.ndarray | None = None,
              q_offset: jnp.ndarray | int = 0,
              scale: float | None = None,
              implementation: str = "auto",
              block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Prefill attention with implementation dispatch.

    implementation: 'xla' | 'pallas' | 'interpret' | 'auto'.
    The pallas path requires causal attention and int(q_offset)==0 (the
    serving prefill shape); anything else falls back to XLA.
    """
    use_pallas = False
    interpret = False
    if implementation == "pallas":
        use_pallas = True
    elif implementation == "interpret":
        use_pallas, interpret = True, True
    elif implementation == "auto":
        use_pallas = is_tpu() and causal and isinstance(q_offset, int) \
            and q_offset == 0 and q.shape[1] > 1
    if use_pallas:
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, kv_lengths=kv_lengths, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return xla_attention(q, k, v, causal=causal, kv_lengths=kv_lengths,
                         q_offset=q_offset, scale=scale)
