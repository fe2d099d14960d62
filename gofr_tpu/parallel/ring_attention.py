"""Ring attention: causal attention over sequence-sharded q/k/v.

Long-context path (SURVEY §5 "long-context obligation"): the sequence
axis is sharded over the ``sp`` mesh axis; each device holds a
contiguous sequence chunk and K/V blocks rotate around the ring with
``lax.ppermute`` while a running online-softmax accumulator merges
partial results — attention over sequences far beyond one chip's VMEM/
HBM without ever materializing the full [S, S] score matrix on one
device.

Causality across chunks: with chunk index ``r`` (this device) and the
k/v chunk currently held originating from device ``src``, the block is
- fully visible when ``src < r`` (entirely in the past),
- causal-diagonal when ``src == r``,
- fully masked when ``src > r`` (entirely in the future) — skipped by
  zero-weighting, keeping the loop shape static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, scale, row_off, col_off, mode):
    """Partial attention of q against one k/v block with running-softmax
    stats. mode: 0 full, 1 diagonal-causal, 2 masked."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    sq, skv = q.shape[1], k.shape[1]
    row = row_off + jnp.arange(sq)
    col = col_off + jnp.arange(skv)
    causal = col[None, :] <= row[:, None]
    mask = jnp.where(mode == 2, False,
                     jnp.where(mode == 1, causal, True))
    s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [b,h,q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis_name: str = "sp",
                   scale: float | None = None) -> jnp.ndarray:
    """Causal attention inside shard_map: q/k/v [B, S_local, H, D] are
    this device's sequence chunk; returns the local output chunk."""
    ring = int(jax.lax.axis_size(axis_name))
    rank = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    m_run = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l_run = jnp.zeros((b, h, s_local), jnp.float32)

    row_off = rank * s_local
    k_cur, v_cur = k, v
    src = rank  # origin of the k/v chunk currently held

    for step in range(ring):
        mode = jnp.where(src == rank, 1, jnp.where(src < rank, 0, 2))
        col_off = src * s_local
        o_blk, m_blk, l_blk = _block_attend(q, k_cur, v_cur, scale,
                                            row_off, col_off, mode)
        o_blk = jnp.moveaxis(o_blk, 1, 2)  # [b,q,h,d] -> [b,h,q,d]
        m_new = jnp.maximum(m_run, m_blk)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_blk - m_new)
        acc = acc * alpha[..., None] + o_blk * beta[..., None]
        l_run = l_run * alpha + l_blk * beta
        m_run = m_new
        if step < ring - 1:
            # rotate k/v to the next device; origin index rotates with it
            perm = [(i, (i + 1) % ring) for i in range(ring)]
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            src = jax.lax.ppermute(src, axis_name, perm)

    out = acc / jnp.maximum(l_run, 1e-30)[..., None]  # [b,h,q,d]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)    # [b,q,h,d]


def make_ring_attention(mesh: Mesh, axis_name: str = "sp"):
    """Jitted sequence-sharded causal attention over the mesh.

    Takes global [B, S, H, D] arrays (sequence sharded over
    ``axis_name``) and returns the same layout.
    """
    spec = P(None, axis_name, None, None)

    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def apply(q, k, v):
        sharding = NamedSharding(mesh, spec)
        q = jax.lax.with_sharding_constraint(q, sharding)
        k = jax.lax.with_sharding_constraint(k, sharding)
        v = jax.lax.with_sharding_constraint(v, sharding)
        return fn(q, k, v)

    return jax.jit(apply)
