"""Pallas flash attention for TPU (causal prefill).

Online-softmax tiling: grid ``(B, Hq, Sq/BQ)``; each step streams K/V
blocks for one (batch, head) through VMEM with float32 running
max/sum/accumulator. GQA maps query head ``h`` to kv head ``h // group``
in the BlockSpec index map, so kv heads are never materialized
``group``-fold. Per-sequence lengths arrive via scalar prefetch so
padded batches mask correctly.

VMEM budget: one q block [BQ, D] + full K,V rows [Skv, D] per grid step
— bf16 Skv=4096, D=128 is ~2 MB, well inside ~16 MB VMEM. Longer
sequences should go through ring attention (gofr_tpu/parallel) or the
XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *,
                  scale: float, block_k: int, seq_kv: int, block_q: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # [BQ, D]
    kv_len = len_ref[b]

    bq, d = q.shape
    acc = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    num_blocks = pl.cdiv(seq_kv, block_k)

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k_blk.T                                  # [BQ, BK]
        col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = (col <= row) & (col < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + p @ v_blk
        return acc_new, m_new, l_new

    # causal: kv blocks strictly after this q block contribute nothing
    last = jnp.minimum(num_blocks,
                       pl.cdiv((qi + 1) * block_q, block_k))
    acc, m, l = jax.lax.fori_loop(0, last, body, (acc, m, l))

    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    kv_lengths: jnp.ndarray | None = None,
                    scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Causal flash attention. q [B,Sq,Hq,D]; k,v [B,Skv,Hkv,D]."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(skv, 128))

    # layout: [B, H, S, D] for MXU-friendly tiles
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_k

    if kv_lengths is None:
        kv_lengths = jnp.full((b,), skv, jnp.int32)
    kv_lengths = kv_lengths.astype(jnp.int32)

    grid = (b, hq, sq_p // block_q)

    kernel = functools.partial(_flash_kernel, scale=scale, block_k=block_k,
                               seq_kv=skv_p, block_q=block_q)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda bi, hi, qi, lens: (bi, hi, qi, 0)),
                pl.BlockSpec((1, 1, skv_p, d),
                             lambda bi, hi, qi, lens: (bi, hi // group, 0, 0)),
                pl.BlockSpec((1, 1, skv_p, d),
                             lambda bi, hi, qi, lens: (bi, hi // group, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda bi, hi, qi, lens: (bi, hi, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        # (batch, head, q-block) cells carry no cross-iteration state —
        # the online-softmax accumulator lives within one cell's k loop
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(kv_lengths, qt, kt, vt)

    out = jnp.swapaxes(out, 1, 2)  # [B, Sq_p, Hq, D]
    if pad_q:
        out = out[:, :sq]
    return out
