"""Latent (MLA) paged attention — every query head against ONE cached
row a token, read in place through the block table.

Multi-head latent attention (the ``deepseek_v3`` family) caches one
vector per token and layer: the compressed latent ``c`` (``kv_lora_rank``
lanes, after its norm) followed by the shared rope key ``k_pe``
(``qk_rope_head_dim`` lanes, after rope) — 512 + 64 = 576 numbers where
the materialised K and V of 32 heads would be 10,240. The page pool is
:mod:`.paged_kv`'s, with one "head" a row: ``[L, 1, Np, pg, R]``, R the
576 lanes padded with zeros to the 128-lane tile (640). The TPU lays a
576-lane row out in 640 lanes of HBM whatever its logical shape, and
Mosaic refuses to DMA the 576-lane slice of it ("Slice shape along
dimension 4 must be aligned to tiling (128), but is 576"), so the pad
is stated, not hidden: 1,280 bytes stored for 1,152 needed.

Attention over it runs in the ABSORBED form. With W_kvb split per head
into W_UK and W_UV, the caller folds W_UK into the query (``q_lat =
W_UK^T q_nope``, so ``q = q_lat ‖ q_pe`` has the row's width) and W_UV
into the output; what is left is attention in which all heads share
one key row and the value is that row's first ``value_width`` lanes:

    score[h, j] = q[h] . row[j] * scale        (R lanes)
    out[h]      = sum_j softmax(score[h])[j] * row[j, :value_width]

which is multi-QUERY attention: the kernel below is the ragged kernel's
page walk (:mod:`.paged_attention`) with the heads of a query block as
its rows, one DMA per page (the row is key and value both), and the
matmuls in the pool's dtype with float32 accumulation. Same masks as
there: *chunk* (Sq new positions already written at rows ``[history,
history + chunk_len)``, causal) and *decode* (the chunk of one row).

Dispatch as there: 'pallas' (TPU), 'interpret' (the kernel under the
interpreter — CPU tests), 'xla' (gather reference), 'auto'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, SUBLANE, _dispatch
from .paged_kv import LANES, gather_view

#: kv rows folded into the online softmax at once, in whole pages
KV_CHUNK = 256
#: query rows (positions x heads) of one grid cell
Q_ROWS = 512


def check_latent_layout(pool, value_width: int) -> None:
    """Raise ``ValueError`` naming the constraint if the compiled latent
    kernel cannot take ``pool`` [..., 1, Np, pg, R]."""
    heads, _, page, width = pool.shape[-4:]
    if heads != 1:
        raise ValueError(
            f"latent attention kernel: the pool has {heads} heads a row; "
            f"a latent pool keeps ONE vector per token and layer")
    if width % LANES or value_width % LANES or \
            not 0 < value_width <= width:
        raise ValueError(
            f"latent attention kernel: a {width}-lane row with a "
            f"{value_width}-lane value. The TPU stores and DMAs a row in "
            f"whole {LANES}-lane tiles (a 576-lane row occupies 640 in "
            f"HBM and Mosaic refuses the 576-lane slice of it), and the "
            f"value is a tile-aligned slice of the row: pad the row to a "
            f"multiple of {LANES} lanes (models/deepseek.py "
            f"latent_row_width), or use paged_attention='xla'.")
    if page % SUBLANE:
        raise ValueError(
            f"latent attention kernel: page size {page} is not a "
            f"multiple of {SUBLANE} (a page is DMA'd to row offset "
            f"j * page of a VMEM buffer tiled in {SUBLANE}-row sublanes)")


def _latent_kernel(tables_ref, history_ref, chunk_ref, layer_ref,
                   q_ref, pool_hbm, o_ref, buf, acc_ref, m_ref, l_ref, sems,
                   *, page: int, pages_per_chunk: int, max_pages: int,
                   n_pages: int, scale: float, block_q: int, heads: int,
                   value_width: int):
    li = layer_ref[0]
    b = pl.program_id(0)
    qb = pl.program_id(1)
    chunk = pages_per_chunk * page
    hist = history_ref[b]
    clen = chunk_ref[b]
    # rows this q block may attend: the history plus the in-chunk prefix
    # ending at the block's last row, bounded by what the chunk wrote
    kv_limit = hist + jnp.minimum((qb + 1) * block_q, clen)
    n_chunks = jnp.maximum(pl.cdiv(kv_limit, chunk), 1)

    def page_dmas(ci, slot):
        dmas = []
        for j in range(pages_per_chunk):
            # tail chunks index past the table: clamp — their rows are
            # masked off below, they just must not fault
            page_idx = jnp.minimum(ci * pages_per_chunk + j, max_pages - 1)
            pid = jnp.minimum(tables_ref[b, page_idx], n_pages - 1)
            dmas.append(pltpu.make_async_copy(
                pool_hbm.at[li, 0, pid],
                buf.at[slot, pl.ds(j * page, page), :], sems.at[slot, j]))
        return dmas

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    for dma in page_dmas(0, 0):
        dma.start()
    # q arrives flattened to [block_q * heads, R]: row r is query
    # position history + qb * block_q + r // heads
    rows = block_q * heads
    ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    q_pos = hist + qb * block_q + ridx // heads
    q = q_ref[0]                                        # [rows, R]

    def body(ci, _):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _():
            for dma in page_dmas(ci + 1, jax.lax.rem(ci + 1, 2)):
                dma.start()

        for dma in page_dmas(ci, slot):
            dma.wait()
        kv = buf[slot]                                  # [chunk, R]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, chunk]
        pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # causal against history + in-chunk prefix; the pos < hist +
        # clen bound turns zero-length slots into exact zeros through
        # the denominator's clamp
        visible = (pos <= q_pos) & (pos < hist + clen)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # mask p explicitly: a fully masked row has s == m_new == NEG_INF
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :value_width],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [rows, C]
        m_ref[:] = m_new
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    denom = jnp.maximum(l_ref[:], 1e-30)  # all-masked rows: zeros, not NaN
    o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _pick_block_q(sq: int, heads: int) -> int:
    """Largest power-of-two divisor of Sq whose block stays within
    ``Q_ROWS`` query rows (at least one position)."""
    best = 1
    for cand in (2, 4, 8, 16, 32, 64, 128):
        if sq % cand == 0 and cand * heads <= Q_ROWS:
            best = cand
    return best


def latent_chunk_attention_pallas(q, pool, tables, history_lens, chunk_lens,
                                  *, value_width: int, scale: float,
                                  layer=None, block_q: int | None = None,
                                  interpret: bool = False):
    """Ragged latent chunk attention. q [B, Sq, H, R] holds Sq new
    positions per slot (``q_lat ‖ q_pe``), already written into the
    pool at rows ``[history_lens, history_lens + chunk_lens)``; ``pool``
    [L, 1, Np, pg, R] read at ``layer`` (a traced scalar, prefetched),
    or one layer's [1, Np, pg, R] with ``layer=None``. Returns
    [B, Sq, H, value_width]: per head, the attention-weighted mean of
    the rows' first ``value_width`` lanes. Rows past ``chunk_lens[b]``
    are padding the caller discards; zero-length slots return zeros."""
    if layer is None:
        pool, layer = pool[None], 0
    b, sq, heads, width = q.shape
    _, _, n_pages, page, _ = pool.shape
    max_pages = tables.shape[1]
    if block_q is None:
        block_q = _pick_block_q(sq, heads)
    if sq % block_q:
        raise ValueError(f"block_q {block_q} must divide Sq {sq}")
    rows = block_q * heads
    if not interpret:
        check_latent_layout(pool, value_width)
        if rows % SUBLANE:
            raise ValueError(
                f"latent attention kernel: {block_q} positions x {heads} "
                f"heads = {rows} query rows a block, not a multiple of "
                f"{SUBLANE}")
    pages_per_chunk = max(1, min(max_pages, KV_CHUNK // page))
    chunk = pages_per_chunk * page
    kernel = functools.partial(
        _latent_kernel, page=page, pages_per_chunk=pages_per_chunk,
        max_pages=max_pages, n_pages=n_pages, scale=scale,
        block_q=block_q, heads=heads, value_width=value_width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, sq // block_q),
        in_specs=[pl.BlockSpec((1, rows, width), lambda i, j, *_: (i, j, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],  # the pool stays in HBM
        out_specs=pl.BlockSpec((1, rows, value_width),
                               lambda i, j, *_: (i, j, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, width), pool.dtype),
            pltpu.VMEM((rows, value_width), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2, pages_per_chunk)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, sq * heads, value_width), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(tables.astype(jnp.int32), history_lens.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(pool.dtype).reshape(b, sq * heads, width), pool)
    return out.reshape(b, sq, heads, value_width)


def latent_decode_attention_pallas(q, pool, tables, lengths, *,
                                   value_width: int, scale: float,
                                   layer=None, interpret: bool = False):
    """Decode: q [B, H, R] is the one new position per slot, ``lengths``
    [B] the valid rows AFTER this step's write."""
    return latent_chunk_attention_pallas(
        q[:, None], pool, tables, jnp.maximum(lengths - 1, 0),
        jnp.minimum(lengths, 1), value_width=value_width, scale=scale,
        layer=layer, block_q=1, interpret=interpret)[:, 0]


# ---------------------------------------------------------- xla reference

def latent_chunk_attention_xla(q, pool, tables, history_lens, chunk_lens, *,
                               value_width: int, scale: float, layer=None):
    """Reference path: gather each slot's rows [B, Mp*pg, R] and run
    dense masked multi-query attention in float32. Correct everywhere;
    materialises the slot view per call."""
    one = pool[None] if layer is None else \
        jax.lax.dynamic_index_in_dim(pool, layer, 0)
    view = gather_view(one, tables)[0, :, :, 0].astype(jnp.float32)
    sq = q.shape[1]
    scores = jnp.einsum("bqhr,bkr->bhqk", q.astype(jnp.float32), view,
                        precision="highest") * scale
    pos = jnp.arange(view.shape[1])[None, None, :]
    q_pos = history_lens[:, None, None] + jnp.arange(sq)[None, :, None]
    total = history_lens + chunk_lens
    visible = (pos <= q_pos) & (pos < total[:, None, None])
    scores = jnp.where(visible[:, None], scores, NEG_INF)
    out = jnp.einsum("bhqk,bkc->bqhc", jax.nn.softmax(scores, axis=-1),
                     view[..., :value_width], precision="highest")
    # zero-length slots: exact zeros, as the kernel's clamp gives
    return jnp.where(total[:, None, None, None] > 0, out,
                     jnp.zeros_like(out)).astype(q.dtype)


def latent_decode_attention_xla(q, pool, tables, lengths, *,
                                value_width: int, scale: float, layer=None):
    return latent_chunk_attention_xla(
        q[:, None], pool, tables, jnp.maximum(lengths - 1, 0),
        jnp.minimum(lengths, 1), value_width=value_width, scale=scale,
        layer=layer)[:, 0]


# --------------------------------------------------------------- dispatch

def latent_chunk_attention(q, pool, tables, history_lens, chunk_lens, *,
                           value_width: int, scale: float, layer=None,
                           implementation: str = "auto"):
    return _dispatch(implementation, latent_chunk_attention_pallas,
                     latent_chunk_attention_xla, q, pool, tables,
                     history_lens, chunk_lens, value_width=value_width,
                     scale=scale, layer=layer)


def latent_decode_attention(q, pool, tables, lengths, *, value_width: int,
                            scale: float, layer=None,
                            implementation: str = "auto"):
    return _dispatch(implementation, latent_decode_attention_pallas,
                     latent_decode_attention_xla, q, pool, tables, lengths,
                     value_width=value_width, scale=scale, layer=layer)
