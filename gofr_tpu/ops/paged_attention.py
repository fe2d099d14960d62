"""Ragged paged attention — pages read in place via block table.

The paged KV layout (:mod:`.paged_kv`) stores K/V in a head-major,
lane-packed page pool ``[L, Hg, Np, pg, W]`` with per-slot block
tables. The generic engine path materialises a dense per-slot view of
the WHOLE pool allocation every K-step pass (``gather_view``), which
costs O(full-cache) extra HBM traffic on top of attention's own reads.

The kernels here remove the materialisation: a grid cell walks ONLY
the pages covering the rows it may attend (ragged — shorter slots read
fewer pages), DMA-ing pages HBM→VMEM double-buffered and folding them
into an online-softmax accumulator. The pool is never reshaped,
copied, or padded to the per-slot maximum.

One algorithm, two ``pallas_call``s that share the fold
(:func:`_softmax_fold`, :func:`_row_scales`):

- the *q-block* walk (:func:`_ragged_kernel`), a cell a (slot, head
  group, q block), serves the paths with many query rows a slot; they
  differ only in the mask:

  - *chunk* (``paged_chunk_attention``): Sq new positions per slot —
    chunked prefill, prefix-cache suffix reattachment — already
    written at pool rows ``[history, history + chunk_len)``; query row
    i attends causally to rows ``<= history + i``.
  - *tree* (``paged_tree_attention``): speculative verify; the Sq rows
    are NODES of a draft tree and in-chunk visibility is a packed
    ancestor bitmask instead of causal order (see below).

- the *decode* walk (:func:`_decode_kernel`, ``paged_decode_attention``)
  serves the chunk of ONE row — ``history = length - 1`` — where the
  price is the walk itself: a cell a slot, every head group of a page
  in one fold (see "the decode walk" below). It is also decode's
  WRITER (``paged_decode_append_attention``, what the dense model step
  calls): the step's fresh row of a live slot is laid over the last
  fold's buffer and the tile-aligned block that holds it goes back to
  the pool, aliased in and out ("the walk writes" below).

What the TPU's compiler takes (established by ahead-of-time compiles
for v5e — ``tests/test_tpu_compile.py`` keeps them):

- Mosaic tiles the trailing two dims of every memref, 8 sublanes x 128
  lanes. ``pool.at[layer, h, pid]`` slices only untiled leading dims,
  so the head axis leads (head-major) and a page is one contiguous
  block.
- The kernel takes the WHOLE pool, all layers, and the layer index as
  a prefetched scalar. A layer's slice of the scan carry handed in as
  the operand is materialised by XLA — a copy of one layer's K and V
  per layer-step — and the pool must arrive row-major, which is why
  :mod:`.paged_kv` writes it by whole pages.
- The block's last dim must fill the 128 lanes. ``head_dim`` 64 alone
  does not ("Slice shape along dimension 3 must be aligned to tiling
  (128), but is 64"), so :mod:`.paged_kv` packs ``pack = 128 //
  head_dim`` kv heads into one row, and a grid cell here attends a
  whole head GROUP at once: its q block carries the group's query
  heads side by side, head ``p``'s rows zero outside lanes
  ``[p*hd, (p+1)*hd)``. One ``q @ k^T`` over the full row then scores
  every head against its own lanes only, and ``p @ v`` leaves head
  ``p``'s output in the same lanes — the MXU contracts 128 lanes
  either way, and the wrapper picks each head's lanes back out.
- The q/out block's row count must be a multiple of 8: the GQA group
  axis is zero-padded up to the tile and sliced back after the call.
- A page is DMA'd to row offset ``j * page`` of the VMEM double
  buffer, so the page size must be a multiple of 8.
- What the decode walk asked of Mosaic, and got at the first compile
  (v5e, jax 0.9.0): ``pool.at[layer, :, pid]`` — EVERY head group of
  a page as one strided DMA ``[Hg, page, W]`` (only untiled leading
  dims are sliced) into ``buf.at[half, :, pl.ds(j * page, page), :]``;
  a DMA started in one grid step and waited in the next (the axis is
  ``"arbitrary"``; v5e has one TensorCore); SMEM scratch that lives
  across grid steps; ``dot_general`` batched over the head-group axis
  with the contraction on the LAST dim of both operands
  (``[Hg, rows, W] x [Hg, kv, W]``); bf16 x bf16 with a float32
  result; int8 -> bf16 of a whole fold; a float32 lhs split into
  three bf16 terms, concatenated along sublanes as float32 and cast
  (32 rows: a bf16 tile is 16). What it refused: a fold of 8 MiB —
  two of them are 16.19 MiB of scoped VMEM against the 16 MiB a
  kernel gets unasked; folds of 1, 2 and 4 MiB compile.
- What the walk's write asked, and got at the first compile: the pool
  as an aliased operand (``input_output_aliases``) inside the model's
  layer scan with no copy of it; in one cell, a page read from the
  pool by one DMA and a block of it written back by another
  (``buf.at[half, :, pl.ds(r, 16), :]`` to ``pool.at[layer, :, pid,
  pl.ds(r0, 16), :]``, both offsets dynamic multiples of the block). A
  ONE-row DMA is refused ("Slice shape along dimension 3 must be
  aligned to tiling (8), but is 1"): the write is a block's.
- What the chip said (PERF.md section 6, PR 29): a fold of the q-block
  walk costs 0.43 us and a cell 0.62 us WHATEVER they hold, because a
  fold is a chain (matmul -> row max -> exp -> row sum -> matmul ->
  accumulate) on one 8 x 128 tile, priced by latency; bf16 instead of
  float32 operands change nothing there. More work under one chain —
  head groups batched, 256-512 kv rows a fold — is what made a fold
  cost its bytes (78% of 819 GB/s at 31 slots of ~800 rows).

A shape the kernel cannot take is a ``ValueError`` from
:func:`check_kernel_layout` — at engine construction, not a Mosaic
trace out of warmup — never a silent switch to another path.

Layouts:
- ``q``        [B, Sq, Hq, hd] (decode: [B, Hq, hd])
- ``k_pool``   [L, Hg, Np, pg, W] with ``layer=`` a (traced) index —
  what the model steps pass; or one layer's [Hg, Np, pg, W] with
  ``layer=None`` (viewed as a pool of one layer: kernel tests, tools)
- ``tables``   [B, Mp] int32 — page ids, out-of-range = unallocated
- out          like ``q``

Each ``paged_*_attention`` dispatches: 'pallas' (TPU), 'interpret'
(the kernel under the interpreter — CPU tests), 'xla' (gather
reference), 'auto' (pallas on TPU, xla elsewhere).

Quantized pools (``kv_dtype="int8"``) arrive as the two-leaf pytree
``{"q": int8 [L, Hg, Np, pg, W], "s": f32 [L, Hg, Np, 1, SW]}`` from
:mod:`.paged_kv`. The kernels DMA each int8 page plus its one-row
scale block and never dequantize a page: the scale of kv row t is
constant along the contraction, so it multiplies the SCORE column
(``(q @ codes^T) * ks``) and the probability column (``(p * vs) @
codes``) instead — both have the kv row on the lane axis, which is
where the lane-major scale row already lies. The ``_xla`` references
dequantize the gathered view with the same scales.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import is_tpu
from .paged_kv import LANES, gather_view, pool_write

NEG_INF = -1e30

#: Mosaic tiles the second-to-last dim of a VMEM memref in units of 8
#: rows: a BlockSpec block or memref slice along it must cover a
#: multiple of 8.
SUBLANE = 8
#: ... and of a bf16 memref in units of 16 (two rows share a sublane)
BF16_SUBLANE = 16


def _pad_group(group: int, block_q: int = 1) -> int:
    """Smallest padded GQA group size such that a q block of
    ``block_q * group_padded`` rows is sublane-aligned (multiple of
    8). ``block_q >= 8`` (always a power of two) needs no padding."""
    step = SUBLANE // math.gcd(block_q, SUBLANE)
    return -(-group // step) * step


def _split_pool(pool):
    """(codes, scales-or-None) for either pool representation."""
    if isinstance(pool, dict):
        return pool["q"], pool["s"]
    return pool, None


def check_kernel_layout(pool) -> None:
    """Raise ``ValueError`` naming the constraint if the compiled
    kernel cannot take ``pool`` (any leading dims; the trailing two
    are ``[page, W]``). Interpret mode has no tiling and skips this."""
    page, width = _split_pool(pool)[0].shape[-2:]
    if width % LANES:
        raise ValueError(
            f"paged-attention kernel: pool row width {width} is not a "
            f"multiple of {LANES} lanes. The TPU DMAs whole "
            f"[page, width] blocks and tiles their last dim by {LANES}; "
            f"head_dim < {LANES} needs {LANES} // head_dim kv heads per "
            f"device to pack into one row (ops/paged_kv.head_pack) — "
            f"this model's head_dim / kv-head count cannot. Use "
            f"paged_attention='xla' or 'view' explicitly.")
    if page % SUBLANE:
        raise ValueError(
            f"paged-attention kernel: page size {page} is not a "
            f"multiple of {SUBLANE}. The TPU kernel DMAs whole pages "
            f"to row offset j * page of a VMEM buffer tiled in "
            f"{SUBLANE}-row sublanes — use a page_size multiple of "
            f"{SUBLANE} (or paged_attention='xla'/'view' explicitly).")


# ------------------------------------------------- the fold, shared

def _mxu_dot(lhs, rhs, contract: int):
    """float32 ``lhs [..., R, X] . rhs`` over ``rhs``'s axis ``contract``
    (leading axes batched), with ``rhs`` a tile of the pool entering
    the MXU in the dtype it is STORED in. A bf16 x bf16 product is exact
    in float32, so nothing is rounded that the float32 matmul keeps:

    - a bf16 or int8 tile (int8 codes are exact in bf16) against a bf16
      ``lhs`` is one pass;
    - against a float32 ``lhs`` (the probabilities) the lhs is split
      into three bf16 terms ``hi + mid + lo`` that sum to it exactly —
      what a float32 matmul does inside, except that the tile, which is
      all ``hi``, is loaded into the MXU once instead of six times;
    - a float32 tile (tests, tools) takes the float32 matmul."""
    batch = tuple(range(lhs.ndim - 2))
    dims = (((lhs.ndim - 1,), (contract,)), (batch, batch))

    def dot(a, b):
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)

    if rhs.dtype not in (jnp.bfloat16, jnp.int8):
        return dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32))
    rhs = rhs.astype(jnp.bfloat16)
    if lhs.dtype == jnp.bfloat16:
        return dot(lhs, rhs)
    terms, rest = [], lhs.astype(jnp.float32)
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16).astype(jnp.float32))
        rest = rest - terms[-1]
    r = lhs.shape[-2]
    pad = -3 * r % BF16_SUBLANE        # a bf16 tile is 16 rows
    if pad:
        terms.append(jnp.zeros((*lhs.shape[:-2], pad, lhs.shape[-1]),
                               jnp.float32))
    out = dot(jnp.concatenate(terms, axis=-2).astype(jnp.bfloat16), rhs)
    return (out[..., :r, :] + out[..., r:2 * r, :]
            + out[..., 2 * r:3 * r, :])


def _softmax_fold(s, visible, pv, acc_ref, m_ref, l_ref):
    """Fold one block of scores ``s [..., rows, kv]`` into the online
    softmax: ``visible`` masks it, ``pv(p)`` is the probabilities'
    product with the block's values."""
    s = jnp.where(visible, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # mask p explicitly: a fully-masked row has s == m_new == NEG_INF
    # and exp(s - m_new) would be 1
    p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + pv(p)
    m_ref[...] = m_new


def _row_scales(pages, row_head, *, page: int, pack: int):
    """Per-(q row, kv row) dequant scales ``[..., rows | 1, kv]`` of a
    fold, from its pages' scale rows ``[..., 1, SW]`` in walk order.
    Page j's row holds packed head p's ``page`` scales at lanes
    [p*page, (p+1)*page); the fold's kv rows want them at lanes
    [j*page, (j+1)*page) — a static lane rotate per (page, head) and a
    select per q row's head, a 128-lane tile of the fold at a time."""
    sw = pages[0].shape[-1]
    axis = pages[0].ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1,) * axis + (sw,), axis)
    per_tile = max(1, LANES // page)
    tiles = []
    for t in range(0, len(pages), per_tile):
        tile, out = pages[t:t + per_tile], None
        for p in range(pack):
            sc = None
            for j, row in enumerate(tile):
                shift = ((j - p) * page) % sw
                if shift:
                    row = pltpu.roll(row, shift, axis)
                sc = row if sc is None else \
                    jnp.where(lane >= j * page, row, sc)
            sc = sc[..., :len(tile) * page]
            out = sc if out is None else \
                jnp.where(row_head == p, sc, out)
        tiles.append(out)
    return tiles[0] if len(tiles) == 1 else \
        jnp.concatenate(tiles, axis=-1)


# ------------------------------------------------------------------ kernel
#
# Tree verify: the Sq rows of a verify pass are NODES of a draft tree
# (node 0 = the committed root token, nodes packed topologically so
# every parent index < child index), not a linear chunk. Node i must
# attend the full history plus its ANCESTOR nodes only — two sibling
# branches must not see each other, or the verify logits would differ
# from the sequential decode they stand in for. The per-node ancestor
# set rides as a packed int32 bitmask (bit j set iff node j is an
# ancestor of node i, or j == i), which caps the tree at 32 nodes —
# far above any sane draft budget.

def _ragged_kernel(tables_ref, history_ref, chunk_ref, layer_ref, *refs,
                   page: int,
                   pages_per_chunk: int, max_pages: int, n_pages: int,
                   scale: float, block_q: int, group: int, pack: int,
                   tree: bool, quantized: bool):
    li = layer_ref[0]
    tree_ref = None
    if tree:
        tree_ref, *refs = refs
    q_ref, k_hbm, v_hbm, *refs = refs
    ks_hbm = vs_hbm = ks_buf = vs_buf = None
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         acc_ref, m_ref, l_ref, sems) = refs
    else:
        o_ref, k_buf, v_buf, acc_ref, m_ref, l_ref, sems = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    qb = pl.program_id(2)
    chunk = pages_per_chunk * page
    hist = history_ref[b]
    clen = chunk_ref[b]
    # rows this q-block may attend to: the full history plus the
    # in-chunk prefix ending at the block's last row, bounded by what
    # the chunk actually wrote. Tree nodes are packed topologically
    # (parent < child), so a node's ancestors all sit at lower rows and
    # the same bound is exact for them. clen == 0 rows are padding —
    # they read whatever the walk covers and are discarded upstream.
    kv_limit = hist + jnp.minimum((qb + 1) * block_q, clen)
    n_chunks = jnp.maximum(pl.cdiv(kv_limit, chunk), 1)

    def page_dmas(ci, slot):
        # one DMA per page: pages are scattered in the pool, so a
        # chunk is pages_per_chunk independent copies — each a
        # CONTIGUOUS [page, W] block of the head-major pool. A
        # quantized pool adds the page's [1, SW] f32 scale row.
        dmas = []
        for j in range(pages_per_chunk):
            # tail chunks index past the table: clamp — their rows are
            # masked off below, they just must not fault
            page_idx = jnp.minimum(ci * pages_per_chunk + j,
                                   max_pages - 1)
            pid = jnp.minimum(tables_ref[b, page_idx], n_pages - 1)
            dst = pl.ds(j * page, page)
            dmas.append(pltpu.make_async_copy(
                k_hbm.at[li, h, pid], k_buf.at[slot, dst, :],
                sems.at[slot, 0, j]))
            dmas.append(pltpu.make_async_copy(
                v_hbm.at[li, h, pid], v_buf.at[slot, dst, :],
                sems.at[slot, 1, j]))
            if quantized:
                dmas.append(pltpu.make_async_copy(
                    ks_hbm.at[li, h, pid], ks_buf.at[slot, j],
                    sems.at[slot, 2, j]))
                dmas.append(pltpu.make_async_copy(
                    vs_hbm.at[li, h, pid], vs_buf.at[slot, j],
                    sems.at[slot, 3, j]))
        return dmas

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    for dma in page_dmas(0, 0):
        dma.start()
    # q arrives pre-flattened to [BQ*group, W] rows: row r is query
    # node r // group (absolute position history + qb*BQ + that), and
    # within a node the rows run [pack, group // pack] — packed kv
    # head, then its (padded) GQA query heads
    rows = block_q * group
    ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    node = ridx // group
    q_pos = hist + qb * block_q + node
    row_head = (ridx % group) // (group // pack)
    if tree:
        # broadcast each row's packed ancestor mask out of SMEM: a
        # gather by traced per-row index is not Mosaic-expressible, but
        # block_q is static and small, so an unrolled select ladder
        # over the block's nodes builds the [rows, 1] mask vector from
        # scalar loads
        mask_row = jnp.zeros((rows, 1), jnp.int32)
        for t in range(block_q):
            mask_row = jnp.where(node == t,
                                 tree_ref[b, qb * block_q + t], mask_row)
    qf = q_ref[0, 0].astype(jnp.float32) * scale        # [rows, W]

    def body(ci, _):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _():
            for dma in page_dmas(ci + 1, jax.lax.rem(ci + 1, 2)):
                dma.start()

        for dma in page_dmas(ci, slot):
            dma.wait()
        s = jax.lax.dot_general(
            qf, k_buf[slot].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [rows, chunk]
        if quantized:
            s = s * _row_scales(
                [ks_buf[slot, j] for j in range(pages_per_chunk)],
                row_head, page=page, pack=pack)
        pos = ci * chunk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        if tree:
            # history rows (pos < hist) are visible to every node; tree
            # rows (rel = pos - hist in [0, clen)) are visible iff the
            # node's ancestor bit for them is set
            rel = pos - hist
            bit = jax.lax.shift_right_logical(
                mask_row, jnp.clip(rel, 0, 31)) & 1
            visible = (rel < 0) | ((rel < clen) & (bit == 1))
        else:
            # causal against history + in-chunk prefix: position p is
            # visible to query q_idx iff p <= history + q_idx (the
            # chunk's own row q_idx was written before attention). The
            # pos < hist + clen bound is a no-op for valid rows but
            # turns zero-length slots — hist == clen == 0, every
            # position masked — into exact zeros via the denom clamp
            # instead of finite garbage.
            visible = (pos <= q_pos) & (pos < hist + clen)

        def pv(p):
            if quantized:
                p = p * _row_scales(
                    [vs_buf[slot, j] for j in range(pages_per_chunk)],
                    row_head, page=page, pack=pack)
            return jax.lax.dot_general(
                p, v_buf[slot].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [rows, W]

        _softmax_fold(s, visible, pv, acc_ref, m_ref, l_ref)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    denom = jnp.maximum(l_ref[:], 1e-30)  # all-masked rows: zeros, not NaN
    o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _pick_block_q(sq: int) -> int:
    """Largest power-of-two divisor of Sq, capped at 128 (one MXU pass
    of q rows); non-power-of-two chunk widths fall back to smaller
    divisors so the grid tiles Sq exactly."""
    for cand in (128, 64, 32, 16, 8, 4, 2, 1):
        if sq % cand == 0:
            return min(cand, sq)
    return 1


def _group_rows(q, hg: int, pack: int, block_q: int):
    """q [B, Sq, Hq, hd] -> ([B, Hg, Sq*group, W], padded GQA group):
    the q rows flattened OUTSIDE the kernel so each grid cell reads
    plain 2D [BQ*group, W] blocks — the q-block axis slices the (tiled)
    second-to-last dim in BQ*group-row steps, which must be multiples
    of 8: narrow blocks (decode, a spec-verify window with block_q=1)
    pad the GQA axis up to the tile and the pad comes back off the
    output (:func:`_ungroup_rows`). Each packed head's rows are zero
    outside its own lanes (block diagonal)."""
    b, sq, hq, hd = q.shape
    g = hq // (hg * pack)
    gp = _pad_group(g, block_q * pack)
    q6 = q.reshape(b, sq, hg, pack, g, hd)
    if gp != g:
        q6 = jnp.pad(q6, ((0, 0),) * 4 + ((0, gp - g), (0, 0)))
    eye = jnp.eye(pack, dtype=q.dtype)[None, None, None, :, None, :, None]
    q4 = (q6[..., None, :] * eye).transpose(0, 2, 1, 3, 4, 5, 6) \
        .reshape(b, hg, sq * pack * gp, pack * hd)
    return q4, gp


def _ungroup_rows(out, sq: int, hq: int, hd: int, pack: int, gp: int):
    """The kernel's [B, Hg, Sq*group, W] back to [B, Sq, Hq, hd]: packed
    head p's output sits in its rows' lanes [p*hd, (p+1)*hd)."""
    b, hg = out.shape[:2]
    g = hq // (hg * pack)
    out = out.reshape(b, hg, sq, pack, gp, pack, hd)
    out = jnp.stack([out[:, :, :, p, :g, p] for p in range(pack)], axis=3)
    return out.transpose(0, 2, 1, 3, 4, 5).reshape(b, sq, hq, hd)


def _ragged_attention(q, k_pool, v_pool, tables, history_lens, chunk_lens,
                      tree_masks, *, layer, scale, block_q, interpret):
    """The pallas_call behind all three paths. q [B, Sq, Hq, hd]; the
    pools whole, [L, Hg, Np, pg, W], read at ``layer`` (a traced
    scalar, prefetched) — or one layer's [Hg, Np, pg, W] with
    ``layer=None``, viewed as a pool of one layer (a bitcast)."""
    if layer is None:
        k_pool, v_pool = jax.tree.map(lambda x: x[None], (k_pool, v_pool))
        layer = 0
    k_codes, k_scales = _split_pool(k_pool)
    v_codes, v_scales = _split_pool(v_pool)
    quantized = k_scales is not None
    tree = tree_masks is not None
    b, sq, hq, hd = q.shape
    _, hg, n_pages, page, width = k_codes.shape
    _, max_pages = tables.shape
    pack = width // hd
    scale = scale if scale is not None else hd ** -0.5
    if tree and sq > 32:
        raise ValueError(f"tree width {sq} exceeds the 32-node packed "
                         f"ancestor bitmask")
    if block_q is None:
        block_q = _pick_block_q(sq)
    if sq % block_q != 0:
        raise ValueError(f"block_q {block_q} must divide Sq {sq}")
    if not interpret:
        check_kernel_layout(k_pool)

    # ~128 kv rows per softmax fold, in whole pages
    pages_per_chunk = max(1, min(max_pages, LANES // page))
    chunk = pages_per_chunk * page

    q4, gp = _group_rows(q, hg, pack, block_q)
    group = pack * gp
    kernel = functools.partial(
        _ragged_kernel, page=page, pages_per_chunk=pages_per_chunk,
        max_pages=max_pages, n_pages=n_pages, scale=scale,
        block_q=block_q, group=group, pack=pack, tree=tree,
        quantized=quantized)
    rows = block_q * group
    q_spec = pl.BlockSpec((1, 1, rows, width),
                          lambda i, j, k, *_: (i, j, k, 0),
                          memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)  # pools stay in HBM
    # scale rows ride as two extra HBM operands + two f32 double
    # buffers; the semaphore array gains a pair of rows for them
    scale_bufs = [pltpu.VMEM((2, pages_per_chunk, 1, k_scales.shape[-1]),
                             jnp.float32)] * 2 if quantized else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if tree else 4,
        grid=(b, hg, sq // block_q),
        in_specs=[q_spec] + [in_hbm] * (4 if quantized else 2),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk, width), k_codes.dtype),
            pltpu.VMEM((2, chunk, width), v_codes.dtype),
            *scale_bufs,
            pltpu.VMEM((rows, width), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 4 if quantized else 2,
                                     pages_per_chunk)),
        ],
    )
    args = [tables.astype(jnp.int32), history_lens.astype(jnp.int32),
            chunk_lens.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1)]
    if tree:
        args.append(tree_masks.astype(jnp.int32))
    args += [q4, k_codes, v_codes]
    if quantized:
        args += [k_scales, v_scales]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hg, sq * group, width),
                                       q.dtype),
        grid_spec=grid_spec,
        # grid cells (slot, head group, q block) are independent:
        # declaring them parallel lets Mosaic software-pipeline across
        # cells instead of fencing between iterations
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(*args)
    return _ungroup_rows(out, sq, hq, hd, pack, gp)


# --------------------------------------------------------- decode walk
#
# Decode is the chunk of one row, and the q-block grid above prices it
# badly (v5e, PERF.md section 6, PR 29): a cell (slot, head group)
# costs 0.62 us and a fold 0.43 us whatever they hold — a cell starts
# its first fold and waits on it at once, a fold is a chain of
# dependent operations on one 8 x 128 tile — at 512 cells a layer-step
# for 32 slots x 16 head groups, one fold even for a slot that holds
# no request. The walk below is the same algorithm with the parameters
# one row a slot wants: a cell is a SLOT, a fold carries every head
# group of its pages (one strided DMA a page and side, 16 or 8 times
# the work under one chain), the next fold — the next live slot's
# first one included — is in flight while this one is folded, a slot
# without rows costs a grid step and a zero store, and K and V enter
# the MXU as stored (:func:`_mxu_dot`).

#
# The walk writes. The step's fresh K/V row of a slot belongs at row
# ``length - 1``: the last row of the last page of the slot's last fold,
# a page the walk has in VMEM anyway. Written in FRONT of the walk by
# whole pages (ops/paged_kv.pool_write) one row a slot cost four page
# sets of every compiled slot a layer-step — three times the walk
# (PERF.md section 6, PR 31). So for a plain pool the pools are aliased
# in and out of the call, the fresh rows ride in packed as the pool
# packs them, and a LIVE slot's cell lays its row over the last fold's
# buffer after that fold's wait, attends as ever, and copies the
# tile-aligned block that holds the row back to its page, waited on
# before the cell ends. A slot that is not live writes nothing; a tail
# page the table does not hold drops the row — what ``pool_write`` did
# to both.

#: bytes of K and V one fold of the decode walk brings in; two folds
#: are resident (the double buffer)
FOLD_BYTES = 2 << 20


def _fold_pages(hg: int, page: int, width: int, itemsize: int,
                max_pages: int) -> int:
    """Pages a fold of the decode walk carries: about ``FOLD_BYTES`` of
    K and V over all ``hg`` head groups, in whole 128-row tiles of kv
    rows — at most eight, so a fold's float32 scores stay a few vector
    registers a head group — and at most the table."""
    tile = max(1, LANES // page)
    if (tile * page) % LANES:       # a page that does not tile the lanes
        return min(tile, max_pages)
    want = FOLD_BYTES // (2 * hg * page * width * itemsize)
    return max(1, min(max(tile, want // tile * tile), 8 * tile, max_pages))


def _has_rows(length, first_page, capacity: int, n_pages: int):
    """Whether a decode slot holds rows to attend, from its length, its
    table's first entry and the rows its table can hold (scalars in
    the kernel, arrays in the XLA reference): a positive length that
    the table can hold, and a first page that is allocated. The engine
    never hands decode a zero length — an empty slot's length counts
    up from 1 inside a pass and a slot mid-prefill carries ``max_seq``
    — but an empty slot's table is all unallocated, and no table holds
    more than ``max_pages * page`` rows."""
    return (length > 0) & (length <= capacity) & (first_page < n_pages)


def _decode_kernel(tables_ref, lengths_ref, layer_ref, q_ref, *refs,
                   page: int, fold_pages: int, n_pages: int, scale: float,
                   pack: int, quantized: bool, write_rows: int):
    ks_hbm = vs_hbm = ks_buf = vs_buf = kn_ref = vn_ref = wsems = None
    if write_rows:
        # the pools are aliased in and out: ONE buffer a side, read and
        # written through the output's ref (the input's is not touched)
        (kn_ref, vn_ref, _, _, o_ref, k_hbm, v_hbm, k_buf, v_buf, acc_ref,
         m_ref, l_ref, sems, next_ref, half_ref, wsems) = refs
    elif quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         acc_ref, m_ref, l_ref, sems, next_ref, half_ref) = refs
    else:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, acc_ref, m_ref, l_ref, sems,
         next_ref, half_ref) = refs
    li = layer_ref[0]
    b = pl.program_id(0)
    n_slots, max_pages = tables_ref.shape
    chunk = fold_pages * page
    rows = q_ref.shape[2]

    def live(slot):
        return _has_rows(lengths_ref[slot], tables_ref[slot, 0],
                         max_pages * page, n_pages)

    def fold_dmas(slot, ci, half, wait=False):
        """Start (or wait on) the pages of fold ``ci`` of ``slot`` into
        buffer half ``half``: K and V of EVERY head group of a page in
        one strided descriptor each (``[Hg, page, W]``, a head group's
        page contiguous), plus the scale rows of a quantized pool. A
        page past the slot's rows is not fetched: its buffer rows keep
        what an earlier fold left (finite — the V side starts as
        zeros) and are masked."""
        for j in range(fold_pages):
            idx = ci * fold_pages + j
            pid = jnp.minimum(
                tables_ref[slot, jnp.minimum(idx, max_pages - 1)],
                n_pages - 1)
            dst = pl.ds(j * page, page)
            copies = [(k_hbm, k_buf.at[half, :, dst, :]),
                      (v_hbm, v_buf.at[half, :, dst, :])]
            if quantized:
                copies += [(ks_hbm, ks_buf.at[half, j]),
                           (vs_hbm, vs_buf.at[half, j])]

            @pl.when(idx * page < lengths_ref[slot])
            def _():
                for side, (src, dst_ref) in enumerate(copies):
                    dma = pltpu.make_async_copy(
                        src.at[li, :, pid], dst_ref, sems.at[half, side, j])
                    if wait:
                        dma.wait()
                    else:
                        dma.start()

    @pl.when(b == 0)
    def _():
        # thread the live slots (each one's successor, in SMEM) and put
        # the first one's first fold in flight: from here on a cell's
        # first fold was always started by the cell before it
        def link(i, nxt):
            slot = n_slots - 1 - i
            next_ref[slot] = nxt
            return jnp.where(live(slot), slot, nxt)

        first = jax.lax.fori_loop(0, n_slots, link, n_slots)
        half_ref[0] = 0
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            vs_buf[...] = jnp.zeros_like(vs_buf)

        @pl.when(first < n_slots)
        def _():
            fold_dmas(first, 0, 0)

    is_live = live(b)

    @pl.when(jnp.logical_not(is_live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(is_live)
    def _():
        length = lengths_ref[b]
        n_folds = pl.cdiv(length, chunk)
        first_half = half_ref[0]
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[0]                                    # [Hg, rows, W]
        # within a head group the rows run [pack, rows // pack]: packed
        # kv head, then its (padded) GQA query heads
        row_head = jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, 1), 1) // (rows // pack)
        if write_rows:
            # the step's fresh row is the slot's last, in the last page
            # of its last fold: it is laid over that fold's buffer and
            # the tile-aligned block that holds it (rows ``at`` of the
            # buffer, ``at % page`` of the page) goes back to the pool. A
            # tail page the table does not hold drops the row, as
            # ``pool_write`` drops it
            tail = length - 1
            tail_pid = tables_ref[b, tail // page]
            writes = tail_pid < n_pages
            at = pl.multiple_of(
                tail % chunk // write_rows * write_rows, write_rows)

            def write_back(half):
                return [pltpu.make_async_copy(
                    buf.at[half, :, pl.ds(at, write_rows), :],
                    pool.at[li, :, tail_pid,
                            pl.ds(pl.multiple_of(at % page, write_rows),
                                  write_rows), :],
                    wsems.at[side])
                    for side, (buf, pool) in enumerate(
                        ((k_buf, k_hbm), (v_buf, v_hbm)))]

        def body(ci, _):
            half = jax.lax.rem(first_half + ci, 2)
            # what is folded next goes in flight first: this slot's
            # next fold, or the first fold of the next live slot
            last = ci + 1 == n_folds
            next_slot = jnp.where(last, next_ref[b], b)

            @pl.when(next_slot < n_slots)
            def _():
                fold_dmas(next_slot, jnp.where(last, 0, ci + 1), 1 - half)

            fold_dmas(b, ci, half, wait=True)
            if write_rows:
                @pl.when(last & writes)
                def _():
                    here = jax.lax.broadcasted_iota(
                        jnp.int32, (1, write_rows, 1), 1) == tail % chunk - at
                    for buf, new in ((k_buf, kn_ref), (v_buf, vn_ref)):
                        block = buf.at[half, :, pl.ds(at, write_rows), :]
                        block[...] = jnp.where(here, new[0], block[...])
                    for dma in write_back(half):
                        dma.start()

            s = _mxu_dot(q, k_buf[half], 2) * scale     # [Hg, rows, chunk]
            if quantized:
                s = s * _row_scales(
                    [ks_buf[half, j] for j in range(fold_pages)],
                    row_head, page=page, pack=pack)
            pos = ci * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, chunk), 2)

            def pv(p):
                if quantized:
                    p = p * _row_scales(
                        [vs_buf[half, j] for j in range(fold_pages)],
                        row_head, page=page, pack=pack)
                return _mxu_dot(p, v_buf[half], 1)      # [Hg, rows, W]

            # the one query row is the slot's last: every row is behind it
            _softmax_fold(s, pos < length, pv, acc_ref, m_ref, l_ref)
            return 0

        jax.lax.fori_loop(0, n_folds, body, 0)
        half_ref[0] = jax.lax.rem(first_half + n_folds, 2)
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        if write_rows:
            # the next cell's first fetch goes into the half the block
            # leaves from: it has left before the cell ends
            @pl.when(writes)
            def _():
                for dma in write_back(1 - half_ref[0]):
                    dma.wait()


def _write_block_rows(page: int, itemsize: int) -> int:
    """Rows of the block in which the decode walk hands a fresh row
    back to the pool: one tile of the pool's dtype along the sublanes
    (8 rows of 32 bits; 16 of bf16, two to a sublane) where tiles
    divide the page, else the 8 rows every page is a multiple of."""
    tile = SUBLANE * max(1, 4 // itemsize)
    return SUBLANE if page % tile else tile


def _decode_walk(q, k_pool, v_pool, tables, lengths, *, layer, scale,
                 interpret, new_rows=None):
    """The pallas_call behind decode. q [B, Hq, hd]; pools and ``layer``
    as :func:`_ragged_attention` takes them. With ``new_rows`` — the
    step's fresh ``(k, v)``, each [B, Hkv, hd], for a plain pool — the
    walk also WRITES: the pools are aliased in and out, a live slot's
    row lands at position ``length - 1``, and the result is ``(out,
    k_pool, v_pool)``."""
    one_layer = layer is None
    if one_layer:
        k_pool, v_pool = jax.tree.map(lambda x: x[None], (k_pool, v_pool))
        layer = 0
    k_codes, k_scales = _split_pool(k_pool)
    v_codes, v_scales = _split_pool(v_pool)
    quantized = k_scales is not None
    b, hq, hd = q.shape
    _, hg, n_pages, page, width = k_codes.shape
    _, max_pages = tables.shape
    pack = width // hd
    scale = scale if scale is not None else hd ** -0.5
    if not interpret:
        check_kernel_layout(k_pool)
    fold_pages = _fold_pages(hg, page, width, k_codes.dtype.itemsize,
                             max_pages)
    chunk = fold_pages * page
    q4, gp = _group_rows(q[:, None], hg, pack, 1)       # [B, Hg, rows, W]
    rows = pack * gp
    write_rows = 0 if new_rows is None else \
        _write_block_rows(page, k_codes.dtype.itemsize)
    kernel = functools.partial(
        _decode_kernel, page=page, fold_pages=fold_pages, n_pages=n_pages,
        scale=scale, pack=pack, quantized=quantized, write_rows=write_rows)
    q_spec = pl.BlockSpec((1, hg, rows, width), lambda i, *_: (i, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)  # pools stay in HBM
    scale_bufs = [pltpu.VMEM((2, fold_pages, hg, 1, k_scales.shape[-1]),
                             jnp.float32)] * 2 if quantized else []
    args = [tables.astype(jnp.int32), lengths.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1), q4]
    in_specs, out_specs = [q_spec], q_spec
    out_shape = jax.ShapeDtypeStruct((b, hg, rows, width), q.dtype)
    aliases = {}
    if write_rows:
        # the fresh rows packed as the pool packs them, a slot a block
        row_spec = pl.BlockSpec((1, hg, 1, width),
                                lambda i, *_: (i, 0, 0, 0),
                                memory_space=pltpu.VMEM)
        args += [x.astype(k_codes.dtype).reshape(b, hg, 1, width)
                 for x in new_rows]
        in_specs += [row_spec, row_spec]
        aliases = {len(args): 1, len(args) + 1: 2}
        out_specs = [q_spec, in_hbm, in_hbm]
        out_shape = [out_shape, k_codes, v_codes]
    args += [k_codes, v_codes]
    if quantized:
        args += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs + [in_hbm] * (4 if quantized else 2),
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, hg, chunk, width), k_codes.dtype),
            pltpu.VMEM((2, hg, chunk, width), v_codes.dtype),
            *scale_bufs,
            pltpu.VMEM((hg, rows, width), jnp.float32),
            pltpu.VMEM((hg, rows, 1), jnp.float32),
            pltpu.VMEM((hg, rows, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 4 if quantized else 2,
                                     fold_pages)),
            pltpu.SMEM((b,), jnp.int32),    # each live slot's successor
            pltpu.SMEM((1,), jnp.int32),    # buffer half of the next fold
            # the block's write-back, K and V
            *([pltpu.SemaphoreType.DMA((2,))] if write_rows else []),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        # a cell starts the next live cell's first fold: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
    if not write_rows:
        return _ungroup_rows(out, 1, hq, hd, pack, gp)[:, 0]
    out, *pools = out
    if one_layer:
        pools = [x[0] for x in pools]
    return (_ungroup_rows(out, 1, hq, hd, pack, gp)[:, 0], *pools)


def paged_chunk_attention_pallas(q: jnp.ndarray, k_pool,
                                 v_pool, tables: jnp.ndarray,
                                 history_lens: jnp.ndarray,
                                 chunk_lens: jnp.ndarray, *,
                                 layer=None,
                                 scale: float | None = None,
                                 block_q: int | None = None,
                                 interpret: bool = False) -> jnp.ndarray:
    """Ragged chunk attention. q [B, Sq, Hq, hd] holds Sq new positions
    per slot, already written into the pool at rows
    ``[history_lens, history_lens + chunk_lens)``; pools
    [L, Hg, Np, pg, W] read at ``layer``, or one layer's
    [Hg, Np, pg, W] with ``layer=None`` (plain, or the ``{"q", "s"}``
    quantized pytree).
    Query row i of slot b attends causally to pool
    rows <= history_lens[b] + i, bounded by the slot's written total
    ``history + chunk``. Rows past ``chunk_lens[b]`` are padding the
    caller discards; zero-length slots (history == chunk == 0) return
    exact zeros."""
    return _ragged_attention(q, k_pool, v_pool, tables, history_lens,
                             chunk_lens, None, layer=layer, scale=scale,
                             block_q=block_q, interpret=interpret)


def paged_decode_attention_pallas(q: jnp.ndarray, k_pool,
                                  v_pool, tables: jnp.ndarray,
                                  lengths: jnp.ndarray, *,
                                  layer=None,
                                  scale: float | None = None,
                                  interpret: bool = False) -> jnp.ndarray:
    """Decode: q [B, Hq, hd] is the one new position per slot,
    ``lengths`` [B] the valid rows AFTER this step's write — the chunk
    of one row ending at ``lengths``. Zero-length slots return exact
    zeros."""
    return _decode_walk(q, k_pool, v_pool, tables, lengths, layer=layer,
                        scale=scale, interpret=interpret)


def _append_rows(k_pool, v_pool, k_new, v_new, tables, lengths, layer):
    """:func:`..paged_kv.pool_write` of a decode step's fresh rows
    [B, Hkv, hd], at position ``lengths - 1`` of the slots that hold
    rows (:func:`_has_rows`: the others drop there anyway — an empty
    slot's table is all unallocated, ``max_seq`` is past the table)."""
    pools = (k_pool, v_pool)
    if layer is None:
        pools = jax.tree.map(lambda x: x[None], pools)
    n_pages, page = _split_pool(pools[0])[0].shape[2:4]
    counts = _has_rows(lengths, tables[:, 0], tables.shape[1] * page,
                       n_pages).astype(jnp.int32)
    pools = tuple(
        pool_write(pool, 0 if layer is None else layer, tables, lengths - 1,
                   counts, rows[:, None])
        for pool, rows in zip(pools, (k_new, v_new)))
    return jax.tree.map(lambda x: x[0], pools) if layer is None else pools


def paged_decode_append_attention_pallas(q: jnp.ndarray, k_new: jnp.ndarray,
                                         v_new: jnp.ndarray, k_pool, v_pool,
                                         tables: jnp.ndarray,
                                         lengths: jnp.ndarray, *,
                                         layer=None,
                                         scale: float | None = None,
                                         interpret: bool = False):
    """Decode with the step's write in it: ``k_new`` / ``v_new``
    [B, Hkv, hd] are the fresh rows of the one new position a slot,
    ``lengths`` the valid rows AFTER they land (at ``lengths - 1``).
    Returns ``(out, k_pool, v_pool)``. A plain pool is written inside
    the walk (:func:`_decode_walk`); a quantized pool keeps
    write-then-walk — its scale leaf is a lane-major row a page with a
    layout of its own, and no deployment measured here runs one."""
    if _split_pool(k_pool)[1] is None:
        return _decode_walk(q, k_pool, v_pool, tables, lengths, layer=layer,
                            scale=scale, interpret=interpret,
                            new_rows=(k_new, v_new))
    k_pool, v_pool = _append_rows(k_pool, v_pool, k_new, v_new, tables,
                                  lengths, layer)
    return _decode_walk(q, k_pool, v_pool, tables, lengths, layer=layer,
                        scale=scale, interpret=interpret), k_pool, v_pool


def paged_tree_attention_pallas(q: jnp.ndarray, k_pool,
                                v_pool, tables: jnp.ndarray,
                                history_lens: jnp.ndarray,
                                chunk_lens: jnp.ndarray,
                                tree_masks: jnp.ndarray, *,
                                layer=None,
                                scale: float | None = None,
                                block_q: int | None = None,
                                interpret: bool = False) -> jnp.ndarray:
    """Tree-verify attention. q [B, Sq, Hq, hd] holds the Sq draft-tree
    nodes per slot, already written into the pool at rows
    ``[history_lens, history_lens + chunk_lens)`` in topological order
    (parent row < child row); ``tree_masks`` [B, Sq] int32 packs each
    node's ancestor-or-self set as bits over the in-chunk node index.
    Node i of slot b attends pool rows < history_lens[b] plus in-chunk
    rows j with bit j of tree_masks[b, i] set. Nodes past
    ``chunk_lens[b]`` are padding; a fully-masked row returns zeros."""
    return _ragged_attention(q, k_pool, v_pool, tables, history_lens,
                             chunk_lens, tree_masks, layer=layer,
                             scale=scale, block_q=block_q,
                             interpret=interpret)


# ---------------------------------------------------------- xla reference

def _slot_view(pool, layer, tables: jnp.ndarray,
               head_dim: int) -> jnp.ndarray:
    """Gather one layer of the pool (``layer=None``: the pool IS one
    layer) into the dense slot view [B, Mp*pg, Hkv, hd]; quantized
    pools dequantize to f32 with the scales the kernel applies."""
    one = jax.tree.map(
        lambda x: x[None] if layer is None else
        jax.lax.dynamic_index_in_dim(x, layer, 0), pool)
    return gather_view(one, tables, dtype=jnp.float32,
                       head_dim=head_dim)[0]


def paged_decode_attention_xla(q: jnp.ndarray, k_pool,
                               v_pool, tables: jnp.ndarray,
                               lengths: jnp.ndarray, *, layer=None,
                               scale: float | None = None) -> jnp.ndarray:
    """Reference path: gather the slot views, run dense masked decode
    attention. Correct everywhere; materialises [B, Mp*pg, Hkv, hd]."""
    from .attention import decode_attention
    hd = q.shape[-1]
    out = decode_attention(q[:, None],
                           _slot_view(k_pool, layer, tables, hd),
                           _slot_view(v_pool, layer, tables, hd),
                           lengths, scale=scale)[:, 0]
    # slots without rows: every position is masked (or garbage), so the
    # dense softmax degrades to an average over garbage rows — the
    # kernel does not walk them and stores exact zeros. Match it, so
    # the reference and the kernel agree on EVERY row, not just live
    # ones.
    n_pages, page = _split_pool(k_pool)[0].shape[-3:-1]
    live = _has_rows(lengths, tables[:, 0], tables.shape[1] * page, n_pages)
    return jnp.where(live[:, None, None], out, jnp.zeros_like(out))


def paged_decode_append_attention_xla(q: jnp.ndarray, k_new: jnp.ndarray,
                                      v_new: jnp.ndarray, k_pool, v_pool,
                                      tables: jnp.ndarray,
                                      lengths: jnp.ndarray, *, layer=None,
                                      scale: float | None = None):
    """Reference path: the page-granular write, then the gather
    reference — what the walk's own write is held to, byte for byte."""
    k_pool, v_pool = _append_rows(k_pool, v_pool, k_new, v_new, tables,
                                  lengths, layer)
    return paged_decode_attention_xla(
        q, k_pool, v_pool, tables, lengths, layer=layer,
        scale=scale), k_pool, v_pool


def paged_chunk_attention_xla(q: jnp.ndarray, k_pool,
                              v_pool, tables: jnp.ndarray,
                              history_lens: jnp.ndarray,
                              chunk_lens: jnp.ndarray, *, layer=None,
                              scale: float | None = None) -> jnp.ndarray:
    """Reference path: gather the slot views, run dense causal
    attention offset by the history. Materialises [B, Mp*pg, Hkv, hd]
    per call — the traffic the kernel exists to avoid."""
    from .attention import xla_attention
    hd = q.shape[-1]
    out = xla_attention(q, _slot_view(k_pool, layer, tables, hd),
                        _slot_view(v_pool, layer, tables, hd), causal=True,
                        q_offset=history_lens,
                        kv_lengths=history_lens + chunk_lens,
                        scale=scale)
    # zero-length slots (hist == clen == 0): exact zeros, as above
    total = history_lens + chunk_lens
    return jnp.where(total[:, None, None, None] > 0, out,
                     jnp.zeros_like(out))


def paged_tree_attention_xla(q: jnp.ndarray, k_pool,
                             v_pool, tables: jnp.ndarray,
                             history_lens: jnp.ndarray,
                             chunk_lens: jnp.ndarray,
                             tree_masks: jnp.ndarray, *, layer=None,
                             scale: float | None = None) -> jnp.ndarray:
    """Reference path: gather the slot views, run dense tree-masked
    attention. Materialises [B, Mp*pg, Hkv, hd] per call."""
    from .attention import tree_attention
    hd = q.shape[-1]
    out = tree_attention(q, _slot_view(k_pool, layer, tables, hd),
                         _slot_view(v_pool, layer, tables, hd),
                         history_lens=history_lens,
                         chunk_lens=chunk_lens,
                         tree_masks=tree_masks, scale=scale)
    # zero-length slots (hist == clen == 0): exact zeros, as above —
    # this parity is what lets output digests compare across
    # implementations bit-for-bit
    total = history_lens + chunk_lens
    return jnp.where(total[:, None, None, None] > 0, out,
                     jnp.zeros_like(out))


# --------------------------------------------------------------- dispatch

def _dispatch(implementation: str, pallas_fn, xla_fn, *args, **kw):
    """implementation: 'pallas' | 'interpret' | 'xla' | 'auto'."""
    if implementation == "pallas" or (
            implementation == "auto" and is_tpu()):
        return pallas_fn(*args, **kw)
    if implementation == "interpret":
        return pallas_fn(*args, interpret=True, **kw)
    return xla_fn(*args, **kw)


def paged_tree_attention(q: jnp.ndarray, k_pool,
                         v_pool, tables: jnp.ndarray,
                         history_lens: jnp.ndarray,
                         chunk_lens: jnp.ndarray,
                         tree_masks: jnp.ndarray, *, layer=None,
                         scale: float | None = None,
                         implementation: str = "auto") -> jnp.ndarray:
    return _dispatch(implementation, paged_tree_attention_pallas,
                     paged_tree_attention_xla, q, k_pool, v_pool, tables,
                     history_lens, chunk_lens, tree_masks, layer=layer,
                     scale=scale)


def paged_chunk_attention(q: jnp.ndarray, k_pool,
                          v_pool, tables: jnp.ndarray,
                          history_lens: jnp.ndarray,
                          chunk_lens: jnp.ndarray, *, layer=None,
                          scale: float | None = None,
                          implementation: str = "auto") -> jnp.ndarray:
    return _dispatch(implementation, paged_chunk_attention_pallas,
                     paged_chunk_attention_xla, q, k_pool, v_pool, tables,
                     history_lens, chunk_lens, layer=layer, scale=scale)


def paged_decode_attention(q: jnp.ndarray, k_pool,
                           v_pool, tables: jnp.ndarray,
                           lengths: jnp.ndarray, *, layer=None,
                           scale: float | None = None,
                           implementation: str = "auto") -> jnp.ndarray:
    return _dispatch(implementation, paged_decode_attention_pallas,
                     paged_decode_attention_xla, q, k_pool, v_pool, tables,
                     lengths, layer=layer, scale=scale)


def paged_decode_append_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                                  v_new: jnp.ndarray, k_pool, v_pool,
                                  tables: jnp.ndarray,
                                  lengths: jnp.ndarray, *, layer=None,
                                  scale: float | None = None,
                                  implementation: str = "auto"):
    return _dispatch(implementation, paged_decode_append_attention_pallas,
                     paged_decode_append_attention_xla, q, k_new, v_new,
                     k_pool, v_pool, tables, lengths, layer=layer,
                     scale=scale)
