"""Paged KV cache primitives — block-table indirection over a page pool.

The serving engine's paged layout (vLLM-style, re-designed for XLA's
static-shape world): K/V live in a HEAD-MAJOR, LANE-PACKED pool
``[L, Hg, n_pages, page, W]`` and each slot owns an ordered list of
page ids (its *block table*, shape ``[max_pages]``). Capacity is
decoupled from ``max_batch x max_seq``: slots allocate pages as they
grow and free them on retire, so many long-tailed requests overcommit
a pool that a contiguous per-slot layout could never fit.

Why this layout — both halves are what the TPU's compiler accepts:

- *Head-major* (head axis OUTSIDE the page grid): the ragged
  paged-attention kernel's per-(head group, page) DMA slices only the
  untiled leading dims, and every page read is one contiguous
  ``[page, W]`` block. A trailing head axis cannot be sliced per grid
  cell at all ("Slice shape along dimension 2 must be aligned to
  tiling (8), but is 1").
- *Lane-packed*: Mosaic tiles the last dim of every memref in units of
  128 lanes, so a page of ``head_dim`` 64 cannot be sliced either
  ("…aligned to tiling (128), but is 64"). ``pack = 128 // head_dim``
  kv heads therefore share one row: ``Hg = Hkv // pack`` head groups,
  row width ``W = pack * head_dim``, head ``hg * pack + p`` at lanes
  ``[p * head_dim, (p + 1) * head_dim)``. ``head_dim >= 128`` has
  ``pack == 1`` and the layout is the plain ``[L, Hkv, Np, pg, hd]``.
  :func:`head_pack` is the one rule; nothing is padded in HBM.

Every function here takes the per-token K/V it reads or writes in the
model's own ``[..., Hkv, head_dim]`` shape and infers ``pack`` from the
pool's row width, so callers never see the packing
(:func:`gather_view`, which has no such operand, takes ``head_dim``).
A pool whose row width equals ``head_dim`` is simply ``pack == 1``.

Everything here is a pure jittable function on static shapes:

- :func:`gather_view` materialises a slot-contiguous ``[L, B, S, ...]``
  view once per K-step decode pass (NOT per token) — the engine then
  runs the model family's ordinary dense decode step on the view, so
  paged mode needs zero model changes.
- :func:`scatter_prefill` / :func:`scatter_decode` write prompt slabs /
  freshly decoded rows back through the table. Unallocated positions
  map to the out-of-range page id (``n_pages``), which XLA's scatter
  drops — padding rows and dummy slots cost nothing and corrupt
  nothing.

Free-list bookkeeping is host-side (``serving/engine.py``): the device
never sees an allocator, only tables.

Quantized pools
---------------
``kv_dtype="int8"`` swaps the plain array for a two-leaf pytree
``{"q": int8 [L, Hg, Np, pg, W], "s": f32 [L, Hg, Np, 1, SW]}`` —
narrow codes plus one f32 scale per written ROW and kv head (same
``amax / 127`` contract as :func:`gofr_tpu.ops.quant.quantize_int8`
with ``axis=-1``). Per-row (not per-page-scalar) granularity is
load-bearing: decode appends one row to a partially filled page, and a
page-wide amax recomputation would silently re-quantize — and degrade
— rows written earlier.

Scales are LANE-major: a page's ``pack * page`` scales sit in one row,
head ``p`` of the group at lanes ``[p * page, (p + 1) * page)``, the
row padded to ``SW = round_up(pack * page, 128)`` lanes. A
``[page, 1]`` column per page (the first int8 layout) is the same
128-lane fault as above ("…but is 1"); the ``[1, SW]`` row DMAs whole,
and the kernel applies it to the score matrix, whose lane axis is the
kv row. At head_dim 64 / page 64 the row is exactly 128 lanes — no
padding; shorter pages waste the pad lanes (:func:`pool_row_bytes`
counts them).

Every scatter quantizes ON WRITE inside the same jitted graph (the
engine's hot closures never dequantize host-side or ``.astype`` the
pool — ``gofrlint``'s kv-quant-boundary rule pins this), and
:func:`gather_view` dequantizes for the view fallback. bf16 pools stay
plain arrays.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: Mosaic tiles the last dim of every memref in units of 128 lanes
LANES = 128


def head_pack(n_kv_heads: int, head_dim: int) -> int:
    """kv heads sharing one pool row: as many as fill 128 lanes, as far
    as the head count divides (``n_kv_heads`` is the PER-SHARD count
    under tensor parallelism, so a group never straddles devices)."""
    if head_dim >= LANES or LANES % head_dim:
        return 1
    return math.gcd(LANES // head_dim, n_kv_heads)


def scale_width(pack: int, page: int) -> int:
    """Lanes of one page's scale row: ``pack * page`` rounded up to the
    128-lane tile."""
    return -(-pack * page // LANES) * LANES


def is_quantized_pool(pool) -> bool:
    """True for the ``{"q": int8, "s": f32}`` quantized pool pytree."""
    return isinstance(pool, dict)


def quantize_rows(rows: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rows [..., d] -> (int8 codes [..., d], f32 scales [..., 1]).

    Same contract as ``quantize_int8(w, axis=-1)``: symmetric,
    ``scale = max(amax, 1e-8) / 127``, codes clipped to ±127. Zero rows
    quantize to all-zero codes (scale floor), so fresh pool pages
    dequantize to exact zeros.
    """
    rf = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(rf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_rows(q: jnp.ndarray, s: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    """Codes [..., d] * scales [..., 1] -> values [..., d] in ``dtype``."""
    return (q.astype(jnp.float32) * s).astype(dtype)


def pack_pool(pool: jnp.ndarray) -> jnp.ndarray:
    """Re-lay an unpacked head-major pool [..., Hkv, Np, pg, hd] as the
    lane-packed [..., Hg, Np, pg, W]. Tests and tools only — the engine
    allocates packed pools directly (:func:`empty_pool`)."""
    *lead, h, n, pg, d = pool.shape
    pack = head_pack(h, d)
    x = pool.reshape(*lead, h // pack, pack, n, pg, d)
    return jnp.moveaxis(x, -4, -2).reshape(*lead, h // pack, n, pg,
                                           pack * d)


def quantize_pool(pool: jnp.ndarray, head_dim: int | None = None) -> dict:
    """Re-lay a plain pool [..., Hg, Np, pg, W] as the quantized pytree
    (per-row, per-head scales; ``head_dim`` splits a packed row, default
    unpacked). Tests and tools only; steady-state writes go through the
    scatters."""
    *lead, n, pg, w = pool.shape
    pack = w // (head_dim or w)
    q, s = quantize_rows(pool.reshape(*lead, n, pg, pack, w // pack))
    s = jnp.swapaxes(s[..., 0], -1, -2).reshape(*lead, n, 1, pack * pg)
    pad = scale_width(pack, pg) - pack * pg
    return {"q": q.reshape(pool.shape),
            "s": jnp.pad(s, ((0, 0),) * (s.ndim - 1) + ((0, pad),))}


def empty_pool(like: jnp.ndarray, n_pages: int, quantized: bool):
    """The engine's pool constructor: ``like`` is a ONE-page unpacked
    head-major allocation [L, Hkv, 1, pg, hd] from the model family's
    cache constructor — it supplies the dims, the dtype and (under a
    mesh) the sharding of the head axis. Returns the zero pool of
    ``n_pages`` in its final representation, built in place: no
    unpacked or unquantized transient the size of the pool."""
    l, h, _, pg, d = like.shape
    # placed like ``like`` only where it was placed on purpose (a mesh):
    # a pool pinned to the default device would commit every array the
    # jitted steps return and recompile each program at first use
    sharding = like.sharding if like.committed else None
    # pack within one device's heads, so head groups shard like heads
    pack = head_pack(like.sharding.shard_shape(like.shape)[1], d)
    shape = (l, h // pack, n_pages, pg, pack * d)
    if not quantized:
        return jnp.zeros(shape, like.dtype, device=sharding)
    return {"q": jnp.zeros(shape, jnp.int8, device=sharding),
            "s": jnp.zeros((*shape[:3], 1, scale_width(pack, pg)),
                           jnp.float32, device=sharding)}


def pool_shape(pool) -> tuple:
    """[L, Hg, Np, pg, W] shape for either pool representation."""
    return pool["q"].shape if is_quantized_pool(pool) else pool.shape


def pool_row_bytes(pool) -> int:
    """HBM bytes per KV ROW (one token, all layers/heads, K or V side
    only) as allocated — a quantized pool's scale rows, pad lanes
    included, are spread over the page's rows."""
    _, _, n_pages, pg, _ = pool_shape(pool)
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(pool))
    return -(-total // (n_pages * pg))


def pool_layer(pool, li):
    """Layer ``li``'s [Hg, Np, pg, W] slice (pytree-aware) — what the
    ragged attention dispatchers take as ``k_pool`` / ``v_pool``."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, li, 0, keepdims=False),
        pool)


def _scale_lanes(offs: jnp.ndarray, pack: int, pg: int) -> jnp.ndarray:
    """Lane of each packed head's scale for rows at in-page offsets
    ``offs`` [...] -> [..., pack]."""
    return offs[..., None] + (jnp.arange(pack) * pg).reshape(
        (1,) * offs.ndim + (pack,))


def pool_write(pool, li, pids, offs, rows):
    """Write ``rows`` into layer ``li`` at (page, offset) coordinates —
    the single-layer scatter the model families use inside their layer
    scan. ``pids``/``offs`` are the advanced-index arrays ([B] decode,
    [B, S] chunk); ``rows`` is the model's [..., Hkv, hd] K or V for
    those positions. Packs heads into lanes (a reshape), quantizes on
    write for quantized pools; plain pools absorb the dtype cast here so
    callers never touch the pool dtype. The advanced indices are split
    by the sliced head-group axis, so their broadcast dims lead the
    update: [..., Hg, W] codes, [..., pack, Hg] scales."""
    hg, _, pg, w = pool_shape(pool)[1:]
    pack = w // rows.shape[-1]
    if not is_quantized_pool(pool):
        rows = rows.reshape(*rows.shape[:-2], hg, w)
        return pool.at[li, :, pids, offs].set(rows.astype(pool.dtype),
                                              mode="drop")
    q, s = quantize_rows(rows)
    s = jnp.swapaxes(s.reshape(*s.shape[:-2], hg, pack), -1, -2)
    return {"q": pool["q"].at[li, :, pids, offs].set(
                q.reshape(*q.shape[:-2], hg, w), mode="drop"),
            "s": pool["s"].at[li, :, pids[..., None], 0,
                              _scale_lanes(offs, pack, pg)].set(
                s, mode="drop")}


def _pool_set(pool, pids, offs, rows):
    """All-layer scatter: token-major rows [L, P, S, Hkv, hd] at
    pids/offs [P, S]."""
    hg, _, pg, w = pool_shape(pool)[1:]
    l, p, s_, _, d = rows.shape
    pack = w // d
    if not is_quantized_pool(pool):
        packed = rows.reshape(l, p, s_, hg, w).transpose(0, 3, 1, 2, 4)
        return pool.at[:, :, pids, offs].set(packed.astype(pool.dtype),
                                             mode="drop")
    q, s = quantize_rows(rows)
    q = q.reshape(l, p, s_, hg, w).transpose(0, 3, 1, 2, 4)
    # advanced indices (page, 0, lane) are adjacent: their broadcast
    # [P, S, pack] lands where they sat, after [L, Hg]
    s = s.reshape(l, p, s_, hg, pack).transpose(0, 3, 1, 2, 4)
    return {"q": pool["q"].at[:, :, pids, offs].set(q, mode="drop"),
            "s": pool["s"].at[:, :, pids[..., None], 0,
                              _scale_lanes(offs, pack, pg)].set(
                s, mode="drop")}


def gather_view(pool, tables: jnp.ndarray, dtype=None,
                head_dim: int | None = None) -> jnp.ndarray:
    """Pool [L, Hg, Np, pg, W] + tables [B, Mp] -> view
    [L, B, Mp*pg, Hkv, hd]. ``head_dim`` unpacks the lanes (default:
    the row is one head).

    Out-of-range table entries (unallocated = Np) clamp to the last
    page on gather; those rows are masked by the caller's kv_lengths.
    Quantized pools dequantize here (``dtype`` picks the view dtype,
    default bf16); for plain pools ``dtype`` is ignored — the view is
    the pool dtype, exactly as before.
    """
    l, hg, _, pg, w = pool_shape(pool)
    b, mp = tables.shape
    d = head_dim or w
    pack = w // d

    def rows(x):                                # [L, Hg, B, Mp, pg, W]
        return x.transpose(0, 2, 3, 4, 1, 5).reshape(
            l, b, mp * pg, hg * pack, d)

    if not is_quantized_pool(pool):
        return rows(pool[:, :, tables])
    sv = pool["s"][:, :, tables, 0, :pack * pg]         # [L, Hg, B, Mp, *]
    sv = sv.reshape(l, hg, b, mp, pack, pg).transpose(0, 2, 3, 5, 1, 4)
    return dequantize_rows(
        rows(pool["q"][:, :, tables]),
        sv.reshape(l, b, mp * pg, hg * pack, 1),
        jnp.bfloat16 if dtype is None else dtype)


def scatter_prefill(pool, tables: jnp.ndarray,
                    k_slab: jnp.ndarray):
    """Write a prompt K (or V) slab [L, P, S, H, d] into the pool via
    per-row tables [P, Mp]. Positions whose table entry is the OOB page
    id are dropped (padding beyond each row's allocation, dummy rows).
    """
    pg = pool_shape(pool)[3]
    s = k_slab.shape[2]
    pos = jnp.arange(s)
    pids = jnp.take(tables, pos // pg, axis=1)          # [P, S]
    offs = jnp.broadcast_to(pos % pg, pids.shape)       # [P, S]
    return _pool_set(pool, pids, offs, k_slab)


def scatter_chunk(pool, tables: jnp.ndarray,
                  slab: jnp.ndarray, offsets: jnp.ndarray,
                  chunk_lens: jnp.ndarray):
    """Write a chunk slab [L, P, S, H, d] whose row b covers logical
    positions ``[offsets[b], offsets[b] + chunk_lens[b])`` into the
    pool — touching only the pages the chunk spans. ``scatter_prefill``
    writes every slab position of every row (pad rows past a prompt's
    real length included, dropped only where the table has no page);
    here rows past ``chunk_lens`` and positions past the table map to
    the OOB page id and drop, so a 5-token suffix in a 512-wide bucket
    writes one page, not the slot's whole allocation.
    """
    n_pages, pg = pool_shape(pool)[2:4]
    mp = tables.shape[1]
    s = slab.shape[2]
    pos = offsets[:, None] + jnp.arange(s)[None, :]             # [P, S]
    valid = jnp.arange(s)[None, :] < chunk_lens[:, None]        # [P, S]
    pids = jnp.take_along_axis(
        tables, jnp.clip(pos // pg, 0, mp - 1), axis=1)         # [P, S]
    pids = jnp.where(valid & (pos < mp * pg), pids, n_pages)
    return _pool_set(pool, pids, pos % pg, slab)


def scatter_decode(pool, tables: jnp.ndarray,
                   view: jnp.ndarray, lengths: jnp.ndarray,
                   k_steps: int):
    """Copy the ``k_steps`` rows a decode pass appended to ``view``
    (at logical positions lengths .. lengths+K-1 per slot) back into
    the pool. view [L, B, S, H, d], tables [B, Mp], lengths [B].
    """
    n_pages, pg = pool_shape(pool)[2:4]
    s = view.shape[2]
    positions = lengths[:, None] + jnp.arange(k_steps)[None, :]   # [B, K]
    clamped = jnp.minimum(positions, s - 1)
    new_rows = jnp.take_along_axis(
        view, clamped[None, :, :, None, None], axis=2)  # [L, B, K, H, d]
    pids = jnp.take_along_axis(tables, clamped // pg, axis=1)     # [B, K]
    # positions past the logical view (a slot at the cache ceiling
    # taking a partial pass) must drop, not overwrite the last row
    pids = jnp.where(positions < s, pids, n_pages)
    return _pool_set(pool, pids, clamped % pg, new_rows)


def pool_move_rows(pool, tables: jnp.ndarray,
                   src_pos: jnp.ndarray, dst_pos: jnp.ndarray):
    """Move KV rows between logical positions of each slot:
    row ``src_pos[b, k]`` -> ``dst_pos[b, k]`` through slot b's table.
    Used by speculative tree verify to compact the accepted
    root-to-leaf path out of the node-indexed scratch rows.

    Moves the RAW pool representation — int8 codes plus their f32
    scales for quantized pools — so the copy is exact by construction:
    no dequantize/requantize round trip. All gathers complete before
    any scatter (one advanced-index gather, one scatter), so
    overlapping src/dst sets cannot order-corrupt. Entries with
    ``dst_pos`` outside the slot's table (the caller's "no move"
    sentinel) drop; ``src_pos`` for those entries may be anything
    in-range-clamped.
    """
    n_pages, pg = pool_shape(pool)[2:4]
    mp = tables.shape[1]

    def coords(pos, clamp):
        pids = jnp.take_along_axis(
            tables, jnp.clip(pos // pg, 0, mp - 1), axis=1)
        pids = jnp.where((pos >= 0) & (pos < mp * pg), pids, n_pages)
        if clamp:
            pids = jnp.minimum(pids, n_pages - 1)
        return pids, pos % pg

    s_pids, s_offs = coords(src_pos, clamp=True)
    d_pids, d_offs = coords(dst_pos, clamp=False)
    if not is_quantized_pool(pool):
        return pool.at[:, :, d_pids, d_offs].set(
            pool[:, :, s_pids, s_offs], mode="drop")
    codes, scales = pool["q"], pool["s"]
    # every lane a row's scales can sit in: offset + p*pg for each head
    # the scale row has room for (pad lanes move garbage to garbage)
    n = scales.shape[-1] // pg
    return {"q": codes.at[:, :, d_pids, d_offs].set(
                codes[:, :, s_pids, s_offs], mode="drop"),
            "s": scales.at[:, :, d_pids[..., None], 0,
                           _scale_lanes(d_offs, n, pg)].set(
                scales[:, :, s_pids[..., None], 0,
                       _scale_lanes(s_offs, n, pg)], mode="drop")}


def pool_from_cache_shape(k_cache: jnp.ndarray) -> jnp.ndarray:
    """Re-lay a dense [L, Np, pg, H, d] allocation (what
    ``make_cache(n_pages, page)`` returns) as the unpacked head-major
    [L, H, Np, pg, d] — the shape :func:`empty_pool` reads its dims
    from. Used by the engine so model glue only needs one cache
    constructor."""
    return k_cache.transpose(0, 3, 1, 2, 4)
