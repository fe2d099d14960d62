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

A model family states its cache row through its cache constructor,
``make_cache(batch, max_seq) -> (k, v)`` each ``[L, B, S, heads,
width]``, and the pool's row follows from that statement side by side
(:func:`empty_pool`). A family that keeps ONE vector per token — latent
attention's ``[L, B, S, 1, R]`` (models/deepseek.py) — states a V side
of zero lanes: that side's pool holds zero bytes, every writer here
hands it back untouched, and :func:`pool_row_bytes` counts nothing for
it. No pool-sized stand-in takes its place.

Everything here is a pure jittable function on static shapes:

- :func:`gather_view` materialises a slot-contiguous ``[L, B, S, ...]``
  view once per K-step decode pass (NOT per token) — the engine then
  runs the model family's ordinary dense decode step on the view, so
  paged mode needs zero model changes.
- :func:`pool_write` is the writer of XLA's side: each slot's
  contiguous run of new positions goes through its table by WHOLE
  PAGES (below) — prompt runs, tree-verify nodes, the rows of a
  one-vector family and of a quantized pool. The ONE row a dense
  decode step appends to a plain pool is written by the decode walk
  itself ("Decode's one row", below).
  :func:`scatter_prefill` / :func:`scatter_chunk` /
  :func:`scatter_decode` are its all-layer entries for prompt slabs
  and the view path's freshly decoded rows. A table's unallocated
  entries hold the out-of-range page id (``n_pages``), which the
  gather clamps and XLA's scatter drops — padding rows and dummy
  slots cost nothing and corrupt nothing.

Free-list bookkeeping is host-side (``serving/engine.py``): the device
never sees an allocator, only tables.

One physical layout: writes are by page
---------------------------------------
The Mosaic kernel reads the pool row-major (``{4,3,2,1,0}``: a page is
one contiguous ``[page, W]`` block). XLA picks a buffer's layout for
the op that writes it, and for a scatter of single ``[Hg, W]`` rows —
``pool.at[li, :, pids, offs].set(rows)``, how this module wrote until
PR 26 — it picks TOKEN-major, ``{4,1,3,2,0}``, the head-group axis next
to the lanes. The pool then changed layout four times a program (K and
V, on entry and on exit: whole-pool copies) and every layer-step sliced
one layer out token-major and transposed it for the kernel: a third of
the device's time in the benchmark's traces (PERF.md section 6, PR 26).
Handing the kernel the whole pool instead makes XLA copy the WHOLE pool
per layer-step; a layout constraint on the scan carry copies around
every scatter; ``dynamic_update_slice`` row by row is laid out
token-major too. XLA does not write single rows of a ``[..., pg,
W]``-tiled pool in place. It does write whole pages in place: a page is
a window over the pool's leading dims. So every writer gathers the
pages its run touches, lays the new rows over them, and scatters the
pages back — and the kernel takes the whole pool and a layer index
(``ops/paged_attention.py``). The pool keeps one layout from program
entry to exit, with no copy of it and no temp of its size
(``tests/test_tpu_compile.py`` holds the compiled programs to that).

Decode's one row
----------------
A page-granular write prices a run by the pages it touches, and a
decode step's run is one row: ``pool_write`` gathered the 64-row tail
page of every COMPILED slot on both sides, laid a row over each and
scattered the pages back — four page sets a layer-step (4 x 8.4 MB at
SmolLM2-1.7B's widths and 32 slots) to store 32 x 2 x 4 KiB, live slot
or not: 56-80 us of a 329 us layer-step in the chat cell, three times
the decode walk it fed (PERF.md section 6, PR 31). A narrower XLA
write is the token-major relayout again; a Pallas operand's layout is
fixed. So the dense decode step hands its fresh rows to
``ops/paged_attention.paged_decode_append_attention`` and the walk —
which already holds a live slot's tail page in VMEM, in its last fold
— lays the row over it and copies the tile-aligned block that holds
it back to the pool, aliased in and out of the kernel. The stored
bytes are what ``pool_write`` stores. This routine stays that entry's
reference (its ``xla`` path) and the writer of everything else.

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
    if d == 0:      # the side a one-vector family does not keep
        return jnp.zeros((l, h, n_pages, pg, 0), like.dtype)
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
    included, are spread over the page's rows; a row's own pad lanes
    (a latent row's 576 numbers in 640 lanes) count, since they are
    stored. Nought for the side a one-vector family does not keep."""
    _, _, n_pages, pg, _ = pool_shape(pool)
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(pool))
    return -(-total // (n_pages * pg))


def _scale_lanes(offs: jnp.ndarray, pack: int, pg: int) -> jnp.ndarray:
    """Lane of each packed head's scale for rows at in-page offsets
    ``offs`` [...] -> [..., pack]."""
    return offs[..., None] + (jnp.arange(pack) * pg).reshape(
        (1,) * offs.ndim + (pack,))


def pool_write(pool, layer, tables, starts, counts, rows):
    """XLA's pool writer: slot b's ``rows[..., b, :counts[b]]`` land
    at logical positions ``[starts[b], starts[b] + counts[b])`` of its
    table, by WHOLE PAGES (module docstring: written by rows, XLA lays
    the pool out token-major and copies it for the kernel in every
    program and layer-step). Still the writer of runs (bucket and chunk
    prefill, tree verify, the view path), of one-vector families and
    of quantized pools; a dense decode step's single row into a plain
    pool goes through the decode walk instead — here it cost four page
    sets of every compiled slot ("Decode's one row"). ``rows`` is the
    model's token-major K or V:
    ``[B, S, Hkv, hd]`` for one ``layer`` (a traced index — what the
    model families call inside their layer scan; S = 1 is one row) or
    ``[L, B, S, Hkv, hd]`` with ``layer=None`` (all layers: the
    ``scatter_*`` entries below). Packs heads into lanes, quantizes on
    write for quantized pools; plain pools absorb the dtype cast here
    so callers never touch the pool dtype.

    A run of static width S touches at most ``P = (S + pg - 2) // pg +
    1`` consecutive pages of a table. Gather those pages, lay the new
    rows over them — old bytes stay wherever a position is outside the
    run — and scatter the pages back. Pages the run does not reach, a
    table's unallocated entries (page id ``n_pages``) and positions
    past the table drop: the gather clamps, the scatter drops.

    Why it is safe: no scatter here carries two different updates for
    one page. A slot's rows within a page are merged before the
    scatter (tree-verify nodes included: they are the run ``[offset,
    offset + nodes)``); the pages a run reaches are distinct entries
    of one table; and two slots never write one page in one call —
    tail pages have one owner, and the prefix cache shares
    page-ALIGNED prefixes only (``Engine._register_prefix``), which a
    later run starts behind.

    A side of zero lanes (the V side of a one-vector family) has
    nothing to write and comes back as it is."""
    if rows.shape[-1] == 0:
        return pool
    hg, n_pages, pg, w = pool_shape(pool)[1:]
    s, _, d = rows.shape[-3:]
    pack = w // d
    mp = tables.shape[1]
    p = (s + pg - 2) // pg + 1
    counts = jnp.minimum(counts, s)
    page_idx = (starts // pg)[:, None] + jnp.arange(p)[None, :]  # [B, P]
    pids = jnp.take_along_axis(tables, jnp.minimum(page_idx, mp - 1),
                               axis=1)
    reached = page_idx * pg < (starts + counts)[:, None]
    pids = jnp.where(reached & (page_idx < mp), pids, n_pages)
    # the window: the P pages' rows; its row t is slab row t - shift
    shift = starts % pg
    src = jnp.arange(p * pg)[None, :] - shift[:, None]          # [B, T]
    fresh = ((src >= 0) & (src < counts[:, None])).reshape(-1, p, 1, pg)

    def pages(x):
        """Rows [..., B, S, Hg, X] -> their window as pages [..., B, P,
        Hg, pg, X]: head-major, and only the NEW rows are transposed.
        Rows of the window outside the run hold no matter what."""
        if s == 1:      # one row has nothing to shift: ``fresh`` places it
            x = jnp.broadcast_to(x, (*x.shape[:-3], pg, *x.shape[-2:]))
        else:           # one slice a slot out of the zero-padded slab
            pad = [(0, 0)] * x.ndim
            pad[-3] = (pg - 1, p * pg - s)
            x = jax.vmap(lambda xb, lo: jax.lax.dynamic_slice_in_dim(
                xb, lo, p * pg, axis=-3), in_axes=(-4, 0), out_axes=-4)(
                    jnp.pad(x, pad), pg - 1 - shift)
        return jnp.swapaxes(
            x.reshape(*x.shape[:-3], p, pg, *x.shape[-2:]), -3, -2)

    at = (slice(None) if layer is None else layer, slice(None), pids)

    def merge(leaf, new, mask):
        """``new`` [..., B, P, Hg, rows, X] over the pages ``leaf`` holds
        where ``mask`` [B, P, 1, rows | 1, X | 1]. The head axis sits
        where the indexing puts it: behind [B, P] under a layer index,
        in place ([L, Hg, B, P, ...]) without one."""
        old = leaf.at[at].get(mode="clip")
        if layer is None:
            new, mask = jnp.moveaxis(new, -3, 1), mask[None, None, :, :, 0]
        return leaf.at[at].set(
            jnp.where(mask, new.astype(leaf.dtype), old), mode="drop")

    if not is_quantized_pool(pool):
        return merge(pool, pages(rows.reshape(*rows.shape[:-2], hg, w)),
                     fresh[..., None])
    q, sc = quantize_rows(rows)

    def scale_row(x):
        """Per-row values [..., pg, pack] -> the page's ONE lane-major
        row [..., 1, SW]: packed head p's at lanes [p * pg, (p + 1) *
        pg), zero-padded to the scale width."""
        x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], 1, pack * pg)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                       + [(0, pool["s"].shape[-1] - pack * pg)])

    return {"q": merge(pool["q"], pages(q.reshape(*q.shape[:-2], hg, w)),
                       fresh[..., None]),
            "s": merge(pool["s"],
                       scale_row(pages(sc.reshape(*sc.shape[:-2], hg, pack))),
                       scale_row(jnp.broadcast_to(
                           fresh[..., None], (*fresh.shape, pack))))}


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
    zero = jnp.zeros(k_slab.shape[1], jnp.int32)
    return pool_write(pool, None, tables, zero, zero + k_slab.shape[2],
                      k_slab)


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
    return pool_write(pool, None, tables, offsets, chunk_lens, slab)


def scatter_decode(pool, tables: jnp.ndarray,
                   view: jnp.ndarray, lengths: jnp.ndarray,
                   k_steps: int):
    """Copy the ``k_steps`` rows a decode pass appended to ``view``
    (at logical positions lengths .. lengths+K-1 per slot) back into
    the pool. view [L, B, S, H, d], tables [B, Mp], lengths [B].
    """
    positions = lengths[:, None] + jnp.arange(k_steps)[None, :]   # [B, K]
    new_rows = jnp.take_along_axis(
        view, jnp.minimum(positions, view.shape[2] - 1)[
            None, :, :, None, None], axis=2)            # [L, B, K, H, d]
    # positions past the logical view (a slot at the cache ceiling
    # taking a partial pass) lie past the table, and drop
    return pool_write(pool, None, tables, lengths,
                      jnp.full_like(lengths, k_steps), new_rows)


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
