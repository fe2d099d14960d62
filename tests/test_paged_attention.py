"""Ragged paged decode-attention kernel: interpret-mode parity against
the dense reference, ragged lengths, OOB tables, GQA grouping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import decode_attention
from gofr_tpu.ops.paged_attention import (paged_decode_attention,
                                          paged_decode_attention_pallas,
                                          paged_decode_attention_xla)


def _random_paged_case(key, *, b=3, hq=4, hkv=2, hd=16, page=8,
                       max_pages=6, n_pages=32, lengths=(5, 17, 48)):
    """Build a pool + tables + the equivalent dense cache."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, hd), jnp.float32)
    # head-major pool [Hkv, Np, pg, hd] (ops/paged_kv.py)
    k_pool = jax.random.normal(ks[1], (hkv, n_pages, page, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (hkv, n_pages, page, hd), jnp.float32)
    rng = np.random.default_rng(0)
    tables = np.full((b, max_pages), n_pages, np.int32)  # OOB = unalloc
    for i, ln in enumerate(lengths):
        need = -(-ln // page)
        tables[i, :need] = rng.choice(n_pages, size=need, replace=False)
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(list(lengths), jnp.int32)
    # dense equivalent: gather allocated pages (OOB clamps, rows masked)
    safe = jnp.minimum(tables, n_pages - 1)
    k_dense = k_pool[:, safe].transpose(1, 2, 3, 0, 4).reshape(
        b, max_pages * page, hkv, hd)
    v_dense = v_pool[:, safe].transpose(1, 2, 3, 0, 4).reshape(
        b, max_pages * page, hkv, hd)
    return q, k_pool, v_pool, tables, lengths, k_dense, v_dense


def test_interpret_matches_dense_reference():
    case = _random_paged_case(jax.random.key(0))
    q, k_pool, v_pool, tables, lengths, k_dense, v_dense = case
    want = decode_attention(q[:, None], k_dense, v_dense, lengths)[:, 0]
    got = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_xla_fallback_matches_dense_reference():
    case = _random_paged_case(jax.random.key(1), lengths=(1, 30, 41))
    q, k_pool, v_pool, tables, lengths, k_dense, v_dense = case
    want = decode_attention(q[:, None], k_dense, v_dense, lengths)[:, 0]
    got = paged_decode_attention_xla(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ragged_lengths_ignore_unallocated_tail():
    """Rows past each slot's length must not contribute — poison the
    unallocated pages and the masked tail rows."""
    case = _random_paged_case(jax.random.key(2), lengths=(9, 9, 9))
    q, k_pool, v_pool, tables, lengths, k_dense, v_dense = case
    # poison every page NOT referenced by the first ceil(9/8)=2 entries
    used = set(np.asarray(tables)[:, :2].ravel().tolist())
    poison = np.asarray(k_pool).copy()
    for p in range(poison.shape[1]):
        if p not in used:
            poison[:, p] = 1e6
    got_clean = paged_decode_attention_pallas(
        q, k_pool, v_pool, tables, lengths, interpret=True)
    got_poisoned = paged_decode_attention_pallas(
        q, jnp.asarray(poison), v_pool, tables, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got_poisoned),
                               np.asarray(got_clean), rtol=2e-5, atol=2e-5)


def test_single_chunk_and_multi_chunk_agree():
    """Slot long enough to span several 128-row chunks (page walk with
    double buffering) matches the reference."""
    case = _random_paged_case(jax.random.key(3), b=2, page=16,
                              max_pages=24, n_pages=64,
                              lengths=(300, 77))
    q, k_pool, v_pool, tables, lengths, k_dense, v_dense = case
    want = decode_attention(q[:, None], k_dense, v_dense, lengths)[:, 0]
    got = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_auto_on_cpu_is_xla():
    case = _random_paged_case(jax.random.key(4))
    q, k_pool, v_pool, tables, lengths, k_dense, v_dense = case
    got = paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                 implementation="auto")
    want = decode_attention(q[:, None], k_dense, v_dense, lengths)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_zero_length_slot_returns_zeros_not_nan():
    case = _random_paged_case(jax.random.key(5), lengths=(0, 8, 16))
    q, k_pool, v_pool, tables, lengths, *_ = case
    got = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got[0]), 0.0, atol=1e-6)


# --------------------------------------------- Mosaic sublane alignment
#
# Round 5's first real-TPU compile died in Mosaic: "Slice shape
# along dimension 2 must be aligned to tiling (8), but is 1" — a grid
# cell's q/out block carried fewer than 8 rows along the sublane dim
# (small GQA group x short q block). The wrappers now pad those blocks
# to the 8-row tile; these tests pin (a) the alignment arithmetic for
# every group/block_q the serving shapes can produce and (b) interpret
# -mode parity on the exact shapes that used to emit misaligned slices,
# so the regression is caught on CPU (tests/test_tpu_compile.py
# compiles the real shapes for a described chip besides).

def test_sublane_padding_always_tile_aligned():
    from gofr_tpu.ops.paged_attention import SUBLANE, _pad_group
    for group in range(1, 33):
        padded = _pad_group(group)
        assert padded >= group and padded % SUBLANE == 0, (group, padded)
        for block_q in (1, 2, 4, 8, 16, 32, 64, 128):
            rows = block_q * _pad_group(group, block_q)
            assert rows % SUBLANE == 0, (group, block_q, rows)
            assert _pad_group(group, block_q) >= group
    # no waste where none is needed: already-aligned shapes unchanged
    assert _pad_group(8) == 8
    assert _pad_group(4, 2) == 4
    assert _pad_group(1, 8) == 1


@pytest.mark.parametrize("hq,hkv", [(4, 4),    # MHA: group=1, the
                                               # "but is 1" failure
                                    (8, 2),    # group=4 (llama3-1b)
                                    (6, 2)])   # group=3: odd group
def test_decode_parity_with_sub_tile_group(hq, hkv):
    """Small-GQA-group decode blocks (sublane-padded) still match the
    dense reference bit-for-bit in interpret mode."""
    case = _random_paged_case(jax.random.key(7), hq=hq, hkv=hkv,
                              lengths=(5, 17, 48))
    q, k_pool, v_pool, tables, lengths, k_dense, v_dense = case
    want = decode_attention(q[:, None], k_dense, v_dense, lengths)[:, 0]
    got = paged_decode_attention_pallas(q, k_pool, v_pool, tables,
                                        lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunk_parity_with_sub_tile_rows():
    """Chunk blocks whose block_q x group < 8 (the spec-verify window
    shape: tiny Sq, small group) pad to the tile and stay correct."""
    from gofr_tpu.ops.attention import xla_attention
    from gofr_tpu.ops.paged_attention import paged_chunk_attention_pallas
    b, sq, hq, hkv, hd = 2, 5, 4, 4, 16     # group=1, block_q=1 -> 1 row
    page, max_pages, n_pages = 8, 6, 32
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (hkv, n_pages, page, hd),
                               jnp.float32)
    v_pool = jax.random.normal(ks[2], (hkv, n_pages, page, hd),
                               jnp.float32)
    rng = np.random.default_rng(3)
    history = np.asarray([11, 0], np.int32)
    chunk_lens = np.asarray([sq, 3], np.int32)
    tables = np.full((b, max_pages), n_pages, np.int32)
    for i in range(b):
        need = -(-int(history[i] + chunk_lens[i]) // page)
        tables[i, :need] = rng.choice(n_pages, size=need, replace=False)
    tables = jnp.asarray(tables)
    got = paged_chunk_attention_pallas(
        q, k_pool, v_pool, tables, jnp.asarray(history),
        jnp.asarray(chunk_lens), interpret=True)
    safe = jnp.minimum(tables, n_pages - 1)
    k_dense = k_pool[:, safe].transpose(1, 2, 3, 0, 4).reshape(
        b, max_pages * page, hkv, hd)
    v_dense = v_pool[:, safe].transpose(1, 2, 3, 0, 4).reshape(
        b, max_pages * page, hkv, hd)
    want = xla_attention(q, k_dense, v_dense, causal=True,
                         q_offset=jnp.asarray(history),
                         kv_lengths=jnp.asarray(history)
                         + jnp.asarray(chunk_lens))
    for i in range(b):
        n = int(chunk_lens[i])  # rows past chunk_len are padding
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   rtol=2e-5, atol=2e-5)


def test_untakeable_shapes_raise_actionable_errors():
    """A pool the compiled kernel cannot take must fail with a message
    naming the constraint, not a Mosaic internal error (only on the
    compiled path — interpret mode has no tiling): a page size that
    cannot DMA into sublane-tiled VMEM, and a row that does not fill
    the 128 lanes (head_dim 16 with two kv heads packs to 32)."""
    case = _random_paged_case(jax.random.key(9), hd=128, page=4,
                              max_pages=12, lengths=(5, 9, 3))
    q, k_pool, v_pool, tables, lengths, *_ = case
    with pytest.raises(ValueError, match="multiple of 8"):
        paged_decode_attention_pallas(q, k_pool, v_pool, tables,
                                      lengths, interpret=False)
    # interpret mode still accepts it (CPU tests use small pages)
    paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                  interpret=True)
    case = _random_paged_case(jax.random.key(9))
    q, k_pool, v_pool, tables, lengths, *_ = case
    with pytest.raises(ValueError, match="128 lanes"):
        paged_decode_attention_pallas(q, pack_pool(k_pool),
                                      pack_pool(v_pool), tables,
                                      lengths, interpret=False)


def test_engine_construction_rejects_untakeable_kernel_shape():
    """paged_attention='kernel' with a pool the compiled kernel cannot
    take is a ValueError at ENGINE CONSTRUCTION naming the constraint —
    never a Mosaic trace out of warmup, never a quiet switch to
    xla/view. The tiny config's rows pack to 32 lanes."""
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import demo_llama_engine
    with pytest.raises(ValueError, match="128 lanes"):
        demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=64, kv_layout="paged", page_size=16,
            paged_attention="kernel"))
    # the same shape is fine for the paths that were asked for by name
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, kv_layout="paged", page_size=16,
        paged_attention="interpret"))
    assert eng.paged_attention_impl == "interpret"


# ------------------------------------------------- lane-packed pools
#
# head_dim < 128 packs 128 // head_dim kv heads into one pool row
# (ops/paged_kv.py) and the kernel attends a head group per grid cell.
# The cases above build UNPACKED pools by hand (pack == 1); these run
# the same three paths on the packed re-lay of the same values — the
# kernel on the packed pool must reproduce the XLA reference on the
# unpacked one, for plain and quantized pools alike.

from gofr_tpu.ops.paged_attention import (  # noqa: E402
    paged_chunk_attention_pallas, paged_chunk_attention_xla,
    paged_decode_append_attention_pallas, paged_tree_attention_pallas,
    paged_tree_attention_xla)
from gofr_tpu.ops.paged_kv import pack_pool, quantize_pool  # noqa: E402


def _packed_case(hd, hkv, group, page, sq=5, seed=70):
    b, max_pages, n_pages = 3, 6, 16
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hkv * group, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (hkv, n_pages, page, hd),
                               jnp.float32)
    v_pool = jax.random.normal(ks[2], (hkv, n_pages, page, hd),
                               jnp.float32)
    # mid-page history, a history spanning several chunks, and a
    # zero-length slot; the 2nd slot's chunk is shorter than Sq
    history = jnp.asarray([3, 2 * page + 1, 0], jnp.int32)
    chunk_lens = jnp.asarray([sq, max(1, sq - 2), 0], jnp.int32)
    rng = np.random.default_rng(seed)
    tables = np.full((b, max_pages), n_pages, np.int32)
    for i in range(b):
        need = -(-int(history[i] + chunk_lens[i]) // page)
        if need:
            tables[i, :need] = rng.choice(n_pages, size=need,
                                          replace=False)
    return q, k_pool, v_pool, jnp.asarray(tables), history, chunk_lens


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("hd,hkv,group,page", [
    (64, 4, 2, 64),     # the serving shape: two heads to a row
    (32, 4, 1, 16),     # four to a row, four pages to a chunk
    (16, 2, 4, 8),      # head count caps the pack: 32-lane rows
    (64, 3, 2, 8),      # odd head count: no packing at all
])
def test_packed_pool_kernel_matches_unpacked_xla(hd, hkv, group, page,
                                                 quantized):
    from gofr_tpu.ops.paged_kv import head_pack
    q, k_pool, v_pool, tables, history, chunk_lens = _packed_case(
        hd, hkv, group, page)
    kp, vp = pack_pool(k_pool), pack_pool(v_pool)
    assert kp.shape[-1] == hd * head_pack(hkv, hd)
    if quantized:
        kp, vp = (quantize_pool(kp, head_dim=hd),
                  quantize_pool(vp, head_dim=hd))
        k_pool, v_pool = quantize_pool(k_pool), quantize_pool(v_pool)

    def close(got, want, n_valid):
        assert not np.isnan(np.asarray(got)).any()
        for i, n in enumerate(n_valid):  # rows past chunk_len: padding
            np.testing.assert_allclose(np.asarray(got)[i, :n],
                                       np.asarray(want)[i, :n],
                                       rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got)[2], 0.0, atol=1e-6)

    n_valid = [int(n) for n in chunk_lens]
    close(paged_chunk_attention_pallas(q, kp, vp, tables, history,
                                       chunk_lens, interpret=True),
          paged_chunk_attention_xla(q, k_pool, v_pool, tables, history,
                                    chunk_lens), n_valid)
    # the packed XLA reference is the on-chip yardstick: same answer
    close(paged_chunk_attention_xla(q, kp, vp, tables, history,
                                    chunk_lens),
          paged_chunk_attention_xla(q, k_pool, v_pool, tables, history,
                                    chunk_lens), n_valid)
    lengths = history + chunk_lens
    close(paged_decode_attention_pallas(q[:, 0], kp, vp, tables, lengths,
                                        interpret=True)[:, None],
          paged_decode_attention_xla(q[:, 0], k_pool, v_pool, tables,
                                     lengths)[:, None], [1, 1, 0])
    # a two-branch tree over the 5 nodes: 0 -> (1 -> 3, 2 -> 4)
    masks = jnp.asarray([[0b00001, 0b00011, 0b00101, 0b01011, 0b10101]]
                        * 3, jnp.int32)
    close(paged_tree_attention_pallas(q, kp, vp, tables, history,
                                      chunk_lens, masks, interpret=True),
          paged_tree_attention_xla(q, k_pool, v_pool, tables, history,
                                   chunk_lens, masks), n_valid)


# ------------------------------------------------------ the decode walk
#
# Decode has its own walk (ops/paged_attention.py ``_decode_kernel``): a
# cell a slot, every head group of a page in one fold, folds of
# ``_fold_pages`` pages, the next live slot's first fold in flight, no
# walk for a slot without rows. These run it interpreted against the
# XLA reference on the SAME pool at the two geometries the benchmark
# serves (their real head counts and page: the fold is sized from
# them), bf16 and int8: live and empty slots interleaved, nobody live,
# contexts around the fold's edges, a table used to its last page, and
# the two ways the ENGINE presents a slot without rows (an
# all-unallocated table under a small length; a length no table
# holds). Every traffic is six slots over one pool shape, so a
# (geometry, pool, query dtype) is traced and compiled once.

WALK_PAGE, WALK_SLOTS = 64, 6
WALK_GEOMETRIES = {
    "hd64-packed-mha": dict(hq=32, hkv=32, hd=64),      # SmolLM2-1.7B
    "hd128-gqa-4to1": dict(hq=32, hkv=8, hd=128),       # Mistral-7B
}
WALK_LENGTHS = {
    "interleaved": lambda fold, cap: (0, 700, 0, 0, 65, 0),
    "all-empty": lambda fold, cap: (0,) * WALK_SLOTS,
    "fold-edges": lambda fold, cap: (fold - 1, fold, fold + 1,
                                     3 * fold + 1, 0, 0),
    "last-page": lambda fold, cap: (cap, 0, cap - WALK_PAGE + 1, 0, 0, 0),
    # (length, holds pages): as the engine hands an empty slot and a
    # slot mid-prefill to decode
    "engine-empty": lambda fold, cap: ((5, False), 130, (cap + 1, True),
                                       (1, False), 0, (8, False)),
}
_walk_kernel = jax.jit(lambda *a: paged_decode_attention_pallas(
    *a, interpret=True))
_walk_reference = jax.jit(paged_decode_attention_xla)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(WALK_GEOMETRIES))
@pytest.mark.parametrize("traffic", sorted(WALK_LENGTHS))
def test_decode_walk_matches_xla(traffic, geometry, quantized):
    from gofr_tpu.ops.paged_attention import _fold_pages
    from gofr_tpu.ops.paged_kv import head_pack
    g = WALK_GEOMETRIES[geometry]
    hq, hkv, hd = g["hq"], g["hkv"], g["hd"]
    pack = head_pack(hkv, hd)
    fold_pages = _fold_pages(hkv // pack, WALK_PAGE, pack * hd,
                             1 if quantized else 2, 10 ** 6)
    max_pages = 3 * fold_pages + 2          # holds three folds + 1
    fold, cap = fold_pages * WALK_PAGE, max_pages * WALK_PAGE
    n_pages = 2 * max_pages + 4
    spec = [x if isinstance(x, tuple) else (x, x > 0)
            for x in WALK_LENGTHS[traffic](fold, cap)]
    assert len(spec) == WALK_SLOTS
    held = [min(-(-n // WALK_PAGE), max_pages) if pages else 0
            for n, pages in spec]
    rng = np.random.default_rng(len(traffic) + hd + quantized)
    order = rng.permutation(n_pages)
    tables = np.full((WALK_SLOTS, max_pages), n_pages, np.int32)
    at = 0
    for i, need in enumerate(held):
        tables[i, :need] = order[at:at + need]
        at += need
    ks = jax.random.split(jax.random.key(hd + len(traffic)), 3)
    kp, vp = (pack_pool(jax.random.normal(
        k, (hkv, n_pages, WALK_PAGE, hd), jnp.float32)
        .astype(jnp.bfloat16)) for k in ks[:2])
    if quantized:
        kp, vp = (quantize_pool(x, head_dim=hd) for x in (kp, vp))
    # the reference attends a bf16 view in bf16: hand it the same
    # values as float32 (an int8 pool it dequantizes to float32 itself)
    ref_pools = (kp, vp) if quantized else \
        (kp.astype(jnp.float32), vp.astype(jnp.float32))
    tables = jnp.asarray(tables)
    lens = jnp.asarray([n for n, _ in spec], jnp.int32)
    live = np.asarray([pages and 0 < n <= cap for n, pages in spec])
    # float32 queries: nothing the float32 walk kept is rounded, so the
    # kernel and the reference differ by summation order alone; bf16
    # queries (the engine's) take the one-pass products and round the
    # output to bf16
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        q = jax.random.normal(ks[2], (WALK_SLOTS, hq, hd),
                              jnp.float32).astype(dtype)
        got = np.asarray(_walk_kernel(q, kp, vp, tables, lens), np.float32)
        want = np.asarray(_walk_reference(q, *ref_pools, tables, lens),
                          np.float32)
        assert not np.isnan(got).any()
        np.testing.assert_allclose(got[live], want[live], rtol=tol,
                                   atol=tol)
        # a slot without rows: exact zeros on both paths
        np.testing.assert_array_equal(got[~live], 0.0)
        np.testing.assert_array_equal(want[~live], 0.0)


def test_decode_fold_is_sized_from_what_the_kernel_sees():
    """About 2 MiB of K and V a fold whatever the geometry, in whole
    128-row tiles, never more than the table."""
    from gofr_tpu.ops.paged_attention import FOLD_BYTES, _fold_pages
    for hg, itemsize in ((16, 2), (8, 2), (16, 1), (8, 1), (1, 2)):
        pages = _fold_pages(hg, 64, 128, itemsize, 128)
        assert pages % 2 == 0 and pages <= 16
        assert 2 * hg * pages * 64 * 128 * itemsize <= FOLD_BYTES
    assert _fold_pages(16, 64, 128, 2, 128) == 4      # SmolLM2, bf16
    assert _fold_pages(8, 64, 128, 2, 128) == 8       # Mistral, bf16
    assert _fold_pages(16, 64, 128, 2, 3) == 3        # a three-page table
    assert _fold_pages(2, 24, 128, 2, 64) == 5        # a page off the lanes


# ------------------------------------------- the walk writes the row
#
# The model's decode step hands the step's fresh K/V rows to the walk
# (``paged_decode_append_attention``): a plain pool is written INSIDE
# the walk — the row laid over the last fold's buffer, the tile-aligned
# block that holds it copied back — and an int8 pool in front of it, by
# ``pool_write`` as before; the pool's type selects. Both are held to
# the page-granular write + the gather reference: pools equal bit for
# bit, every byte outside the written rows untouched. Slots are
# ``length after the write`` or ``(length, pages held)``: ``False``
# none, ``True`` all it needs, a number that many.

APPEND_LENGTHS = {
    # a page's first and last position, the first page and the second
    "page-edges": lambda blk, fold, cap: (
        1, WALK_PAGE, WALK_PAGE + 1, 2 * WALK_PAGE, (3, False), 2),
    # either side of the write-back block's boundary
    "block-edges": lambda blk, fold, cap: (
        blk, blk + 1, WALK_PAGE + blk, WALK_PAGE + blk + 1, 2 * blk,
        (9, False)),
    # the row closes a fold, opens the next one
    "fold-edges": lambda blk, fold, cap: (
        fold, fold + 1, 2 * fold, 2 * fold + 1, 3 * fold + 1, (1, False)),
    # a table used to its last page and row; a slot mid-prefill
    # carries max_seq and lands past the table
    "last-page": lambda blk, fold, cap: (
        cap, (cap + 1, True), cap - WALK_PAGE + 1, (2, False), cap - 1,
        (7, False)),
    # the tail page is not in the table: the row drops, the walk attends
    "tail-unallocated": lambda blk, fold, cap: (
        (WALK_PAGE + 1, 1), 70, (2 * WALK_PAGE + 5, 2), (1, False),
        (fold + 1, fold // WALK_PAGE), 130),
    "interleaved": lambda blk, fold, cap: (
        (5, False), 700, (1, False), 66, (cap + 1, True), 17),
    "nobody-live": lambda blk, fold, cap: (
        (1, False), (2, False), (cap + 1, True), (cap + 5, True),
        (3, False), (8, False)),
}
APPEND_LAYER = 1        # of two: the other layer's bytes stay


_append_kernel = jax.jit(lambda *a: paged_decode_append_attention_pallas(
    *a, layer=APPEND_LAYER, interpret=True))


@jax.jit
def _append_reference(q, k_new, v_new, k_pool, v_pool, tables, lens):
    """``pool_write`` of every slot's row, then the gather reference
    (a bf16 pool attended as float32: it attends a bf16 view in bf16)."""
    from gofr_tpu.ops.paged_kv import is_quantized_pool, pool_write
    one = jnp.ones_like(lens)
    pools = tuple(pool_write(pool, APPEND_LAYER, tables, lens - 1, one,
                             rows[:, None])
                  for pool, rows in ((k_pool, k_new), (v_pool, v_new)))
    seen = pools if is_quantized_pool(k_pool) else \
        tuple(x.astype(jnp.float32) for x in pools)
    return (paged_decode_attention_xla(q, *seen, tables, lens,
                                       layer=APPEND_LAYER), *pools)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(WALK_GEOMETRIES))
@pytest.mark.parametrize("traffic", sorted(APPEND_LENGTHS))
def test_decode_walk_writes_the_fresh_row(traffic, geometry, quantized):
    from gofr_tpu.ops.paged_attention import (_fold_pages,
                                              _write_block_rows)
    from gofr_tpu.ops.paged_kv import head_pack
    g = WALK_GEOMETRIES[geometry]
    hq, hkv, hd = g["hq"], g["hkv"], g["hd"]
    pack = head_pack(hkv, hd)
    itemsize = 1 if quantized else 2
    fold_pages = _fold_pages(hkv // pack, WALK_PAGE, pack * hd, itemsize,
                             10 ** 6)
    max_pages = 3 * fold_pages + 2          # holds three folds + 1
    fold, cap = fold_pages * WALK_PAGE, max_pages * WALK_PAGE
    blk = _write_block_rows(WALK_PAGE, itemsize)
    assert blk in (16, 32) and WALK_PAGE % blk == 0
    spec = [x if isinstance(x, tuple) else (x, True)
            for x in APPEND_LENGTHS[traffic](blk, fold, cap)]
    assert len(spec) == WALK_SLOTS
    held = [min(-(-n // WALK_PAGE), max_pages) if pages is True
            else int(pages) for n, pages in spec]
    n_pages = sum(held) + 3
    rng = np.random.default_rng(len(traffic) + hd + quantized)
    # the last page is nobody's: an unallocated entry reads it clamped
    order = rng.permutation(n_pages - 1)
    tables = np.full((WALK_SLOTS, max_pages), n_pages, np.int32)
    at = 0
    for i, need in enumerate(held):
        tables[i, :need] = order[at:at + need]
        at += need
    ks = jax.random.split(jax.random.key(hd + len(traffic)), 5)
    kp, vp = (pack_pool(jax.random.normal(
        k, (2, hkv, n_pages, WALK_PAGE, hd), jnp.float32)
        .astype(jnp.bfloat16)) for k in ks[:2])
    if quantized:
        kp, vp = (quantize_pool(x, head_dim=hd) for x in (kp, vp))
    k_new, v_new = (jax.random.normal(k, (WALK_SLOTS, hkv, hd), jnp.float32)
                    .astype(jnp.bfloat16) for k in ks[2:4])
    lens = jnp.asarray([n for n, _ in spec], jnp.int32)
    live = np.asarray([bool(h) and 0 < n <= cap
                       for (n, _), h in zip(spec, held)])
    # the slots whose row lands: live, and the tail page in the table
    lands = {i: (int(tables[i, (n - 1) // WALK_PAGE]), (n - 1) % WALK_PAGE)
             for i, (n, _) in enumerate(spec)
             if live[i] and (n - 1) // WALK_PAGE < held[i]}
    tables = jnp.asarray(tables)
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        q = jax.random.normal(ks[4], (WALK_SLOTS, hq, hd),
                              jnp.float32).astype(dtype)
        got, *got_pools = _append_kernel(q, k_new, v_new, kp, vp, tables,
                                         lens)
        want, *want_pools = _append_reference(q, k_new, v_new, kp, vp,
                                              tables, lens)
        for a, b in zip(jax.tree.leaves(got_pools),
                        jax.tree.leaves(want_pools)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert not np.isnan(got).any()
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
        np.testing.assert_array_equal(got[~live], 0.0)
    # ... and what changed is those rows of that layer, nothing else
    for old, new in zip((kp, vp), got_pools):
        old, new = (np.asarray(x["q"] if quantized else x, np.float32)
                    for x in (old, new))
        changed = {(int(l), int(pid), int(row)) for l, _, pid, row, _ in
                   np.argwhere(old != new)}
        assert changed <= {(APPEND_LAYER, *at) for at in lands.values()}
        assert len(changed) == len(lands)


# ---------------------------------------------------- quantized pools
#
# int8 KV pages (ops/paged_kv.py: {"q": int8, "s": f32 per-row}). The
# kernel DMAs the codes page plus its scale column and dequantizes
# in-register; the XLA fallback dequantizes its gathered view. Both
# paths therefore see the SAME f32 inputs, so kernel-vs-fallback
# parity is as tight as the unquantized case (2e-5, the repo's
# interpret-parity idiom) — while int8-vs-f32 is bounded by the
# quantization error itself (per element <= amax/254; observed worst
# case ~0.018 on N(0,1) pools, asserted at 0.05 = ~3x margin).

def _quant_decode_case(seed, *, page, hq, hkv, lengths=(5, 17, 0)):
    """Mid-page histories + a zero-length tail slot, quantized pools
    alongside their f32 source."""
    case = _random_paged_case(jax.random.key(seed), hq=hq, hkv=hkv,
                              page=page, max_pages=8, n_pages=32,
                              lengths=lengths)
    q, k_pool, v_pool, tables, lens, *_ = case
    return (q, k_pool, v_pool, quantize_pool(k_pool),
            quantize_pool(v_pool), tables, lens)


@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("hq,hkv", [(4, 4),   # GQA group 1
                                    (8, 2)])  # GQA group 4
def test_int8_decode_kernel_matches_int8_xla(page, hq, hkv):
    q, _, _, kq, vq, tables, lens = _quant_decode_case(
        41 + page, page=page, hq=hq, hkv=hkv)
    got = paged_decode_attention_pallas(q, kq, vq, tables, lens,
                                        interpret=True)
    want = paged_decode_attention_xla(q, kq, vq, tables, lens)
    # full-batch comparison: the fallback masks zero-length slots to
    # exact zeros, matching the kernel's denom-clamp contract
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got)[2], 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(want)[2], 0.0, atol=1e-6)


@pytest.mark.parametrize("page", [8, 16])
def test_int8_decode_within_quant_bound_of_f32(page):
    q, k_pool, v_pool, kq, vq, tables, lens = _quant_decode_case(
        43 + page, page=page, hq=8, hkv=2)
    got = paged_decode_attention_pallas(q, kq, vq, tables, lens,
                                        interpret=True)
    want = paged_decode_attention_xla(q, k_pool, v_pool, tables, lens)
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                               atol=0.05)


def _quant_chunk_case(seed, *, page, hq, hkv):
    """Chunk shapes: histories starting mid-page (3, 9) and a
    zero-length tail row."""
    b, sq, hd, max_pages, n_pages = 3, 5, 16, 8, 32
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (hkv, n_pages, page, hd),
                               jnp.float32)
    v_pool = jax.random.normal(ks[2], (hkv, n_pages, page, hd),
                               jnp.float32)
    history = jnp.asarray([3, 9, 0], jnp.int32)
    chunk_lens = jnp.asarray([sq, 3, 0], jnp.int32)
    rng = np.random.default_rng(seed)
    tables = np.full((b, max_pages), n_pages, np.int32)
    for i in range(b):
        need = -(-int(history[i] + chunk_lens[i]) // page)
        if need:
            tables[i, :need] = rng.choice(n_pages, size=need,
                                          replace=False)
    return (q, k_pool, v_pool, quantize_pool(k_pool),
            quantize_pool(v_pool), jnp.asarray(tables), history,
            chunk_lens)


@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_int8_chunk_kernel_matches_int8_xla(page, hq, hkv):
    (q, _, _, kq, vq, tables, history,
     chunk_lens) = _quant_chunk_case(47 + page + hq, page=page,
                                     hq=hq, hkv=hkv)
    got = paged_chunk_attention_pallas(q, kq, vq, tables, history,
                                       chunk_lens, interpret=True)
    want = paged_chunk_attention_xla(q, kq, vq, tables, history,
                                     chunk_lens)
    assert not np.isnan(np.asarray(got)).any()
    for i in range(3):
        n = int(chunk_lens[i])  # rows past chunk_len are padding
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   rtol=2e-5, atol=2e-5)
    # the zero-length tail slot (history == chunk == 0) is exact zeros
    # on BOTH paths now — the fallback masks it like the kernel
    np.testing.assert_allclose(np.asarray(want)[2], 0.0, atol=1e-6)


def test_int8_chunk_within_quant_bound_of_f32():
    (q, k_pool, v_pool, kq, vq, tables, history,
     chunk_lens) = _quant_chunk_case(53, page=8, hq=8, hkv=2)
    got = paged_chunk_attention_pallas(q, kq, vq, tables, history,
                                       chunk_lens, interpret=True)
    want = paged_chunk_attention_xla(q, k_pool, v_pool, tables,
                                     history, chunk_lens)
    for i in range(3):
        n = int(chunk_lens[i])
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n], atol=0.05)


# ------------------------------------------------- engine-level parity

@pytest.mark.parametrize("page_size,prompt_len", [
    (16, 3),
    # a 24-row page does not tile the lanes, so a fold is 5 pages = 120
    # rows: the first 8-step pass appends rows 115..122 — across a page
    # boundary that is a fold boundary too, and a write-back block's
    (24, 115)], ids=["short", "across-a-fold"])
def test_paged_native_engine_matches_view_engine(page_size, prompt_len):
    """The native paged decode path (the decode walk in interpret mode,
    writing each fresh row through the table as it attends) must
    reproduce the view engine's greedy outputs exactly. The view engine
    is the reference: the dense step functions on a gathered per-slot
    view, no kernel, no table writes by the model."""
    import time

    from gofr_tpu.ops.paged_attention import _fold_pages
    from gofr_tpu.serving.engine import EngineConfig, SamplingParams
    from gofr_tpu.serving.glue import demo_llama_engine

    def drain(reqs, timeout=180):
        deadline = time.time() + timeout
        while time.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            time.sleep(0.01)
        return reqs

    def prompt(i):
        return [5 + i, 2, 9] if prompt_len == 3 else \
            [(5 + i + 3 * j) % 200 + 3 for j in range(prompt_len)]

    cfg = dict(max_batch=3, max_seq=128, seed=23, page_size=page_size)
    view = demo_llama_engine(EngineConfig(paged_attention="view", **cfg))
    view.start()
    want = [view.submit(prompt(i), SamplingParams(
        temperature=0.0, max_new_tokens=9)) for i in range(3)]
    drain(want)
    view.stop()

    native = demo_llama_engine(EngineConfig(
        paged_attention="interpret", **cfg))
    assert native._decode is not None
    if prompt_len > 3:  # the pass does cross a fold of the engine's pool
        hg, _, pg, w = native.k_cache.shape[1:]
        fold = pg * _fold_pages(hg, pg, w, native.k_cache.dtype.itemsize,
                                native._pages_per_slot)
        assert prompt_len < fold < prompt_len + 8 and fold % pg == 0
    native.start()
    got = [native.submit(prompt(i), SamplingParams(
        temperature=0.0, max_new_tokens=9)) for i in range(3)]
    drain(got)
    native.stop()

    assert all(r.error is None for r in got)
    assert all(len(r.generated) == 9 for r in got)
    assert [r.generated for r in got] == [r.generated for r in want]


# ----------------------------------------- pipelined-prefill races

def _unstarted_paged_engine(**cfg):
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import demo_llama_engine

    # pipeline_depth=1 forces the pipelined regime these races live
    # in: adaptive depth would collect prefills at admit time below
    # pipeline_min_slots and the dispatch->collect window would vanish
    base = dict(max_batch=2, max_seq=128, seed=31, kv_layout="paged",
                page_size=16, pipeline_depth=1)
    base.update(cfg)
    return demo_llama_engine(EngineConfig(**base))


def test_stale_prefill_result_discarded_after_preempt():
    """A batch prefill dispatched for request R must be discarded if R
    was preempted before its first token was collected — the recompute
    owns its own prefill (epoch protocol)."""
    from gofr_tpu.serving.engine import SamplingParams

    engine = _unstarted_paged_engine()
    req = engine.submit([5, 9, 2], SamplingParams(temperature=0.0,
                                                  max_new_tokens=6))
    # drive the engine internals directly (loop not started)
    engine._admit_batch([engine.waiting.pop_batch(1)[0]])
    assert engine._pending_prefills and req.pending_prefill
    slot = req.slot
    engine._preempt(slot)                  # evicted before collect
    assert not req.pending_prefill
    engine._collect_prefills()             # stale: must emit NOTHING
    assert req.generated == []
    assert req.finished_at is None         # still live, just requeued
    # the requeued life re-admits and produces its first token cleanly
    batch, engine._requeued = engine._requeued, []
    engine._requeued_set.clear()
    engine._admit_batch(batch)
    engine._collect_prefills()
    assert len(req.generated) == 1
    engine._shutdown_cleanup("test over")


def test_cancelled_pending_prefill_discarded():
    """Cancellation between prefill dispatch and collect retires the
    slot; the late first token must not land after the terminal None."""
    from gofr_tpu.serving.engine import SamplingParams

    engine = _unstarted_paged_engine()
    req = engine.submit([7, 7, 7], SamplingParams(temperature=0.0,
                                                  max_new_tokens=6))
    engine._admit_batch([engine.waiting.pop_batch(1)[0]])
    req.cancelled = True
    engine._retire_unservable()            # retires the pending slot
    assert req.finished_at is not None
    engine._collect_prefills()
    assert req.generated == []             # nothing after the None
    engine._shutdown_cleanup("test over")


def test_prefill_spans_do_not_double_count():
    """Two bucket groups dispatched back-to-back then collected
    together must accumulate a UNION of wall spans, not a 2x sum."""
    import time as _t

    from gofr_tpu.serving.engine import SamplingParams

    engine = _unstarted_paged_engine(max_batch=4)
    t0 = _t.perf_counter()
    for prompt in ([1] * 10, [2] * 40):    # two different buckets
        engine.submit(prompt, SamplingParams(temperature=0.0,
                                             max_new_tokens=4))
    engine._admit_batch(engine.waiting.pop_batch(4))
    assert len(engine._pending_prefills) == 2
    engine._collect_prefills()
    wall = _t.perf_counter() - t0
    assert engine.stats["prefill_s"] <= wall + 0.01
    engine._shutdown_cleanup("test over")


# ------------------------------------------------------ tree verify
#
# Multi-draft tree verify (ops/paged_attention.py paged_tree_attention):
# Sq tree nodes per slot attend the full history plus exactly their
# packed-ancestor in-tree rows. Parity cases mirror the serving shapes:
# branch counts 1/2/4, histories starting mid-page, a zero-length tail
# slot, GQA groups 1 and 4, f32/bf16/int8 pools. A chain-shaped tree
# must reduce bit-for-bit to the causal chunk kernel — speculation's
# greedy-identity contract rides on that.

from gofr_tpu.ops.attention import tree_attention
from gofr_tpu.ops.paged_attention import (paged_tree_attention,
                                          paged_tree_attention_pallas,
                                          paged_tree_attention_xla)
from gofr_tpu.serving.spec import build_draft_tree


def _branch_chains(branches):
    if branches == 1:
        return [[1, 2, 3, 4]]
    if branches == 2:
        return [[1, 2, 3], [1, 5], [6, 7]]  # shared prefix + fork
    return [[1, 2], [3, 4], [5, 6], [7, 8]]


def _tree_case(seed, *, branches, hq, hkv, page=8, dtype=jnp.float32):
    """3 slots: mid-page histories (3, 9) and a zero-length tail; the
    2nd slot verifies a topological PREFIX of the tree (shorter
    chunk), the 3rd is inactive."""
    tree = build_draft_tree(0, _branch_chains(branches))
    sq = tree.n_nodes
    b, hd, max_pages, n_pages = 3, 16, 8, 32
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (hkv, n_pages, page, hd),
                               jnp.float32).astype(dtype)
    v_pool = jax.random.normal(ks[2], (hkv, n_pages, page, hd),
                               jnp.float32).astype(dtype)
    history = jnp.asarray([3, 9, 0], jnp.int32)
    chunk_lens = jnp.asarray([sq, min(sq, 3), 0], jnp.int32)
    masks = np.ones((b, sq), np.int32)
    masks[0] = tree.masks
    masks[1, :sq] = tree.masks  # prefix rows are the ones compared
    rng = np.random.default_rng(seed)
    tables = np.full((b, max_pages), n_pages, np.int32)
    for i in range(b):
        need = -(-int(history[i] + chunk_lens[i]) // page)
        if need:
            tables[i, :need] = rng.choice(n_pages, size=need,
                                          replace=False)
    return (q, k_pool, v_pool, jnp.asarray(tables), history,
            chunk_lens, jnp.asarray(masks))


@pytest.mark.parametrize("branches", [1, 2, 4])
@pytest.mark.parametrize("hq,hkv", [(4, 4),   # GQA group 1
                                    (8, 2)])  # GQA group 4
def test_tree_kernel_matches_xla(branches, hq, hkv):
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(61 + branches, branches=branches, hq=hq,
                         hkv=hkv)
    got = paged_tree_attention_pallas(q, k_pool, v_pool, tables,
                                      history, chunk_lens, masks,
                                      interpret=True)
    want = paged_tree_attention_xla(q, k_pool, v_pool, tables,
                                    history, chunk_lens, masks)
    assert not np.isnan(np.asarray(got)).any()
    for i in range(3):
        n = int(chunk_lens[i])  # rows past chunk_len are padding
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   rtol=2e-5, atol=2e-5)
    # the zero-length tail slot returns exact zeros on both paths
    np.testing.assert_allclose(np.asarray(got)[2], 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(want)[2], 0.0, atol=1e-6)


def test_tree_chain_reduces_to_causal_chunk():
    """A chain-shaped tree's ancestor bitmask IS the causal window:
    the tree kernel must match the chunk kernel on it (speculation's
    greedy bit-identity rides this)."""
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(67, branches=1, hq=8, hkv=2)
    got = paged_tree_attention_pallas(q, k_pool, v_pool, tables,
                                      history, chunk_lens, masks,
                                      interpret=True)
    want = paged_chunk_attention_pallas(q, k_pool, v_pool, tables,
                                        history, chunk_lens,
                                        interpret=True)
    for i in range(3):
        n = int(chunk_lens[i])
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   rtol=2e-5, atol=2e-5)


def test_tree_sibling_cannot_see_sibling():
    """Poisoning a sibling branch's pool rows must not change a node's
    output — only ancestors are visible in-tree."""
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(71, branches=2, hq=4, hkv=4)
    tree = build_draft_tree(0, _branch_chains(2))
    clean = paged_tree_attention_pallas(q, k_pool, v_pool, tables,
                                        history, chunk_lens, masks,
                                        interpret=True)
    # poison the LAST node's pool row for slot 0 (a leaf on the other
    # fork): nodes not descending from it must be unchanged
    leaf = tree.n_nodes - 1
    pos = int(history[0]) + leaf
    pid = int(tables[0, pos // k_pool.shape[2]])
    poisoned = np.asarray(k_pool).copy()
    poisoned[:, pid, pos % k_pool.shape[2]] = 1e6
    got = paged_tree_attention_pallas(q, jnp.asarray(poisoned), v_pool,
                                      tables, history, chunk_lens,
                                      masks, interpret=True)
    unaffected = [i for i in range(tree.n_nodes)
                  if not (tree.masks[i] >> leaf) & 1]
    np.testing.assert_allclose(np.asarray(got)[0, unaffected],
                               np.asarray(clean)[0, unaffected],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("branches", [2, 4])
def test_int8_tree_kernel_matches_int8_xla(branches):
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(73 + branches, branches=branches, hq=8, hkv=2)
    kq, vq = quantize_pool(k_pool), quantize_pool(v_pool)
    got = paged_tree_attention_pallas(q, kq, vq, tables, history,
                                      chunk_lens, masks,
                                      interpret=True)
    want = paged_tree_attention_xla(q, kq, vq, tables, history,
                                    chunk_lens, masks)
    assert not np.isnan(np.asarray(got)).any()
    for i in range(3):
        n = int(chunk_lens[i])
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got)[2], 0.0, atol=1e-6)


def test_bf16_tree_pools_within_cast_bound():
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(79, branches=2, hq=4, hkv=4,
                         dtype=jnp.bfloat16)
    got = paged_tree_attention_pallas(q, k_pool, v_pool, tables,
                                      history, chunk_lens, masks,
                                      interpret=True)
    want = paged_tree_attention_xla(q, k_pool, v_pool, tables,
                                    history, chunk_lens, masks)
    for i in range(3):
        n = int(chunk_lens[i])
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[i, :n],
            np.asarray(want, np.float32)[i, :n], atol=2e-2)


# ------------------------------------- len-0 slot kernel/XLA parity
#
# The Pallas kernels return exact zeros for zero-length slots (denom
# clamp + masked DMA); the _xla fallbacks used to let the dense
# softmax degrade to an unmasked average over garbage rows there,
# leaving the engine's discard of inactive-slot tokens load-bearing
# for correctness. All three fallbacks now zero len-0 rows, so which
# path served a pass can never leak into output bytes — the integrity
# plane's digest parity (serving/integrity.py) rides this. These pin
# exact (atol=0) zeros on BOTH paths for every kernel family.

def test_len0_slot_zeroed_on_both_paths_decode():
    case = _random_paged_case(jax.random.key(91), lengths=(0, 8, 16))
    q, k_pool, v_pool, tables, lengths, *_ = case
    kernel = paged_decode_attention_pallas(q, k_pool, v_pool, tables,
                                           lengths, interpret=True)
    fallback = paged_decode_attention_xla(q, k_pool, v_pool, tables,
                                          lengths)
    assert not np.isnan(np.asarray(fallback)).any()
    np.testing.assert_array_equal(np.asarray(kernel)[0],
                                  np.zeros_like(np.asarray(kernel)[0]))
    np.testing.assert_array_equal(np.asarray(fallback)[0],
                                  np.zeros_like(np.asarray(fallback)[0]))


def test_len0_slot_zeroed_on_both_paths_chunk():
    (q, k_pool, v_pool, _, _, tables, history,
     chunk_lens) = _quant_chunk_case(97, page=8, hq=4, hkv=4)
    kernel = paged_chunk_attention_pallas(q, k_pool, v_pool, tables,
                                          history, chunk_lens,
                                          interpret=True)
    fallback = paged_chunk_attention_xla(q, k_pool, v_pool, tables,
                                         history, chunk_lens)
    assert not np.isnan(np.asarray(fallback)).any()
    # slot 2 has history == chunk == 0: every row is dead padding
    np.testing.assert_array_equal(np.asarray(kernel)[2],
                                  np.zeros_like(np.asarray(kernel)[2]))
    np.testing.assert_array_equal(np.asarray(fallback)[2],
                                  np.zeros_like(np.asarray(fallback)[2]))


def test_len0_slot_zeroed_on_both_paths_tree():
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(101, branches=2, hq=4, hkv=4)
    kernel = paged_tree_attention_pallas(q, k_pool, v_pool, tables,
                                         history, chunk_lens, masks,
                                         interpret=True)
    fallback = paged_tree_attention_xla(q, k_pool, v_pool, tables,
                                        history, chunk_lens, masks)
    assert not np.isnan(np.asarray(fallback)).any()
    np.testing.assert_array_equal(np.asarray(kernel)[2],
                                  np.zeros_like(np.asarray(kernel)[2]))
    np.testing.assert_array_equal(np.asarray(fallback)[2],
                                  np.zeros_like(np.asarray(fallback)[2]))


def test_tree_dispatch_auto_on_cpu_matches_dense():
    (q, k_pool, v_pool, tables, history, chunk_lens,
     masks) = _tree_case(83, branches=2, hq=8, hkv=2)
    got = paged_tree_attention(q, k_pool, v_pool, tables, history,
                               chunk_lens, masks,
                               implementation="auto")
    safe = jnp.minimum(tables, k_pool.shape[1] - 1)
    k_dense = k_pool[:, safe].transpose(1, 2, 3, 0, 4).reshape(
        3, -1, k_pool.shape[0], k_pool.shape[3])
    v_dense = v_pool[:, safe].transpose(1, 2, 3, 0, 4).reshape(
        3, -1, v_pool.shape[0], v_pool.shape[3])
    want = tree_attention(q, k_dense, v_dense, history_lens=history,
                          chunk_lens=chunk_lens, tree_masks=masks)
    for i in range(3):
        n = int(chunk_lens[i])
        np.testing.assert_allclose(np.asarray(got)[i, :n],
                                   np.asarray(want)[i, :n],
                                   rtol=2e-5, atol=2e-5)
