"""Paged KV primitives + the engine's paged mode."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.paged_kv import (gather_view, scatter_chunk,
                                   scatter_decode, scatter_prefill)

L, NP, PG, H, D = 2, 6, 4, 2, 3   # layers, pages, page size, heads, head dim


def _pool(fill=0.0):
    return jnp.full((L, H, NP, PG, D), fill, jnp.float32)


def test_scatter_prefill_then_gather_roundtrip():
    pool = _pool()
    # one row owning pages [2, 0], prompt length 6 (spans both pages)
    tables = jnp.asarray([[2, 0, NP]], jnp.int32)           # Mp = 3
    slab = jnp.arange(L * 1 * 8 * H * D, dtype=jnp.float32).reshape(
        L, 1, 8, H, D)                                       # S = 8 > 6: padded
    pool = scatter_prefill(pool, tables, slab)
    view = gather_view(pool, tables)
    np.testing.assert_array_equal(np.asarray(view[:, :, :8]),
                                  np.asarray(slab))


def test_scatter_prefill_drops_unallocated_padding():
    pool = _pool(-1.0)
    tables = jnp.asarray([[1, NP, NP]], jnp.int32)          # only page 1
    slab = jnp.ones((L, 1, 8, H, D), jnp.float32)           # rows 4..7 OOB
    pool = scatter_prefill(pool, tables, slab)
    got = np.asarray(pool)
    assert (got[:, :, 1] == 1.0).all()                         # page 1 written
    mask = np.ones(NP, bool)
    mask[1] = False
    assert (got[:, :, mask] == -1.0).all()                     # others untouched


def test_scatter_prefill_dummy_row_dropped():
    pool = _pool(-1.0)
    tables = jnp.asarray([[NP, NP, NP]], jnp.int32)         # dummy row
    slab = jnp.ones((L, 1, 4, H, D), jnp.float32)
    pool = scatter_prefill(pool, tables, slab)
    assert (np.asarray(pool) == -1.0).all()


def test_scatter_chunk_writes_only_chunk_rows():
    pool = _pool(-1.0)
    tables = jnp.asarray([[3, 1, NP]], jnp.int32)
    # chunk of 3 rows starting at logical position 3: spans the page
    # boundary (page 3 offset 3, then page 1 offsets 0-1)
    slab = jnp.zeros((L, 1, 8, H, D), jnp.float32)
    slab = slab.at[:, 0, 0].set(7.0).at[:, 0, 1].set(8.0) \
        .at[:, 0, 2].set(9.0)
    pool = scatter_chunk(pool, tables, slab, jnp.asarray([3]),
                         jnp.asarray([3]))
    got = np.asarray(pool)
    assert (got[:, :, 3, 3] == 7.0).all()
    assert (got[:, :, 1, 0] == 8.0).all()
    assert (got[:, :, 1, 1] == 9.0).all()
    # rows 3..7 of the slab are past chunk_len: dropped, not written
    written = np.zeros_like(got, bool)
    written[:, :, 3, 3] = written[:, :, 1, 0] = written[:, :, 1, 1] = True
    assert (got[~written] == -1.0).all()


def test_scatter_chunk_matches_prefill_on_prompt_rows():
    """With offset 0 and chunk_len = prompt length, scatter_chunk and
    scatter_prefill agree on every prompt row; only the padding rows
    within the last allocated page differ (chunk drops them)."""
    tables = jnp.asarray([[2, 0, NP]], jnp.int32)
    slab = jnp.arange(L * 1 * 8 * H * D, dtype=jnp.float32).reshape(
        L, 1, 8, H, D)
    a = scatter_prefill(_pool(), tables, slab)
    b = scatter_chunk(_pool(), tables, slab, jnp.asarray([0]),
                      jnp.asarray([6]))
    view_a = gather_view(a, tables)
    view_b = gather_view(b, tables)
    np.testing.assert_array_equal(np.asarray(view_a[:, :, :6]),
                                  np.asarray(view_b[:, :, :6]))
    # rows 6,7 were pad rows: prefill wrote them, chunk dropped them
    assert (np.asarray(view_b[:, :, 6:8]) == 0.0).all()
    assert not (np.asarray(view_a[:, :, 6:8]) == 0.0).all()


def test_scatter_chunk_dummy_row_dropped():
    pool = _pool(-1.0)
    tables = jnp.asarray([[NP, NP, NP]], jnp.int32)
    slab = jnp.ones((L, 1, 4, H, D), jnp.float32)
    pool = scatter_chunk(pool, tables, slab, jnp.asarray([0]),
                         jnp.asarray([4]))
    assert (np.asarray(pool) == -1.0).all()


def test_scatter_chunk_past_table_end_drops():
    pool = _pool(-1.0)
    tables = jnp.asarray([[0, 1, 2]], jnp.int32)   # 12 logical rows
    slab = jnp.zeros((L, 1, 4, H, D), jnp.float32)
    pool = scatter_chunk(pool, tables, slab, jnp.asarray([11]),
                         jnp.asarray([4]))
    got = np.asarray(pool)
    # position 11 lands (page 2, offset 3); 12..14 drop
    assert (got[:, :, 2, 3] == 0.0).all()
    untouched = np.full_like(got, -1.0)
    untouched[:, :, 2, 3] = 0.0
    np.testing.assert_array_equal(got, untouched)


def test_scatter_decode_writes_k_rows():
    pool = _pool()
    tables = jnp.asarray([[3, 1, NP]], jnp.int32)
    view = jnp.zeros((L, 1, 12, H, D), jnp.float32)
    # pass appended K=2 rows at logical positions 3, 4 (page boundary!)
    view = view.at[:, 0, 3].set(7.0)
    view = view.at[:, 0, 4].set(8.0)
    pool = scatter_decode(pool, tables, view, jnp.asarray([3]), 2)
    got = np.asarray(pool)
    assert (got[:, :, 3, 3] == 7.0).all()   # logical 3 -> page 3, offset 3
    assert (got[:, :, 1, 0] == 8.0).all()   # logical 4 -> page 1, offset 0
    assert got.sum() == (7.0 + 8.0) * L * H * D


def test_scatter_decode_past_view_end_drops():
    pool = _pool(-1.0)
    tables = jnp.asarray([[0, 1, 2]], jnp.int32)
    view = jnp.zeros((L, 1, 12, H, D), jnp.float32)
    pool = scatter_decode(pool, tables, view, jnp.asarray([11]), 2)
    got = np.asarray(pool)
    # position 11 lands (page 2, offset 3); position 12 is dropped
    assert (got[:, :, 2, 3] == 0.0).all()
    untouched = np.full_like(got, -1.0)
    untouched[:, :, 2, 3] = 0.0
    np.testing.assert_array_equal(got, untouched)


# ---------------------------------------------------- quantized pools

from gofr_tpu.ops.paged_kv import (dequantize_rows, is_quantized_pool,  # noqa: E402
                                   pool_row_bytes, quantize_pool,
                                   quantize_rows)


def _qpool():
    return quantize_pool(_pool())


def test_quantized_roundtrip_within_quant_bound():
    """scatter (quantize-on-write) then gather (dequantize) reproduces
    the written rows within the symmetric-int8 bound: per element the
    error is at most scale/2 = amax/254."""
    pool = _qpool()
    tables = jnp.asarray([[2, 0, NP]], jnp.int32)
    slab = jax.random.normal(jax.random.key(0), (L, 1, 8, H, D),
                             jnp.float32)
    pool = scatter_prefill(pool, tables, slab)
    assert is_quantized_pool(pool)
    view = gather_view(pool, tables, dtype=jnp.float32)
    err = np.abs(np.asarray(view[:, :, :8]) - np.asarray(slab))
    bound = np.max(np.abs(np.asarray(slab)), axis=-1,
                   keepdims=True) / 254 + 1e-6
    assert (err <= bound).all()


def test_quantized_decode_append_preserves_earlier_rows():
    """Per-row scales are load-bearing: appending one decode row to a
    partially filled page must leave every earlier row's codes AND
    scale bit-identical (a page-wide amax would re-quantize them)."""
    pool = _qpool()
    tables = jnp.asarray([[3, NP, NP]], jnp.int32)
    slab = jax.random.normal(jax.random.key(1), (L, 1, 4, H, D),
                             jnp.float32) * 5.0
    pool = scatter_prefill(pool, tables, slab[:, :, :3])  # rows 0..2
    before_q = np.asarray(pool["q"][:, :, 3, :3]).copy()
    # scales are lane-major: page 3's row r sits at lane r of its row
    before_s = np.asarray(pool["s"][:, :, 3, 0, :3]).copy()
    # append logical row 3 (offset 3 of page 3) with a much larger amax
    view = jnp.zeros((L, 1, 12, H, D), jnp.float32)
    view = view.at[:, 0, 3].set(100.0)
    pool = scatter_decode(pool, tables, view, jnp.asarray([3]), 1)
    np.testing.assert_array_equal(np.asarray(pool["q"][:, :, 3, :3]),
                                  before_q)
    np.testing.assert_array_equal(np.asarray(pool["s"][:, :, 3, 0, :3]),
                                  before_s)
    got = dequantize_rows(pool["q"][:, :, 3, 3],
                          pool["s"][:, :, 3, 0, 3:4])
    np.testing.assert_allclose(np.asarray(got), 100.0, rtol=1e-2)


def test_quantized_view_roundtrip_is_idempotent():
    """The view fallback round-trips untouched rows (gather ->
    dequantize -> requantize -> scatter). Requantizing dequantized
    values must reproduce the exact codes and scale: each written row
    has an element at |q| = 127, so the amax — and everything derived
    from it — is reconstructed bit-for-bit. Zero rows hit the scale
    floor and stay exactly zero."""
    rows = jnp.concatenate([
        jax.random.normal(jax.random.key(2), (6, D), jnp.float32),
        jnp.zeros((2, D), jnp.float32)])
    q1, s1 = quantize_rows(rows)
    q2, s2 = quantize_rows(dequantize_rows(q1, s1))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_quantized_scatter_drops_like_plain():
    """OOB table entries drop on BOTH leaves — dummy rows must not
    corrupt codes or scales."""
    pool = _qpool()
    q0 = np.asarray(pool["q"]).copy()
    s0 = np.asarray(pool["s"]).copy()
    tables = jnp.asarray([[NP, NP, NP]], jnp.int32)
    slab = jnp.ones((L, 1, 4, H, D), jnp.float32)
    pool = scatter_chunk(pool, tables, slab, jnp.asarray([0]),
                         jnp.asarray([4]))
    np.testing.assert_array_equal(np.asarray(pool["q"]), q0)
    np.testing.assert_array_equal(np.asarray(pool["s"]), s0)


# ------------------------------------ the page-granular writer, row by row

from gofr_tpu.ops.paged_kv import empty_pool, pool_write  # noqa: E402

#: (run width S, table width Mp, per slot: table, start, count). Page 4,
#: 40 pages (id 40 = unallocated), two kv heads packed in one row.
_RUNS = {
    # start mid-page, end in the next page; a second slot inside one page
    "two_pages": (6, 4, [([7, 3, 40, 40], 2, 6), ([9, 1, 40, 40], 5, 2)]),
    # positions 3..64: seventeen pages, the first and last partly
    "seventeen_pages": (62, 20, [(list(range(20, 40)), 3, 62)]),
    # decode: one row a slot, one at a page's last offset, one past the
    # table (dropped)
    "one_row": (1, 3, [([5, 6, 40], 7, 1), ([8, 40, 40], 0, 1),
                       ([2, 3, 4], 12, 1)]),
    # nothing to write: both slots keep every byte
    "count_zero": (5, 3, [([5, 6, 7], 3, 0), ([8, 9, 10], 0, 0)]),
    # the run's middle page is unallocated: its rows drop, the rest land
    "dropped_page": (10, 4, [([11, 40, 13, 14], 1, 10)]),
    # ends with the table's last page; the second slot runs past it
    "table_end": (6, 3, [([1, 2, 3], 6, 6), ([4, 5, 6], 9, 6)]),
    # padding rows past ``count`` stay out, mid-page on both ends
    "short_count": (8, 4, [([21, 22, 23, 24], 5, 3)]),
}
_PG, _NP, _HKV, _HD = 4, 40, 4, 64


def _rows_reference(pool, layer, tables, starts, counts, rows):
    """``pool_write`` one row at a time, on the host: the plain
    statement of what it means (numpy pools in, numpy pools out)."""
    quantized = is_quantized_pool(pool)
    codes = np.array(pool["q"] if quantized else pool)
    scales = np.array(pool["s"]) if quantized else None
    pg, w = codes.shape[3:]
    rows = rows[None] if layer is not None else rows
    layers = [layer] if layer is not None else range(codes.shape[0])
    s, hkv, d = rows.shape[2:]
    pack = w // d
    if quantized:
        # jitted like the writer: XLA folds the division by 127
        vals, sc = (np.asarray(x) for x in jax.jit(quantize_rows)(rows))
    else:
        vals = np.asarray(rows.astype(pool.dtype))
    for k, li in enumerate(layers):
        for b, (start, count) in enumerate(zip(starts, counts)):
            for i in range(min(count, s)):
                page, off = divmod(start + i, pg)
                if page >= tables.shape[1] or tables[b, page] >= _NP:
                    continue
                for h in range(hkv):
                    at = (li, h // pack, tables[b, page])
                    lane = (h % pack) * d
                    codes[at][off, lane:lane + d] = vals[k, b, i, h]
                    if quantized:
                        scales[at][0, (h % pack) * pg + off] = \
                            sc[k, b, i, h, 0]
    return {"q": codes, "s": scales} if quantized else codes


@pytest.mark.parametrize("entry", ["one_layer", "all_layers"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_RUNS))
def test_pool_write_matches_the_row_by_row_reference(case, quantized,
                                                     entry):
    """Whole pages go through the writer, single rows must come out:
    the pool is bit-identical to writing each row of each run where its
    table says, and every other byte keeps its value."""
    s, mp, slots = _RUNS[case]
    tables = np.asarray([t for t, _, _ in slots], np.int32)
    starts = [a for _, a, _ in slots]
    counts = [n for _, _, n in slots]
    keys = jax.random.split(jax.random.key(len(case)), 4)
    like = jnp.zeros((L, _HKV, 1, _PG, _HD), jnp.bfloat16)
    pool = empty_pool(like, _NP, quantized)
    # a pool full of old bytes, scales and pad lanes included
    if quantized:
        pool = {"q": jax.random.randint(keys[0], pool["q"].shape, -127, 128,
                                        jnp.int8),
                "s": jax.random.uniform(keys[1], pool["s"].shape) + 0.5}
    else:
        pool = jax.random.normal(keys[0], pool.shape, jnp.bfloat16)
    layer = 1 if entry == "one_layer" else None
    rows = 3.0 * jax.random.normal(
        keys[2], (*(() if layer is not None else (L,)), len(slots), s,
                  _HKV, _HD), jnp.float32)
    want = _rows_reference(pool, layer, tables, starts, counts, rows)
    got = jax.jit(pool_write)(pool, layer, jnp.asarray(tables),
                              jnp.asarray(starts, jnp.int32),
                              jnp.asarray(counts, jnp.int32), rows)
    for leaf_got, leaf_want in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(leaf_got), leaf_want)


def test_quantized_row_bytes_accounting():
    """Rows are billed AS ALLOCATED — the engine's byte-budget sizing
    leans on this. A page's scales are one 128-lane f32 row per head
    group, so at the serving shape (head_dim 64 packed two to a row,
    page 64) an int8 row costs hd + 4 bytes per head against 2*hd for
    bf16 — exactly the f32-per-row ideal; a 4-row toy page spreads the
    same 512-byte scale row over 4 rows."""
    plain, quant = _pool(), _qpool()
    assert pool_row_bytes(plain) == L * H * D * 4
    assert pool_row_bytes(quant) == L * H * (D + 128 * 4 // PG)
    serving = jnp.zeros((16, 4, 2, 64, 128), jnp.bfloat16)  # 8 heads, hd 64
    assert pool_row_bytes(serving) == 16 * 8 * 64 * 2
    assert pool_row_bytes(quantize_pool(serving, head_dim=64)) \
        == 16 * 8 * (64 + 4)


# ---------------------------------------------------------------- engine

from gofr_tpu.serving.engine import EngineConfig, SamplingParams  # noqa: E402
from gofr_tpu.serving.glue import demo_llama_engine  # noqa: E402


def _drain(reqs, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.01)
    return reqs


def test_native_engine_matches_view_engine():
    """The reference is the view engine: the dense step functions on a
    gathered per-slot view, no kernel, no table writes by the model.
    The native path (rows written through the tables, attention
    gathering inside the op) must reproduce its greedy streams."""
    cfg = dict(max_batch=4, max_seq=128, seed=17, page_size=16)
    view = demo_llama_engine(EngineConfig(paged_attention="view", **cfg))
    view.start()
    want = [view.submit([3 + i, 1, 4], SamplingParams(
        temperature=0.0, max_new_tokens=10)) for i in range(4)]
    _drain(want)
    view.stop()

    native = demo_llama_engine(EngineConfig(paged_attention="xla", **cfg))
    native.start()
    got = [native.submit([3 + i, 1, 4], SamplingParams(
        temperature=0.0, max_new_tokens=10)) for i in range(4)]
    _drain(got)
    native.stop()

    assert [r.generated for r in got] == [r.generated for r in want]
    assert all(r.error is None for r in got)


def test_paged_overcommit_beyond_contiguous_capacity():
    """Total logical capacity (max_batch * max_seq = 4*128 rows) does
    not fit the pool (12 pages * 16 = 192 rows), but short requests do:
    the engine must serve more concurrent requests than the contiguous
    layout could hold in the same memory."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=2,
        kv_layout="paged", page_size=16, kv_pages=12))
    eng.start()
    reqs = [eng.submit([1 + i, 2, 3], SamplingParams(
        temperature=0.0, max_new_tokens=8)) for i in range(8)]
    _drain(reqs)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    assert all(len(r.generated) == 8 for r in reqs)


def test_paged_preemption_recomputes_and_completes():
    """Pool too small for all admitted requests to run to their full
    length: the engine preempts (freeing pages, recomputing later) and
    every request still finishes with exactly its token budget."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=4, max_seq=128, seed=8,
        kv_layout="paged", page_size=16, kv_pages=8))  # 128 rows total
    eng.start()
    reqs = [eng.submit(list(range(1, 30)), SamplingParams(
        temperature=0.0, max_new_tokens=24)) for _ in range(4)]
    _drain(reqs)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    assert all(len(r.generated) == 24 for r in reqs)


def test_paged_greedy_unaffected_by_preemption():
    """Preemption-by-recompute must not change greedy outputs."""
    roomy = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, seed=4, kv_layout="paged", page_size=16))
    roomy.start()
    want = roomy.submit_sync(list(range(1, 20)), SamplingParams(
        temperature=0.0, max_new_tokens=16)).generated
    roomy.stop()

    tight = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, seed=4,
        kv_layout="paged", page_size=16, kv_pages=5))
    tight.start()
    got = [tight.submit(list(range(1, 20)), SamplingParams(
        temperature=0.0, max_new_tokens=16)) for _ in range(2)]
    _drain(got)
    tight.stop()
    assert all(r.error is None for r in got)
    assert all(r.generated == want for r in got)


def test_recovered_pool_keeps_head_major_layout():
    """_recover_lost_cache must rebuild the pool in the SAME head-major
    [L, Hkv, Np, pg, hd] layout the init path allocates (a recovery
    that reverts to the dense-cache axis order silently corrupts every
    subsequent scatter/gather)."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, seed=5, kv_layout="paged", page_size=16))
    shape_before = eng.k_cache.shape
    eng.k_cache.delete()
    eng.v_cache.delete()
    eng._recover_lost_cache(RuntimeError("induced"))
    assert eng.k_cache.shape == shape_before
    assert eng.v_cache.shape == shape_before
    # and the engine still serves after recovery
    eng.start()
    reqs = [eng.submit([3, 1, 4], SamplingParams(
        temperature=0.0, max_new_tokens=6)) for _ in range(2)]
    _drain(reqs)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    assert all(len(r.generated) == 6 for r in reqs)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_single_device_pool_is_not_pinned(kv_dtype):
    """The pool is built directly in its final representation, but it
    must not be COMMITTED to the default device: a committed pool
    commits every array the jitted steps hand back (lengths, tokens),
    whose signature then differs from warm-up's — each program would
    compile a second time at its first real dispatch, behind the
    shape-keyed recompile sentinel's back (scripts/cost_smoke.py saw
    it as a 10x first-pass cost). Only a mesh places the pool."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, kv_layout="paged", page_size=16,
        kv_dtype=kv_dtype))
    leaves = jax.tree_util.tree_leaves((eng.k_cache, eng.v_cache))
    assert leaves and not any(leaf.committed for leaf in leaves)


def test_kv_dtype_validation():
    """Engine construction (where every config knob is validated)
    rejects unknown kv_dtypes; an int8 pool and a byte budget need no
    other option since the pool is the one layout."""
    with pytest.raises(ValueError, match="kv_dtype"):
        demo_llama_engine(EngineConfig(kv_dtype="fp8"))
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                         kv_dtype="int8"))
    assert is_quantized_pool(eng.k_cache)
    sized = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                           kv_pool_bytes=1 << 20))
    assert 0 < sized._kv_bytes_total <= 1 << 20


def test_slot_layout_is_refused_by_name():
    """``kv_layout`` is accepted only as "paged": the slot layout's
    removal is named, with what reserves the same capacity."""
    with pytest.raises(ValueError, match="removed in PR 30") as err:
        demo_llama_engine(EngineConfig(kv_layout="slot"))
    assert "kv_pages=None" in str(err.value)
    assert demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, kv_layout="paged")) is not None


def test_int8_view_and_native_paths_agree_exactly():
    """The int8 view fallback (gather + dense decode + scatter) and the
    int8 native path (pool_write + ragged XLA fallback) see the SAME
    dequantized rows, so greedy outputs must agree token-for-token —
    this pins the two quantized implementations against each other the
    way the bf16 native paths are pinned against the view engine."""
    def run(**extra):
        eng = demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=128, seed=13, kv_layout="paged",
            page_size=16, kv_dtype="int8", **extra))
        eng.start()
        reqs = [eng.submit(list(range(2, 9)), SamplingParams(
            temperature=0.0, max_new_tokens=12)) for _ in range(2)]
        _drain(reqs)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        return [r.generated for r in reqs]

    view = run()                               # auto on CPU -> view
    native = run(paged_attention="xla")
    assert view == native
    assert all(len(t) == 12 for t in view)


def test_int8_engine_greedy_close_to_bf16():
    """End-to-end accuracy bound: int8 KV shifts logits by the quant
    error, which a tiny random model (near-uniform logits) amplifies —
    real checkpoints have far larger logit margins. The documented
    tolerance is therefore token-LEVEL, not bitwise: at least half the
    greedy tokens must agree with the f32-KV engine's, and both runs
    must complete error-free."""
    def run(dt):
        eng = demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=128, seed=19, kv_layout="paged",
            page_size=16, kv_dtype=dt))
        eng.start()
        reqs = [eng.submit([3, 1, 4, 1, 5], SamplingParams(
            temperature=0.0, max_new_tokens=12)) for _ in range(2)]
        _drain(reqs)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        return [r.generated for r in reqs]

    want, got = run("bf16"), run("int8")
    agree = sum(a == b for w, g in zip(want, got)
                for a, b in zip(w, g))
    total = sum(len(w) for w in want)
    assert agree >= total // 2, (want, got)


def test_int8_pool_doubles_pages_at_same_byte_budget():
    """Capacity is the point: at one fixed kv_pool_bytes budget the
    int8 pool must hold >= 1.8x the pages of the bf16 pool. Uses the
    serving shape — head_dim=64, page 64 (ratio 2*hd/(hd+4) = 1.88):
    a page's scales fill one 128-lane row per packed head pair there;
    shorter pages pad that row and give back part of the win."""
    import jax as _jax

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.serving.glue import llama_engine

    c = LlamaConfig(vocab_size=64, dim=256, n_layers=2, n_heads=4,
                    n_kv_heads=2, ffn_dim=64, max_seq=256,
                    dtype=jnp.bfloat16)
    assert c.head_dim == 64
    params = llama_init(_jax.random.key(0), c)
    budget = 1 << 20

    def pages(dt):
        eng = llama_engine(params, c, EngineConfig(
            max_batch=2, max_seq=256, kv_layout="paged", page_size=64,
            kv_dtype=dt, kv_pool_bytes=budget), implementation="xla")
        return eng._n_pages, eng._kv_bytes_total

    bf16_pages, bf16_bytes = pages("bf16")
    int8_pages, int8_bytes = pages("int8")
    assert int8_pages >= 1.8 * bf16_pages, (int8_pages, bf16_pages)
    # both pools actually fit the budget they were sized against
    assert bf16_bytes <= budget and int8_bytes <= budget


def test_recovered_pool_stays_quantized():
    """_recover_lost_cache must rebuild the int8 pool in the SAME
    quantized representation (a plain-array rebuild would break every
    compiled graph's pytree signature)."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, seed=5, kv_layout="paged",
        page_size=16, kv_dtype="int8"))
    shape_before = eng.k_cache["q"].shape
    eng.k_cache["q"].delete()
    assert eng._kv_lost()                      # pytree-aware probe
    eng._recover_lost_cache(RuntimeError("induced"))
    assert eng.k_cache["q"].shape == shape_before
    assert eng.k_cache["s"].shape == shape_before[:3] + (1, 128)
    eng.start()
    reqs = [eng.submit([3, 1, 4], SamplingParams(
        temperature=0.0, max_new_tokens=6)) for _ in range(2)]
    _drain(reqs)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    assert all(len(r.generated) == 6 for r in reqs)


@pytest.mark.parametrize("path", ["view", "xla"])
def test_decode_windows_match_and_only_the_view_compiles_them(path):
    """``decode_windows`` is the width of the VIEW path's gather: the
    windowed view decode (only the table columns covering the window)
    must match the unwindowed engine greedily across a window boundary.
    The native path walks live pages only: windows change neither its
    streams nor the programs it compiles."""
    shapes = {}

    def run(**extra):
        eng = demo_llama_engine(EngineConfig(
            max_batch=2, max_seq=128, seed=21, page_size=16,
            paged_attention=path, **extra))
        eng.warmup(prompt_lens=(10,))
        shapes[bool(extra)] = eng.sentinel.state()["known_shapes"]
        assert len(eng._decode_by_window) == \
            (len(extra.get("decode_windows", ())) if path == "view" else 0)
        eng.start()
        reqs = [eng.submit(list(range(2, 12)), SamplingParams(
            temperature=0.0, max_new_tokens=40)) for _ in range(2)]
        _drain(reqs)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        assert all(len(r.generated) == 40 for r in reqs)
        return [r.generated for r in reqs]

    want = run()
    got = run(decode_windows=(32, 64))
    assert got == want
    assert shapes[True] - shapes[False] == (2 if path == "view" else 0)


def test_default_pool_reserves_every_slots_full_length():
    """``EngineConfig()`` is the page pool, and left alone
    (``kv_pages=None``) it reserves ``max_batch x ceil(max_seq /
    page_size)`` pages: what the removed slot layout reserved."""
    default = EngineConfig()
    assert default.kv_layout == "paged" and default.kv_pages is None
    eng = demo_llama_engine()           # max_batch 4, max_seq 128
    assert eng.paged_attention_impl == "view"     # "auto" off the TPU
    assert eng._n_pages == 4 * -(-128 // default.page_size) == 8
    odd = demo_llama_engine(EngineConfig(max_batch=3, max_seq=72,
                                         page_size=16))
    assert odd._n_pages == 3 * 5 and odd._pages_per_slot == 5


@pytest.mark.parametrize("path", ["view", "xla"])
def test_default_pool_runs_every_slot_to_max_seq_unpreempted(path):
    """The slot layout's capacity guarantee, kept: with the default
    pool every slot can fill its ``max_seq`` rows at once and nothing
    is preempted, requeued or refused."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=3, max_seq=72, page_size=16, seed=2,
        prefill_buckets=(16,), paged_attention=path))
    eng.start()
    reqs = [eng.submit([7 + i, 3, 1, 4, 1, 5], SamplingParams(
        temperature=0.0, max_new_tokens=500)) for i in range(3)]
    _drain(reqs)
    stats = dict(eng.stats)
    peak = eng.efficiency_state()["watermarks"]["kv_pages"]["value"]
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    # a slot ends when its 72 rows are full (the last token sampled
    # needs no row of its own)
    assert [len(r.prompt_tokens) + len(r.generated) for r in reqs] \
        == [73, 73, 73]
    assert stats["preemptions"] == 0
    assert peak == eng._n_pages == 15
