"""Ragged paged chunk-attention kernel (Sq > 1) + the native paged
prefill/verify engine paths it unlocks.

Kernel-level: interpret-mode parity against the dense XLA reference
across history lengths (0 / page-aligned / mid-page), chunk lengths
that end mid-page, zero-length tail slots and GQA group sizes 1 and 4
— only rows < chunk_len per slot are compared (padding rows are
defined as discarded garbage).

Engine-level: with the kernel path active, chunked prefill, prefix
reattachment and speculative verify must dispatch ZERO ``gather_view``
calls (the prefill-side twin of the decode transfer-guard) while
staying greedy-bit-identical to the view path.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.paged_attention import (paged_chunk_attention,
                                          paged_chunk_attention_pallas,
                                          paged_chunk_attention_xla)


def _chunk_case(key, *, hq=4, hkv=2, hd=16, page=8, max_pages=10,
                n_pages=32, hists=(0, 11, 16), clens=(13, 5, 0), sq=16):
    """Pools + per-slot tables covering history + chunk rows, with the
    history/chunk K/V already resident (the model writes the chunk
    before attending, exactly like decode)."""
    b = len(hists)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (hkv, n_pages, page, hd),
                               jnp.float32)
    v_pool = jax.random.normal(ks[2], (hkv, n_pages, page, hd),
                               jnp.float32)
    rng = np.random.default_rng(0)
    tables = np.full((b, max_pages), n_pages, np.int32)  # OOB = unalloc
    for i, (h_, c_) in enumerate(zip(hists, clens)):
        need = -(-(h_ + c_) // page)
        if need:
            tables[i, :need] = rng.choice(n_pages, size=need,
                                          replace=False)
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(hists, jnp.int32), jnp.asarray(clens, jnp.int32))


def _assert_valid_rows_match(got, want, clens, rtol=2e-5, atol=2e-5):
    """Rows past each slot's chunk length are padding garbage by
    contract — compare only the defined rows."""
    got, want = np.asarray(got), np.asarray(want)
    assert not np.isnan(got).any()
    valid = np.arange(got.shape[1])[None, :] < np.asarray(clens)[:, None]
    np.testing.assert_allclose(got[valid], want[valid],
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("hists,clens", [
    ((0, 0, 0), (16, 9, 1)),          # fresh prompts, chunk ends mid-page
    ((8, 16, 24), (16, 13, 5)),       # page-aligned histories
    ((3, 11, 21), (16, 13, 7)),       # mid-page histories
    ((0, 19, 40), (16, 16, 0)),       # zero-length tail slot
])
def test_interpret_matches_xla_reference(hists, clens):
    case = _chunk_case(jax.random.key(0), hists=hists, clens=clens)
    q, kp, vp, tables, h, c = case
    got = paged_chunk_attention_pallas(q, kp, vp, tables, h, c,
                                       interpret=True)
    want = paged_chunk_attention_xla(q, kp, vp, tables, h, c)
    _assert_valid_rows_match(got, want, clens)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])  # GQA groups 1, 4
def test_gqa_group_sizes(hq, hkv):
    case = _chunk_case(jax.random.key(1), hq=hq, hkv=hkv,
                       hists=(0, 11, 16), clens=(13, 16, 7))
    q, kp, vp, tables, h, c = case
    got = paged_chunk_attention_pallas(q, kp, vp, tables, h, c,
                                       interpret=True)
    want = paged_chunk_attention_xla(q, kp, vp, tables, h, c)
    _assert_valid_rows_match(got, want, np.asarray(c))


def test_multi_q_block_and_multi_kv_chunk():
    """Sq wide enough to split into several q-blocks, histories long
    enough that the page walk double-buffers several 128-row chunks."""
    case = _chunk_case(jax.random.key(2), page=16, max_pages=24,
                       n_pages=64, hists=(200, 77), clens=(64, 37),
                       sq=64)
    q, kp, vp, tables, h, c = case
    got = paged_chunk_attention_pallas(q, kp, vp, tables, h, c,
                                       block_q=16, interpret=True)
    want = paged_chunk_attention_xla(q, kp, vp, tables, h, c)
    _assert_valid_rows_match(got, want, np.asarray(c))


def test_causal_mask_ignores_future_chunk_rows():
    """Poison pool rows past each query's causal horizon (future
    in-chunk rows AND rows past history+chunk): outputs of valid rows
    must not move."""
    case = _chunk_case(jax.random.key(3), hists=(8,), clens=(5,), sq=8)
    q, kp, vp, tables, h, c = case
    got_clean = paged_chunk_attention_pallas(q, kp, vp, tables, h, c,
                                             interpret=True)
    # poison everything at logical positions >= hist + clen = 13
    page = kp.shape[2]
    tab = np.asarray(tables)[0]
    poisoned = np.asarray(kp).copy()
    for logical in range(13, tab.size * page):
        pid = tab[logical // page]
        if pid < kp.shape[1]:
            poisoned[:, pid, logical % page] = 1e6
    got_poisoned = paged_chunk_attention_pallas(
        q, jnp.asarray(poisoned), vp, tables, h, c, interpret=True)
    _assert_valid_rows_match(got_poisoned, got_clean, np.asarray(c))


def test_dispatch_auto_on_cpu_is_xla():
    case = _chunk_case(jax.random.key(4))
    q, kp, vp, tables, h, c = case
    got = paged_chunk_attention(q, kp, vp, tables, h, c,
                                implementation="auto")
    want = paged_chunk_attention_xla(q, kp, vp, tables, h, c)
    _assert_valid_rows_match(got, want, np.asarray(c))


def test_bad_block_q_rejected():
    case = _chunk_case(jax.random.key(5), sq=12)
    q, kp, vp, tables, h, c = case
    with pytest.raises(ValueError, match="block_q"):
        paged_chunk_attention_pallas(q, kp, vp, tables, h, c,
                                     block_q=5, interpret=True)


# ------------------------------------------------- engine-level guard

from gofr_tpu.serving.engine import EngineConfig, SamplingParams  # noqa: E402
from gofr_tpu.serving.glue import demo_llama_engine  # noqa: E402

PROMPT = list(np.random.RandomState(5).randint(3, 200, size=30))


def _run(cfg, prompts, n=5):
    eng = demo_llama_engine(cfg)
    eng.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=n)
    reqs = [eng.submit(p, sp) for p in prompts]
    deadline = time.time() + 240
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.005)
    eng.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.generated for r in reqs], dict(eng.stats)


def test_native_paged_hot_paths_never_gather_view(monkeypatch):
    """Chunked prefill (narrow buckets force a 4-chunk walk), prefix
    reattachment (shared head re-admitted after a retire) and
    speculative verify must all run without materialising a dense
    per-slot view — and stay greedy-bit-identical to the view path,
    which still gathers (sanity check that the spy sees real calls)."""
    import gofr_tpu.ops.paged_kv as paged_kv

    calls = []
    real = paged_kv.gather_view

    def spy(pool, tables, **kw):
        calls.append(jax.tree_util.tree_leaves(pool)[0].shape)
        return real(pool, tables, **kw)

    monkeypatch.setattr(paged_kv, "gather_view", spy)

    shared = PROMPT[:16]
    # a prompt the 1-gram drafter can draft from, whatever the passes'
    # timing: behind a run of 220 this model's greedy stream starts
    # 220 220 220 220, so the first token out of prefill is already in
    # the prompt and the drafts behind it are accepted
    prompts = [shared + [220] * 14, shared + [9, 9], shared + [11, 4]]
    base = dict(max_batch=2, max_seq=128, prefill_buckets=(8,),
                page_size=16, kv_layout="paged", seed=7,
                speculative=True, spec_ngram=1)

    got, stats = _run(EngineConfig(paged_attention="interpret", **base),
                      prompts)
    assert calls == [], f"native path gathered views: {calls}"
    # every guarded path actually ran
    assert stats["prefill_calls"] > 0
    assert stats["prefix_hits"] > 0
    assert stats["spec_passes"] > 0
    assert stats["spec_accepted"] > 0   # ... and compacted a real path
    assert stats["view_bytes_avoided"] > 0

    want, view_stats = _run(EngineConfig(paged_attention="view", **base),
                            prompts)
    assert calls, "view path should exercise the spy"
    assert view_stats["view_bytes_avoided"] == 0
    assert got == want


def test_native_chunk_walk_matches_the_view_engine():
    """Long prompt through the native chunk walk (interpret kernel)
    reproduces the view engine's greedy stream. The view engine is the
    reference: dense step functions on a gathered view, no kernel, no
    table writes by the model."""
    native = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, prefill_buckets=(8,), seed=7,
        kv_layout="paged", page_size=16, paged_attention="interpret"))
    assert native._native_chunk and native._native_verify
    native.start()
    got = native.submit_sync(PROMPT, SamplingParams(
        temperature=0.0, max_new_tokens=6))
    native.stop()
    assert got.error is None and len(got.prompt_tokens) == len(PROMPT)

    view = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, prefill_buckets=(8,), seed=7,
        page_size=16, paged_attention="view"))
    view.start()
    want = view.submit_sync(PROMPT, SamplingParams(
        temperature=0.0, max_new_tokens=6))
    view.stop()
    assert got.generated == want.generated


def test_native_chunk_ignores_decode_windows():
    """decode_windows bound the VIEW path's gather; the native walk is
    length-bounded already and must not compile windowed chunk
    variants (nor crash when windows are configured)."""
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=256, prefill_buckets=(16,), seed=7,
        kv_layout="paged", page_size=16, paged_attention="interpret",
        decode_windows=(48,)))
    assert eng._chunk_window(16, 16) is None
    eng.warmup(prompt_lens=(16,), chunked=True)
    eng.start()
    req = eng.submit_sync(PROMPT + PROMPT, SamplingParams(
        temperature=0.0, max_new_tokens=4))
    eng.stop()
    assert req.error is None and len(req.generated) == 4
