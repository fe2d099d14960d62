"""Chunked prefill: prompts longer than the widest prefill bucket run
in bucket-width chunks against the growing cache — no truncation, and
greedy outputs identical to a single wide prefill."""

import numpy as np
import pytest

from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine

PROMPT = list(np.random.RandomState(5).randint(3, 200, size=30))


def _generate(engine, prompt, n=6):
    engine.start()
    try:
        req = engine.submit_sync(prompt,
                                 SamplingParams(temperature=0.0,
                                                max_new_tokens=n))
        assert req.error is None, req.error
        return list(req.generated), len(req.prompt_tokens)
    finally:
        engine.stop()


def test_long_prompt_is_not_truncated_and_matches_wide_prefill():
    # narrow buckets: the 30-token prompt takes 4 chunks of 8
    chunked = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     seed=7))
    toks_chunked, kept_chunked = _generate(chunked, PROMPT)
    assert kept_chunked == len(PROMPT)  # nothing clamped

    # one wide bucket: the same prompt prefills in a single call
    wide = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(32,),
                     seed=7))
    toks_wide, kept_wide = _generate(wide, PROMPT)
    assert kept_wide == len(PROMPT)

    # same model weights (same init seed), greedy: identical output
    assert toks_chunked == toks_wide


def test_chunked_head_of_prompt_matters():
    """Truncation would drop the prompt head; chunked prefill must
    see it — two prompts differing only in their first token generate
    differently (greedy, tiny random model: near-certain)."""
    engine_a = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     seed=7))
    toks_a, _ = _generate(engine_a, PROMPT)
    engine_b = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     seed=7))
    changed = [(PROMPT[0] + 1) % 200] + PROMPT[1:]
    toks_b, _ = _generate(engine_b, changed)
    assert toks_a != toks_b


def test_chunked_interleaves_with_bucketed_admission():
    """Short and long prompts admitted together: both complete, the
    long one unclamped."""
    engine = demo_llama_engine(
        EngineConfig(max_batch=4, max_seq=128, prefill_buckets=(8,),
                     seed=3))
    engine.start()
    try:
        long_req = engine.submit(PROMPT, SamplingParams(
            temperature=0.0, max_new_tokens=4))
        short_req = engine.submit([5, 6, 7], SamplingParams(
            temperature=0.0, max_new_tokens=4))
        import time
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(r.finished_at is not None or r.error
                   for r in (long_req, short_req)):
                break
            time.sleep(0.01)
        assert long_req.error is None and short_req.error is None
        assert len(long_req.generated) == 4
        assert len(short_req.generated) == 4
        assert len(long_req.prompt_tokens) == len(PROMPT)
    finally:
        engine.stop()


def test_native_chunk_walk_matches_the_view_path():
    """The native walk (chunk rows written through the tables, history
    read from the pool) is unclamped and greedy-identical to the view
    path — the reference: gather view → dense ``llama_prefill_chunk`` →
    scatter back, no table writes by the model."""
    base = dict(max_batch=2, max_seq=128, prefill_buckets=(8,),
                page_size=16, seed=7)
    native = demo_llama_engine(EngineConfig(paged_attention="xla", **base))
    toks_native, kept = _generate(native, PROMPT)
    assert kept == len(PROMPT)  # nothing clamped

    view = demo_llama_engine(EngineConfig(paged_attention="view", **base))
    toks_view, _ = _generate(view, PROMPT)
    assert toks_native == toks_view


def test_cancel_mid_chunk_walk_frees_the_slot():
    """A client that vanishes while its long prompt is mid-walk must
    release the reserved slot (the walk spans several engine passes
    with prefill_chunks_per_pass=1)."""
    import time

    engine = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     prefill_chunks_per_pass=1, seed=2))
    engine.start()
    try:
        req = engine.submit(PROMPT, SamplingParams(temperature=0.0,
                                                   max_new_tokens=50))
        engine.cancel(req)      # racing the walk is the point
        deadline = time.time() + 30
        while time.time() < deadline and req.finished_at is None:
            time.sleep(0.01)
        assert req.finished_at is not None
        deadline = time.time() + 10
        while time.time() < deadline and any(
                r is not None for r in engine.active):
            time.sleep(0.01)
        assert all(r is None for r in engine.active)
        # the engine still serves
        follow = engine.submit_sync([1, 2, 3], SamplingParams(
            temperature=0.0, max_new_tokens=3))
        assert follow.error is None and len(follow.generated) == 3
    finally:
        engine.stop()


def test_paged_prompt_exceeding_pool_fails_cleanly():
    """A prompt that can never fit the page pool fails with a clear
    error instead of walking forever or crashing the loop."""
    engine = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     kv_layout="paged", kv_pages=4, page_size=8,
                     seed=1))
    engine.start()
    try:
        req = engine.submit_sync(PROMPT, SamplingParams(
            temperature=0.0, max_new_tokens=4))
        assert req.error is not None and "kv pool" in req.error
        # a fitting prompt still serves
        ok = engine.submit_sync([1, 2, 3], SamplingParams(
            temperature=0.0, max_new_tokens=3))
        assert ok.error is None and len(ok.generated) == 3
    finally:
        engine.stop()


def test_two_long_prompts_contend_for_the_pool():
    """Pool smaller than both walks: preemption-by-recompute plus the
    requeue machinery must land BOTH requests with exact token
    budgets (regression: double-requeue once emitted a bogus extra
    token; slot-holding walks once deadlocked the requeue drain)."""
    import time

    engine = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=128, prefill_buckets=(8,),
        kv_layout="paged", kv_pages=20, page_size=8,
        prefill_chunks_per_pass=1, seed=4))
    engine.start()
    try:
        a = engine.submit(list(range(3, 90)), SamplingParams(
            temperature=0.0, max_new_tokens=4))
        b = engine.submit(list(range(90, 175)), SamplingParams(
            temperature=0.0, max_new_tokens=4))
        deadline = time.time() + 240
        while time.time() < deadline:
            if all(r.finished_at is not None or r.error for r in (a, b)):
                break
            time.sleep(0.02)
        assert a.error is None and b.error is None, (a.error, b.error)
        assert len(a.generated) == 4, len(a.generated)
        assert len(b.generated) == 4, len(b.generated)
    finally:
        engine.stop()


@pytest.mark.parametrize("path", ["view", "xla"])
def test_warmup_chunked_compiles_both_paths(path):
    engine = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=64, prefill_buckets=(8,),
                     page_size=16, paged_attention=path, seed=1))
    engine.warmup(prompt_lens=(8,), chunked=True)  # must not crash
    toks, _ = _generate(engine, list(range(3, 30)), n=3)
    assert len(toks) == 3


def test_walker_does_not_starve_waiting_admission():
    """A mid-walk long prompt holds one slot; a short prompt must be
    admitted into the OTHER free slot while the walk is still going."""
    import time

    engine = demo_llama_engine(
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     prefill_chunks_per_pass=1, seed=6))
    engine.start()
    try:
        long_req = engine.submit(PROMPT, SamplingParams(
            temperature=0.0, max_new_tokens=4))
        short_req = engine.submit([9, 9, 9], SamplingParams(
            temperature=0.0, max_new_tokens=2))
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(r.finished_at is not None or r.error
                   for r in (long_req, short_req)):
                break
            time.sleep(0.01)
        assert short_req.error is None and len(short_req.generated) == 2
        assert long_req.error is None and len(long_req.generated) == 4
    finally:
        engine.stop()


def test_paged_windowed_chunk_walk_matches_full():
    """The windowed chunk-walk variant (gathers only the table columns
    the largest configured window covers) must reproduce the full
    graph's greedy output for long paged prompts — including walks
    whose history outgrows the window and falls back mid-walk."""
    base = dict(max_batch=2, max_seq=256, prefill_buckets=(16,), seed=7,
                kv_layout="paged", page_size=16)
    long_prompt = PROMPT + PROMPT  # 60 tokens -> 4 chunk passes

    full = demo_llama_engine(EngineConfig(**base))
    want, kept = _generate(full, long_prompt)
    assert kept == len(long_prompt)

    # window 48: the walk starts windowed (offsets 0,16,32 need <=48
    # rows), outgrows it at offset 48, and falls back to full
    windowed = demo_llama_engine(EngineConfig(decode_windows=(48,),
                                              **base))
    got, _ = _generate(windowed, long_prompt)
    assert got == want
