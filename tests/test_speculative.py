"""Speculative decoding: prompt-lookup drafts verified in one parallel
pass. The contract is absolute: greedy outputs are identical to
vanilla decode — speculation only changes how many passes they take.
"""

import time

import numpy as np
import pytest

from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine

# a strongly repetitive prompt: prompt-lookup drafting thrives on it
PATTERN = [11, 22, 33, 44] * 12


def _cfg(**kw):
    base = dict(max_batch=2, max_seq=256, prefill_buckets=(64,), seed=9)
    base.update(kw)
    return EngineConfig(**base)


def _run(engine, prompt, n=24, temperature=0.0):
    engine.start()
    try:
        req = engine.submit_sync(prompt, SamplingParams(
            temperature=temperature, max_new_tokens=n))
        assert req.error is None, req.error
        return list(req.generated), dict(engine.stats)
    finally:
        engine.stop()


def test_greedy_tokens_identical_to_vanilla():
    vanilla, _ = _run(demo_llama_engine(_cfg()), PATTERN)
    spec, stats = _run(demo_llama_engine(_cfg(speculative=True)), PATTERN)
    assert spec == vanilla
    assert stats["spec_passes"] > 0


@pytest.mark.parametrize("adaptive", [False, True])
def test_pipelined_speculation_drafts_from_the_settled_stream(adaptive):
    """With a decode pass left in flight (``pipeline_depth=1``) the
    drafting loop must settle it before it reads a stream's tail: a
    tree rooted at the token BEFORE the in-flight pass's verified a
    stale root and the stream left the greedy one (every engine did,
    sharded or not, until PR 30)."""
    cfg = dict(spec_ngram=1, decode_steps_per_pass=2, page_size=16)
    vanilla, _ = _run(demo_llama_engine(_cfg(**cfg)), PATTERN)
    spec, stats = _run(demo_llama_engine(_cfg(
        speculative=True, spec_adaptive=adaptive, pipeline_depth=1,
        **cfg)), PATTERN)
    assert spec == vanilla
    assert stats["spec_passes"] > 0


def test_native_path_matches_too():
    """Native tree verify (fed nodes written through the tables, raw
    pool rows compacted) against plain decode on the view engine — the
    reference: dense step functions, no table writes by the model."""
    vanilla, _ = _run(demo_llama_engine(
        _cfg(page_size=16, paged_attention="view")), PATTERN)
    spec, stats = _run(
        demo_llama_engine(_cfg(page_size=16, paged_attention="xla",
                               speculative=True)), PATTERN)
    assert spec == vanilla
    assert stats["spec_passes"] > 0


def test_oracle_draft_accepts_and_saves_passes():
    """A perfect draft (the model's own continuation) must be fully
    accepted: same tokens, strictly fewer verify passes than tokens."""
    n = 24
    vanilla, _ = _run(demo_llama_engine(_cfg()), PATTERN, n=n)

    engine = demo_llama_engine(_cfg(speculative=True))
    future = {"tokens": vanilla}

    def oracle(req):
        done = len(req.generated)
        return future["tokens"][done:done + engine.config.spec_draft]

    engine._draft_proposals = oracle
    spec, stats = _run(engine, PATTERN, n=n)
    assert spec == vanilla
    assert stats["spec_accepted"] > 0
    # every pass lands spec_draft+1 tokens: far fewer passes than
    # tokens (vanilla takes ceil(n/decode_steps_per_pass) SCANNED
    # passes of 8 sequential steps; spec verifies in parallel)
    assert stats["spec_passes"] <= 2 + n // (engine.config.spec_draft + 1)


def test_mixed_greedy_and_sampled_slots():
    """A sampled request sharing the batch with a speculating greedy
    one: both complete with exact budgets; the greedy one still
    matches vanilla."""
    vanilla, _ = _run(demo_llama_engine(_cfg()), PATTERN, n=16)
    engine = demo_llama_engine(_cfg(speculative=True))
    engine.start()
    try:
        greedy = engine.submit(PATTERN, SamplingParams(
            temperature=0.0, max_new_tokens=16))
        sampled = engine.submit(list(np.random.RandomState(1)
                                     .randint(3, 200, size=20)),
                                SamplingParams(temperature=0.9,
                                               max_new_tokens=16))
        deadline = time.time() + 120
        while time.time() < deadline and not all(
                r.finished_at is not None or r.error
                for r in (greedy, sampled)):
            time.sleep(0.01)
        assert greedy.error is None and sampled.error is None
        assert list(greedy.generated) == vanilla
        assert len(sampled.generated) == 16
    finally:
        engine.stop()


def test_non_repetitive_prompt_just_decodes():
    """No n-gram matches -> no drafts -> pure vanilla path, still
    correct."""
    prompt = list(np.random.RandomState(4).randint(3, 200, size=40))
    vanilla, _ = _run(demo_llama_engine(_cfg()), prompt, n=8)
    spec, stats = _run(demo_llama_engine(_cfg(speculative=True)),
                       prompt, n=8)
    assert spec == vanilla


def test_cancel_during_speculation_retires_promptly():
    """A cancelled request must stop consuming verify passes even when
    its repetitive context would keep producing drafts."""
    engine = demo_llama_engine(_cfg(speculative=True))
    engine.start()
    try:
        req = engine.submit(PATTERN, SamplingParams(
            temperature=0.0, max_new_tokens=4096))
        deadline = time.time() + 30
        while time.time() < deadline and not req.generated:
            time.sleep(0.01)
        engine.cancel(req)
        deadline = time.time() + 30
        while time.time() < deadline and req.finished_at is None:
            time.sleep(0.01)
        assert req.finished_at is not None
        assert len(req.generated) < 4096  # nowhere near the budget
        follow = engine.submit_sync([1, 2, 3], SamplingParams(
            temperature=0.0, max_new_tokens=2))
        assert follow.error is None
    finally:
        engine.stop()


def test_paged_speculation_under_pool_pressure():
    """Verify-pass headroom contends with other slots: preemption
    inside the spec pass must not crash the loop, and both requests
    complete with exact budgets."""
    engine = demo_llama_engine(_cfg(
        kv_layout="paged", page_size=8, kv_pages=14,
        speculative=True, max_seq=128, prefill_buckets=(64,)))
    engine.start()
    try:
        a = engine.submit(PATTERN, SamplingParams(
            temperature=0.0, max_new_tokens=12))
        b = engine.submit(PATTERN[:24], SamplingParams(
            temperature=0.0, max_new_tokens=12))
        deadline = time.time() + 120
        while time.time() < deadline and not all(
                r.finished_at is not None or r.error for r in (a, b)):
            time.sleep(0.02)
        assert a.error is None and b.error is None, (a.error, b.error)
        assert len(a.generated) == 12 and len(b.generated) == 12
        assert engine._failed is None
    finally:
        engine.stop()
