"""Continuous-batching engine tests (tiny model, CPU)."""

import threading
import time

import pytest

from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import demo_llama_engine
from gofr_tpu.serving.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def engine():
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=128))
    eng.start()
    yield eng
    eng.stop()


def test_single_generation(engine):
    req = engine.submit_sync([1, 2, 3],
                             SamplingParams(temperature=0.0, max_new_tokens=8))
    assert len(req.generated) == 8
    assert req.error is None
    assert req.ttft_ms is not None and req.ttft_ms >= 0
    assert req.finished_at is not None


def test_greedy_determinism(engine):
    a = engine.submit_sync([5, 6, 7],
                           SamplingParams(temperature=0.0, max_new_tokens=10))
    b = engine.submit_sync([5, 6, 7],
                           SamplingParams(temperature=0.0, max_new_tokens=10))
    assert a.generated == b.generated


def test_concurrent_requests_all_complete(engine):
    reqs = []
    for i in range(8):  # 2x the slot count -> queueing must work
        reqs.append(engine.submit(
            [1 + i, 2, 3],
            SamplingParams(temperature=0.0, max_new_tokens=6)))
    deadline = time.time() + 60
    while time.time() < deadline:
        if all(r.finished_at is not None for r in reqs):
            break
        time.sleep(0.01)
    assert all(r.finished_at is not None for r in reqs)
    assert all(len(r.generated) == 6 for r in reqs)


def test_batched_identical_to_solo(engine):
    """Continuous batching must not change greedy outputs."""
    solo = engine.submit_sync([9, 8, 7],
                              SamplingParams(temperature=0.0, max_new_tokens=6))
    others = [engine.submit([3 + i, 1, 4],
                            SamplingParams(temperature=0.7, max_new_tokens=12))
              for i in range(3)]
    batched = engine.submit_sync([9, 8, 7],
                                 SamplingParams(temperature=0.0, max_new_tokens=6))
    deadline = time.time() + 60
    while time.time() < deadline and any(r.finished_at is None for r in others):
        time.sleep(0.01)
    assert solo.generated == batched.generated


def test_long_prompt_truncated(engine):
    req = engine.submit_sync(list(range(1, 200)) * 2,
                             SamplingParams(temperature=0.0, max_new_tokens=4))
    assert req.error is None
    assert len(req.generated) == 4


def test_health_check(engine):
    health = engine.health_check()
    assert health["status"] == "UP"
    assert health["total_generated"] > 0


def test_max_seq_stops_generation(engine):
    # prompt near the cap: generation must stop at max_seq, not crash
    req = engine.submit_sync(list(range(1, 120)),
                             SamplingParams(temperature=0.0, max_new_tokens=50))
    assert req.error is None
    assert 0 < len(req.generated) <= 50


def test_stochastic_sampling_varies(engine):
    outs = set()
    for i in range(4):
        req = engine.submit_sync([1, 2],
                                 SamplingParams(temperature=5.0, top_p=1.0,
                                                max_new_tokens=8))
        outs.add(tuple(req.generated))
    assert len(outs) > 1  # very high temperature -> variety


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    text = "hello TPU — ünïcode ✓"
    ids = tok.encode(text)
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == text


def test_submit_from_thread_without_loop(engine):
    result = {}

    def worker():
        req = engine.submit_sync([2, 4, 6],
                                 SamplingParams(temperature=0.0,
                                                max_new_tokens=3))
        result["tokens"] = req.generated

    t = threading.Thread(target=worker)
    t.start()
    t.join(60)
    assert len(result["tokens"]) == 3


def test_top_k_one_equals_greedy(engine):
    """top_k=1 restricts sampling to the argmax even at temperature>0,
    so it must reproduce the greedy continuation."""
    prompt = list(range(1, 9))
    greedy = engine.submit_sync(
        prompt, SamplingParams(temperature=0.0, max_new_tokens=8))
    k1 = engine.submit_sync(
        prompt, SamplingParams(temperature=1.0, top_k=1, max_new_tokens=8))
    assert k1.generated == greedy.generated


def test_sample_batch_top_k_masks_rows():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from gofr_tpu.serving.engine import _sample_batch
    logits = jnp.asarray([[0.0, 5.0, 4.0, 1.0],
                          [0.0, 5.0, 4.0, 1.0]])
    temps = jnp.asarray([1.0, 1.0], jnp.float32)
    top_ps = jnp.asarray([1.0, 1.0], jnp.float32)
    top_ks = jnp.asarray([1, 0], jnp.int32)  # row0 k=1, row1 unrestricted
    seen0 = set()
    seen1 = set()
    for i in range(32):
        out = np.asarray(_sample_batch(logits, jax.random.key(i),
                                       temps, top_ps, top_ks))
        seen0.add(int(out[0]))
        seen1.add(int(out[1]))
    assert seen0 == {1}          # k=1: always the argmax
    assert len(seen1) > 1        # unrestricted row actually samples


def test_crash_containment():
    """A throwing hot loop must fail every stream and flip health DOWN
    — never hang submitters (reference panic-recovery stance,
    /root/reference/pkg/gofr/handler.go:141)."""
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64))

    def boom(*a, **kw):
        raise RuntimeError("injected decode failure")

    eng._decode = boom
    eng.start()
    reqs = [eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                                 max_new_tokens=8))
            for _ in range(4)]
    deadline = time.time() + 30
    while time.time() < deadline:
        if all(r.finished_at is not None for r in reqs):
            break
        time.sleep(0.01)
    assert all(r.finished_at is not None for r in reqs)
    assert all(r.error and "injected decode failure" in r.error for r in reqs)
    health = eng.health_check()
    assert health["status"] == "DOWN"
    assert "injected decode failure" in health["error"]
    eng.stop()


def test_stop_retires_active_slots():
    """stop() must terminate streams still holding a slot — no stream
    may hang after shutdown."""
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=128))
    eng.start()
    # long generation that cannot finish before stop()
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                               max_new_tokens=100))
    deadline = time.time() + 30
    while time.time() < deadline and req.first_token_at is None:
        time.sleep(0.01)
    assert req.first_token_at is not None
    eng.stop()
    assert req.finished_at is not None
    assert req.error == "engine stopped"


def test_seeded_engines_reproduce_streams():
    """Same seed => identical stochastic generations; different seed
    => (overwhelmingly) different."""
    sp = SamplingParams(temperature=1.0, max_new_tokens=12)

    def run(seed):
        eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                             seed=seed))
        eng.start()
        out = eng.submit_sync([1, 2, 3], sp).generated
        eng.stop()
        return out

    assert run(7) == run(7)
    assert run(7) != run(1234)


def test_top_p_applied_after_top_k_renormalisation():
    """With top_k=2 and top_p=0.6 the top-p mass must be computed on
    the top-k-renormalised distribution: the two survivors split the
    mass ~50/50, so the nucleus keeps both; pre-top-k (the old bug)
    the first token already holds >0.6 of the full mass and the second
    could never be drawn."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from gofr_tpu.serving.engine import _sample_batch
    # token0 and token1 nearly tied, the rest far behind
    logits = jnp.asarray([[5.0, 4.9, -10.0, -10.0]])
    temps = jnp.asarray([1.0], jnp.float32)
    top_ps = jnp.asarray([0.6], jnp.float32)
    top_ks = jnp.asarray([2], jnp.int32)
    seen = set()
    for i in range(64):
        out = np.asarray(_sample_batch(logits, jax.random.key(i),
                                       temps, top_ps, top_ks))
        seen.add(int(out[0]))
    assert seen == {0, 1}


def test_prefill_batches_admit_together():
    """A burst larger than prefill_batch still completes, with groups
    admitted batch-at-a-time."""
    eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=64))
    eng.config.prefill_batch = 2
    eng.start()
    reqs = [eng.submit([i + 1, 2, 3], SamplingParams(temperature=0.0,
                                                     max_new_tokens=5))
            for i in range(6)]
    deadline = time.time() + 60
    while time.time() < deadline:
        if all(r.finished_at is not None for r in reqs):
            break
        time.sleep(0.01)
    assert all(r.error is None for r in reqs)
    assert all(len(r.generated) == 5 for r in reqs)
    eng.stop()


def test_moe_engine_generates():
    """The MoE glue path must serve end to end (tiny config, greedy)."""
    import jax
    from gofr_tpu.models.moe import MoEConfig, moe_init
    from gofr_tpu.serving.glue import moe_engine
    c = MoEConfig.tiny()
    params = moe_init(jax.random.key(0), c)
    eng = moe_engine(params, c, EngineConfig(max_batch=2, max_seq=64, seed=3),
                     implementation="xla")
    eng.start()
    req = eng.submit_sync([1, 2, 3], SamplingParams(temperature=0.0,
                                                    max_new_tokens=6))
    eng.stop()
    assert req.error is None
    assert len(req.generated) == 6


def test_moe_engine_on_the_pool_matches_the_dense_steps_driven_directly():
    """``moe_engine`` has no paged steps, so it serves from the page
    pool through the dense view. Its greedy streams must be those of
    ``moe_prefill_last`` + ``moe_decode_step`` driven by hand on a
    dense cache, with no engine and no pool in between."""
    import jax
    import jax.numpy as jnp
    from gofr_tpu.models.moe import (MoEConfig, moe_decode_step, moe_init,
                                     moe_prefill_last)
    from gofr_tpu.serving.glue import moe_engine
    c = MoEConfig.tiny()
    params = moe_init(jax.random.key(0), c)
    prompts, n_new, max_seq = [[1, 2, 3], [9, 4, 7, 7, 2]], 20, 64

    def by_hand(prompt):
        tokens = jnp.zeros((1, 8), jnp.int32).at[0, :len(prompt)].set(
            jnp.asarray(prompt))
        n = jnp.asarray([len(prompt)], jnp.int32)
        logits, (k, v), _ = moe_prefill_last(params, tokens, c,
                                             kv_lengths=n,
                                             implementation="xla")
        shape = (c.n_layers, 1, max_seq, c.n_kv_heads, c.head_dim)
        kc = jnp.zeros(shape, c.dtype).at[:, :, :8].set(k)
        vc = jnp.zeros(shape, c.dtype).at[:, :, :8].set(v)
        out = [int(jnp.argmax(logits[0]))]
        for _ in range(n_new - 1):
            logits, kc, vc = moe_decode_step(
                params, jnp.asarray(out[-1:], jnp.int32), kc, vc, n, c)
            out.append(int(jnp.argmax(logits[0])))
            n = n + 1
        return out

    eng = moe_engine(params, c, EngineConfig(
        max_batch=2, max_seq=max_seq, page_size=16,
        prefill_buckets=(8,), seed=3), implementation="xla")
    assert eng.paged_attention_impl == "view" and eng._n_pages == 8
    eng.start()
    reqs = [eng.submit_sync(p, SamplingParams(
        temperature=0.0, max_new_tokens=n_new)) for p in prompts]
    eng.stop()
    assert all(r.error is None for r in reqs)
    assert [r.generated for r in reqs] == [by_hand(p) for p in prompts]


def test_engine_warmup_precompiles_and_serves():
    """warmup() before start() must leave the engine fully functional
    and identical in output to an unwarmed engine."""
    eng = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64, seed=5))
    eng.warmup(prompt_lens=(3,))
    eng.start()
    warm = eng.submit_sync([1, 2, 3], SamplingParams(temperature=0.0,
                                                     max_new_tokens=6))
    eng.stop()
    ref = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64, seed=5))
    ref.start()
    cold = ref.submit_sync([1, 2, 3], SamplingParams(temperature=0.0,
                                                     max_new_tokens=6))
    ref.stop()
    assert warm.error is None and warm.generated == cold.generated


def test_engine_exports_saturation_gauges():
    from gofr_tpu.metrics.registry import Manager
    from gofr_tpu.serving.glue import demo_llama_engine
    from gofr_tpu.serving.engine import EngineConfig, SamplingParams

    metrics = Manager()
    engine = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                            seed=1), metrics=metrics)
    engine.start()
    try:
        req = engine.submit_sync([1, 2, 3], SamplingParams(
            temperature=0.0, max_new_tokens=4))
        assert req.error is None
    finally:
        engine.stop()
    scrape = metrics.render_prometheus()
    assert "app_engine_active_slots" in scrape
    assert "app_engine_waiting" in scrape


def test_stalled_engine_reports_degraded():
    """A wedged device call (a device runtime that hangs) must flip
    health to DEGRADED while work is in flight —
    exceptions go DOWN via _crash; a hang has no exception."""
    import threading
    import time as _time

    from gofr_tpu.serving.glue import demo_llama_engine
    from gofr_tpu.serving.engine import EngineConfig, SamplingParams

    engine = demo_llama_engine(EngineConfig(max_batch=2, max_seq=64,
                                            stall_threshold_s=0.2,
                                            seed=1))
    release = threading.Event()
    original = engine._decode

    def wedged(*args, **kw):
        release.wait(30)  # simulate a hung device call
        return original(*args, **kw)

    engine._decode = wedged
    engine.start()
    try:
        req = engine.submit(list(range(40)), SamplingParams(
            temperature=0.0, max_new_tokens=8))
        deadline = _time.time() + 10
        while _time.time() < deadline:
            if engine.health_check()["status"] == "DEGRADED":
                break
            _time.sleep(0.05)
        health = engine.health_check()
        assert health["status"] == "DEGRADED", health
        assert health["stalled_for_s"] >= 0.2
        release.set()  # device "recovers": request completes, health UP
        deadline = _time.time() + 30
        while _time.time() < deadline and req.finished_at is None \
                and req.error is None:
            _time.sleep(0.05)
        assert req.error is None and len(req.generated) == 8
        assert engine.health_check()["status"] == "UP"
    finally:
        release.set()
        engine.stop()


def test_decode_windows_match_full_attention():
    """Windowed view decode (gathers O(window) rows a slot, not
    O(max_seq)) must be greedily identical to the full graph,
    including prompts whose lengths cross a window boundary
    mid-generation."""
    import time as _t

    from gofr_tpu.serving.glue import demo_llama_engine

    def run(**extra):
        eng = demo_llama_engine(EngineConfig(max_batch=4, max_seq=256,
                                             seed=13, page_size=16,
                                             **extra))
        eng.start()
        # 10-token prompt + 40 generated: passes need 18, 26, 34, ...
        # rows (len + K, K=8) — the 32-window graph runs the early
        # passes, then selection hands the SAME donated pools to the
        # 64 graph and finally the full graph as lengths cross each
        # boundary (the riskiest path: variant switches mid-request)
        reqs = [eng.submit(list(range(2, 12)), SamplingParams(
            temperature=0.0, max_new_tokens=40)) for _ in range(3)]
        deadline = _t.time() + 120
        while _t.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            _t.sleep(0.01)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        assert all(len(r.generated) == 40 for r in reqs)
        return [r.generated for r in reqs]

    want = run()
    got = run(decode_windows=(32, 64))
    assert got == want


def test_moe_decode_windows_match_full_attention():
    """MoE windowed decode must match the full graph greedily across a
    window boundary (same contract as the llama test: windows are the
    view path's, whatever family's dense steps run on the view)."""
    import time as _t

    import jax
    from gofr_tpu.models.moe import MoEConfig, moe_init
    from gofr_tpu.serving.glue import moe_engine

    c = MoEConfig.tiny()
    params = moe_init(jax.random.key(0), c)

    def run(**extra):
        eng = moe_engine(params, c,
                         EngineConfig(max_batch=2, max_seq=128, seed=7,
                                      page_size=16, **extra),
                         implementation="xla")
        eng.start()
        reqs = [eng.submit([4 + i, 2, 9], SamplingParams(
            temperature=0.0, max_new_tokens=40)) for i in range(2)]
        deadline = _t.time() + 120
        while _t.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            _t.sleep(0.01)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        assert all(len(r.generated) == 40 for r in reqs)
        return [r.generated for r in reqs]

    want = run()
    got = run(decode_windows=(16, 32))
    assert got == want
