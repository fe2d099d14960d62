"""Mesh-sharded serving: the engine on a tp/dp mesh must generate the
same tokens as a single-device engine (BASELINE config 5's CPU-mesh
analog — a model too big for one chip is served by passing ``mesh=``).
"""

import time

import jax
import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.parallel.mesh import create_mesh
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import llama_engine

TINY = LlamaConfig.tiny()


def _generate(mesh):
    params = llama_init(jax.random.key(0), TINY)
    eng = llama_engine(
        params, TINY,
        EngineConfig(max_batch=4, max_seq=128, seed=11),
        mesh=mesh, implementation="xla")
    eng.start()
    try:
        outs = []
        reqs = [eng.submit([3 + i, 1, 4, 1, 5],
                           SamplingParams(temperature=0.0, max_new_tokens=8))
                for i in range(6)]
        deadline = time.time() + 120
        while time.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            time.sleep(0.01)
        for r in reqs:
            assert r.error is None, r.error
            outs.append(r.generated)
        return outs
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def single_device_outputs():
    return _generate(None)


def test_tp_sharded_decode_matches_single_device(single_device_outputs):
    mesh = create_mesh({"tp": 2}, jax.devices()[:2])
    assert _generate(mesh) == single_device_outputs


def test_wider_tp_matches_single_device(single_device_outputs):
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    # tiny config has 2 kv heads; tp=2 shards them, wider tp shards
    # the q-head/ffn dims via the same specs
    mesh = create_mesh({"tp": 2, "dp": 4}, jax.devices())
    assert _generate(mesh) == single_device_outputs


def test_sharded_params_actually_sharded():
    mesh = create_mesh({"tp": 2}, jax.devices()[:2])
    params = llama_init(jax.random.key(0), TINY)
    eng = llama_engine(params, TINY,
                       EngineConfig(max_batch=2, max_seq=64),
                       mesh=mesh, implementation="xla")
    wq = eng.params["layers"]["wq"]
    # column-parallel: output dim split over tp=2
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(TINY.n_layers, TINY.dim,
                             TINY.n_heads * TINY.head_dim // 2)}
    # the page pool [L, Hg, Np, pg, W] (two slots of one 64-row page):
    # head groups split over tp=2, packed within a device's own heads
    assert eng.paged_attention_impl == "view"
    for pool in (eng.k_cache, eng.v_cache):
        assert pool.shape == (TINY.n_layers, TINY.n_kv_heads, 2, 64,
                              TINY.head_dim)
        assert {s.data.shape for s in pool.addressable_shards} == {
            (TINY.n_layers, TINY.n_kv_heads // 2, 2, 64, TINY.head_dim)}


def _generate_long(mesh):
    import numpy as np
    prompt = list(np.random.RandomState(9).randint(3, 200, size=40))
    params = llama_init(jax.random.key(0), TINY)
    eng = llama_engine(
        params, TINY,
        EngineConfig(max_batch=2, max_seq=128, prefill_buckets=(8,),
                     seed=11),
        mesh=mesh, implementation="xla")
    eng.start()
    try:
        req = eng.submit(prompt, SamplingParams(temperature=0.0,
                                                max_new_tokens=6))
        deadline = time.time() + 180
        while time.time() < deadline and req.finished_at is None \
                and req.error is None:
            time.sleep(0.01)
        assert req.error is None, req.error
        assert len(req.prompt_tokens) == 40  # chunked, not clamped
        return list(req.generated)
    finally:
        eng.stop()


def test_chunked_prefill_sharded_matches_single_device():
    """A long prompt walking in chunks on a tp-sharded engine must
    produce the single-device tokens — the chunk graph's cache slicing
    and scatters compose with the mesh sharding."""
    single = _generate_long(None)
    sharded = _generate_long(create_mesh({"tp": 2}, jax.devices()[:2]))
    assert sharded == single


def _generate_modern(mesh, **kw):
    """The production engine shape, all features on at once: the page
    pool through the gather/scatter view (the one path under a mesh),
    prefix cache, chunked prefill, speculative decode, pipelined
    dispatch."""
    params = llama_init(jax.random.key(0), TINY)
    cfg = dict(max_batch=4, max_seq=128, prefill_buckets=(16, 32),
               seed=11, page_size=16, paged_attention="view",
               prefix_cache=True, speculative=True, spec_draft=3,
               # drafting is consulted only at pass boundaries (the
               # matched tail ends at the boundary token): short
               # passes + 1-gram lookup make engagement deterministic
               # within the tiny token budget, and the static policy
               # keeps the NUMBER of verify passes off the host's
               # clock (the adaptive controller prices drafting from
               # measured pass times)
               spec_ngram=1, decode_steps_per_pass=2,
               spec_adaptive=False, pipeline_depth=1)
    cfg.update(kw)
    eng = llama_engine(params, TINY, EngineConfig(**cfg), mesh=mesh,
                       implementation="xla")
    eng.start()
    try:
        outs = []
        system = list(range(40, 40 + 32))  # two full pages: cacheable
        # long prompt (chunk walk), two prefix-sharers (second hits
        # the cache), and a repetitive prompt generated long enough
        # that the greedy loop repeats its own n-grams (drafts fire)
        prompts = [(list(range(3, 3 + 48)), 10),
                   (system + [7, 8, 9], 10),
                   (system + [9, 8, 7], 10),
                   ([5, 6] * 5, 24)]
        for prompt, gen in prompts:  # sequential: prefix registration
            req = eng.submit(prompt, SamplingParams(  # is retire-time
                temperature=0.0, max_new_tokens=gen))
            deadline = time.time() + 180
            while time.time() < deadline and req.finished_at is None \
                    and req.error is None:
                time.sleep(0.01)
            assert req.error is None, req.error
            assert req.finished_at is not None, "timed out"
            outs.append(list(req.generated))
        stats = dict(eng.stats)
        return outs, stats
    finally:
        eng.stop()


def _generate_int8(mesh):
    params = llama_init(jax.random.key(0), TINY)
    eng = llama_engine(params, TINY,
                       EngineConfig(max_batch=4, max_seq=128, seed=11),
                       mesh=mesh, implementation="xla",
                       quantize="int8")
    eng.start()
    try:
        reqs = [eng.submit([3 + i, 1, 4, 1, 5],
                           SamplingParams(temperature=0.0,
                                          max_new_tokens=8))
                for i in range(4)]
        deadline = time.time() + 120
        while time.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            time.sleep(0.01)
        assert all(r.error is None for r in reqs)
        return [r.generated for r in reqs]
    finally:
        eng.stop()


def test_int8_sharded_matches_int8_single_device():
    """Weight-only int8 composes with tp sharding: the {'q','s'}
    leaves shard like their bf16 matrix (scales keep the output axis,
    reduction axis unsharded) and greedy outputs are identical to
    single-device int8."""
    single = _generate_int8(None)
    sharded = _generate_int8(create_mesh({"tp": 2}, jax.devices()[:2]))
    assert sharded == single


def test_int8_sharded_params_actually_sharded():
    mesh = create_mesh({"tp": 2}, jax.devices()[:2])
    params = llama_init(jax.random.key(0), TINY)
    eng = llama_engine(params, TINY,
                       EngineConfig(max_batch=2, max_seq=64),
                       mesh=mesh, implementation="xla", quantize="int8")
    wq = eng.params["layers"]["wq"]
    out_dim = TINY.n_heads * TINY.head_dim
    assert {s.data.shape for s in wq["q"].addressable_shards} == \
        {(TINY.n_layers, TINY.dim, out_dim // 2)}
    # scales: per-output-channel, sharded with the output axis
    assert {s.data.shape for s in wq["s"].addressable_shards} == \
        {(TINY.n_layers, 1, out_dim // 2)}
    # engine never started: nothing to stop


def test_modern_engine_sharded_matches_single_device():
    """Greedy equivalence for the full modern feature set — the page
    pool through the view, prefix cache, chunked prefill, speculative
    decode, pipelining — between single-device and tp-sharded engines,
    with the features proven to actually engage (VERDICT r4 #4). Both
    are also held to the plain single-device engine (no speculation,
    no pipelining): two engines that are wrong alike must not pass,
    which is how this test used to pass on the runs where both made
    the same number of verify passes (PR 30)."""
    plain, _ = _generate_modern(None, speculative=False,
                                pipeline_depth=0)
    single, sstats = _generate_modern(None)
    sharded, mstats = _generate_modern(
        create_mesh({"tp": 2}, jax.devices()[:2]))
    assert single == plain
    assert sharded == plain
    assert mstats["spec_passes"] == sstats["spec_passes"]
    for stats in (sstats, mstats):
        assert stats["prefix_hits"] >= 1, stats
        assert stats["spec_passes"] >= 1, stats
        assert stats["spec_accepted"] >= 1, stats
