"""Weight-only int8 quantization: numerics bounds, llama forward
parity, and the serving engine running quantized end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.llama import (LlamaConfig, llama_init, llama_prefill)
from gofr_tpu.ops.quant import (qgather, qmatmul, quantize_int4,
                                quantize_int8,
                                quantize_llama_int8, quantized_bytes)


def test_quantize_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.key(0), (64, 48), jnp.float32)
    qw = quantize_int8(w, axis=0)
    deq = qw["q"].astype(jnp.float32) * qw["s"].astype(jnp.float32)
    # symmetric rounding: error <= half a quantization step per element
    step = np.asarray(qw["s"], np.float32)        # [1, 48]
    err = np.abs(np.asarray(deq) - np.asarray(w))
    assert (err <= step / 2 + 1e-6).all()


def test_qmatmul_close_to_dense():
    k1, k2 = jax.random.split(jax.random.key(1))
    x = jax.random.normal(k1, (8, 64), jnp.float32)
    w = jax.random.normal(k2, (64, 32), jnp.float32)
    want = np.asarray(x @ w)
    got = np.asarray(qmatmul(x, quantize_int8(w, axis=0)))
    denom = np.abs(want).mean()
    assert np.abs(got - want).mean() / denom < 0.01   # ~1% relative


def test_qgather_scales_rows():
    table = jax.random.normal(jax.random.key(2), (10, 16), jnp.float32)
    qt = quantize_int8(table, axis=1)              # per-row scales
    idx = jnp.asarray([3, 7])
    got = np.asarray(qgather(qt, idx, jnp.float32))
    want = np.asarray(table[idx])
    assert np.abs(got - want).max() <= np.asarray(qt["s"]).max() / 2 + 1e-6


@pytest.mark.parametrize("tie", [True, False])
def test_llama_logits_parity(tie):
    config = LlamaConfig.tiny().scaled(tie_embeddings=tie)
    params = llama_init(jax.random.key(3), config)
    qparams = quantize_llama_int8(params)
    tokens = jnp.asarray([[5, 9, 2, 31, 7, 12]], jnp.int32)
    logits, _ = llama_prefill(params, tokens, config,
                              implementation="xla")
    qlogits, _ = llama_prefill(qparams, tokens, config,
                               implementation="xla")
    a = np.asarray(logits, np.float64).ravel()
    b = np.asarray(qlogits, np.float64).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.995, corr


def test_quantized_bytes_shrink():
    config = LlamaConfig.tiny()
    params = llama_init(jax.random.key(4), config)
    before = quantized_bytes(params)               # f32 tiny weights
    after = quantized_bytes(quantize_llama_int8(params))
    assert after < before / 2                       # int8 + small scales


def test_engine_serves_quantized():
    import time

    from gofr_tpu.serving.engine import EngineConfig, SamplingParams
    from gofr_tpu.serving.glue import llama_engine

    config = LlamaConfig.tiny()
    params = llama_init(jax.random.key(5), config)
    engine = llama_engine(params, config,
                          EngineConfig(max_batch=2, max_seq=128, seed=6),
                          implementation="xla", quantize="int8")
    engine.start()
    reqs = [engine.submit([3 + i, 1, 4], SamplingParams(
        temperature=0.0, max_new_tokens=8)) for i in range(3)]
    deadline = time.time() + 120
    while time.time() < deadline and any(
            r.finished_at is None and r.error is None for r in reqs):
        time.sleep(0.01)
    engine.stop()
    assert all(r.error is None for r in reqs)
    assert all(len(r.generated) == 8 for r in reqs)
    # greedy determinism holds WITHIN the quantized model
    again = llama_engine(params, config,
                         EngineConfig(max_batch=2, max_seq=128, seed=6),
                         implementation="xla", quantize="int8")
    again.start()
    rep = again.submit([3, 1, 4], SamplingParams(temperature=0.0,
                                                 max_new_tokens=8))
    deadline = time.time() + 120
    while time.time() < deadline and rep.finished_at is None \
            and rep.error is None:
        time.sleep(0.01)
    again.stop()
    assert rep.generated == reqs[0].generated


def test_engine_quantize_rejects_unknown():
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import llama_engine

    config = LlamaConfig.tiny()
    params = llama_init(jax.random.key(7), config)
    with pytest.raises(ValueError, match="int8"):
        llama_engine(params, config, EngineConfig(max_batch=2),
                     quantize="fp4")


def test_int8_composes_with_native_paged_kernel():
    """int8 weights + the native paged decode path (row writes through
    the block table, ragged kernel in interpret mode) must match the
    int8-weight view engine greedily (the reference: dense step
    functions on a gathered view, no kernel, no table writes by the
    model) — protects the best-known TPU serving composition (paged
    kernel + int8)."""
    import time

    from gofr_tpu.serving.engine import EngineConfig, SamplingParams
    from gofr_tpu.serving.glue import llama_engine

    config = LlamaConfig.tiny()
    params = llama_init(jax.random.key(11), config)

    def run(**extra):
        eng = llama_engine(params, config,
                           EngineConfig(max_batch=2, max_seq=128, seed=9,
                                        **extra),
                           implementation="xla", quantize="int8")
        eng.start()
        reqs = [eng.submit([5 + i, 2, 9], SamplingParams(
            temperature=0.0, max_new_tokens=8)) for i in range(2)]
        deadline = time.time() + 120
        while time.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            time.sleep(0.01)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        assert all(len(r.generated) == 8 for r in reqs)  # really finished
        return [r.generated for r in reqs]

    want = run(page_size=16, paged_attention="view")
    got = run(page_size=16, paged_attention="interpret")
    assert got == want


def test_int4_roundtrip_bounds():
    w = jax.random.normal(jax.random.key(4), (32, 16), jnp.float32)
    qw = quantize_int4(w, axis=0)
    assert str(qw["q"].dtype) == "int4"
    deq = np.asarray(qw["q"].astype(jnp.float32) * qw["s"])
    # full-range scheme (scale = amax/8): error <= half a step per
    # element, except weights in the top half-step below +amax — the
    # exact-amax guard clips their unrepresentable +8 down to +7, so
    # their error is bounded by one step instead
    step = np.broadcast_to(np.asarray(qw["s"])[0], w.shape)
    err = np.abs(deq - np.asarray(w))
    clipped = np.asarray(w) > 7.5 * step - 1e-6
    assert (err[~clipped] <= step[~clipped] / 2 + 1e-6).all()
    assert (err <= step + 1e-6).all()


def test_int4_uses_full_range():
    """scale = amax/8 must actually reach the -8 code point (the old
    [-7, 7] scheme wasted it) and pin +amax to +7."""
    w = jnp.asarray([[-1.0, -0.97, 0.5, 1.0]], jnp.float32).T  # [4, 1]
    qw = quantize_int4(w, axis=0)
    q = np.asarray(qw["q"].astype(jnp.int8)).ravel()
    assert q.min() == -8          # -amax -> -8 exactly
    assert q.max() == 7           # +amax clipped by the guard
    assert np.isclose(np.asarray(qw["s"]).ravel()[0], 1.0 / 8.0)


def test_int4_engine_serves_and_is_deterministic():
    from gofr_tpu.serving.engine import EngineConfig, SamplingParams
    from gofr_tpu.serving.glue import llama_engine

    config = LlamaConfig.tiny()
    params = llama_init(jax.random.key(2), config)

    def run():
        eng = llama_engine(params, config,
                           EngineConfig(max_batch=2, max_seq=64, seed=3),
                           implementation="xla", quantize="int4")
        eng.start()
        req = eng.submit_sync([4, 2, 9], SamplingParams(
            temperature=0.0, max_new_tokens=8))
        eng.stop()
        assert req.error is None, req.error
        assert len(req.generated) == 8
        return req.generated

    assert run() == run()  # greedy determinism within the int4 model


def test_int4_quarter_bytes():
    config = LlamaConfig.tiny()
    params = llama_init(jax.random.key(0), config)
    from gofr_tpu.ops.quant import quantize_llama_int4
    before = quantized_bytes(params)
    after = quantized_bytes(quantize_llama_int4(params))
    # tiny config is f32 (4 B/param): int4 storage should be ~1/8th
    # plus scale overhead
    assert after < before / 6


def test_quantized_bytes_dtype_detection():
    """Explicit dtype comparison, not substring matching: int4 AND
    uint4 count the packed half byte; everything else counts its
    itemsize."""
    tree = {"a": jnp.zeros((10,), jnp.int4),
            "b": jnp.zeros((10,), jnp.uint4),
            "c": jnp.zeros((10,), jnp.int8),
            "d": jnp.zeros((10,), jnp.float32)}
    assert quantized_bytes(tree) == int(10 * 0.5 + 10 * 0.5 + 10 + 40)


def test_quantized_bytes_covers_kv_pool_tree():
    """The engine's kv_bytes accounting is quantized_bytes over the
    (k_cache, v_cache) pytree — the paged pool's {"q", "s"} split must
    sum codes + scale rows (one 128-lane f32 row per page and head
    group, ops/paged_kv.py), and the bf16 pool its plain array."""
    from gofr_tpu.ops.paged_kv import quantize_pool
    l, h, np_, pg, d = 2, 2, 4, 8, 16
    plain = jnp.zeros((l, h, np_, pg, d), jnp.bfloat16)
    assert quantized_bytes((plain, plain)) == 2 * l * h * np_ * pg * d * 2
    qp = quantize_pool(plain)
    want = l * h * np_ * (pg * d + 128 * 4)    # int8 codes + scale row
    assert quantized_bytes((qp, qp)) == 2 * want
