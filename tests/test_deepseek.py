"""The ``deepseek_v3`` family (Kanana-2) and its ``xing4_0`` descendant
(Xing4.0): latent (MLA) attention over a one-vector page pool and
sigmoid-routed sparse experts — and for ``xing4_0`` a four-stream
mHC residual, compressed queries and YaRN — each against the plain
reference the benchmark keeps for it (``benchmarks/references/
deepseek_v3_mla_moe.py``, ``xing4_mhc_mla_moe.py``, which import
nothing of the program).

Everything here runs at a small size of the same shape — d 64, 4 heads,
nope 16 / rope 8 / v 16, latent 32, 8 experts top-2 + 1 shared, 1 dense
+ 2 expert layers (``xing4_0``: 4 streams mixed by 4 Sinkhorn rounds —
the published 20 are ``tests/test_hyper_connections.py``'s — queries
through 24, YaRN x 8 over 32 positions) — in float32 on the CPU.
Tolerances, and why:

- ``LOGIT_TOL`` 2e-4 on logits of standard deviation ~1: both sides
  compute in float32 and differ by the order of their sums (absorbed
  against materialised attention, a grouped matmul against a masked
  loop, an online softmax against a dense one; with streams, the
  stream mix as adds of slabs against an einsum): 5e-6 as measured. The
  same model in bf16 — the nearest precision below — reads 5e-2 and
  more, and ``test_bf16_where_float32_is_stated_fails`` holds that it
  fails the tolerance.
- ``ATTN_TOL`` / ``EXPERT_TOL`` 2e-5 on single-layer outputs of order
  1: one layer's worth of the same reordering.
"""

import importlib.util
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import deepseek
from gofr_tpu.models.deepseek import (DeepseekConfig, deepseek_decode_step_paged,
                                      deepseek_init,
                                      deepseek_prefill_chunk_paged,
                                      deepseek_prefill_last,
                                      latent_row_bytes, latent_row_width,
                                      make_latent_cache)
from gofr_tpu.ops.latent_attention import (check_latent_layout,
                                           latent_chunk_attention_pallas,
                                           latent_chunk_attention_xla)
from gofr_tpu.ops.moe import sigmoid_routing, sparse_experts
from gofr_tpu.ops.paged_kv import (empty_pool, gather_view,
                                   pool_from_cache_shape, pool_row_bytes,
                                   pool_write, scatter_chunk)
from gofr_tpu.ops.rope import rope_frequencies

REPO = Path(__file__).resolve().parent.parent
LOGIT_TOL = 2e-4
ATTN_TOL = EXPERT_TOL = 2e-5
PAGE, N_PAGES, MAX_PAGES = 8, 32, 16
S = 40          # tokens of the test sequence


#: family -> (its reference's file, the small config of its shape)
FAMILIES = {"deepseek_v3": ("deepseek_v3_mla_moe", DeepseekConfig.tiny),
            "xing4_0": ("xing4_mhc_mla_moe", DeepseekConfig.tiny_mhc)}


def load_reference(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, REPO / "benchmarks" / "references" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def published_keys(c: DeepseekConfig) -> dict:
    """The config as a ``config.json`` would hold it."""
    keys = {k: getattr(c, k) for k in c.__dataclass_fields__ if k != "dtype"}
    return {**keys, "attention_bias": False}


@pytest.fixture(scope="module", params=list(FAMILIES))
def case(request):
    """Seeded float32 weights from the reference's own initialiser, a
    token sequence, and the reference's logits at every position."""
    name, tiny = FAMILIES[request.param]
    reference, c = load_reference(name), tiny()
    cfg = published_keys(c)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          reference.init_weights(cfg, 7))
    tokens = np.random.default_rng(0).integers(0, c.vocab_size, S)
    seq = np.zeros(reference.Q_BLOCK, np.int32)
    seq[:S] = tokens
    want = np.asarray(reference.forward_logits(cfg, params, seq,
                                               np.arange(S)))
    return c, params, tokens, want


def fresh_pools(c):
    probes = [pool_from_cache_shape(x) for x in make_latent_cache(c, 1, PAGE)]
    return [empty_pool(p, N_PAGES, False) for p in probes]


TABLES = jnp.arange(MAX_PAGES, dtype=jnp.int32)[None, :] + 3


def serve(c, params, tokens, *, path, impl):
    """Logits at every position a serving path produces them for:
    ``bucket`` prefills 24 tokens at once and decodes the rest;
    ``chunk`` walks two 16-token chunks (the second with history) and
    decodes the rest. Returns {position: logits}. The steps are jitted:
    called bare, every call would trace and compile its layer scans
    anew."""
    pool, v_pool = fresh_pools(c)
    got = {}
    prefill = jax.jit(lambda *a, **kw: deepseek_prefill_last(*a, c, **kw))
    chunk = jax.jit(lambda *a: deepseek_prefill_chunk_paged(
        *a, c, implementation=impl))
    decode = jax.jit(lambda *a: deepseek_decode_step_paged(
        *a, c, implementation=impl))
    if path == "bucket":
        done = 24
        padded = np.zeros((1, 32), np.int32)
        padded[0, :done] = tokens[:done]
        logits, (k, v) = prefill(params, jnp.asarray(padded),
                                 kv_lengths=jnp.array([done]))
        got[done - 1] = logits[0]
        zero, n = jnp.zeros(1, jnp.int32), jnp.array([done])
        pool = scatter_chunk(pool, TABLES, k, zero, n)
        v_pool = scatter_chunk(v_pool, TABLES, v, zero, n)
    else:
        done = 32
        for off in (0, 16):
            logits, pool, v_pool = chunk(
                params, jnp.asarray(tokens[None, off:off + 16]), pool,
                v_pool, TABLES, jnp.array([off]), jnp.array([16]))
            got[off + 15] = logits[0]
    for t in range(done, len(tokens)):
        logits, pool, v_pool, _ = decode(
            params, jnp.asarray(tokens[t:t + 1]), pool, v_pool, TABLES,
            jnp.array([t]))
        got[t] = logits[0]
    assert v_pool.size == 0
    return got


def worst(got, want):
    return max(float(np.abs(np.asarray(v) - want[t]).max())
               for t, v in got.items())


# ------------------------------------------------ against the reference

@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("path", ["bucket", "chunk"])
def test_prefill_then_paged_decode_matches_reference(case, path, impl):
    c, params, tokens, want = case
    got = serve(c, params, tokens, path=path, impl=impl)
    assert len(got) >= S - 32 + 1
    assert worst(got, want) < LOGIT_TOL


def test_bf16_where_float32_is_stated_fails(case):
    """The comparison is tight enough to tell the precision below."""
    c, params, tokens, want = case
    low = DeepseekConfig(**{**{k: getattr(c, k)
                               for k in c.__dataclass_fields__},
                            "dtype": jnp.bfloat16})
    got = serve(low, jax.tree.map(lambda x: x.astype(jnp.bfloat16), params),
                tokens, path="chunk", impl="xla")
    assert worst(got, want) > 10 * LOGIT_TOL


# ------------------------------------- absorbed = materialised attention

@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("history", [0, 8, 13])
def test_absorbed_attention_equals_materialised(case, history, impl):
    """One layer's attention output for the rows past ``history``: the
    materialised form over the whole block, against the absorbed form
    that reads the first ``history`` rows back from the page pool."""
    c, params, tokens, _ = case
    lp = jax.tree.map(lambda x: x[0], params["dense"])
    n = 24
    x = jax.random.normal(jax.random.key(history), (1, n, c.hidden_size),
                          jnp.float32)
    inv_freq = c.rope_inv_freq
    positions = jnp.arange(n)[None, :]
    want, rows = deepseek._attn_materialised(
        x, lp, c, positions, inv_freq, jnp.array([n]))
    pool, _ = fresh_pools(c)
    li = jnp.int32(1)
    pool = pool_write(pool, li, TABLES, jnp.array([0]),
                      jnp.array([history]), rows)
    got, _ = deepseek._attn_absorbed(
        x[:, history:], lp, li, pool, TABLES, jnp.array([history]),
        jnp.array([n - history]), c, positions[:, history:], inv_freq, impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[:, history:],
                               atol=ATTN_TOL, rtol=0)


@pytest.mark.parametrize("hists,clens", [
    ((0, 0, 0), (16, 9, 1)), ((3, 11, 21), (16, 13, 7)),
    ((0, 19, 40), (16, 16, 0))])
def test_latent_kernel_matches_its_xla_twin(hists, clens):
    """The Pallas kernel under the interpreter against the gather
    reference, over histories that end mid-page and a zero-length
    slot; rows past a slot's chunk length are padding by contract."""
    ks = jax.random.split(jax.random.key(0), 2)
    heads, width, value = 4, 128, 96
    q = jax.random.normal(ks[0], (3, 16, heads, width), jnp.float32)
    pool = jax.random.normal(ks[1], (2, 1, N_PAGES, PAGE, width),
                             jnp.float32)
    rng = np.random.default_rng(0)
    tables = np.full((3, 10), N_PAGES, np.int32)
    for i, (h, n) in enumerate(zip(hists, clens)):
        need = -(-(h + n) // PAGE)
        tables[i, :need] = rng.choice(N_PAGES, size=need, replace=False)
    args = (q, pool, jnp.asarray(tables), jnp.asarray(hists, jnp.int32),
            jnp.asarray(clens, jnp.int32))
    kw = dict(value_width=value, scale=0.2, layer=jnp.int32(1))
    got = np.asarray(latent_chunk_attention_pallas(*args, **kw, block_q=4,
                                                   interpret=True))
    want = np.asarray(latent_chunk_attention_xla(*args, **kw))
    valid = np.arange(16)[None, :] < np.asarray(clens)[:, None]
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got[valid], want[valid], atol=ATTN_TOL,
                               rtol=0)
    assert (got[np.asarray(hists) + np.asarray(clens) == 0] == 0).all()


# ------------------------------------------------------- sparse experts

def dense_experts(h, weights, indices, w1, w3, w2):
    """Every expert over every token, combined by the routing weights:
    the all-experts sum the grouped form must equal."""
    combine = jnp.einsum("tk,tke->te", weights,
                         jax.nn.one_hot(indices, w1.shape[0]))
    gate = jax.nn.silu(jnp.einsum("td,edf->tef", h, w1, precision="highest"))
    up = jnp.einsum("td,edf->tef", h, w3, precision="highest")
    out = jnp.einsum("tef,efd->ted", gate * up, w2, precision="highest")
    return jnp.einsum("te,ted->td", combine, out)


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("tokens", [1, 5, 64])
def test_grouped_experts_equal_the_dense_sum(tokens, stacked):
    e, d, f, k = 8, 32, 16, 2
    ks = jax.random.split(jax.random.key(tokens), 6)
    h = jax.random.normal(ks[0], (tokens, d), jnp.float32)
    w1, w3 = (jax.random.normal(kk, (3, e, d, f), jnp.float32) * d ** -0.5
              for kk in ks[1:3])
    w2 = jax.random.normal(ks[3], (3, e, f, d), jnp.float32) * f ** -0.5
    weights, indices = sigmoid_routing(
        h, jax.random.normal(ks[4], (d, e), jnp.float32),
        jax.random.normal(ks[5], (e,), jnp.float32) * 0.1, k,
        route_scale=2.448)
    want = dense_experts(h, weights, indices, w1[1], w3[1], w2[1])
    if stacked:     # the whole stack and a traced layer index
        got, sizes = jax.jit(lambda li: sparse_experts(
            h, weights, indices, w1, w3, w2, layer=li))(jnp.int32(1))
    else:
        got, sizes = sparse_experts(h, weights, indices, w1[1], w3[1], w2[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=EXPERT_TOL, rtol=0)
    assert int(sizes.sum()) == tokens * k
    assert int((sizes > 0).sum()) == len(set(np.asarray(indices).ravel()))


@pytest.fixture(scope="module")
def routing():
    ks = jax.random.split(jax.random.key(11), 3)
    h = jax.random.normal(ks[0], (64, 32), jnp.float32)
    gate = jax.random.normal(ks[1], (32, 16), jnp.float32) * 0.3
    bias = jnp.zeros(16).at[5].set(4.0)     # expert 5 is always chosen
    return h, gate, bias


@pytest.mark.parametrize("prop", ["bias_moves_selection_not_weights",
                                  "weights_sum_to_the_scale", "top_k_count",
                                  "scores_in_float32"])
def test_router(routing, prop):
    h, gate, bias = routing
    k, scale = 4, 2.448
    w, idx = sigmoid_routing(h, gate, bias, k, route_scale=scale)
    scores = jax.nn.sigmoid(jnp.matmul(h, gate, precision="highest"))
    if prop == "bias_moves_selection_not_weights":
        w0, idx0 = sigmoid_routing(h, gate, jnp.zeros(16), k,
                                   route_scale=scale)
        assert (np.asarray(idx) == 5).any(axis=1).all()
        assert not (np.asarray(idx0) == 5).any(axis=1).all()
        # the weights are the plain scores of what was chosen,
        # normalised: the bias is nowhere in them
        picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), 1)
        np.testing.assert_allclose(
            np.asarray(w), scale * picked / picked.sum(1, keepdims=True),
            rtol=1e-6)
    elif prop == "weights_sum_to_the_scale":
        np.testing.assert_allclose(np.asarray(w).sum(1), scale, rtol=1e-6)
        raw, _ = sigmoid_routing(h, gate, bias, k, normalize=False)
        assert not np.allclose(np.asarray(raw).sum(1), 1.0)
    elif prop == "top_k_count":
        assert idx.shape == (64, k)
        assert all(len(set(row)) == k for row in np.asarray(idx))
    else:   # bf16 activations: scores still float32, from the values
        w16, idx16 = sigmoid_routing(h.astype(jnp.bfloat16), gate, bias, k,
                                     route_scale=scale)
        again, idx_again = sigmoid_routing(
            h.astype(jnp.bfloat16).astype(jnp.float32), gate, bias, k,
            route_scale=scale)
        assert w16.dtype == jnp.float32
        assert (np.asarray(idx16) == np.asarray(idx_again)).all()
        np.testing.assert_array_equal(np.asarray(w16), np.asarray(again))


# ------------------------------------------------- the latent page pool

@pytest.mark.parametrize("width", [576, 640])
def test_latent_rows_through_the_one_writer(width):
    """``pool_write`` / ``gather_view`` take a one-head row of any
    width (the published 576 lanes, or padded to the lane tile)."""
    layers, n = 2, 21
    pool = jnp.zeros((layers, 1, N_PAGES, PAGE, width), jnp.float32)
    rows = jax.random.normal(jax.random.key(1), (layers, 1, n, 1, width))
    pool = pool_write(pool, None, TABLES, jnp.array([5]), jnp.array([n]),
                      rows)
    view = gather_view(pool, TABLES)
    assert view.shape == (layers, 1, MAX_PAGES * PAGE, 1, width)
    np.testing.assert_array_equal(np.asarray(view[:, :, 5:5 + n]),
                                  np.asarray(rows))
    assert not np.asarray(view[:, :, :5]).any()
    assert pool_row_bytes(pool) == layers * width * 4


def test_the_v_side_of_a_one_vector_family_holds_nothing():
    c = DeepseekConfig.tiny()
    k, v = make_latent_cache(c, 1, PAGE)
    assert k.shape[-2:] == (1, latent_row_width(c)) and v.shape[-1] == 0
    pool, v_pool = fresh_pools(c)
    assert v_pool.size == 0 and pool_row_bytes(v_pool) == 0
    assert pool_row_bytes(pool) == latent_row_bytes(c)[1]
    same = pool_write(v_pool, jnp.int32(0), TABLES, jnp.array([0]),
                      jnp.array([4]), jnp.zeros((1, 4, 1, 0)))
    assert same is v_pool


@pytest.mark.parametrize("shape,value,names", [
    ((1, 16, 64, 576), 512, "640"), ((1, 16, 64, 640), 500, "multiple of"),
    ((1, 16, 12, 640), 512, "page size 12"), ((2, 16, 64, 640), 512, "ONE")])
def test_latent_layout_check_names_the_constraint(shape, value, names):
    check_latent_layout(jnp.zeros((1, 4, 64, 640)), 512)   # the real row
    with pytest.raises(ValueError, match=names):
        check_latent_layout(jnp.zeros(shape), value)


# ------------------------------------------------------------ the engine

from gofr_tpu.serving.engine import EngineConfig, SamplingParams  # noqa: E402
from gofr_tpu.serving.glue import deepseek_engine  # noqa: E402

ENGINE = dict(max_batch=2, max_seq=128, prefill_buckets=(8, 16), page_size=8,
              kv_layout="paged", seed=7, kv_pages=24)


@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    """Four prompts (two walk chunks) through the engine on both
    implementations: ids, the decode pass records, the engine's own
    account of its cache."""
    c = FAMILIES[request.param][1]()
    params = deepseek_init(jax.random.key(3), c)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(3, 200, size=n)) for n in (30, 7, 45, 12)]
    out = {}
    for impl in ("xla", "interpret"):
        eng = deepseek_engine(params, c, EngineConfig(
            paged_attention=impl, **ENGINE))
        eng.start()
        sp = SamplingParams(temperature=0.0, max_new_tokens=9)
        reqs = [eng.submit(p, sp) for p in prompts]
        deadline = time.time() + 240
        while time.time() < deadline and any(
                r.finished_at is None and r.error is None for r in reqs):
            time.sleep(0.005)
        eng.stop()
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        out[impl] = {
            "ids": [r.generated for r in reqs],
            "decode": [p for p in eng.recorder.log.passes
                       if p["kind"] == "decode"],
            "stats": dict(eng.stats), "kv_bytes": eng._kv_bytes_total,
            "pools": (eng.k_cache.shape, eng.v_cache.shape),
            "impl": eng.paged_attention_impl}
    return c, out


def test_engine_ids_equal_on_kernel_and_xla(served):
    _, out = served
    assert out["xla"]["ids"] == out["interpret"]["ids"]
    assert all(len(ids) == 9 for ids in out["xla"]["ids"])
    assert out["interpret"]["impl"] == "interpret"
    assert out["interpret"]["stats"]["prefill_calls"] > 0


def test_engine_accounts_one_vector_a_token(served):
    c, out = served
    k_shape, v_shape = out["xla"]["pools"]
    assert k_shape == (3, 1, 24, 8, latent_row_width(c))
    assert v_shape[-1] == 0
    assert out["xla"]["kv_bytes"] == int(np.prod(k_shape)) * 4


def test_decode_pass_record_carries_the_routing_facts(served):
    c, out = served
    for rec in out["interpret"]["decode"]:
        steps = rec["steps"]
        per_step = ENGINE["max_batch"] * c.num_experts_per_tok \
            * c.n_moe_layers
        assert rec["assignments"] == steps * per_step
        # an expert layer-step touches between 1 and min(E, rows * k)
        assert steps * c.n_moe_layers <= rec["experts_touched"] \
            <= steps * c.n_moe_layers * min(
                c.n_routed_experts,
                ENGINE["max_batch"] * c.num_experts_per_tok)
        assert rec["kv_row_bytes"] == latent_row_bytes(c)[1]


def test_decode_pass_record_carries_the_stream_facts(served):
    """With ``hc_mult`` a decode pass names its residual streams and
    the largest row error any stream mix of the pass left; the plain
    residual carries neither."""
    c, out = served
    for impl in ("xla", "interpret"):
        assert out[impl]["decode"]
        for rec in out[impl]["decode"]:
            if c.hc_mult is None:
                assert "streams" not in rec and "mhc_row_err" not in rec
            else:
                assert rec["streams"] == c.hc_mult
                # the small config's 4 rounds leave a positive residue,
                # not nought and not a row's own size
                assert 0.0 < rec["mhc_row_err"] < 0.5


def test_the_builder_names_the_facts_and_the_engine_none():
    """The step's counters reach the pass record under the names and
    through the reducers the family hands over, in the vector's order;
    the engine's own code names no counter."""
    plain = deepseek.step_fact_readers(DeepseekConfig.tiny())
    mhc = deepseek.step_fact_readers(DeepseekConfig.tiny_mhc())
    assert list(plain) == ["experts_touched", "assignments"]
    assert list(mhc) == [*plain, "streams", "mhc_row_err"]
    assert plain["experts_touched"](np.array([3, 5, 4], np.int32)) == 12
    assert mhc["streams"](np.array([4, 4, 4], np.int32)) == 4
    # a pass's largest row error: float32 bits ride the int32 column
    bits = np.array([0.25, 0.5, 0.125], np.float32).view(np.int32)
    assert mhc["mhc_row_err"](bits) == 0.5
    import inspect
    from gofr_tpu.serving import engine
    source = inspect.getsource(engine)
    assert not any(f'"{name}"' in source for name in mhc)


def test_llama_pass_records_are_as_they_were():
    from gofr_tpu.serving.glue import demo_llama_engine
    eng = demo_llama_engine(EngineConfig(
        max_batch=2, max_seq=64, kv_layout="paged",
        paged_attention="interpret", page_size=8, seed=1))
    eng.start()
    req = eng.submit([5, 6, 7], SamplingParams(temperature=0.0,
                                               max_new_tokens=9))
    deadline = time.time() + 120
    while time.time() < deadline and req.finished_at is None:
        time.sleep(0.005)
    eng.stop()
    decode = [p for p in eng.recorder.log.passes if p["kind"] == "decode"]
    assert decode and len(req.generated) == 9
    assert not any(k in p for p in decode
                   for k in ("experts_touched", "assignments",
                             "kv_row_bytes"))


@pytest.mark.parametrize("kw,names", [
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(speculative=True), "speculative"),
    (dict(kv_layout="slot"), "removed in PR 30"),
    (dict(paged_attention="view"), "view"),
    (dict(mesh=object()), "mesh")])
def test_unsupported_combinations_are_refused_at_build(kw, names):
    c = DeepseekConfig.tiny()
    params = jax.eval_shape(lambda: deepseek_init(jax.random.key(0), c))
    mesh = kw.pop("mesh", None)
    with pytest.raises(ValueError, match=names):
        deepseek_engine(params, c, EngineConfig(**{**ENGINE, **kw}),
                        mesh=mesh)


YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096}


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("n_group", 8), ("rope_interleave", False),
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("rope_scaling", {"type": "yarn"}),              # lacks its sizes
    ("rope_scaling", {**YARN, "mscale": 0.5}),       # cos/sin scaled
    ("hc_mult", 1), ("hc_mult", 2.0), ("q_lora_rank", 0)])
def test_config_refuses_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=key):
        DeepseekConfig(**{key: value})


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", YARN), ("hc_mult", 4),
    ("hc_mult", None)])
def test_config_accepts_what_is_implemented(key, value):
    assert getattr(DeepseekConfig(**{key: value}), key) == value


def test_yarn_frequencies_and_scale_are_the_published_formulas():
    """Xing4.0's rope: 64 lanes, theta 1e4, factor 64 over 4,096. The
    correction dims are 64 ln(4096 / (32 x 2 pi)) / (2 ln 1e4) = 10.47
    -> 10 and 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23: pairs 0
    to 10 keep 1e4^(-i/32), pairs 23 to 31 have it over 64, and pair i
    between them blends by (i - 10) / 13. The softmax scale is
    192^-1/2 x (0.1 ln 64 + 1)^2."""
    c = DeepseekConfig(rope_theta=10000.0, rope_scaling=YARN)
    got = np.asarray(c.rope_inv_freq, np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0.0, 1.0)
    np.testing.assert_allclose(got, plain / 64 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(got[16], plain[16] * (7 / 13 + 6 / 13 / 64),
                               rtol=1e-6)
    assert c.softmax_scale == pytest.approx(0.07216878 * 1.4158883 ** 2,
                                            rel=1e-6)
    assert c.softmax_scale == pytest.approx(0.1446788, rel=1e-5)
    # no scaling: the plain frequencies and the plain scale, as before
    plain_c = DeepseekConfig(rope_theta=10000.0)
    np.testing.assert_array_equal(
        np.asarray(plain_c.rope_inv_freq),
        np.asarray(rope_frequencies(64, 10000.0)))
    assert plain_c.softmax_scale == 192 ** -0.5


def test_reference_head_in_blocks_equals_the_whole_head():
    """The xing4_0 reference multiplies by the head V_BLOCK columns at
    a time (a float32 copy of 131,072 columns would be 1.9 GB); the
    tiny vocabulary does not divide, so the blocks are tested here."""
    ref = load_reference("xing4_mhc_mla_moe")
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], (5, 32), jnp.float32)
    head = jax.random.normal(ks[1], (32, 256), jnp.float32)
    for low in (None, "int8"):
        whole = np.asarray(ref._head(x, head, low))     # 256 % 16384
        ref.V_BLOCK = 64
        try:
            blocks = np.asarray(ref._head(x, head, low))
        finally:
            ref.V_BLOCK = 16384
        # blocks of other widths sum in another order
        np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- capacity maths

@pytest.mark.parametrize("config,needed,stored", [
    ("kanana-2-30b-a3b-6l", 6912, 7680), ("xing4-29b-a4b-8l", 9216, 10240),
    ("smollm2-1.7b", 196608, 196608),
    ("mistral-7b-16l", 65536, 65536)])
def test_capacity_tool_reads_the_row_from_the_model(config, needed, stored):
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "capacity.py"), "--kv-row",
         str(REPO / "benchmarks" / "configs" / f"{config}.json")],
        capture_output=True, text=True, timeout=300, check=True)
    report = json.loads(proc.stdout)
    assert report["needed_bytes_per_token"] == needed
    assert report["stored_bytes_per_token"] == stored
    assert report["pool_bytes"] == report["pool_tokens"] * stored
