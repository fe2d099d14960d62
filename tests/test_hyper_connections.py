"""Manifold-constrained hyper-connections (``ops/hyper_connections.py``)
and the residual path of ``models/deepseek.py`` around them: the
Sinkhorn mix is doubly stochastic after the published 20 rounds and not
after 2, the clamp holds, the three mixes are the equations written out
with einsums, and ``hc_mult=None`` is the residual that stood — bit for
bit against what the parent commit produced.

Float32 on the CPU. ``MIX_TOL`` 1e-5 on values of order one: the module
sums streams as adds of slabs where the einsum here contracts, so the
two differ by the order of a four-term float32 sum.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import deepseek
from gofr_tpu.models.deepseek import (DeepseekConfig,
                                      deepseek_decode_step_paged,
                                      deepseek_init,
                                      deepseek_prefill_chunk_paged,
                                      deepseek_prefill_last,
                                      make_latent_cache)
from gofr_tpu.ops import hyper_connections as hc
from gofr_tpu.ops.paged_kv import (empty_pool, pool_from_cache_shape,
                                   scatter_chunk)

MIX_TOL = 1e-5
N, T = 4, 96


@pytest.fixture(scope="module")
def logits():
    """H~_res of T tokens, [n, n, T], of the order the seeded model's
    are: a unit dynamic term and a diagonal that leans to identity."""
    base = jax.random.normal(jax.random.key(0), (N, N, T), jnp.float32)
    return base + jnp.eye(N)[:, :, None]


def sums(m):
    m = np.asarray(m, np.float64)
    return np.abs(m.sum(1) - 1).max(), np.abs(m.sum(0) - 1).max()


@pytest.mark.parametrize("iters,doubly", [(20, True), (2, False)])
def test_sinkhorn_is_doubly_stochastic_after_twenty_rounds_not_two(
        logits, iters, doubly):
    """A shortened loop fails here: after 2 rounds the rows are still
    off by more than 1e-3 where 20 leave less than 1e-5."""
    m = hc.sinkhorn(logits, iters, -30.0, 30.0)
    rows, cols = sums(m)
    assert (np.asarray(m) > 0).all() and cols < 1e-5   # the last step
    assert (rows < 1e-5) == doubly
    assert doubly or rows > 1e-3
    assert float(hc.row_error(m)) == pytest.approx(rows, abs=1e-6)


def test_sinkhorn_normalises_rows_before_columns():
    """One round by hand: rows first, so the columns are the ones that
    sum to one exactly after it."""
    x = jax.random.normal(jax.random.key(1), (N, N, 3), jnp.float32)
    m = np.exp(np.asarray(x, np.float64))
    m = m / m.sum(1, keepdims=True)
    m = m / m.sum(0, keepdims=True)
    np.testing.assert_allclose(np.asarray(hc.sinkhorn(x, 1, -30.0, 30.0)),
                               m, rtol=1e-5)


@pytest.mark.parametrize("big", [1e4, 31.0])
def test_the_clamp_holds_at_thirty(big):
    """Logits beyond +-30 are those AT +-30: exp(1e4) would be inf and
    the mix NaN; the result is finite and equal to the clipped one."""
    x = jnp.zeros((N, N, 2)).at[0, 1, 0].set(big).at[2, 2, 1].set(-big)
    got = np.asarray(hc.sinkhorn(x, 20, -30.0, 30.0))
    want = np.asarray(hc.sinkhorn(jnp.clip(x, -30.0, 30.0), 20, -30.0, 30.0))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    # and a narrower clamp is a different matrix: the bounds are used
    assert not np.allclose(got, np.asarray(hc.sinkhorn(x, 20, -3.0, 3.0)))


@pytest.fixture(scope="module")
def sublayer():
    """Streams [n, B, S, C], one sublayer's phi / alpha / bias."""
    b, s, c = 2, 5, 16
    ks = jax.random.split(jax.random.key(2), 5)
    return (jax.random.normal(ks[0], (N, b, s, c), jnp.float32),
            jax.random.normal(ks[1], (N, c, 2 * N + N * N)) * (N * c) ** -0.5,
            1.0 + 0.1 * jax.random.normal(ks[2], (3,)),
            0.5 * jax.random.normal(ks[3], (2 * N + N * N,)),
            jax.random.normal(ks[4], (b, s, c), jnp.float32))


def test_mappings_and_mixes_are_the_equations(sublayer):
    """x~ = RMSNorm(vec(X)); H = f(alpha x~ phi + b); X' = H_res X +
    H_post^T F(H_pre X) — against the same written with einsums over a
    token-major [T, n, C] copy of the streams."""
    streams, phi, alpha, bias, out = sublayer
    n, b, s, c = streams.shape
    maps = hc.mhc_mappings(streams, phi, alpha, bias, iters=20, eps=1e-6,
                           clamp=(-30.0, 30.0))
    x = np.asarray(streams, np.float64).transpose(1, 2, 0, 3).reshape(
        b * s, n, c)
    flat = x.reshape(b * s, n * c)
    flat = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6)
    dyn = flat @ np.asarray(phi, np.float64).reshape(n * c, -1)
    al, bi = np.asarray(alpha, np.float64), np.asarray(bias, np.float64)
    sig = lambda z: 1 / (1 + np.exp(-z))     # noqa: E731
    pre = sig(al[0] * dyn[:, :n] + bi[:n])
    post = 2 * sig(al[1] * dyn[:, n:2 * n] + bi[n:2 * n])
    m = np.exp(np.clip(al[2] * dyn[:, 2 * n:] + bi[2 * n:], -30, 30)
               ).reshape(-1, n, n)
    for _ in range(20):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    np.testing.assert_allclose(np.asarray(maps.pre).T, pre, atol=MIX_TOL)
    np.testing.assert_allclose(np.asarray(maps.post).T, post, atol=MIX_TOL)
    np.testing.assert_allclose(np.asarray(maps.res).transpose(2, 0, 1), m,
                               atol=MIX_TOL)
    # F sees one C-wide vector a token
    seen = hc.read_in(streams, maps.pre)
    assert seen.shape == (b, s, c)
    np.testing.assert_allclose(
        np.asarray(seen).reshape(b * s, c),
        np.einsum("tn,tnc->tc", pre, x), atol=MIX_TOL)
    new = hc.write_out(streams, out, maps)
    f = np.asarray(out, np.float64).reshape(b * s, c)
    want = np.einsum("tmn,tnc->tmc", m, x) + post[:, :, None] * f[:, None]
    np.testing.assert_allclose(
        np.asarray(new).transpose(1, 2, 0, 3).reshape(b * s, n, c), want,
        atol=MIX_TOL)


def test_the_stream_mix_keeps_the_sum_of_the_streams(sublayer):
    """A doubly stochastic H_res carries the plain residual's signal:
    sum_m X'_m = sum_j X_j + (sum_m H_post_m) F."""
    streams, phi, alpha, bias, out = sublayer
    maps = hc.mhc_mappings(streams, phi, alpha, bias, iters=20, eps=1e-6,
                           clamp=(-30.0, 30.0))
    new = hc.write_out(streams, out, maps)
    n, b, s, _ = streams.shape
    gain = np.asarray(maps.post).sum(0).reshape(b, s, 1)
    np.testing.assert_allclose(
        np.asarray(new).sum(0),
        np.asarray(streams).sum(0) + gain * np.asarray(out), atol=1e-4)


def test_the_mappings_differ_token_by_token(sublayer):
    streams, phi, alpha, bias, _ = sublayer
    maps = hc.mhc_mappings(streams, phi, alpha, bias, iters=20, eps=1e-6,
                           clamp=(-30.0, 30.0))
    assert np.asarray(maps.res).std(-1).min() > 1e-3
    assert np.asarray(maps.pre).std(-1).min() > 1e-3


def test_streams_are_stored_in_their_dtype_coefficients_in_float32(sublayer):
    streams, phi, alpha, bias, out = sublayer
    low = streams.astype(jnp.bfloat16)
    maps = hc.mhc_mappings(low, phi.astype(jnp.bfloat16), alpha, bias,
                           iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert all(m.dtype == jnp.float32 for m in maps)
    assert hc.read_in(low, maps.pre).dtype == jnp.bfloat16
    assert hc.write_out(low, out.astype(jnp.bfloat16),
                        maps).dtype == jnp.bfloat16


# ------------------------------------------------ the residual that stood

#: what the parent commit (PR 31) produced on ``DeepseekConfig.tiny()``
#: with ``deepseek_init(key(3))``: the first four logits and the sum of
#: all 256, as float32 bytes — bucket prefill, one decode step, one
#: chunk with history (``_plain_outputs`` below is the recipe).
PARENT = {"prefill": ("4cde3a3efabcb0bcd5e394be9b76bf3f", "d7e58641"),
          "decode": ("b1b0203fc4d317be9cb7a2bb7de22840", "c379c041"),
          "chunk": ("296f363e1f0aa43e0f524cbea15cc43f", "481b3141")}


@pytest.fixture(scope="module")
def plain_outputs():
    c = DeepseekConfig.tiny()
    p = deepseek_init(jax.random.key(3), c)
    toks = (jnp.arange(16) * 7 % 256)[None]
    out = {}
    out["prefill"], (k, v) = deepseek_prefill_last(
        p, toks, c, kv_lengths=jnp.array([16]))
    pools = [empty_pool(pool_from_cache_shape(x), 32, False)
             for x in make_latent_cache(c, 1, 8)]
    tables = jnp.arange(16, dtype=jnp.int32)[None, :] + 3
    pool = scatter_chunk(pools[0], tables, k, jnp.zeros(1, jnp.int32),
                         jnp.array([16]))
    out["decode"], pool, _, facts = deepseek_decode_step_paged(
        p, jnp.array([5]), pool, pools[1], tables, jnp.array([16]), c,
        implementation="xla")
    out["chunk"], pool, _ = deepseek_prefill_chunk_paged(
        p, toks[:, :8] + 1, pool, pools[1], tables, jnp.array([17]),
        jnp.array([8]), c, implementation="xla")
    return out, facts


@pytest.mark.parametrize("step", ["prefill", "decode", "chunk"])
def test_no_streams_is_the_residual_that_stood_bit_for_bit(plain_outputs,
                                                           step):
    logits = np.asarray(plain_outputs[0][step], np.float32)[0]
    first, total = PARENT[step]
    assert logits[:4].tobytes().hex() == first
    assert np.float32(logits.sum()).tobytes().hex() == total


def test_no_streams_no_stream_facts(plain_outputs):
    assert plain_outputs[1].shape == (2,)
    x = jnp.ones((1, 2, 8))
    got, aux, err = deepseek._around(
        x, {}, "attn", lambda h: (2 * h, "aux"), DeepseekConfig.tiny())
    assert err is None and aux == "aux"
    np.testing.assert_array_equal(np.asarray(got), 3 * np.ones((1, 2, 8)))


def test_streams_start_as_copies_and_end_summed():
    """With sublayers that write nothing the model is embed -> n copies
    -> mixes whose columns sum to one -> sum -> norm -> head: the
    logits of n x the embedding."""
    c = DeepseekConfig.tiny_mhc()
    p = deepseek_init(jax.random.key(4), c)
    for group in ("dense", "moe"):
        for k in ("wo", "w2", "s2"):
            if k in p[group]:
                p[group][k] = jnp.zeros_like(p[group][k])
    toks = jnp.arange(8)[None]
    got, _ = deepseek_prefill_last(p, toks, c, kv_lengths=jnp.array([8]))
    want = deepseek._logits(
        p, c, c.hc_mult * jnp.take(p["embed"], toks[:, -1], axis=0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_decode_facts_carry_streams_and_the_row_error():
    c = DeepseekConfig.tiny_mhc()
    p = deepseek_init(jax.random.key(5), c)
    pools = [empty_pool(pool_from_cache_shape(x), 8, False)
             for x in make_latent_cache(c, 1, 8)]
    tables = jnp.arange(4, dtype=jnp.int32)[None, :]
    step = jax.jit(lambda cfg_iters: deepseek_decode_step_paged(
        p, jnp.array([5]), pools[0], pools[1], tables, jnp.array([0]),
        dataclasses.replace(c, hc_sinkhorn_iters=cfg_iters),
        implementation="xla")[3], static_argnums=0)
    errs = {}
    for iters in (8, 2):    # (20 unrolled rounds compile slowly here)
        facts = np.asarray(step(iters))
        assert facts.shape == (4,) and facts.dtype == np.int32
        assert facts[2] == c.hc_mult
        errs[iters] = float(facts[3:].view(np.float32)[0])
    # a shortened loop shows in the counter the pass record carries
    assert 0 < errs[8] < errs[2] and errs[2] > 5 * errs[8]
