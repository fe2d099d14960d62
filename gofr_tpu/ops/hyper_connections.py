"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606): the residual path as ``n`` parallel
streams, mixed per token around every sublayer.

For one token with streams X in R^{n x C} and a sublayer F (its own
pre-norm inside), ``x + F(norm(x))`` becomes

    x~      = RMSNorm(vec(X))                 (nC wide, no gain, eps)
    H~_pre  = a_pre  * (x~ phi_pre)  + b_pre   in R^n
    H~_post = a_post * (x~ phi_post) + b_post  in R^n
    H~_res  = a_res  * mat(x~ phi_res) + b_res in R^{n x n}
    H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
    H_res = Sinkhorn(H~_res):  M = exp(clip(H~_res, lo, hi)), then
            ``iters`` times: each row over its sum, each column over its
            sum (doubly stochastic in the limit: the manifold)
    X' = H_res X + H_post^T F(H_pre X)

so F sees ONE C-wide vector (the read-in) and writes to every stream
(the write-out), and the streams are mixed by a doubly stochastic
matrix, which keeps their sum — the signal a plain residual carries.

Layout, and why. The streams are ``[n, B, S, C]`` — the stream index
LEADS: a bf16 ``[.., 4, C]`` array would be tiled (16, 128) over its
last two dimensions on the TPU and stored in four times its bytes, and
a stream ``X[i]`` is then a contiguous slab. The three projections are
one matrix ``phi [n, C, n + n + n*n]`` (columns pre ‖ post ‖ res,
``phi[i]`` the rows that multiply stream i), ``alpha [3]`` and ``bias
[n + n + n*n]`` beside it. The mappings are computed with the token
index LAST — ``[n, T]`` and ``[n, n, T]`` — and every sum over a stream
index is written out as adds of slabs, not as a ``reduce``: the
Sinkhorn chain is then elementwise on token-wide arrays and XLA may
fuse it (:func:`sinkhorn`); a ``[T, 4, 4]`` array would waste a
(8, 128) tile a token and a ``reduce`` ends a fusion 40 times a
sublayer.

``x~ phi`` is computed as ``rsqrt(mean(X^2) + eps) * sum_i X_i phi_i``:
the norm has no gain, so the per-token factor leaves the matrix product
(the same sum, one pass over the streams fewer). Coefficients are
float32 from the streams as stored; the mixes accumulate in float32 and
are stored in the streams' dtype.
"""

from __future__ import annotations

from functools import reduce
from operator import add
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Mappings(NamedTuple):
    """One sublayer's coefficients for T = B*S tokens, float32."""
    pre: jnp.ndarray        # [n, T]     H_pre
    post: jnp.ndarray       # [n, T]     H_post
    res: jnp.ndarray        # [n, n, T]  H_res[m, j]: stream j into m
    row_err: jnp.ndarray    # []         max |row sum - 1| of any H_res


def _total(parts):
    """Sum of a sequence of equal-shaped arrays as adds (no reduce)."""
    return reduce(add, parts)


def sinkhorn(logits: jnp.ndarray, iters: int, lo: float, hi: float
             ) -> jnp.ndarray:
    """Sinkhorn-Knopp on ``logits [n, n, ...]`` (row index, column
    index, then any token axes): exp of the clipped logits, then
    ``iters`` times rows-then-columns. Unrolled (``iters`` is static)
    over the n x n ENTRIES as separate token-wide arrays, so that a
    round is adds and divides of equal shapes and nothing else: written
    on the stacked ``[n, n, T]`` array, with a slice a sum and a
    broadcast a divide, the TPU compiler made 80-100 fusions of 20
    rounds; so, 30."""
    n = logits.shape[0]
    m = jnp.exp(jnp.clip(logits.astype(jnp.float32), lo, hi))
    m = [[m[i, j] for j in range(n)] for i in range(n)]
    for _ in range(iters):
        rows = [_total(m[i]) for i in range(n)]
        m = [[m[i][j] / rows[i] for j in range(n)] for i in range(n)]
        cols = [_total([m[i][j] for i in range(n)]) for j in range(n)]
        m = [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]
    return jnp.stack([jnp.stack(row) for row in m])


def row_error(res: jnp.ndarray) -> jnp.ndarray:
    """max |row sum - 1| over every matrix of ``res [n, n, ...]``: what
    the last column step leaves (columns sum to one by construction)."""
    rows = _total([res[:, j] for j in range(res.shape[0])])
    return jnp.max(jnp.abs(rows - 1.0))


def mhc_mappings(streams: jnp.ndarray, phi: jnp.ndarray, alpha: jnp.ndarray,
                 bias: jnp.ndarray, *, iters: int, eps: float,
                 clamp: tuple[float, float]) -> Mappings:
    """streams [n, B, S, C] -> the sublayer's three mappings."""
    n, b, s, c = streams.shape
    x = streams.reshape(n, b * s, c)
    sq = _total([jnp.sum(jnp.square(x[i].astype(jnp.float32)), -1)
                 for i in range(n)])                            # [T]
    raw = _total([jnp.matmul(x[i], phi[i], precision="highest",
                             preferred_element_type=jnp.float32)
                  for i in range(n)])                           # [T, K]
    dyn = (raw * jax.lax.rsqrt(sq / (n * c) + eps)[:, None]).T  # [K, T]
    alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * dyn[:n] + bias[:n, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * dyn[n:2 * n]
                                + bias[n:2 * n, None])
    res = sinkhorn((alpha[2] * dyn[2 * n:]
                    + bias[2 * n:, None]).reshape(n, n, b * s),
                   iters, *clamp)
    return Mappings(pre, post, res, row_error(res))


def read_in(streams: jnp.ndarray, pre: jnp.ndarray) -> jnp.ndarray:
    """H_pre X: streams [n, B, S, C], pre [n, T] -> [B, S, C], the one
    vector a token the sublayer sees."""
    n, b, s, _ = streams.shape
    w = pre.reshape(n, b, s, 1)
    return _total([w[i] * streams[i].astype(jnp.float32)
                   for i in range(n)]).astype(streams.dtype)


def write_out(streams: jnp.ndarray, out: jnp.ndarray, maps: Mappings
              ) -> jnp.ndarray:
    """H_res X + H_post^T F: the new streams [n, B, S, C]."""
    n, b, s, _ = streams.shape
    res = maps.res.reshape(n, n, b, s, 1)
    post = maps.post.reshape(n, b, s, 1)
    x = [streams[j].astype(jnp.float32) for j in range(n)]
    y = out.astype(jnp.float32)
    return jnp.stack([
        _total([res[m, j] * x[j] for j in range(n)]) + post[m] * y
        for m in range(n)]).astype(streams.dtype)
