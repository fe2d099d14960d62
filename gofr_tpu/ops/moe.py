"""Mixture-of-experts ops: top-k routing + gated expert MLP.

The dense formulation here computes every expert for every token and
combines with routing weights — correct, static-shaped, and the
building block the EP-sharded path reuses: with experts sharded over a
mesh axis, each device computes only its expert slice of the same
einsums and the combine is a ``psum`` (see gofr_tpu/parallel).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def top_k_routing(gate_logits: jnp.ndarray, k: int,
                  renormalize: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Route tokens: [T, E] logits -> (weights [T, k], indices [T, k])."""
    values, indices = jax.lax.top_k(gate_logits, k)
    if renormalize:
        weights = jax.nn.softmax(values.astype(jnp.float32), axis=-1)
    else:
        weights = jax.nn.softmax(
            gate_logits.astype(jnp.float32), axis=-1)
        weights = jnp.take_along_axis(weights, indices, axis=-1)
    return weights, indices


def moe_layer(x: jnp.ndarray, gate_w: jnp.ndarray, w1: jnp.ndarray,
              w3: jnp.ndarray, w2: jnp.ndarray, *, num_selected: int = 2
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mixtral-style sparse MLP.

    x [T, Dm]; gate_w [Dm, E]; w1,w3 [E, Dm, F]; w2 [E, F, Dm].
    Returns (output [T, Dm], router_logits [T, E] for aux loss).
    """
    dtype = x.dtype
    gate_logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # [T, E]
    weights, indices = top_k_routing(gate_logits, num_selected)

    # combine[t, e] = routing weight of expert e for token t (0 if unrouted)
    num_experts = gate_w.shape[-1]
    onehot = jax.nn.one_hot(indices, num_experts, dtype=jnp.float32)  # [T,k,E]
    combine = jnp.einsum("tk,tke->te", weights, onehot)  # [T, E]

    xf = x.astype(jnp.float32)
    up = jnp.einsum("td,edf->tef", xf, w1.astype(jnp.float32))
    gate = jnp.einsum("td,edf->tef", xf, w3.astype(jnp.float32))
    hidden = jax.nn.silu(up) * gate
    expert_out = jnp.einsum("tef,efd->ted", hidden, w2.astype(jnp.float32))
    out = jnp.einsum("te,ted->td", combine, expert_out)
    return out.astype(dtype), gate_logits


def load_balancing_loss(router_logits: jnp.ndarray, num_selected: int) -> jnp.ndarray:
    """Switch-style aux loss: E * sum_e (fraction_tokens_e * mean_prob_e)."""
    num_experts = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    _, indices = jax.lax.top_k(router_logits, num_selected)
    counts = jax.nn.one_hot(indices, num_experts).sum(axis=(-3, -2))
    fraction = counts / jnp.maximum(counts.sum(), 1.0)
    mean_prob = probs.mean(axis=tuple(range(probs.ndim - 1)))
    return num_experts * jnp.sum(fraction * mean_prob)


# ------------------------------------------------------- sparse experts
#
# The ``deepseek_v3`` family's expert layer (Kanana-2, DeepSeek-V3):
# sigmoid scores, a bias that moves the SELECTION only, normalised
# top-k weights times a route scale, and the experts computed sparsely
# — tokens sorted by expert, one grouped matmul per projection over the
# group sizes, so an expert that received nothing costs nothing.
# ``moe_layer`` above stays the dense all-experts form (Mixtral-style
# softmax routing; what ``moe_engine`` and the EP-sharded path use).

def sigmoid_routing(h: jnp.ndarray, gate_w: jnp.ndarray,
                    bias: jnp.ndarray, k: int, *,
                    route_scale: float = 1.0, normalize: bool = True
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Route tokens the ``noaux_tc`` way: h [T, D], gate_w [D, E],
    bias [E] -> (weights [T, k] float32, indices [T, k]).

    Scores are ``sigmoid(h @ gate_w)`` in float32 from the hidden state
    (as published, whatever the activations' dtype); the top-k is taken
    over ``scores + bias`` but the weights are the scores WITHOUT the
    bias, divided by their sum (+1e-20) when ``normalize``, times
    ``route_scale``. With one expert group (``n_group`` 1,
    ``topk_group`` 1) the published group step selects the one group
    there is and is not computed."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision="highest"))
    _, indices = jax.lax.top_k(
        scores + bias.astype(jnp.float32).reshape(1, -1), k)
    weights = jnp.take_along_axis(scores, indices, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * route_scale, indices


def sparse_experts(h: jnp.ndarray, weights: jnp.ndarray,
                   indices: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                   w2: jnp.ndarray, *, layer=None
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """sum_k weights[t, k] * SwiGLU_{indices[t, k]}(h[t]), computed only
    for the (token, expert) pairs routed: h [T, D]; weights, indices
    [T, k]; w1, w3 [E, D, F]; w2 [E, F, D]. Returns (out [T, D] in h's
    dtype, group_sizes [E] — tokens each expert received).

    The T*k assignments are sorted by expert (stable), their rows
    gathered, and the three projections run as grouped matmuls
    (``jax.lax.ragged_dot``: XLA's own, a Mosaic kernel on the TPU) over
    ``group_sizes``; the outputs go back to token order by the inverse
    permutation — a gather, not a scatter-add — and are combined in
    float32.

    ``layer`` (a traced index) takes the weights STACKED over layers,
    [L, E, D, F] / [L, E, F, D] — what a model's layer scan holds. A
    layer's slice handed to the grouped matmul is materialised (a custom
    call's operand is a buffer of its own: three copies of every expert
    of the layer, 1.2 GB a layer-step at Kanana-2's widths, in the v5e's
    compiled decode program), so the stack goes in whole, viewed as
    L * E groups of which only this layer's E have any rows: an empty
    group costs the grouped matmul nothing."""
    t, k = indices.shape
    n_experts = w1.shape[-3]
    flat = indices.reshape(-1)
    order = jnp.argsort(flat, stable=True)                  # [T*k]
    group_sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    sizes = group_sizes
    if layer is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(w1.shape[0] * n_experts, jnp.int32), group_sizes,
            (layer * n_experts,))
        w1, w3, w2 = (w.reshape(-1, *w.shape[2:]) for w in (w1, w3, w2))
    xs = jnp.take(h, order // k, axis=0)                    # [T*k, D]
    gate = jax.lax.ragged_dot(xs, w1, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w3, sizes,
                            preferred_element_type=jnp.float32)
    ys = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(h.dtype), w2,
                            sizes, preferred_element_type=jnp.float32)
    back = jnp.argsort(order)                               # inverse
    y = jnp.take(ys, back, axis=0).reshape(t, k, -1)
    out = jnp.sum(y * weights.astype(jnp.float32)[:, :, None], axis=1)
    return out.astype(h.dtype), group_sizes


def swiglu(h: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
           w2: jnp.ndarray) -> jnp.ndarray:
    """Plain SwiGLU, gate in float32: the shared expert (every token
    passes through it) and the leading dense layers."""
    gate = jax.nn.silu(jnp.matmul(
        h, w1, preferred_element_type=jnp.float32))
    up = jnp.matmul(h, w3, preferred_element_type=jnp.float32)
    return jnp.matmul((gate * up).astype(h.dtype), w2)
