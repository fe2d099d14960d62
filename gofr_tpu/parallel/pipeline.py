"""Pipeline-parallel (GPipe) training step via shard_map + ppermute.

Layer stages live on the ``pp`` mesh axis (the stacked ``[L, ...]``
weights shard their leading axis, sharding.py), activations flow
stage-to-stage over ICI with ``lax.ppermute``, microbatches fill the
pipeline GPipe-style: with ``P`` stages and ``M`` microbatches the loop
runs ``M + P - 1`` ticks and every stage is busy in the steady state.
Data parallel composes manually inside the same shard_map (gradient
psum over ``dp``).

Differentiating straight through the shard_map gives the backward
pipeline for free (jax ADs ppermute into the reverse permute).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig, _attn_block, _logits, _mlp_block
from ..ops.rope import rope_frequencies
from .mesh import mesh_axes
from .train import TrainState, cross_entropy_loss, default_optimizer


def _stage_forward(x, layers_local, c: LlamaConfig, inv_freq, positions):
    """Run this stage's slice of layers over activations x [mb, S, D].

    Reuses the dense path's block math (models/llama.py) so pipeline
    stages can never drift from single-chip semantics."""

    def layer_fn(x, lp):
        out, _k, _v = _attn_block(x, lp, c, inv_freq, positions, None, "xla")
        x = x + out
        return x + _mlp_block(x, lp, c), None

    x, _ = jax.lax.scan(layer_fn, x, layers_local)
    return x


def make_pipeline_train_step(config: LlamaConfig, mesh: Mesh, *,
                             optimizer: optax.GradientTransformation | None = None,
                             num_microbatches: int | None = None,
                             donate: bool = True) -> Callable:
    """GPipe train step for a ('dp','pp') mesh.

    Batch layout: tokens/targets/mask [M, mb, S] where M = microbatches
    (defaults to the pp size) and mb is the per-dp-shard microbatch.
    """
    axes = mesh_axes(mesh)
    pp = axes.get("pp", 1)
    if axes.get("tp", 1) != 1:
        raise ValueError("pipeline step composes with dp only; use the "
                         "dense GSPMD step for tp/sp meshes")
    M = num_microbatches or pp
    if M < pp:
        raise ValueError(f"need at least {pp} microbatches to fill the pipe")
    optimizer = optimizer or default_optimizer()
    c = config

    def pipe_loss(params, tokens, targets, mask):
        """Runs per (dp, pp) shard: tokens [M, mb, S] local to this dp shard."""
        stage = jax.lax.axis_index("pp")
        inv_freq = rope_frequencies(c.head_dim, c.rope_theta, c.rope_scaling)
        mb, s = tokens.shape[1], tokens.shape[2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (mb, s))
        layers_local = params["layers"]

        def embed(tok):
            return params["embed"][tok]

        def head_loss(x, tgt, msk):
            logits = _logits(params, c, x)
            nll = -jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), tgt[..., None],
                axis=-1)[..., 0]
            mskf = msk.astype(jnp.float32)
            return (nll * mskf).sum(), mskf.sum()

        carry = jnp.zeros((mb, s, c.dim), c.dtype)
        loss_sum = jnp.zeros((), jnp.float32)
        count_sum = jnp.zeros((), jnp.float32)
        # M + pp - 1 pipeline ticks (python loop: static unroll)
        for t in range(M + pp - 1):
            if t < M:
                injected = embed(tokens[t])
                x_in = jnp.where(stage == 0, injected, carry)
            else:
                x_in = carry
            y = _stage_forward(x_in, layers_local, c, inv_freq, positions)
            out_idx = t - (pp - 1)
            if 0 <= out_idx < M:
                l, n = head_loss(y, targets[out_idx], mask[out_idx])
                is_last = (stage == pp - 1).astype(jnp.float32)
                loss_sum = loss_sum + l * is_last
                count_sum = count_sum + n * is_last
            if pp > 1:
                carry = jax.lax.ppermute(
                    y, "pp", [(i, i + 1) for i in range(pp - 1)])
            else:
                carry = y
        # aggregate over the pipeline (only last stage contributed) and dp
        loss_sum = jax.lax.psum(loss_sum, ("pp", "dp"))
        count_sum = jax.lax.psum(count_sum, ("pp", "dp"))
        return loss_sum / jnp.maximum(count_sum, 1.0)

    # param specs inside shard_map: layers manual over pp, rest replicated.
    # norms are [L, D] -> P('pp', None); weights [L, A, B] -> P('pp', None, None)
    layers_spec = {
        k: (P("pp", None) if k.endswith("norm") else P("pp", None, None))
        for k in ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm",
                  "w1", "w3", "w2")}
    param_specs: dict[str, Any] = {"embed": P(), "layers": layers_spec,
                                   "final_norm": P()}
    if not c.tie_embeddings:
        param_specs["lm_head"] = P()
    batch_spec = P(None, "dp", None)  # [M, mb over dp, S]

    sharded_loss = jax.shard_map(
        pipe_loss, mesh=mesh,
        in_specs=(param_specs, batch_spec, batch_spec, batch_spec),
        out_specs=P(), check_vma=False)

    def train_step(state: TrainState, tokens, targets, mask):
        loss, grads = jax.value_and_grad(sharded_loss)(
            state.params, tokens, targets, mask)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), loss

    batch_sharding = NamedSharding(mesh, batch_spec)
    return jax.jit(
        train_step,
        in_shardings=(None, batch_sharding, batch_sharding, batch_sharding),
        donate_argnums=(0,) if donate else ())
