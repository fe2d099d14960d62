"""Plain reference for the dense Llama/Mistral decoder block, and the
weights both sides are given.

Nothing here imports the program. The forward pass is the published
architecture in straightforward ``jax.numpy``: RMSNorm, rotary
embeddings in the rotate-half convention, grouped-query causal
attention, SwiGLU, a tied or untied output head — float32 throughout
with ``precision="highest"`` (on a TPU a float32 matmul otherwise runs
in bf16 passes). No cache, no batching, no kernels: one sequence, all
positions at once, attention in blocks of query rows so that a
6k-token sequence fits beside the weights.

``precision="int8"`` is the control of the output check: the same
forward pass with every matmul operand (weights per output channel,
activations per row) and every K/V row rounded to int8 codes, the
nearest precision below the bf16 the configurations state.

The weights are the benchmark's own: one jitted call from the seed, on
the device, in bf16, in the tree the program's engine builder takes
(``embed``, ``layers`` stacked on a leading depth axis, ``final_norm``,
``lm_head`` when untied). The program makes none of them.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512          # query rows per attention block


def sizes(cfg: dict) -> dict:
    """The published sizes, by short names."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"V": cfg["vocab_size"], "D": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"], "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": hd,
            "F": cfg["intermediate_size"],
            "tied": bool(cfg["tie_word_embeddings"])}


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded random bf16 weights, made on the default device in one
    jitted call: normal(0, fan_in**-0.5) matrices, unit norms, an
    embedding of standard deviation 0.02."""
    s = sizes(cfg)
    # any whole number up to a little over 2**31: fold the high bits in
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 9)

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(jnp.bfloat16)

        L, D, F = s["L"], s["D"], s["F"]
        q, kv = s["Hq"] * s["hd"], s["Hkv"] * s["hd"]
        params = {
            "embed": (jax.random.normal(ks[0], (s["V"], D), jnp.float32)
                      * 0.02).astype(jnp.bfloat16),
            "layers": {
                "attn_norm": jnp.ones((L, D), jnp.bfloat16),
                "wq": dense(ks[1], (L, D, q), D),
                "wk": dense(ks[2], (L, D, kv), D),
                "wv": dense(ks[3], (L, D, kv), D),
                "wo": dense(ks[4], (L, q, D), q),
                "ffn_norm": jnp.ones((L, D), jnp.bfloat16),
                "w1": dense(ks[5], (L, D, F), D),
                "w3": dense(ks[6], (L, D, F), D),
                "w2": dense(ks[7], (L, F, D), F),
            },
            "final_norm": jnp.ones((D,), jnp.bfloat16),
        }
        if not s["tied"]:
            params["lm_head"] = dense(ks[8], (D, s["V"]), D)
        return params

    return make(key)


def _round_int8(x, axis):
    """Symmetric int8 codes along ``axis`` and back: what an int8
    matmul operand or an int8 cache row holds."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _matmul(x, w, int8):
    w = w.astype(jnp.float32)
    if int8:  # activations per row, weights per output channel
        x, w = _round_int8(x, -1), _round_int8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)[None, :]


def _rope(x, theta):
    """x [S, H, hd], positions 0..S-1, rotate-half convention."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention, q [S, Hq, hd], k/v [S, Hkv, hd],
    in blocks of Q_BLOCK query rows (S is a multiple of it)."""
    s, hq, hd = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    cols = jnp.arange(s)[None, None, :]

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision="highest") * hd ** -0.5
        rows = (start + jnp.arange(Q_BLOCK))[None, :, None]
        scores = jnp.where(cols <= rows, scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                          precision="highest")

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, hq, hd)


@partial(jax.jit, static_argnames=("shape", "theta", "eps", "tied", "int8"))
def _forward(params, tokens, read_pos, *, shape, theta, eps, tied, int8):
    hq, hkv, hd = shape
    x = params["embed"].astype(jnp.float32)[tokens]          # [S, D]
    s = x.shape[0]

    def layer(x, lp):
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = _rope(_matmul(h, lp["wq"], int8).reshape(s, hq, hd), theta)
        k = _rope(_matmul(h, lp["wk"], int8).reshape(s, hkv, hd), theta)
        v = _matmul(h, lp["wv"], int8).reshape(s, hkv, hd)
        if int8:  # an int8 cache: one scale per row and kv head
            k, v = _round_int8(k, -1), _round_int8(v, -1)
        x = x + _matmul(_attention(q, k, v).reshape(s, hq * hd),
                        lp["wo"], int8)
        h = _rms_norm(x, lp["ffn_norm"], eps)
        gate = jax.nn.silu(_matmul(h, lp["w1"], int8))
        return x + _matmul(gate * _matmul(h, lp["w3"], int8),
                           lp["w2"], int8), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms_norm(x[read_pos], params["final_norm"], eps)     # [R, D]
    head = params["embed"].T if tied else params["lm_head"]
    return _matmul(x, head, int8)                             # [R, V]


def forward_logits(cfg: dict, params: dict, tokens, read_pos, *,
                   precision: str = "float32"):
    """Logits [R, V] (float32) at positions ``read_pos`` of one sequence
    ``tokens`` [S]; S must be a multiple of Q_BLOCK (pad on the right:
    attention is causal, so padding never reaches a read position).
    ``precision``: "float32" (the reference) or "int8" (the control)."""
    if precision not in ("float32", "int8"):
        raise ValueError(f"precision {precision!r}")
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"sequence length {len(tokens)} is not a "
                         f"multiple of {Q_BLOCK}")
    s = sizes(cfg)
    return _forward(params, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(read_pos, jnp.int32),
                    shape=(s["Hq"], s["Hkv"], s["hd"]),
                    theta=float(cfg["rope_theta"]),
                    eps=float(cfg["rms_norm_eps"]), tied=s["tied"],
                    int8=precision == "int8")
