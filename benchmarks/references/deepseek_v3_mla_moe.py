"""Plain reference for the ``deepseek_v3`` decoder block — latent (MLA)
attention, one or more leading dense layers, then sigmoid-routed sparse
experts beside shared experts — and the weights both sides are given.

Nothing here imports the program. The forward pass is the published
architecture (the ``deepseek_v3`` modelling code that Kanana-2's
``config.json`` names as its ``model_type``) in straightforward
``jax.numpy``, float32 throughout with ``precision="highest"`` (on a
TPU a float32 matmul otherwise runs in bf16 passes). No cache, no
batching, no kernels, no sort and no grouped matmul: one sequence, all
positions at once; attention in the MATERIALISED form (every head's K
and V up-projected from the latent), in blocks of query rows so that a
17k-token sequence fits beside the weights; every expert is run over
every token and a token keeps the outputs of the six it chose (a mask).

The equations, for hidden state x of one token (h = RMSNorm(x)):

- q = W_q h, per head q_nope (128) ‖ q_pe (64); ``q_lora_rank`` is
  null, so queries are not compressed.
- [c_raw ‖ k_pe_raw] = W_kva h (512 + 64); c = RMSNorm(c_raw; g_kv);
  k_nope_head = W_uk[head] c (128), v_head = W_uv[head] c (128).
- RoPE (theta 1e6, no scaling) on q_pe of every head and on k_pe, ONE
  vector shared by all heads. ``rope_interleave`` is true: the rope
  lanes are stored as adjacent pairs (2i, 2i+1) and are brought to the
  half-split order [evens ‖ odds] before ``rotate_half``, as the
  published code does.
- k_head = k_nope_head ‖ k_pe; scores = q.k / sqrt(192), causal,
  softmax in float32; o_head = sum p v_head (128); x += W_o concat(o).
- Dense layers (the first ``first_k_dense_replace``): SwiGLU of width
  ``intermediate_size``.
- Expert layers: s = sigmoid(W_g h) in float32; chosen = top-k of
  (s + b), b the ``e_score_correction_bias``; w = s[chosen] — WITHOUT
  b — over (sum w + 1e-20), times ``routed_scaling_factor``;
  y = sum_k w_k E_k(h) + S(h), E an expert SwiGLU of width
  ``moe_intermediate_size``, S the shared SwiGLU of width
  ``n_shared_experts`` times that; x += y.
- Final RMSNorm, untied head.

Departures from the published description, each with its reason:

- ``n_group`` 1 and ``topk_group`` 1 make ``noaux_tc``'s group step
  select the one group there is: it is left out.
- ``kv_b_proj`` is one matrix [H x (128 + 128), 512] in the checkpoint;
  here its K half and V half are two matrices, ``w_uk`` and ``w_uv``
  [512, H x 128] — the same numbers, stored so that neither form of
  attention has to slice a weight.
- b is a buffer of the checkpoint; here it is seeded (normal, standard
  deviation ``ROUTER_BIAS_STD``) and non-zero, so that "select with the
  bias, weight without it" is exercised. The configuration's file
  lists it under ``assumed``.

``precision="int8"`` is the control of the output check: the same
forward pass with every matmul operand (weights per output channel,
activations per row) and every cached row (the 576 numbers c ‖ k_pe of
a token) rounded to int8 codes, the nearest precision below the bf16
the configuration states. The router's scores stay float32, as the
architecture states them.

The weights are the benchmark's own: one jitted call from the seed, on
the device, in bf16, in the tree the program's engine builder takes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512           # query rows per attention block
ROUTER_BIAS_STD = 0.02  # of the seeded e_score_correction_bias


def sizes(cfg: dict) -> dict:
    """The published sizes, by short names."""
    return {"V": cfg["vocab_size"], "D": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"],
            "Ld": cfg["first_k_dense_replace"],
            "H": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "C": cfg["kv_lora_rank"],
            "F": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def _check(cfg: dict) -> None:
    """What this reference implements, and nothing it would guess."""
    want = {"q_lora_rank": None, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "rope_scaling": None, "rope_interleave": True,
            "norm_topk_prob": True, "moe_layer_freq": 1,
            "tie_word_embeddings": False, "hidden_act": "silu",
            "attention_bias": False}
    for key, value in want.items():
        if cfg.get(key) != value:
            raise ValueError(f"deepseek_v3 reference: {key} = "
                             f"{cfg.get(key)!r}, implemented for {value!r}")


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded random bf16 weights, made on the default device in one
    jitted call: normal(0, fan_in**-0.5) matrices, unit norms, an
    embedding of standard deviation 0.02, the router's bias as above.
    ``dense`` stacks the leading dense layers, ``moe`` the expert
    layers, each with its own attention weights."""
    _check(cfg)
    s = sizes(cfg)
    # any whole number up to a little over 2**31: fold the high bits in
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)

    @jax.jit
    def make(key):
        D, H, C = s["D"], s["H"], s["C"]
        qk = s["nope"] + s["rope"]

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(jnp.bfloat16)

        def attn(k, n):
            ks = jax.random.split(k, 5)
            return {"attn_norm": jnp.ones((n, D), jnp.bfloat16),
                    "wq": dense(ks[0], (n, D, H * qk), D),
                    "wkva": dense(ks[1], (n, D, C + s["rope"]), D),
                    "kv_norm": jnp.ones((n, C), jnp.bfloat16),
                    "w_uk": dense(ks[2], (n, C, H * s["nope"]), C),
                    "w_uv": dense(ks[3], (n, C, H * s["vd"]), C),
                    "wo": dense(ks[4], (n, H * s["vd"], D), H * s["vd"]),
                    "ffn_norm": jnp.ones((n, D), jnp.bfloat16)}

        ks = jax.random.split(key, 16)
        Ld, Lm, E = s["Ld"], s["L"] - s["Ld"], s["E"]
        return {
            "embed": (jax.random.normal(ks[0], (s["V"], D), jnp.float32)
                      * 0.02).astype(jnp.bfloat16),
            "dense": {**attn(ks[1], Ld),
                      "w1": dense(ks[2], (Ld, D, s["F"]), D),
                      "w3": dense(ks[3], (Ld, D, s["F"]), D),
                      "w2": dense(ks[4], (Ld, s["F"], D), s["F"])},
            "moe": {**attn(ks[5], Lm),
                    "router": dense(ks[6], (Lm, D, E), D),
                    "router_bias": jax.random.normal(
                        ks[7], (Lm, E), jnp.float32) * ROUTER_BIAS_STD,
                    "w1": dense(ks[8], (Lm, E, D, s["Fe"]), D),
                    "w3": dense(ks[9], (Lm, E, D, s["Fe"]), D),
                    "w2": dense(ks[10], (Lm, E, s["Fe"], D), s["Fe"]),
                    "s1": dense(ks[11], (Lm, D, s["Fs"]), D),
                    "s3": dense(ks[12], (Lm, D, s["Fs"]), D),
                    "s2": dense(ks[13], (Lm, s["Fs"], D), s["Fs"])},
            "final_norm": jnp.ones((D,), jnp.bfloat16),
            "lm_head": dense(ks[14], (D, s["V"]), D),
        }

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
    """x [S, H, d] with its lanes as adjacent pairs, positions 0..S-1:
    de-interleave to [evens ‖ odds], then rotate-half — the published
    ``apply_rotary_pos_emb_interleave``."""
    s, _, d = x.shape
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q/k [S, H, qk], v [S, H, vd], in blocks of
    Q_BLOCK query rows (S is a multiple of it)."""
    s, h, qk = q.shape
    cols = jnp.arange(s)[None, None, :]

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision="highest") * qk ** -0.5
        rows = (start + jnp.arange(Q_BLOCK))[None, :, None]
        scores = jnp.where(cols <= rows, scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                          precision="highest")

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, h, v.shape[-1])


def _swiglu(h, w1, w3, w2, int8):
    return _matmul(jax.nn.silu(_matmul(h, w1, int8)) * _matmul(h, w3, int8),
                   w2, int8)


def route(h, router, bias, top_k, scale):
    """The router alone, h [S, D] float32 -> combine weights [S, E]:
    nought for an expert the token did not choose."""
    scores = jax.nn.sigmoid(jnp.matmul(h, router.astype(jnp.float32),
                                       precision="highest"))
    _, chosen = jax.lax.top_k(scores + bias[None, :], top_k)   # WITH b
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                    dtype=jnp.float32), axis=1)
    kept = scores * picked                                     # WITHOUT b
    return kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20) * scale


@partial(jax.jit, static_argnames=("shape", "theta", "eps", "top_k",
                                   "route_scale", "int8"))
def _forward(params, tokens, read_pos, *, shape, theta, eps, top_k,
             route_scale, int8):
    heads, nope, rope, vd = shape
    x = params["embed"].astype(jnp.float32)[tokens]          # [S, D]
    s = x.shape[0]

    def attend(x, lp):
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = _matmul(h, lp["wq"], int8).reshape(s, heads, nope + rope)
        kva = _matmul(h, lp["wkva"], int8)
        c = _rms_norm(kva[:, :-rope], lp["kv_norm"], eps)
        k_pe = _rope(kva[:, None, -rope:], theta)            # [S, 1, rope]
        if int8:  # an int8 cache: one scale per token's latent row
            row = _round_int8(jnp.concatenate([c, k_pe[:, 0]], -1), -1)
            c, k_pe = row[:, :-rope], row[:, None, -rope:]
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
        k = jnp.concatenate(
            [_matmul(c, lp["w_uk"], int8).reshape(s, heads, nope),
             jnp.broadcast_to(k_pe, (s, heads, rope))], -1)
        v = _matmul(c, lp["w_uv"], int8).reshape(s, heads, vd)
        return x + _matmul(_attention(q, k, v).reshape(s, heads * vd),
                           lp["wo"], int8)

    def dense_layer(x, lp):
        x = attend(x, lp)
        h = _rms_norm(x, lp["ffn_norm"], eps)
        return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], int8), None

    def moe_layer(x, lp):
        x = attend(x, lp)
        h = _rms_norm(x, lp["ffn_norm"], eps)
        combine = route(h, lp["router"], lp["router_bias"], top_k,
                        route_scale)                         # [S, E]

        def expert(y, ew):
            w1, w3, w2, col = ew
            return y + col[:, None] * _swiglu(h, w1, w3, w2, int8), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (lp["w1"], lp["w3"], lp["w2"], combine.T))
        return x + y + _swiglu(h, lp["s1"], lp["s3"], lp["s2"], int8), None

    x, _ = jax.lax.scan(dense_layer, x, params["dense"])
    x, _ = jax.lax.scan(moe_layer, x, params["moe"])
    x = _rms_norm(x[read_pos], params["final_norm"], eps)     # [R, D]
    return _matmul(x, params["lm_head"], int8)                # [R, V]


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
    _check(cfg)
    s = sizes(cfg)
    return _forward(params, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(read_pos, jnp.int32),
                    shape=(s["H"], s["nope"], s["rope"], s["vd"]),
                    theta=float(cfg["rope_theta"]),
                    eps=float(cfg["rms_norm_eps"]), top_k=s["K"],
                    route_scale=float(cfg["routed_scaling_factor"]),
                    int8=precision == "int8")
