"""Plain reference for the ``xing4_0`` decoder block (Xing4.0-29B-A4B):
a four-stream residual mixed by manifold-constrained hyper-connections
(mHC, arXiv:2512.24880, on hyper-connections arXiv:2409.19606) around
every attention and FFN sublayer; latent (MLA) attention with
compressed (q-LoRA) queries and YaRN-scaled rope; leading dense layers,
then sigmoid-routed sparse experts beside a shared expert — and the
weights both sides are given.

Nothing here imports the program or the other references. The forward
pass is the architecture as its ``config.json`` keys and the papers
they name state it, in straightforward ``jax.numpy``, float32
throughout with ``precision="highest"`` (on a TPU a float32 matmul
otherwise runs in bf16 passes). No cache, no batching, no kernels, no
sort and no grouped matmul: one sequence, all positions at once;
attention in the MATERIALISED form (every head's K and V up-projected
from the latent), in blocks of query rows; every expert is run over
every token and a token keeps the outputs of the four it chose (a
mask); the head in blocks of the vocabulary.

The equations, for one token (n = ``hc_mult`` = 4, C = ``hidden_size``,
X in R^{n x C} its residual streams, F a sublayer):

- Stream in / out. X_0 = the token's embedding repeated n times. After
  the last layer the streams are summed (hyper-connections' read-out),
  then the final RMSNorm and the untied head.
- mHC around each sublayer; attention and the FFN of every layer have
  their own phi, b, alpha:
  x~ = RMSNorm(vec(X)) in R^{nC}, no gain, eps ``hc_eps``;
  H~_pre = a_pre (x~ phi_pre) + b_pre in R^n;
  H~_post = a_post (x~ phi_post) + b_post in R^n;
  H~_res = a_res mat(x~ phi_res) + b_res in R^{n x n};
  H_pre = sigmoid(H~_pre); H_post = 2 sigmoid(H~_post);
  H_res = Sinkhorn(H~_res): M = exp(clip(H~_res,
  ``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``)), then
  ``hc_sinkhorn_iters`` = 20 times: each row over its sum, then each
  column over its sum;
  X' = H_res X + H_post^T F(H_pre X): F sees ONE C-wide vector, the
  H_pre-weighted sum of the streams, and applies its own pre-norm.
- Attention, h = RMSNorm(F's input): q = W_qb RMSNorm(W_qa h; g_q)
  (``q_lora_rank`` 768 wide), per head q_nope (128) ‖ q_pe (64);
  [c_raw ‖ k_pe_raw] = W_kva h (512 + 64); c = RMSNorm(c_raw; g_kv);
  k_nope_head = W_uk[head] c, v_head = W_uv[head] c. RoPE on q_pe of
  every head and on k_pe, one vector shared by all heads, lanes stored
  as adjacent pairs (``rope_interleave``) and brought to [evens ‖ odds]
  before ``rotate_half``. YaRN as the ``deepseek_v3`` modelling code
  has it: pair i's inverse frequency is (1 - r_i) theta^(-2i/64) +
  r_i theta^(-2i/64) / factor, r the linear ramp between the
  correction dims of ``beta_fast`` and ``beta_slow`` rotations over
  ``original_max_position_embeddings`` (floor and ceiling); cos and
  sin are scaled by mscale(factor, ``mscale``) / mscale(factor,
  ``mscale_all_dim``) = 1; scores = q.k x 192^-1/2 x mscale(factor,
  ``mscale_all_dim``)^2, mscale(f, m) = 0.1 m ln f + 1; causal,
  softmax in float32; out = W_o concat(sum p v_head).
- Dense layers (the first ``first_k_dense_replace`` = 2): SwiGLU of
  width ``intermediate_size``.
- Expert layers: s = sigmoid(W_g h) in float32; chosen = top-4 of
  (s + b), b the ``e_score_correction_bias``; w = s[chosen] — WITHOUT
  b — over (sum w + 1e-20), times ``routed_scaling_factor``;
  y = sum_k w_k E_k(h) + S(h), E an expert SwiGLU of width
  ``moe_intermediate_size``, S the shared SwiGLU.

Departures from the published description, each with its reason:

- The multi-token-prediction module (``num_nextn_predict_layers`` 1)
  is absent: the config does not say how its input meets a four-stream
  residual, the model's own next-token logits do not depend on it
  (DeepSeek-V3 section 2.2: dropped at inference), and it lies on the
  last pipeline stage of the deployment this chip is the first of.
- ``n_group`` 1 and ``topk_group`` 1 make ``noaux_tc``'s group step
  select the one group there is: it is left out.
- ``kv_b_proj`` is stored as its K half and its V half (``w_uk``,
  ``w_uv``); phi_pre, phi_post and phi_res as ONE matrix ``phi
  [n, C, n + n + n*n]`` (columns pre ‖ post ‖ res; ``phi[i]`` the rows
  that multiply stream i, so vec(X) is stream-major), with ``alpha
  [3]`` and ``bias [n + n + n*n]`` — the same numbers, stored so that
  no side has to slice a weight.
- What the config's keys do not fix is listed in the configuration's
  file under ``assumed`` and seeded here: b (``ROUTER_BIAS_STD``); the
  mHC alpha (1 + ``HC_ALPHA_STD`` x normal: the dynamic term is of
  order one, so the mapping differs token by token) and bias
  (``HC_BIAS_STD`` x normal, plus ``HC_RES_DIAGONAL`` on H~_res's
  diagonal: a stream keeps most of itself); the mHC norm has no gain;
  rows are normalised before columns; ``hc_eps`` enters the norm of
  vec(X) and nowhere else.

``precision="int8"`` is the control of the output check: the same
forward pass with every matmul operand (weights per output channel,
activations per row) and every cached row (the 576 numbers c ‖ k_pe of
a token) rounded to int8 codes, the nearest precision below the bf16
the configuration states. The router's scores and the mHC coefficients
stay float32, as the architecture states them.

``precision="bfloat16"`` is no part of the check: a WITNESS for it
(PERF.md section 2). It is this same plain forward pass keeping in
bfloat16 what a bf16 program keeps in memory — every matmul operand and
result, every norm's output, the cached row and the four streams after
every sublayer — with the router's scores, the mHC coefficients, the
softmax and every accumulation float32. Put in the program's place
(the token it puts first at each served position, against the float32
reference's best) it reads the gap that rounding alone makes at the
cell's own sizes: a program whose ``served_gap_mean`` is of that order
is sound, one well above it is not.

The weights are the benchmark's own: one jitted call from the seed, on
the device, in bf16, in the tree the program's engine builder takes;
the routed experts' stacks a layer at a time, so that their float32
transient is one layer's.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512            # query rows per attention block
V_BLOCK = 16384          # head columns per block
ROUTER_BIAS_STD = 0.02   # of the seeded e_score_correction_bias
HC_ALPHA_STD = 0.1       # alpha = 1 + this x normal
HC_BIAS_STD = 0.5        # b = this x normal ...
HC_RES_DIAGONAL = 2.0    # ... plus this on the diagonal of b_res


def sizes(cfg: dict) -> dict:
    """The published sizes, by short names."""
    return {"V": cfg["vocab_size"], "D": cfg["hidden_size"],
            "L": cfg["num_hidden_layers"],
            "Ld": cfg["first_k_dense_replace"],
            "H": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "C": cfg["kv_lora_rank"],
            "Q": cfg["q_lora_rank"], "N": cfg["hc_mult"],
            "F": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def _check(cfg: dict) -> None:
    """What this reference implements, and nothing it would guess."""
    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "moe_layer_freq": 1, "tie_word_embeddings": False,
            "hidden_act": "silu", "attention_bias": False,
            "rope_interleave": True}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"xing4_0 reference: {key} = "
                             f"{cfg.get(key)!r}, implemented for {value!r}")
    for key in ("q_lora_rank", "hc_mult", "hc_sinkhorn_iters"):
        if not isinstance(cfg.get(key), int):
            raise ValueError(f"xing4_0 reference: {key} = {cfg.get(key)!r}, "
                             "implemented for an int")
    if (cfg.get("rope_scaling") or {}).get("type") != "yarn":
        raise ValueError("xing4_0 reference: rope_scaling = "
                         f"{cfg.get('rope_scaling')!r}, implemented for yarn")
    cos_sin = _yarn(cfg)[2]
    if abs(cos_sin - 1.0) > 1e-9:
        raise ValueError(f"xing4_0 reference: cos/sin factor {cos_sin}, "
                         "implemented for 1")


def _mscale(factor: float, m: float = 1.0) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(cfg: dict) -> tuple[tuple, float, float]:
    """(inverse frequencies of the rope pairs, the softmax scale's
    factor, the cos/sin factor) as ``_compute_yarn_parameters`` and the
    attention's ``scaling * mscale * mscale`` have them."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    factor, orig = float(rs["factor"]), \
        float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = correction_dim(rs.get("beta_fast") or 32)
    high = correction_dim(rs.get("beta_slow") or 1)
    if rs.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    inv = []
    for i in range(dim // 2):
        plain = base ** (-2.0 * i / dim)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        inv.append(plain / factor * ramp + plain * (1.0 - ramp))
    all_dim = rs.get("mscale_all_dim")
    scale = _mscale(factor, all_dim) ** 2 if all_dim else 1.0
    if rs.get("mscale") and all_dim:
        cos_sin = _mscale(factor, rs["mscale"]) / _mscale(factor, all_dim)
    else:
        cos_sin = _mscale(factor)
    return tuple(inv), scale, cos_sin


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded random bf16 weights, made on the default device in one
    jitted call: normal(0, fan_in**-0.5) matrices, unit norm gains, an
    embedding of standard deviation 0.02, the router's bias and the mHC
    alpha and bias (float32) as the module docstring says. ``dense``
    stacks the leading dense layers, ``moe`` the expert layers, each
    with its own attention and mHC weights."""
    _check(cfg)
    s = sizes(cfg)
    # any whole number up to a little over 2**31: fold the high bits in
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)

    @jax.jit
    def make(key):
        D, H, C, Q, N = s["D"], s["H"], s["C"], s["Q"], s["N"]
        qk, K = s["nope"] + s["rope"], 2 * s["N"] + s["N"] ** 2

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(jnp.bfloat16)

        def by_layer(k, n, shape, fan_in):
            """[n, *shape], one layer's float32 draw alive at a time."""
            return jax.lax.map(lambda kk: dense(kk, shape, fan_in),
                               jax.random.split(k, n))

        def streams(k, n, which):
            k1, k2, k3 = jax.random.split(k, 3)
            bias = jax.random.normal(k3, (n, K), jnp.float32) * HC_BIAS_STD
            return {
                f"hc_{which}_phi": dense(k1, (n, N, D, K), N * D),
                f"hc_{which}_alpha": 1.0 + HC_ALPHA_STD * jax.random.normal(
                    k2, (n, 3), jnp.float32),
                f"hc_{which}_bias": bias.at[:, 2 * N:].add(
                    HC_RES_DIAGONAL * jnp.eye(N).reshape(-1))}

        def attn(k, n):
            ks = jax.random.split(k, 8)
            return {"attn_norm": jnp.ones((n, D), jnp.bfloat16),
                    "wqa": dense(ks[0], (n, D, Q), D),
                    "q_norm": jnp.ones((n, Q), jnp.bfloat16),
                    "wqb": dense(ks[1], (n, Q, H * qk), Q),
                    "wkva": dense(ks[2], (n, D, C + s["rope"]), D),
                    "kv_norm": jnp.ones((n, C), jnp.bfloat16),
                    "w_uk": dense(ks[3], (n, C, H * s["nope"]), C),
                    "w_uv": dense(ks[4], (n, C, H * s["vd"]), C),
                    "wo": dense(ks[5], (n, H * s["vd"], D), H * s["vd"]),
                    "ffn_norm": jnp.ones((n, D), jnp.bfloat16),
                    **streams(ks[6], n, "attn"), **streams(ks[7], n, "ffn")}

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
                    "w1": by_layer(ks[8], Lm, (E, D, s["Fe"]), D),
                    "w3": by_layer(ks[9], Lm, (E, D, s["Fe"]), D),
                    "w2": by_layer(ks[10], Lm, (E, s["Fe"], D), s["Fe"]),
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


def _round(x, axis, low):
    """``x`` as the precision ``low`` holds it (None: as it is), back
    in float32; int8 codes share a scale along ``axis``."""
    if low == "int8":
        return _round_int8(x, axis)
    if low == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _stored(x, low):
    """What a bf16 program keeps in memory between two operations; the
    int8 control rounds operands and cached rows only."""
    return _round(x, -1, low) if low == "bfloat16" else x


def _matmul(x, w, low):
    # activations per row, weights per output channel
    y = jnp.matmul(_round(x, -1, low),
                   _round(w.astype(jnp.float32), 0, low), precision="highest")
    return _stored(y, low)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return x if w is None else x * w.astype(jnp.float32)[None, :]


def _rope(x, inv_freq):
    """x [S, H, d] with its lanes as adjacent pairs, positions 0..S-1:
    de-interleave to [evens ‖ odds], then rotate-half — the published
    ``apply_rotary_pos_emb_interleave``."""
    s, _, d = x.shape
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal attention, q/k [S, H, qk], v [S, H, vd], in blocks of
    Q_BLOCK query rows (S is a multiple of it)."""
    s, h, _ = q.shape
    cols = jnp.arange(s)[None, None, :]

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision="highest") * scale
        rows = (start + jnp.arange(Q_BLOCK))[None, :, None]
        scores = jnp.where(cols <= rows, scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                          precision="highest")

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, h, v.shape[-1])


def _swiglu(h, w1, w3, w2, low):
    return _matmul(jax.nn.silu(_matmul(h, w1, low)) * _matmul(h, w3, low),
                   w2, low)


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


def sinkhorn(logits, iters: int, lo: float, hi: float):
    """logits [..., n, n] -> exp of the clipped logits after ``iters``
    rounds of rows-then-columns normalisation."""
    m = jnp.exp(jnp.clip(logits, lo, hi))
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)      # each row
        m = m / jnp.sum(m, axis=-2, keepdims=True)      # each column
    return m


def mappings(streams, phi, alpha, bias, *, iters, eps, clamp):
    """streams X [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res
    [S, n, n]), float32 whatever the control rounds."""
    s, n, c = streams.shape
    flat = _rms_norm(streams.reshape(s, n * c), None, eps)
    dyn = jnp.matmul(flat, phi.astype(jnp.float32).reshape(n * c, -1),
                     precision="highest")
    pre = jax.nn.sigmoid(alpha[0] * dyn[:, :n] + bias[None, :n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * dyn[:, n:2 * n]
                                + bias[None, n:2 * n])
    res = sinkhorn((alpha[2] * dyn[:, 2 * n:]
                    + bias[None, 2 * n:]).reshape(s, n, n), iters, *clamp)
    return pre, post, res


@partial(jax.jit, static_argnames=("shape", "inv_freq", "scale", "eps",
                                   "top_k", "route_scale", "hc", "low"))
def _forward(params, tokens, read_pos, *, shape, inv_freq, scale, eps,
             top_k, route_scale, hc, low):
    heads, nope, rope, vd = shape
    n, iters, hc_eps, lo, hi = hc
    x = params["embed"][tokens].astype(jnp.float32)          # [S, D]
    s = x.shape[0]
    streams = jnp.broadcast_to(x[:, None, :], (s, n, x.shape[1]))

    def around(streams, lp, which, f):
        """X' = H_res X + H_post^T F(H_pre X)."""
        pre, post, res = mappings(
            streams, lp[f"hc_{which}_phi"], lp[f"hc_{which}_alpha"],
            lp[f"hc_{which}_bias"], iters=iters, eps=hc_eps, clamp=(lo, hi))
        out = f(_stored(jnp.einsum("sn,snc->sc", pre, streams,
                                   precision="highest"), low))
        return _stored(
            jnp.einsum("smn,snc->smc", res, streams, precision="highest")
            + post[:, :, None] * out[:, None, :], low)

    def attend(lp):
        def f(x):
            h = _rms_norm(x, lp["attn_norm"], eps)
            q = _matmul(_rms_norm(_matmul(h, lp["wqa"], low), lp["q_norm"],
                                  eps), lp["wqb"], low)
            q = q.reshape(s, heads, nope + rope)
            kva = _matmul(h, lp["wkva"], low)
            c = _rms_norm(kva[:, :-rope], lp["kv_norm"], eps)
            k_pe = _rope(kva[:, None, -rope:], inv_freq)     # [S, 1, rope]
            if low:  # the cache in ``low``: int8 codes share a scale per
                #      token's latent row
                row = _round(jnp.concatenate([c, k_pe[:, 0]], -1), -1, low)
                c, k_pe = row[:, :-rope], row[:, None, -rope:]
            q = _stored(jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], inv_freq)], -1), low)
            k = jnp.concatenate(
                [_matmul(c, lp["w_uk"], low).reshape(s, heads, nope),
                 jnp.broadcast_to(k_pe, (s, heads, rope))], -1)
            v = _matmul(c, lp["w_uv"], low).reshape(s, heads, vd)
            return _matmul(_attention(q, k, v, scale).reshape(s, heads * vd),
                           lp["wo"], low)
        return f

    def dense_layer(streams, lp):
        streams = around(streams, lp, "attn", attend(lp))

        def ffn(x):
            h = _rms_norm(x, lp["ffn_norm"], eps)
            return _swiglu(h, lp["w1"], lp["w3"], lp["w2"], low)

        return around(streams, lp, "ffn", ffn), None

    def moe_layer(streams, lp):
        streams = around(streams, lp, "attn", attend(lp))

        def ffn(x):
            h = _stored(_rms_norm(x, lp["ffn_norm"], eps), low)
            combine = route(h, lp["router"], lp["router_bias"], top_k,
                            route_scale)                     # [S, E]

            def expert(y, ew):
                w1, w3, w2, col = ew
                return y + col[:, None] * _swiglu(h, w1, w3, w2, low), None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                                (lp["w1"], lp["w3"], lp["w2"], combine.T))
            return y + _swiglu(h, lp["s1"], lp["s3"], lp["s2"], low)

        return around(streams, lp, "ffn", ffn), None

    streams, _ = jax.lax.scan(dense_layer, streams, params["dense"])
    streams, _ = jax.lax.scan(moe_layer, streams, params["moe"])
    x = _rms_norm(jnp.sum(streams, axis=1)[read_pos], params["final_norm"],
                  eps)                                        # [R, D]
    return _head(x, params["lm_head"], low)                   # [R, V]


def _head(x, head, low):
    """x [R, D] times the head [D, V], V_BLOCK columns at a time where
    they divide the vocabulary: the float32 copy of a 131,072-column
    head would be 1.9 GB. The int8 scales are a row's of x and a
    column's of the head, so blocks change nothing."""
    vocab = head.shape[1]
    if vocab % V_BLOCK:
        return _matmul(x, head, low)

    def block(i, out):
        w = jax.lax.dynamic_slice_in_dim(head, i * V_BLOCK, V_BLOCK, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _matmul(x, w, low), i * V_BLOCK, 1)

    return jax.lax.fori_loop(0, vocab // V_BLOCK, block,
                             jnp.zeros((x.shape[0], vocab), jnp.float32))


def forward_logits(cfg: dict, params: dict, tokens, read_pos, *,
                   precision: str = "float32"):
    """Logits [R, V] (float32) at positions ``read_pos`` of one sequence
    ``tokens`` [S]; S must be a multiple of Q_BLOCK (pad on the right:
    attention is causal, so padding never reaches a read position).
    ``precision``: "float32" (the reference), "int8" (the control) or
    "bfloat16" (the witness: the module's docstring)."""
    if precision not in ("float32", "int8", "bfloat16"):
        raise ValueError(f"precision {precision!r}")
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"sequence length {len(tokens)} is not a "
                         f"multiple of {Q_BLOCK}")
    _check(cfg)
    s = sizes(cfg)
    inv_freq, yarn_scale, _ = _yarn(cfg)
    return _forward(
        params, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(read_pos, jnp.int32),
        shape=(s["H"], s["nope"], s["rope"], s["vd"]), inv_freq=inv_freq,
        scale=(s["nope"] + s["rope"]) ** -0.5 * yarn_scale,
        eps=float(cfg["rms_norm_eps"]), top_k=s["K"],
        route_scale=float(cfg["routed_scaling_factor"]),
        hc=(s["N"], int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]),
            float(cfg["mhc_h_res_clamp_min"]),
            float(cfg["mhc_h_res_clamp_max"])),
        low=None if precision == "float32" else precision)
