"""TPU job: decompose the decode-pass time budget on real hardware.

The r5 sweep measured ~790 tok/s at batch 16 on the 1B config vs a
~5,300 tok/s HBM roofline (15%). This job isolates where the other
85% goes: raw achievable HBM bandwidth, the bare jitted decode step,
the K-step scan wrapper, attention's share (full-pass vs no-attention
model), sampling, and the head matmul. One JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# GOFR_JOB_SMOKE=1 is the CPU dry run of the same script (tiny shapes,
# interpret kernels); without it the job needs a TPU and fails on any
# other backend — a number from the dry run is never a device number
SMOKE = os.environ.get("GOFR_JOB_SMOKE") == "1"
if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp

assert jax.default_backend() == ("cpu" if SMOKE else "tpu"), \
    f"job ran on {jax.default_backend()!r}"

from gofr_tpu.config.env import enable_compile_cache
enable_compile_cache()

from gofr_tpu.models.llama import (LlamaConfig, llama_init, make_empty_cache,
                                   llama_decode_step, param_count)

out = {"job": "decode_microprof", "backend": jax.default_backend(),
       "device": jax.devices()[0].device_kind}

# GOFR_JOB_PROFILE=1: xprof capture of the whole measured region
from _profiling import profile_start, profile_stop
_trace_dir = profile_start("decode_microprof")

c = LlamaConfig.tiny() if SMOKE else LlamaConfig.llama3_1b().scaled(
    max_seq=1024)
B = 4 if SMOKE else 16
REPS = 2 if SMOKE else 20

params = llama_init(jax.random.key(0), c)
jax.block_until_ready(params)
n_params = param_count(params)
out["n_params"] = n_params


def timed(fn, *args, reps=REPS, donate=None):
    """Median wall of reps calls (post-warmup), seconds."""
    r = fn(*args)
    jax.block_until_ready(r)
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        walls.append(time.perf_counter() - t0)
    walls.sort()
    return walls[len(walls) // 2]


# ---- 1) achievable HBM bandwidth: stream ~the param bytes through a
# trivially fusable reduction (sum of a big bf16 buffer)
big = jnp.ones((max(1, n_params // (1 << 20)), 1 << 20), jnp.bfloat16)
bw_fn = jax.jit(lambda x: jnp.sum(x, dtype=jnp.float32))
t = timed(bw_fn, big)
stream_bytes = big.size * 2
out["hbm_stream_gbps"] = round(stream_bytes / t / 1e9, 1)

def timed_donated(step_fn, kc, vc, reps=REPS):
    """Median wall of a donated-cache decode step: the caches thread
    through each call (donation invalidates the previous buffers), so
    the generic timed() helper cannot be used."""
    logits, kc, vc = step_fn(params, tokens, kc, vc, lengths)
    jax.block_until_ready(logits)
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        logits, kc, vc = step_fn(params, tokens, kc, vc, lengths)
        jax.block_until_ready(logits)
        walls.append(time.perf_counter() - t0)
    walls.sort()
    return walls[len(walls) // 2]


# ---- 2) bare decode step (one token, no scan, no sampling)
kc, vc = make_empty_cache(c, B)
lengths = jnp.full((B,), 64 if not SMOKE else 8, jnp.int32)
tokens = jnp.full((B,), 5, jnp.int32)

step = jax.jit(lambda p, t_, k, v, l: llama_decode_step(p, t_, k, v, l, c),
               donate_argnums=(2, 3))
t_step = timed_donated(step, kc, vc)
out["bare_step_ms"] = round(t_step * 1e3, 2)
out["bare_step_tok_per_s"] = round(B / t_step, 1)
out["bare_step_pct_roofline"] = round(
    100 * (2.0 * n_params / out["hbm_stream_gbps"] / 1e9) / t_step, 1)

# ---- 3) no-attention model: same matmul chain, attention replaced by
# identity — isolates attention + cache traffic share
from gofr_tpu.models.llama import rms_norm, qmatmul, _mlp_block


def noattn_step(p, tok, l):
    x = jnp.take(p["embed"], tok, axis=0)[:, None, :].astype(c.dtype)

    def layer_fn(carry, lp):
        x, live = carry
        h = rms_norm(x, lp["attn_norm"], c.norm_eps)
        q = qmatmul(h, lp["wq"])
        k = qmatmul(h, lp["wk"])
        v = qmatmul(h, lp["wv"])
        # q/k/v folded into the carried scalar so XLA cannot DCE the
        # projections; attention itself is replaced by identity
        live = live + jnp.sum(q) + jnp.sum(k) + jnp.sum(v)
        x = x + qmatmul(h, lp["wo"])
        x = x + _mlp_block(x, lp, c)
        return (x, live), None

    (x, live), _ = jax.lax.scan(
        layer_fn, (x, jnp.zeros((), jnp.float32)), p["layers"])
    head = p.get("lm_head")
    logits = (qmatmul(x, p["embed"].T.astype(c.dtype)) if head is None
              else qmatmul(x, head))
    return logits + live.astype(logits.dtype)


na = jax.jit(noattn_step)
t_na = timed(na, params, tokens, lengths)
out["noattn_step_ms"] = round(t_na * 1e3, 2)

# ---- 4) head matmul alone (the [B, D] x [D, V] vocab projection)
x = jnp.ones((B, 1, c.dim), c.dtype)
head_w = params.get("lm_head")
if head_w is None:
    head_fn = jax.jit(lambda x, p: qmatmul(x, p["embed"].T.astype(c.dtype)))
    t_head = timed(head_fn, x, params)
else:
    head_fn = jax.jit(lambda x, w: qmatmul(x, w))
    t_head = timed(head_fn, x, head_w)
out["head_matmul_ms"] = round(t_head * 1e3, 2)

# ---- 4b) prefill: [P, 64] last-logit prefill — compute-bound at
# these shapes (1024 rows -> ~1024 flops/byte, over the MXU ridge),
# so time here vs the ~13 ms ideal is kernel/layout overhead
from gofr_tpu.models.llama import llama_prefill_last

for p_rows in ((2,) if SMOKE else (8, 16)):
    toks = jnp.ones((p_rows, 16 if SMOKE else 64), jnp.int32)
    lens = jnp.full((p_rows,), toks.shape[1], jnp.int32)
    pf = jax.jit(lambda pr, t, l: llama_prefill_last(pr, t, c,
                                                     kv_lengths=l))
    t_pf = timed(pf, params, toks, lens)
    out[f"prefill_{p_rows}x{toks.shape[1]}_ms"] = round(t_pf * 1e3, 2)

# ---- 5) sampling: all-greedy batches take _sample_batch's lax.cond
# argmax fast path; one sampled row forces the vocab-wide top_k branch
from gofr_tpu.serving.engine import _sample_batch

lg = jnp.ones((B, c.vocab_size), jnp.float32)
argmax_fn = jax.jit(lambda l: jnp.argmax(l, axis=-1))
out["argmax_ms"] = round(timed(argmax_fn, lg) * 1e3, 2)
topk_fn = jax.jit(lambda l: jax.lax.top_k(l, 64)[1])
out["topk64_ms"] = round(timed(topk_fn, lg) * 1e3, 2)
tps = jnp.ones((B,), jnp.float32)
tks = jnp.zeros((B,), jnp.int32)
greedy_t = jnp.zeros((B,), jnp.float32)
mixed_t = greedy_t.at[0].set(0.7)
samp_fn = jax.jit(lambda l, k, t: _sample_batch(l, k, t, tps, tks))
out["sample_greedy_ms"] = round(
    timed(samp_fn, lg, jax.random.key(0), greedy_t) * 1e3, 2)
out["sample_mixed_ms"] = round(
    timed(samp_fn, lg, jax.random.key(0), mixed_t) * 1e3, 2)

# ---- 6) padded-attention share: same step against a short cache
if not SMOKE:
    c_short = LlamaConfig.llama3_1b().scaled(max_seq=256)
    kc_s, vc_s = make_empty_cache(c_short, B)
    step_s = jax.jit(
        lambda p, t_, k, v, l: llama_decode_step(p, t_, k, v, l, c_short),
        donate_argnums=(2, 3))
    out["bare_step_seq256_ms"] = round(
        timed_donated(step_s, kc_s, vc_s) * 1e3, 2)

profile_stop(_trace_dir)
out["xprof_trace"] = _trace_dir
print(json.dumps(out))
