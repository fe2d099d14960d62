"""TPU job: decompose chunk-prefill time on real hardware.

PR 2 moved chunked prefill / prefix reattach / speculative verify off
the gather_view dense round-trip onto the ragged paged chunk kernel
(ops/paged_attention.paged_chunk_attention). This job measures, on a
real chip, (a) the bare chunk-attention kernel against the XLA gather
reference at several history lengths, and (b) the full model-level
chunk step: native paged (pages written/read in place) vs the view
path (gather whole allocation -> dense chunk -> scatter back). The
view path's cost is O(pool allocation) per chunk; the kernel's is
O(history + chunk) — the gap is what TTFT for long prompts buys.
One JSON line.
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

from gofr_tpu.models.llama import (LlamaConfig, llama_init,
                                   llama_prefill_chunk,
                                   llama_prefill_chunk_paged)
from gofr_tpu.ops.paged_attention import (paged_chunk_attention_pallas,
                                          paged_chunk_attention_xla)
from gofr_tpu.ops.paged_kv import gather_view, pack_pool, scatter_decode

out = {"job": "prefill_microprof", "backend": jax.default_backend(),
       "device": jax.devices()[0].device_kind}

# GOFR_JOB_PROFILE=1: xprof capture of the whole measured region
from _profiling import profile_start, profile_stop
_trace_dir = profile_start("prefill_microprof")

c = LlamaConfig.tiny() if SMOKE else LlamaConfig.llama3_1b().scaled(
    max_seq=1024)
B = 2 if SMOKE else 8
PAGE = 16 if SMOKE else 64
MAX_SEQ = 128 if SMOKE else 1024
CHUNK = 16 if SMOKE else 256
REPS = 2 if SMOKE else 20
IMPL = "interpret" if SMOKE else "pallas"

params = llama_init(jax.random.key(0), c)
jax.block_until_ready(params)


def timed(fn, *args, reps=REPS):
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


# ---- pool + tables sized to the full per-slot allocation
mp = MAX_SEQ // PAGE
n_pages = B * mp
hd = c.head_dim
kp = pack_pool(jnp.zeros((c.n_layers, c.n_kv_heads, n_pages, PAGE, hd),
                         c.dtype))
vp = jnp.zeros_like(kp)
tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
tokens = jnp.ones((B, CHUNK), jnp.int32)
chunk_lens = jnp.full((B,), CHUNK, jnp.int32)

# ---- 1) bare chunk-attention kernel vs the XLA gather reference at
# several history depths (one layer's pool)
kp1 = pack_pool(jnp.zeros((c.n_kv_heads, n_pages, PAGE, hd), c.dtype))
vp1 = jnp.zeros_like(kp1)
q = jnp.ones((B, CHUNK, c.n_heads, hd), c.dtype)
for hist in (0, MAX_SEQ // 4, MAX_SEQ - CHUNK):
    hl = jnp.full((B,), hist, jnp.int32)
    k_fn = jax.jit(lambda q, k, v, t, h, cl: paged_chunk_attention_pallas(
        q, k, v, t, h, cl, interpret=SMOKE))
    x_fn = jax.jit(paged_chunk_attention_xla)
    out[f"kernel_attn_h{hist}_ms"] = round(
        timed(k_fn, q, kp1, vp1, tables, hl, chunk_lens) * 1e3, 3)
    out[f"xla_attn_h{hist}_ms"] = round(
        timed(x_fn, q, kp1, vp1, tables, hl, chunk_lens) * 1e3, 3)

# ---- 2) full model chunk step: native paged vs view round trip
offsets = jnp.full((B,), MAX_SEQ - CHUNK, jnp.int32)  # worst-case hist


def native_step(params, tokens, kp, vp, tables, offsets, chunk_lens):
    return llama_prefill_chunk_paged(params, tokens, kp, vp, tables,
                                     offsets, chunk_lens, c,
                                     implementation=IMPL)


def view_step(params, tokens, kp, vp, tables, offsets, chunk_lens):
    k_view = gather_view(kp, tables, head_dim=hd)
    v_view = gather_view(vp, tables, head_dim=hd)
    logits, k_view, v_view = llama_prefill_chunk(
        params, tokens, k_view, v_view, offsets, chunk_lens, c,
        implementation="xla")
    # the scatter owns the pool dtype (quantize-on-write for int8)
    kp = scatter_decode(kp, tables, k_view, offsets, tokens.shape[1])
    vp = scatter_decode(vp, tables, v_view, offsets, tokens.shape[1])
    return logits, kp, vp


def timed_donated(fn, kp, vp, reps=REPS):
    jfn = jax.jit(fn, donate_argnums=(2, 3))
    logits, kp, vp = jfn(params, tokens, kp, vp, tables, offsets,
                         chunk_lens)
    jax.block_until_ready(logits)
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        logits, kp, vp = jfn(params, tokens, kp, vp, tables, offsets,
                             chunk_lens)
        jax.block_until_ready(logits)
        walls.append(time.perf_counter() - t0)
    walls.sort()
    return walls[len(walls) // 2]


t_native = timed_donated(native_step, kp, vp)
out["native_chunk_step_ms"] = round(t_native * 1e3, 2)
out["native_chunk_tok_per_s"] = round(B * CHUNK / t_native, 1)
kp = pack_pool(jnp.zeros((c.n_layers, c.n_kv_heads, n_pages, PAGE, hd),
                         c.dtype))
vp = jnp.zeros_like(kp)
t_view = timed_donated(view_step, kp, vp)
out["view_chunk_step_ms"] = round(t_view * 1e3, 2)
out["view_chunk_tok_per_s"] = round(B * CHUNK / t_view, 1)
out["native_vs_view_speedup"] = round(t_view / t_native, 3)
out["config"] = (f"B={B} chunk={CHUNK} max_seq={MAX_SEQ} "
                 f"page={PAGE} impl={IMPL}")

profile_stop(_trace_dir)
out["xprof_trace"] = _trace_dir
print(json.dumps(out))
