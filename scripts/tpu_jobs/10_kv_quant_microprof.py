"""TPU job: int8 vs bf16 KV page DMA bandwidth in the ragged kernels.

The quantized KV pool (EngineConfig.kv_dtype="int8") stores pages as
int8 codes + per-row f32 scales and dequantizes in-register after each
per-page DMA — per history row the kernels move hd+4 bytes instead of
2*hd. This job measures, on a real chip, the bare ragged decode and
chunk kernels over a bf16 pool vs the SAME values quantized to int8:
median step time at several history depths, the implied HBM read
bandwidth for the KV stream, and the realized speedup against the 1.88x
byte-ratio roofline (hd=64). Numbers feed the kv_capacity bench
scenario's tok/s story: capacity is guaranteed by arithmetic, the DMA
win is what this job checks. One JSON line.
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

from gofr_tpu.models.llama import LlamaConfig
from gofr_tpu.ops.paged_attention import (paged_chunk_attention_pallas,
                                          paged_decode_attention_pallas)
from gofr_tpu.ops.paged_kv import pack_pool, quantize_pool

out = {"job": "kv_quant_microprof", "backend": jax.default_backend(),
       "device": jax.devices()[0].device_kind}

# GOFR_JOB_PROFILE=1: xprof capture of the whole measured region
from _profiling import profile_start, profile_stop
_trace_dir = profile_start("kv_quant_microprof")

c = LlamaConfig.tiny() if SMOKE else LlamaConfig.llama3_1b().scaled(
    max_seq=2048)
B = 2 if SMOKE else 16
# int8 pages need page % 32 == 0 on the compiled path; interpret
# (smoke) is unconstrained
PAGE = 16 if SMOKE else 64
MAX_SEQ = 128 if SMOKE else 2048
CHUNK = 16 if SMOKE else 256
REPS = 2 if SMOKE else 20
hd = c.head_dim


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


# ---- one layer's pool, every slot's table pointing at distinct pages
mp = MAX_SEQ // PAGE
n_pages = B * mp
key = jax.random.key(0)
kk, kv, kq = jax.random.split(key, 3)
# the engine's layout: kv heads packed into 128-lane rows (paged_kv.py)
kp = pack_pool(jax.random.normal(kk, (c.n_kv_heads, n_pages, PAGE, hd),
                                 jnp.bfloat16))
vp = pack_pool(jax.random.normal(kv, (c.n_kv_heads, n_pages, PAGE, hd),
                                 jnp.bfloat16))
kp8, vp8 = quantize_pool(kp, head_dim=hd), quantize_pool(vp, head_dim=hd)
tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)

# per-row KV bytes each kernel DMAs (K + V): the roofline the measured
# speedup chases
row_bytes_bf16 = 2 * c.n_kv_heads * hd * 2
row_bytes_int8 = 2 * c.n_kv_heads * (hd + 4)
out["row_bytes_bf16"] = row_bytes_bf16
out["row_bytes_int8"] = row_bytes_int8
out["dma_byte_ratio"] = round(row_bytes_bf16 / row_bytes_int8, 3)

# ---- 1) ragged decode kernel: one query row reads the whole history
q1 = jax.random.normal(kq, (B, c.n_heads, hd), jnp.bfloat16)
dec = jax.jit(lambda q, k, v, t, ln: paged_decode_attention_pallas(
    q, k, v, t, ln, interpret=SMOKE))
for hist in (MAX_SEQ // 4, MAX_SEQ):
    lens = jnp.full((B,), hist, jnp.int32)
    t_b = timed(dec, q1, kp, vp, tables, lens)
    t_i = timed(dec, q1, kp8, vp8, tables, lens)
    out[f"decode_bf16_h{hist}_ms"] = round(t_b * 1e3, 3)
    out[f"decode_int8_h{hist}_ms"] = round(t_i * 1e3, 3)
    out[f"decode_speedup_h{hist}"] = round(t_b / t_i, 3)
    # KV-stream read bandwidth implied by the step time
    out[f"decode_bf16_h{hist}_gbs"] = round(
        B * hist * row_bytes_bf16 / t_b / 1e9, 2)
    out[f"decode_int8_h{hist}_gbs"] = round(
        B * hist * row_bytes_int8 / t_i / 1e9, 2)

# ---- 2) ragged chunk kernel at worst-case history
qc = jax.random.normal(kq, (B, CHUNK, c.n_heads, hd), jnp.bfloat16)
hist = MAX_SEQ - CHUNK
hl = jnp.full((B,), hist, jnp.int32)
cl = jnp.full((B,), CHUNK, jnp.int32)
chk = jax.jit(lambda q, k, v, t, h, l: paged_chunk_attention_pallas(
    q, k, v, t, h, l, interpret=SMOKE))
t_b = timed(chk, qc, kp, vp, tables, hl, cl)
t_i = timed(chk, qc, kp8, vp8, tables, hl, cl)
out["chunk_bf16_ms"] = round(t_b * 1e3, 3)
out["chunk_int8_ms"] = round(t_i * 1e3, 3)
out["chunk_speedup"] = round(t_b / t_i, 3)

out["config"] = (f"B={B} hkv={c.n_kv_heads} hd={hd} page={PAGE} "
                 f"max_seq={MAX_SEQ} chunk={CHUNK} "
                 f"impl={'interpret' if SMOKE else 'pallas'}")

profile_stop(_trace_dir)
out["xprof_trace"] = _trace_dir
print(json.dumps(out))
