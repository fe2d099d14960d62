"""TPU job: tree-verify pass cost vs plain decode on the ragged kernel.

Adaptive speculation's economics rest on one chip fact: a W-node
tree-verify pass streams the same KV history as a 1-row decode pass,
so while the kernel stays memory-bound its cost is ~flat in W and
every accepted draft token is nearly free. This job measures, on a
real chip, the bare ragged kernels: paged_tree_attention_pallas at
each pow-2 verify width the engine buckets to (2..16) against
paged_decode_attention_pallas at the same history depths. It reports
per-width pass-cost ratios (the SpecController's row-cost EWMA in
vitro), the break-even tokens-per-pass each width needs, and the tree
kernel's overhead against the plain causal chunk kernel at the same
row count (what the ancestor-bitmask select ladder costs). One JSON
line.
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
                                          paged_decode_attention_pallas,
                                          paged_tree_attention_pallas)
from gofr_tpu.ops.paged_kv import pack_pool, quantize_pool

out = {"job": "spec_microprof", "backend": jax.default_backend(),
       "device": jax.devices()[0].device_kind}

# GOFR_JOB_PROFILE=1: xprof capture of the whole measured region
from _profiling import profile_start, profile_stop
_trace_dir = profile_start("spec_microprof")

c = LlamaConfig.tiny() if SMOKE else LlamaConfig.llama3_1b().scaled(
    max_seq=2048)
B = 2 if SMOKE else 16
PAGE = 16 if SMOKE else 64
MAX_SEQ = 128 if SMOKE else 2048
REPS = 2 if SMOKE else 20
WIDTHS = (2, 4) if SMOKE else (2, 4, 8, 16)
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

dec = jax.jit(lambda q, k, v, t, ln: paged_decode_attention_pallas(
    q, k, v, t, ln, interpret=SMOKE))
tree = jax.jit(lambda q, k, v, t, h, l, m: paged_tree_attention_pallas(
    q, k, v, t, h, l, m, interpret=SMOKE))
chk = jax.jit(lambda q, k, v, t, h, l: paged_chunk_attention_pallas(
    q, k, v, t, h, l, interpret=SMOKE))


def chain_masks(w):
    # a linear chain: node i sees ancestors 0..i — densest realistic
    # mask row (deep accepted paths), worst case for the select ladder
    bits = (1 << (jnp.arange(w, dtype=jnp.int32) + 1)) - 1
    return jnp.broadcast_to(bits, (B, w)).astype(jnp.int32)


q1 = jax.random.normal(kq, (B, c.n_heads, hd), jnp.bfloat16)
for hist in (MAX_SEQ // 4, MAX_SEQ - 16):
    lens = jnp.full((B,), hist, jnp.int32)
    t_dec = timed(dec, q1, kp, vp, tables, lens)
    out[f"decode_h{hist}_ms"] = round(t_dec * 1e3, 3)
    for w in WIDTHS:
        qw = jax.random.normal(kq, (B, w, c.n_heads, hd), jnp.bfloat16)
        cl = jnp.full((B,), w, jnp.int32)
        t_tree = timed(tree, qw, kp, vp, tables, lens, cl,
                       chain_masks(w))
        ratio = t_tree / t_dec
        out[f"tree_w{w}_h{hist}_ms"] = round(t_tree * 1e3, 3)
        # pass-cost ratio: the controller's verify row economics — a
        # verify pass must yield >= this many tokens (accepted + the
        # bonus) to beat `ratio` decode passes emitting 1 each
        out[f"tree_w{w}_h{hist}_cost_ratio"] = round(ratio, 3)
        out[f"tree_w{w}_h{hist}_breakeven_tok_per_pass"] = round(ratio,
                                                                 3)

# ---- tree-mask overhead vs the plain causal chunk kernel at the same
# row count (same pages walked, same flash accumulation — the delta is
# the ancestor-bitmask visibility ladder)
hist = MAX_SEQ - 16
hl = jnp.full((B,), hist, jnp.int32)
for w in WIDTHS:
    qw = jax.random.normal(kq, (B, w, c.n_heads, hd), jnp.bfloat16)
    cl = jnp.full((B,), w, jnp.int32)
    t_tree = timed(tree, qw, kp, vp, tables, hl, cl, chain_masks(w))
    t_chk = timed(chk, qw, kp, vp, tables, hl, cl)
    out[f"tree_vs_chunk_w{w}_overhead"] = round(t_tree / t_chk, 3)

# ---- int8 pool: verify must ride the same quantized-page DMA win the
# decode kernel gets (acceptance moves raw codes, so spec + int8 KV is
# the production config)
w = WIDTHS[-1]
qw = jax.random.normal(kq, (B, w, c.n_heads, hd), jnp.bfloat16)
cl = jnp.full((B,), w, jnp.int32)
t_b = timed(tree, qw, kp, vp, tables, hl, cl, chain_masks(w))
t_i = timed(tree, qw, kp8, vp8, tables, hl, cl, chain_masks(w))
out[f"tree_w{w}_int8_speedup"] = round(t_b / t_i, 3)

out["config"] = (f"B={B} hq={c.n_heads} hkv={c.n_kv_heads} hd={hd} "
                 f"page={PAGE} max_seq={MAX_SEQ} widths={WIDTHS} "
                 f"impl={'interpret' if SMOKE else 'pallas'}")

profile_stop(_trace_dir)
out["xprof_trace"] = _trace_dir
print(json.dumps(out))
