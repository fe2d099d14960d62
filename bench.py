"""Serving benchmark: continuous-batching /chat throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The parent process never touches JAX (a process that has holds the
chip, and a child that needs it then fails or hangs). It runs ONE
measured child, bounded in time, on the platform it is asked for —
``GOFR_BENCH_PLATFORM``, default ``tpu`` — and the child fails if JAX
comes up on any other: there is no probe, no cached result from an
earlier commit and no CPU fallback. CI asks for ``cpu`` by name and
gets the tiny preset that goes with it; a number from that run is a
count or a smoke, never a device metric. Whatever happens, exactly one
JSON line reaches stdout — on failure it carries value 0.0 and an
"error" field, and the exit code is non-zero.

(ROADMAP A1 replaces this file with the benchmark proper.)

Scenario (BASELINE.json config 3, scaled to the available hardware):
Llama-3.2-1B-architecture model (random weights), N concurrent chat
requests with 64-token prompts and 32 generated tokens each, through
the continuous-batching engine (bucketed prefill + fixed-shape donated
decode + fused in-graph sampling).  vs_baseline is measured against the
north-star target of 2,000 req/s (which assumes a v5e-8; this runs on
however many chips are visible — one in CI).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

TPU_BENCH_TIMEOUT_S = int(os.environ.get("GOFR_BENCH_TPU_TIMEOUT", "1200"))
CPU_BENCH_TIMEOUT_S = int(os.environ.get("GOFR_BENCH_CPU_TIMEOUT", "600"))


def _trunc(s: str, n: int = 200) -> str:
    """Bench artifacts embed error strings at most this long — a JAX
    traceback pasted whole made earlier BENCH_*.json files unreadable."""
    s = str(s)
    return s if len(s) <= n else s[:n - 1] + "…"


# ---------------------------------------------------------------- child

def _child_env(platform: str) -> dict:
    """JAX is held to the platform asked for: it fails at start-up if
    that backend cannot initialise, instead of choosing another."""
    return dict(os.environ, JAX_PLATFORMS=platform,
                GOFR_BENCH_PLATFORM=platform, GOFR_TELEMETRY="false")


def _run_child(code: str, platform: str, timeout_s: int):
    """Run python -c code; return (rc, stdout, stderr) or (None,..) on timeout."""
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           env=_child_env(platform), capture_output=True,
                           text=True, timeout=timeout_s,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
        return p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        out = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
        return None, out, err + f"\n[timeout after {timeout_s}s]"


BENCH_CODE = """
import json, os, statistics, sys, time
import jax
import jax.numpy as jnp

from gofr_tpu.models.llama import LlamaConfig, llama_init, param_count
from gofr_tpu.serving.engine import EngineConfig, SamplingParams
from gofr_tpu.serving.glue import llama_engine

backend = jax.default_backend()
asked = os.environ["GOFR_BENCH_PLATFORM"]
if backend != asked:
    sys.exit(f"bench: asked for {asked!r}, JAX came up on {backend!r}")
on_accel = asked != "cpu"
if on_accel:
    model_config = LlamaConfig.llama3_1b().scaled(max_seq=1024)
    # batch 32: decode streams all params once per K-step pass
    # regardless of batch, and the carry/window work removed the
    # batch-proportional cache waste — wider batches now amortise the
    # weight stream (the r5 sweep showed 32 > 16 even pre-fix)
    max_batch, n_requests = 32, 128
    prompt_len, gen_len = 64, 32
else:  # asked for by name (CI): the tiny smoke preset
    model_config = LlamaConfig.tiny()
    max_batch, n_requests = 4, 8
    prompt_len, gen_len = 16, 8

t0 = time.time()
params = llama_init(jax.random.key(0), model_config)
jax.block_until_ready(params)
n_params = param_count(params)
print(f"# init {model_config.n_layers}L/{model_config.dim}d "
      f"({n_params/1e9:.2f}B params) in {time.time()-t0:.1f}s on {backend}",
      file=sys.stderr)

quant = os.environ.get("GOFR_BENCH_QUANT") or None


def run_scenario(engine_cfg, prompts, gen_len, warm_lens,
                 warm_chunked=False):
    engine = llama_engine(params, model_config, engine_cfg,
                          quantize=quant)
    t0 = time.time()
    engine.warmup(prompt_lens=warm_lens, chunked=warm_chunked)
    print(f"# warmup (compile) {time.time()-t0:.1f}s", file=sys.stderr)
    engine.start()
    engine.stats = {k: 0 if isinstance(v, int) else 0.0
                    for k, v in engine.stats.items()}
    engine.goodput.reset()  # measure this scenario's waste only
    if getattr(engine, "costs", None) is not None and engine.costs.enabled:
        engine.costs.reset()  # per-signature prices for this scenario
    sp = SamplingParams(temperature=0.0, max_new_tokens=gen_len)
    t0 = time.time()
    deadline = t0 + 300.0
    reqs = [engine.submit(p, sp) for p in prompts]
    while any(r.finished_at is None and r.error is None for r in reqs):
        if time.time() > deadline:
            # a wedged scenario must not eat the whole child budget
            # and take the headline JSON line down with it
            engine.stop()
            raise TimeoutError("scenario did not finish in 300s")
        time.sleep(0.001)
    wall = time.time() - t0
    stats = dict(engine.stats)
    stats["goodput"] = engine.goodput.summary()
    stats["costs"] = engine.costs.by_kind() \
        if getattr(engine, "costs", None) is not None \
        and engine.costs.enabled else None
    engine.stop()
    return reqs, wall, stats


def lat_stats(reqs):
    # p50/p95 TTFT and TPOT (per-request mean inter-token latency) in
    # ms for a finished scenario -- the perf trajectory tracks latency,
    # not just tok/s. (No triple-quoted docstring: this function lives
    # inside the BENCH_CODE string literal.)
    ok = [r for r in reqs if r.error is None]
    ttfts = sorted(r.ttft_ms for r in ok if r.ttft_ms is not None)
    tpots = sorted((r.finished_at - r.first_token_at) * 1000.0
                   / (len(r.generated) - 1)
                   for r in ok
                   if r.first_token_at is not None
                   and r.finished_at is not None
                   and len(r.generated) > 1)

    def pct(values, p):
        if not values:
            return -1.0
        return round(values[min(len(values) - 1,
                                int(p * len(values)))], 2)

    return {"p50_ttft_ms": pct(ttfts, 0.50),
            "p95_ttft_ms": pct(ttfts, 0.95),
            "p50_tpot_ms": pct(tpots, 0.50),
            "p95_tpot_ms": pct(tpots, 0.95)}


base_cfg = EngineConfig(max_batch=max_batch, max_seq=model_config.max_seq,
                        prefill_buckets=(64, 128, 256, 512), seed=0,
                        # prompt 64 + gen 32 keeps every live row under
                        # 128: windowed decode attention reads O(128)
                        # rows instead of O(max_seq) per step
                        decode_windows=(128, 256),
                        # group more short prompts per prefill call —
                        # [16, 64] rows feed the MXU better than [8, 64]
                        prefill_batch=16 if on_accel else 8,
                        # fused multi-pass decode: one dispatch yields
                        # K x M = 32 tokens — exactly gen_len on accel,
                        # so each request is ONE dispatch of decode.
                        # The CPU smoke's gen 8 fits a single K=8 pass
                        # already; M > 1 would only waste steps there.
                        decode_passes_per_dispatch=4 if on_accel else 1)
prompt = list(range(1, prompt_len + 1))
reqs, wall, stats = run_scenario(base_cfg, [prompt] * n_requests, gen_len,
                                 (prompt_len,))

ok = [r for r in reqs if r.error is None]
total_tokens = sum(len(r.generated) for r in ok)
req_per_s = len(ok) / wall
tok_per_s = total_tokens / wall
ttfts = sorted(r.ttft_ms for r in ok if r.ttft_ms is not None)
p50_ttft = statistics.median(ttfts) if ttfts else -1.0

# MFU: decode FLOPs ~= 2 * params per generated token (attention adds
# ~2% at these lengths), prefill FLOPs = 2 * params * prompt tokens
# (which already covers each request's first sampled token), against
# the chip's peak bf16 FLOPs over the measured wall time.
# keyed by the EXACT device_kind: an accelerator that is not in the
# table is an error, never a neighbour's peak
PEAK_FLOPS = {"TPU v5 lite": 197e12, "TPU v5p": 459e12,
              "TPU v4": 275e12, "TPU v6 lite": 918e12}
HBM_GBS = {"TPU v5 lite": 819, "TPU v5p": 2765,
           "TPU v4": 1228, "TPU v6 lite": 1640}
kind = jax.devices()[0].device_kind
if on_accel and kind not in PEAK_FLOPS:
    sys.exit(f"bench: no peaks known for device kind {kind!r}")
peak, hbm = PEAK_FLOPS.get(kind), HBM_GBS.get(kind)
flops = 2.0 * n_params * ((total_tokens - len(ok)) + len(ok) * prompt_len)
mfu = round(flops / (wall * peak), 4) if peak else None
# decode roofline: HBM-bound — every decode pass streams all params
# once for up to max_batch tokens (bf16 = 2 B/param; int8 halves it)
bytes_per_param = {"int8": 1.0, "int4": 0.5}.get(quant, 2.0)
roof = (hbm * 1e9) / (bytes_per_param * n_params / max_batch) \
    if hbm else None
# decode_s counts in-flight spans (pipelined passes overlap prefill/
# host work), so the residual is clamped: it is true dead time only
host_s = round(max(0.0, wall - stats["prefill_s"] - stats["decode_s"]), 2)

print(f"# {len(ok)}/{n_requests} ok, wall={wall:.2f}s, "
      f"decode={tok_per_s:.0f} tok/s, p50 TTFT={p50_ttft:.1f}ms, "
      f"mfu={mfu}, phases={stats} host_s={host_s}",
      file=sys.stderr)

# batch-32 decode-overhead scenario: short prompt, long greedy
# generation, all 32 slots saturated, run at decode_steps_per_pass=1 —
# one dispatch per token, the regime where per-dispatch host overhead
# (what the round-5 chip run was bound by) dominates and kernels don't.
# Measured twice: the fused multi-pass dispatch (M=8, one dispatch per
# 8 tokens) and the single-pass path (M=1). Greedy outputs must be
# bit-identical; the tok/s ratio quantifies pure dispatch overhead,
# and h2d_transfers shows the steady-state upload count (event-bounded,
# not per-pass). On the pre-PR engine this workload measured 15.8k
# tok/s on the CPU smoke host; the device-resident state alone moved
# M=1 to ~24k (1.5x) with M=8 adding another ~12% on CPU (on TPU the
# per-dispatch saving is far larger — that's what the TPU jobs verify).
dec_batch = 32
dec_n = 64 if on_accel else 32
dec_gen = 32 if on_accel else 64
dec_prompt = list(range(3, 3 + (64 if on_accel else 8)))


def decode_cfg(m):
    return EngineConfig(
        max_batch=dec_batch, max_seq=model_config.max_seq,
        prefill_buckets=(64, 128, 256, 512) if on_accel else (16, 64),
        seed=0, decode_steps_per_pass=1,
        decode_passes_per_dispatch=m)


try:
    d8, d8_wall, d8_stats = run_scenario(
        decode_cfg(8), [dec_prompt] * dec_n, dec_gen, (len(dec_prompt),))
    d1, d1_wall, d1_stats = run_scenario(
        decode_cfg(1), [dec_prompt] * dec_n, dec_gen, (len(dec_prompt),))
    ok8 = [r for r in d8 if r.error is None]
    ok1 = [r for r in d1 if r.error is None]
    assert len(ok8) == len(ok1) == dec_n, (len(ok8), len(ok1))
    assert [r.generated for r in ok8] == [r.generated for r in ok1], \
        "fused multi-pass decode diverged from the single-pass path"
    tok8 = sum(len(r.generated) for r in ok8) / d8_wall
    tok1 = sum(len(r.generated) for r in ok1) / d1_wall
    decode_payload = {
        "config": f"max_batch={dec_batch}, K=1, greedy, gen={dec_gen}",
        "latency_fused": lat_stats(d8),
        "latency_single": lat_stats(d1),
        "tok_per_s_fused_m8": round(tok8, 1),
        "tok_per_s_single": round(tok1, 1),
        "multi_pass_speedup": round(tok8 / tok1, 3),
        "greedy_identical": True,
        "fused": {k: round(v, 3) if isinstance(v, float) else v
                  for k, v in d8_stats.items()
                  if k in ("decode_passes", "decode_s", "dispatch_s",
                           "collect_s", "h2d_transfers", "sched_syncs")},
        "single": {k: round(v, 3) if isinstance(v, float) else v
                   for k, v in d1_stats.items()
                   if k in ("decode_passes", "decode_s", "dispatch_s",
                            "collect_s", "h2d_transfers",
                            "sched_syncs")},
    }
except Exception as exc:  # the headline number must survive this
    decode_payload = {"error": f"{type(exc).__name__}: {exc}"[:200]}
print(f"# decode-overhead: {decode_payload}", file=sys.stderr)

# prefill-TTFT scenario: long prompts (>= 4 bucket-width chunks) with
# a shared prefix, through the paged chunk walk — the ragged chunk
# KERNEL path (pages read in place; 'interpret' on the CPU smoke host,
# the real kernel on TPU) against the 'view' gather path that
# materialises a dense per-slot [Mp*pg] view of the pool every chunk.
# The pool allocation is max_seq=1024 rows/slot while each chunk only
# needs O(history+chunk), so the view path's O(allocation) HBM traffic
# is what this measures. Greedy outputs must be bit-identical; the
# kernel path must not be slower (prefill tok/s >= view) — both
# asserted in-bench, so a regression kills the scenario payload, not
# the headline.
pf_bucket = 64 if on_accel else 16
pf_n = 16 if on_accel else 8
pf_shared = [7] * (128 if on_accel else 32)  # 2 pages of shared head
pf_prompts = [pf_shared + list(range(100 + 4 * pf_bucket * i,
                                     100 + 4 * pf_bucket * (i + 1)))
              for i in range(pf_n)]  # >= 4 chunks past the shared head


def prefill_cfg(mode):
    return EngineConfig(max_batch=8 if on_accel else 4, max_seq=1024,
                        prefill_buckets=(pf_bucket,), seed=0,
                        kv_layout="paged",
                        page_size=64 if on_accel else 16,
                        prefix_cache=True, paged_attention=mode)


def prefill_run(mode):
    reqs, wall, stats = run_scenario(prefill_cfg(mode), pf_prompts,
                                     4, (pf_bucket,), warm_chunked=True)
    ok = [r for r in reqs if r.error is None]
    assert len(ok) == pf_n, [r.error for r in reqs]
    ptoks = sum(len(r.prompt_tokens) for r in ok)
    ttfts = sorted(r.ttft_ms for r in ok if r.ttft_ms is not None)
    return ([r.generated for r in ok],
            {"prefill_tok_per_s": round(ptoks / max(stats["prefill_s"],
                                                    1e-9), 1),
             "latency": lat_stats(reqs),
             "p50_ttft_ms": round(statistics.median(ttfts), 1),
             "prefill_calls": stats["prefill_calls"],
             "prefill_s": round(stats["prefill_s"], 3),
             "view_bytes_avoided": stats["view_bytes_avoided"]})


try:
    kernel_mode = "kernel" if on_accel else "interpret"
    k_toks, k_stats = prefill_run(kernel_mode)
    v_toks, v_stats = prefill_run("view")
    assert k_toks == v_toks, \
        "ragged chunk kernel diverged from the view path"
    ttft_payload = {
        "config": f"paged chunk walk, {pf_n} x "
                  f"{len(pf_prompts[0])}-token prompts "
                  f"({pf_bucket}-wide buckets), shared "
                  f"{len(pf_shared)}-token prefix, max_seq=1024",
        "kernel_impl": kernel_mode,
        "kernel": k_stats,
        "view": v_stats,
        "prefill_speedup": round(k_stats["prefill_tok_per_s"]
                                 / max(v_stats["prefill_tok_per_s"],
                                       1e-9), 3),
        "greedy_identical": True,
    }
    assert k_stats["prefill_tok_per_s"] >= v_stats["prefill_tok_per_s"], \
        f"kernel prefill slower than view path: {ttft_payload}"
except Exception as exc:  # the headline number must survive this
    ttft_payload = {"error": f"{type(exc).__name__}: {exc}"[:200]}
print(f"# prefill-ttft: {ttft_payload}", file=sys.stderr)

# production-shaped second scenario (VERDICT r4 #6): the full serving
# config — paged KV, prefix cache, speculative decode, max_batch=16
# (which clears pipeline_min_slots, so the decode pipeline engages) —
# on a shared-system-prompt workload, so engine-path regressions that
# the minimal smoke config cannot see surface round-over-round.
page = 64 if on_accel else 16
prod_cfg = EngineConfig(max_batch=16, max_seq=model_config.max_seq,
                        prefill_buckets=(64, 128, 256, 512), seed=0,
                        kv_layout="paged", page_size=page,
                        prefix_cache=True, speculative=True,
                        # drafting is only consulted at PASS boundaries
                        # (the matched tail ends at the boundary
                        # token), so the smoke run shrinks the pass and
                        # the n-gram to get deterministic engagement
                        # within its tiny token budget; accel keeps the
                        # throughput-shaped K=8 with 2-gram lookup
                        spec_ngram=2 if on_accel else 1,
                        decode_steps_per_pass=8 if on_accel else 2,
                        # windows the paged VIEW path's gather (the
                        # mesh/CPU path); the native kernel path is
                        # ragged already and ignores them
                        decode_windows=(256,) if on_accel else (64, 128))
# shared REPETITIVE system prompt spanning 3 full pages: the
# page-aligned prefix is cacheable (prefix_hits > 0) AND the prompt
# tail recurs earlier in the context, so prompt-lookup drafting
# actually engages (spec_passes > 0) — the old all-distinct system
# prompt measured speculative decoding without ever triggering it
# (VERDICT r5 weak #5)
pattern = [7, 11, 13, 17, 19, 23, 29, 31]
system = (pattern * ((3 * page) // len(pattern) + 1))[:3 * page]
prod_n = 64 if on_accel else 32
prod_gen = 32 if on_accel else 16
# per-request marker keeps continuations distinct; the prompt ends
# with the start of `pattern`, whose earlier occurrences feed the
# n-gram draft lookup from the very first decode pass
prod_prompts = [system + [1000 + i] + pattern[:3] for i in range(prod_n)]
try:
    preqs, pwall, pstats = run_scenario(
        prod_cfg, prod_prompts, prod_gen,
        (len(prod_prompts[0]),), warm_chunked=True)
    pok = [r for r in preqs if r.error is None]
    ptok = sum(len(r.generated) for r in pok)
    pttfts = sorted(r.ttft_ms for r in pok if r.ttft_ms is not None)
    prod_payload = {
        "req_per_s": round(len(pok) / pwall, 2),
        "tok_per_s": round(ptok / pwall, 1),
        "latency": lat_stats(preqs),
        "p50_ttft_ms": round(statistics.median(pttfts), 1) if pttfts else -1.0,
        "n_requests": prod_n,
        "config": "paged+prefix+spec+pipeline, max_batch=16",
        "prefix_hits": pstats.get("prefix_hits", 0),
        "spec_accepted": pstats.get("spec_accepted", 0),
        "spec_passes": pstats.get("spec_passes", 0),
        "decode_passes": pstats.get("decode_passes", 0),
        "goodput": pstats.get("goodput"),
    }
except Exception as exc:  # the headline number must survive this
    prod_payload = {"error": f"{type(exc).__name__}: {exc}"[:200]}
print(f"# prod-shaped: {prod_payload}", file=sys.stderr)
if not on_accel:
    # CPU smoke ENFORCES that the speculative path measured something:
    # a prod-shaped scenario reporting spec_passes=0 means the workload
    # never exercised what it claims to measure
    assert prod_payload.get("spec_passes", 0) > 0, (
        "prod-shaped smoke scenario never engaged speculative "
        f"decoding: {prod_payload}")

# kv-capacity scenario (quantized KV pages): at ONE fixed pool byte
# budget, how many resident sessions fit and what does decode run at,
# bf16 vs int8 KV (EngineConfig.kv_dtype)? Capacity is what int8 KV
# buys — per-row HBM drops from native-dtype*hd to hd+4 bytes — and
# the ratio is dtype arithmetic, so the CPU smoke can enforce it.
kv_sess_len = prompt_len + gen_len
kv_pages_per_sess = -(-kv_sess_len // page)
kv_row_native = (2 * model_config.n_layers * model_config.n_kv_heads
                 * model_config.head_dim
                 * jnp.dtype(model_config.dtype).itemsize)
# budget = exactly max_batch resident sessions at the NATIVE page cost
kv_budget = max_batch * kv_pages_per_sess * page * kv_row_native
kv_n = max_batch


def kv_run(dt):
    cfg = EngineConfig(max_batch=max_batch, max_seq=model_config.max_seq,
                       prefill_buckets=(64, 128, 256, 512), seed=0,
                       kv_layout="paged", page_size=page,
                       kv_dtype=dt, kv_pool_bytes=kv_budget)
    engine = llama_engine(params, model_config, cfg, quantize=quant)
    sessions = engine._n_pages // kv_pages_per_sess
    kv_bytes = engine.efficiency_state()["kv_bytes"]
    engine.warmup(prompt_lens=(prompt_len,))
    engine.start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=gen_len)
    t0 = time.time()
    reqs = [engine.submit(prompt, sp) for _ in range(kv_n)]
    deadline = t0 + 300.0
    while any(r.finished_at is None and r.error is None for r in reqs):
        if time.time() > deadline:
            engine.stop()
            raise TimeoutError("kv-capacity run did not finish in 300s")
        time.sleep(0.001)
    wall = time.time() - t0
    engine.stop()
    toks = sum(len(r.generated) for r in reqs if r.error is None)
    return sessions, int(kv_bytes), round(toks / wall, 1)


try:
    kv_sess_b, kv_bytes_b, kv_tps_b = kv_run("bf16")
    kv_sess_i, kv_bytes_i, kv_tps_i = kv_run("int8")
    kv_payload = {
        "budget_bytes": int(kv_budget),
        "sessions_bf16": kv_sess_b, "sessions_int8": kv_sess_i,
        "capacity_ratio": round(kv_sess_i / max(1, kv_sess_b), 3),
        "tok_per_s_bf16": kv_tps_b, "tok_per_s_int8": kv_tps_i,
        "kv_bytes_bf16": kv_bytes_b, "kv_bytes_int8": kv_bytes_i,
    }
except Exception as exc:  # the headline number must survive this
    kv_payload = {"error": f"{type(exc).__name__}: {exc}"[:200]}
print(f"# kv-capacity: {kv_payload}", file=sys.stderr)
if not on_accel:
    # the capacity claim is deterministic dtype arithmetic (per-row
    # bytes native*hd vs hd+4): the CPU smoke enforces >= 1.8x so a
    # sizing regression kills the bench, not just a trajectory number
    assert kv_payload.get("capacity_ratio", 0.0) >= 1.8, (
        f"int8 KV pool holds < 1.8x the bf16 sessions: {kv_payload}")

# spec-decode scenario (adaptive speculation): single-slot greedy
# decode at decode_steps_per_pass=1 — the latency regime speculation
# exists for — on two workloads:
#   repetitive: every request is the same cyclic pattern, so the
#     n-gram index predicts continuations the model actually takes;
#   low-repetition (ADVERSARIAL): the prompt repeats a trigram marker
#     whose every occurrence continues differently, so drafts engage
#     but the model never confirms them — static drafting pays verify
#     rows for nothing, and the adaptive controller must drive
#     drafting ~off after pricing it.
# On CPU a verify pass costs ~width x a decode pass (compute scales
# with rows; there is no dispatch overhead to amortise), so WALL
# speedup is a TPU claim (scripts/tpu_jobs/11_spec_microprof.py).
# What the CPU smoke enforces instead is the dispatch-cost proxy:
# tokens per engine pass (each pass streams all weights once on TPU,
# verify width <= 16 rides the same memory-bound pass), plus the
# controller claims — less waste than static on the adversarial
# workload, near-zero tok/s regression — and greedy bit-identity
# across every spec/plain pair, with zero post-warmup recompiles.
sp_pattern = [7, 11, 13, 17, 19, 23, 29, 31]
sp_rep_prompts = [(sp_pattern * 8)[:61]] * (8 if on_accel else 4)
sp_marker = [41, 43, 47]
sp_low = []
sp_i = 0
while len(sp_low) < 58:  # marker recurs, continuations all diverge
    sp_low.extend(sp_marker)
    sp_low.extend([100 + (7 * sp_i) % 150 + j for j in range(4)])
    sp_i += 1
sp_low_prompts = [sp_low[:58] + sp_marker] * (8 if on_accel else 4)
sp_gen = 64 if on_accel else 48


def spec_cfg(spec, adaptive=True):
    return EngineConfig(max_batch=1, max_seq=256,
                        prefill_buckets=(64,), seed=0,
                        kv_layout="paged", page_size=page,
                        decode_steps_per_pass=1,
                        speculative=spec, spec_ngram=2,
                        spec_draft=4, spec_branches=2,
                        spec_adaptive=adaptive)


def spec_run(cfgv, prompts):
    reqs, wall, stats = run_scenario(cfgv, prompts, sp_gen, (64,),
                                     warm_chunked=True)
    ok = [r for r in reqs if r.error is None]
    assert len(ok) == len(prompts), [r.error for r in reqs]
    toks = sum(len(r.generated) for r in ok)
    passes = stats["decode_passes"] + stats["spec_passes"]
    drafted = stats.get("spec_drafted", 0)
    return {
        "gens": [list(r.generated) for r in ok],
        "tok_per_s": round(toks / wall, 1),
        # decode_s accumulates decode AND verify pass spans
        "decode_tok_per_s": round(toks / max(stats["decode_s"], 1e-9),
                                  1),
        "tok_per_pass": round(toks / max(passes, 1), 3),
        "spec_passes": stats["spec_passes"],
        "decode_passes": stats["decode_passes"],
        "accept_rate": round(stats.get("spec_accepted", 0)
                             / max(1, drafted), 3) if drafted else None,
        "spec_drafted": drafted,
        "recompiles": stats["recompiles"],
        "waste_spec_s": (stats.get("goodput") or {}).get(
            "waste_s", {}).get("spec_rejected", 0.0),
    }


try:
    sp_off_rep = spec_run(spec_cfg(False), sp_rep_prompts)
    sp_static_rep = spec_run(spec_cfg(True, adaptive=False),
                             sp_rep_prompts)
    sp_off_low = spec_run(spec_cfg(False), sp_low_prompts)
    sp_static_low = spec_run(spec_cfg(True, adaptive=False),
                             sp_low_prompts)
    sp_adapt_low = spec_run(spec_cfg(True, adaptive=True),
                            sp_low_prompts)
    for name, run_ in (("static_rep", sp_static_rep),
                       ("static_low", sp_static_low),
                       ("adaptive_low", sp_adapt_low)):
        base = sp_off_rep if name.endswith("rep") else sp_off_low
        assert run_["gens"] == base["gens"], \
            f"greedy speculative output diverged from plain ({name})"
        assert run_["recompiles"] == 0, \
            f"post-warmup recompile in spec run ({name})"
    spec_payload = {
        "config": "max_batch=1, K=1, greedy, ngram=2, draft=4, "
                  "branches=2, paged KV",
        "greedy_identical": True,
        "repetitive": {"off": {k: v for k, v in sp_off_rep.items()
                               if k != "gens"},
                       "static": {k: v for k, v in sp_static_rep.items()
                                  if k != "gens"}},
        "low_repetition": {"off": {k: v for k, v in sp_off_low.items()
                                   if k != "gens"},
                           "static": {k: v for k, v in
                                      sp_static_low.items()
                                      if k != "gens"},
                           "adaptive": {k: v for k, v in
                                        sp_adapt_low.items()
                                        if k != "gens"}},
        # tokens-per-pass ratio on the repetitive workload: the
        # dispatch-cost proxy the TPU wall speedup follows
        "tok_per_pass_ratio": round(sp_static_rep["tok_per_pass"]
                                    / max(sp_off_rep["tok_per_pass"],
                                          1e-9), 3),
        # adaptive regression on the adversarial workload, decode-span
        # based (wall includes prefill noise)
        "adaptive_regression": round(sp_adapt_low["decode_tok_per_s"]
                                     / max(sp_off_low[
                                         "decode_tok_per_s"], 1e-9),
                                     3),
    }
except Exception as exc:  # the headline number must survive this
    spec_payload = {"error": f"{type(exc).__name__}: {exc}"[:200]}
print(f"# spec-decode: {spec_payload}", file=sys.stderr)
if not on_accel and "error" not in spec_payload:
    # the pass-efficiency claim is deterministic at fixed seed: the
    # repetitive workload's drafts must fold >= 1.3 tokens into each
    # engine pass where plain decode folds exactly 1
    assert spec_payload["tok_per_pass_ratio"] >= 1.3, (
        f"speculation folded too few tokens per pass: {spec_payload}")
    # static drafting must have engaged on BOTH workloads (else the
    # adversarial comparison below measures nothing)
    assert sp_static_rep["spec_passes"] > 0, spec_payload
    assert sp_static_low["spec_drafted"] > 0, spec_payload
    # the controller's whole point: on the adversarial workload it
    # stops paying for rejected drafts (strictly less spec_rejected
    # waste than the static policy) without giving up decode speed
    assert (sp_adapt_low["waste_spec_s"]
            < sp_static_low["waste_spec_s"]), (
        f"adaptive controller wasted no less than static: "
        f"{spec_payload}")
    assert spec_payload["adaptive_regression"] >= 0.9, (
        f"adaptive speculation dragged decode down: {spec_payload}")

print("BENCH_JSON " + json.dumps({
    "metric": "chat_req_per_s",
    "value": round(req_per_s, 2),
    "unit": "req/s",
    "vs_baseline": round(req_per_s / 2000.0, 4),
    "tok_per_s": round(tok_per_s, 1),
    "p50_ttft_ms": round(p50_ttft, 1),
    "latency": lat_stats(reqs),
    "mfu": mfu,
    "roofline_tok_per_s": round(roof, 1) if roof else None,
    "pct_of_roofline": round(100 * tok_per_s / roof, 1) if roof else None,
    "phases": {"prefill_s": round(stats["prefill_s"], 2),
               "prefill_calls": stats["prefill_calls"],
               "decode_s": round(stats["decode_s"], 2),
               "decode_passes": stats["decode_passes"],
               "dispatch_s": round(stats["dispatch_s"], 3),
               "collect_s": round(stats["collect_s"], 3),
               "h2d_transfers": stats["h2d_transfers"],
               "sched_syncs": stats["sched_syncs"],
               "host_s": host_s},
    # device-time waste attribution for the headline scenario: the
    # goodput ratio plus the per-cause seconds (padding rows, bubbles,
    # preemption recompute, rejected speculation) — the 2.8%-MFU
    # question "where did the other device-seconds go", answered per run
    "goodput": stats.get("goodput"),
    # per-kind pass prices (us/token) from the cost observatory:
    # report-only context for the trajectory, never a gate
    "costs": stats.get("costs"),
    "platform": backend,
    "quantize": quant,
    "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    "n_requests": n_requests,
    "decode_overhead": decode_payload,
    "prefill_ttft": ttft_payload,
    "prod_shaped": prod_payload,
    "kv_capacity": kv_payload,
    "spec_decode": spec_payload,
}))
"""


# ------------------------------------------------------- perf ledger

TRAJECTORY_FILE = "BENCH_TRAJECTORY.jsonl"


def headline_metrics(payload: dict) -> dict:
    """Flatten the per-scenario headline numbers out of a bench
    payload — the stable metric set the perf ledger tracks run over
    run and scripts/bench_compare.py gates on. Scenarios that errored
    simply contribute nothing (their keys are absent, not zero)."""
    out: dict = {}

    def put(key, value):
        if isinstance(value, (int, float)) and value >= 0:
            out[key] = round(float(value), 3)

    put("chat_req_per_s", payload.get("value"))
    put("chat_tok_per_s", payload.get("tok_per_s"))
    lat = payload.get("latency") or {}
    for k in ("p50_ttft_ms", "p95_ttft_ms", "p50_tpot_ms",
              "p95_tpot_ms"):
        put(k, lat.get(k))
    dec = payload.get("decode_overhead") or {}
    put("decode_tok_per_s_fused", dec.get("tok_per_s_fused_m8"))
    put("decode_tok_per_s_single", dec.get("tok_per_s_single"))
    pf = payload.get("prefill_ttft") or {}
    put("prefill_tok_per_s_kernel",
        (pf.get("kernel") or {}).get("prefill_tok_per_s"))
    put("prefill_tok_per_s_view",
        (pf.get("view") or {}).get("prefill_tok_per_s"))
    put("prefill_p50_ttft_ms", (pf.get("kernel") or {}).get("p50_ttft_ms"))
    prod = payload.get("prod_shaped") or {}
    put("prod_tok_per_s", prod.get("tok_per_s"))
    put("prod_req_per_s", prod.get("req_per_s"))
    # kv_* keys are capacity numbers, not throughput: bench_compare
    # reports them but never gates (not in THROUGHPUT_KEYS, not *_ms)
    kvc = payload.get("kv_capacity") or {}
    put("kv_sessions_bf16", kvc.get("sessions_bf16"))
    put("kv_sessions_int8", kvc.get("sessions_int8"))
    put("kv_capacity_ratio", kvc.get("capacity_ratio"))
    put("kv_tok_per_s_bf16", kvc.get("tok_per_s_bf16"))
    put("kv_tok_per_s_int8", kvc.get("tok_per_s_int8"))
    # spec_* keys are speculation diagnostics, not throughput:
    # bench_compare reports them but never gates (not in
    # THROUGHPUT_KEYS, not *_ms) — accept rates and pass-efficiency
    # ratios are workload properties, not perf trajectory
    spec = payload.get("spec_decode") or {}
    put("spec_tok_per_pass_ratio", spec.get("tok_per_pass_ratio"))
    put("spec_adaptive_regression", spec.get("adaptive_regression"))
    rep = (spec.get("repetitive") or {}).get("static") or {}
    put("spec_accept_rate_rep", rep.get("accept_rate"))
    low = spec.get("low_repetition") or {}
    put("spec_accept_rate_low",
        (low.get("static") or {}).get("accept_rate"))
    put("spec_waste_static_s",
        (low.get("static") or {}).get("waste_spec_s"))
    put("spec_waste_adaptive_s",
        (low.get("adaptive") or {}).get("waste_spec_s"))
    goodput = payload.get("goodput") or {}
    put("goodput_ratio", goodput.get("goodput_ratio"))
    # busy_s rides along so the compare gate can tell a statistically
    # meaningful goodput_ratio from same-host CPU-smoke noise (~20 ms
    # of busy time) — reported, never gated itself
    put("goodput_busy_s", goodput.get("busy_s"))
    for cause, seconds in (goodput.get("waste_s") or {}).items():
        put(f"waste_{cause}_s", seconds)
    # cost_* keys are per-kind µs/token prices from the pass-cost
    # observatory: bench_compare reports them but never gates (not in
    # THROUGHPUT_KEYS, not *_ms) — prices move with host load and
    # shape mix, so they ride the trajectory for context only
    for kind, us_per_token in (payload.get("costs") or {}).items():
        put(f"cost_{kind}_us_per_token", us_per_token)
    return out


def _append_trajectory(payload: dict) -> None:
    """Append this run's headline numbers (plus provenance) to the
    BENCH_TRAJECTORY.jsonl time series next to this file. The ledger
    is append-only and best-effort: a write failure must never take
    down the bench's stdout contract."""
    try:
        import platform as _platform
        import time as _time
        rec = {
            "ts": round(_time.time(), 3),
            "host": _platform.node(),
            "status": payload.get("status") or "unknown",
            "platform": payload.get("platform"),
            "quantize": payload.get("quantize"),
            "metrics": headline_metrics(payload),
        }
        if payload.get("error"):
            rec["error"] = _trunc(payload["error"])
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            TRAJECTORY_FILE)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"# trajectory: appended {rec['status']}/"
              f"{rec['platform']} entry to {TRAJECTORY_FILE}",
              file=sys.stderr)
    except Exception as exc:  # pragma: no cover - ledger is advisory
        print(f"# trajectory append failed: {exc!r}", file=sys.stderr)


# --------------------------------------------------------------- parent

def _bench(platform: str, timeout_s: int):
    """Run the bench child; return (payload|None, error_line)."""
    rc, out, err = _run_child(BENCH_CODE, platform, timeout_s)
    for line in reversed(out.splitlines()):
        if line.startswith("BENCH_JSON "):
            return json.loads(line[len("BENCH_JSON "):]), ""
    # keep the last progress markers so a timeout says which stage hung
    tail = [_trunc(ln) for ln in (err or out).strip().splitlines()
            if ln][-3:]
    return None, _trunc(f"rc={rc}: "
                        f"{' | '.join(tail) if tail else 'no output'}")


def main() -> None:
    platform = os.environ.get("GOFR_BENCH_PLATFORM") or "tpu"
    payload, error = _bench(platform, CPU_BENCH_TIMEOUT_S
                            if platform == "cpu" else TPU_BENCH_TIMEOUT_S)
    if payload is None:
        # no measurement: say so AND exit nonzero — an rc-0 run whose
        # payload cannot be parsed reads as a healthy bench
        print(f"# bench[{platform}] failed: {error}", file=sys.stderr)
        payload = {"metric": "chat_req_per_s", "value": 0.0, "unit": "req/s",
                   "vs_baseline": 0.0, "status": "error",
                   "platform": platform,
                   "error": _trunc(f"{platform}: {error}")}
        print(json.dumps(payload))
        _append_trajectory(payload)
        sys.exit(1)
    payload["status"] = "fresh"
    print(json.dumps(payload))
    _append_trajectory(payload)


if __name__ == "__main__":
    main()
