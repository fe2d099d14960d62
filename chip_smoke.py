"""Chip smoke: the serving path, end to end, on the accelerator.

    python chip_smoke.py              one chip: phases 1-3b
    python chip_smoke.py --chips 4    four chips: the tp=4 engine and
                                      the single-device engine it is
                                      compared with, nothing else
    python chip_smoke.py --rehearse   sandbox dry run (below)
    python chip_smoke.py --phases latent,latent_mhc
                                      those one-chip phases alone (the
                                      last line then names them: it is
                                      not the whole smoke's success)

The quickest proof that the system still starts on the chip. ONE
process: the HTTP server runs in a thread of the process that owns the
chip, the client in the same process; nothing is spawned.

Phases — each fails the run on its own, nothing is caught and carried
on from:

1. device    — JAX's default backend is a TPU, or stop. Versions,
               device kind/count, compile-cache directory, peak FLOPs
               for this kind (must be known), native-library status.
2. default   — ``examples/model-serving`` ``build_app()`` with
               ``MODEL_PRESET=llama3_1b`` (Llama-3.2-1B widths, all 16
               layers, bf16, seeded random weights), ``engine.warmup``,
               the app's own HTTP server on a free port, requests over
               a socket: /chat twice (same greedy ids), /v1/completions,
               one streamed; health UP with a tpu device; app_engine_*
               gauges; zero recompiles after warm-up. The app's engine
               is the default ``EngineConfig`` one: the page pool on the
               paged kernel, so the prefill AND the decode program must
               hold a Pallas kernel.
3. paged     — the same weights with ``paged_attention="auto"``, bf16
               pool then int8 pool: a
               prompt long enough for chunked prefill, plain decode, a
               speculative run. The reference is the same engine built
               with ``paged_attention="xla"`` on the same chip. Judged
               on logits of the engines' own step functions (chunk,
               chunk with history, decode, tree verify); greedy ids
               reported beside them. Engines are built and dropped one
               at a time, so HBM holds one pool.
3b. latent   — the ``deepseek_v3`` family at Kanana-2-30B-A3B's
               published widths, one dense and one expert layer with all
               128 experts, through ``deepseek_engine``: the latent
               (MLA) Pallas kernel against its XLA twin on logits over
               chunk, chunk with history and decode (same judging
               rule), a V side of zero bytes, zero recompiles; then
               decode WITH HISTORY: the 150-token prompt and twelve
               decode steps teacher-forced along the kernel engine's
               own greedy ids through both engines' step functions,
               judged by the same rule at every token, and every id the
               kernel engine served within ``2 * LOGIT_ATOL`` of the
               XLA twin's best at its position (a router's flip, one
               position, is told from a fault, which runs to the end:
               ``isolated_flips``). The two engines' greedy ids: at
               least 6 of 12 equal.
3c. latent_mhc — the same leg for the family's ``xing4_0`` shape at
               Xing4.0-29B-A4B's published widths, two dense and one
               expert layer with all 64 experts: four residual streams
               (``hc_mult`` 4, 20 Sinkhorn rounds), compressed queries
               (``q_lora_rank`` 768) and YaRN (factor 64 over 4,096); the
               decode step's facts must carry the stream count and the
               stream mixes' row error (positive, well under a row's sum).
               No count of equal ids is held here (5 of 12 on the chip:
               near-flat logits part early); the forced logits and the
               served ids' distance from the twin's best are.
4. --chips 4 — the 1B shape under ``create_mesh({"tp": 4})`` through
               the engine vs the single-device engine on the same
               prompts, same judgement; every leaf ``llama_param_specs``
               shards and the page pool (served through the dense view:
               the kernels are single-device) must sit on four devices,
               and ``bytes_in_use`` must be of one order on all four.

Judging rule (phases 3 and 4): ``max |logits - reference| <=
LOGIT_ATOL``. Seeded random weights give near-flat logits, so where the
two argmax ids differ and the reference's top-2 margin is under
``2 * LOGIT_ATOL`` the difference is reported, not failed; any other
id difference fails.

The last line of stdout is one JSON object. On success, and only
then: ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``. A failed phase ends in ``{"ok": false, ...}`` and a
non-zero exit. With no accelerator the script exits 2 and prints no
result at all. Times printed on earlier lines are set-up facts
(compile, warm-up), not results.

``--rehearse`` is the sandbox dry run (on-chip-measurement guide §2):
tiny shapes, the kernels under the Pallas interpreter, JAX pinned to
the CPU (virtual devices for ``--chips 4``). It finds wrong paths and
arguments before chip time is spent. Its output names ``platform:
cpu`` and its last line has no "ok" key — it cannot be read as the
success line.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: bf16 carries 8 bits of mantissa (eps = 2**-8). Kernel and reference
#: see the same bf16 weights and KV but round differently inside
#: attention (f32 vs bf16 probabilities) and sum in a different order
#: (tp=4: four partial sums), once or twice per layer over 16 layers —
#: a random walk of ~sqrt(32) roundings on activations of order 1,
#: read out by a head whose logits have a standard deviation of ~0.9.
#: 32 eps = 0.125 bounds that with room; a wrong mask, a wrong page or
#: a dropped scale moves logits by their own order, 1 or more.
LOGIT_ATOL = 32 * 2.0 ** -8

PROMPT = "The quick brown fox jumps over the lazy dog."


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ----------------------------------------------------------------- device

def phase_device(args) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    want = args.platform
    if dev.platform != want or jax.default_backend() != want:
        print(f"chip_smoke: JAX's default backend is "
              f"{jax.default_backend()!r} ({device}), need {want!r}",
              file=sys.stderr)
        sys.exit(2)
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {device['count']}", file=sys.stderr)
        sys.exit(2)

    from gofr_tpu import native
    from gofr_tpu.config.env import enable_compile_cache
    from gofr_tpu.serving.observability import device_peak_flops

    args.cache_dir = cache_dir = enable_compile_cache()
    peak = device_peak_flops()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        compile_cache_entries=len(os.listdir(cache_dir)),
        peak_bf16_flops=peak,
        native={name: "compiled" if native.available(name) else "python"
                for name in ("bpe", "batchq")})
    check(cache_dir == jax.config.jax_compilation_cache_dir
          and os.path.isdir(cache_dir),
          f"compile cache directory {cache_dir!r} is not the one in use")
    if not args.rehearse:
        check(peak is not None,
              f"no peak FLOPs known for device kind {dev.device_kind!r} "
              f"(serving/observability.TPU_PEAK_FLOPS)")
    return device


# ----------------------------------------------------- phase 2: default

def load_example():
    path = os.path.join(REPO, "examples", "model-serving", "main.py")
    spec = importlib.util.spec_from_file_location("example_serving", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def has_kernel(fn, *args) -> bool:
    """Whether the program ``fn`` lowers to holds a Pallas TPU kernel."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def phase_default(args):
    import jax.numpy as jnp

    from gofr_tpu.config import DictConfig
    from gofr_tpu.serving.tokenizer import ByteTokenizer
    from tests.apputil import AppRunner

    preset = "tiny" if args.rehearse else "llama3_1b"
    t0 = time.perf_counter()
    app = load_example().build_app(DictConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "chip-smoke",
        "GOFR_TELEMETRY": "false", "MODEL_PRESET": preset}))
    engine = app.container.get_model("llama")
    built_s = time.perf_counter() - t0
    n_prompt = len(ByteTokenizer().encode(PROMPT))
    entries = len(os.listdir(args.cache_dir))
    t0 = time.perf_counter()
    engine.warmup(prompt_lens=(n_prompt,))
    say("default.setup", preset=preset, build_s=round(built_s, 1),
        warmup_s=round(time.perf_counter() - t0, 1),
        cache_entries_before_warmup=entries,
        note="set-up facts: cold when the cache was empty, warm after")

    bucket = engine._bucket_for(n_prompt)
    kernel = has_kernel(engine._prefill_fn, engine.params,
                        jnp.zeros((1, bucket), jnp.int32),
                        jnp.ones((1,), jnp.int32))
    b = engine.config.max_batch
    decode_kernel = has_kernel(
        engine._paged_decode_fn, engine.params, jnp.zeros(b, jnp.int32),
        engine.k_cache, engine.v_cache,
        jnp.zeros((b, engine._pages_per_slot), jnp.int32),
        jnp.ones(b, jnp.int32))
    say("default.programs", bucket=bucket, prefill_pallas_kernel=kernel,
        paged_attention=engine.paged_attention_impl,
        pool_pages=engine._n_pages, decode_pallas_kernel=decode_kernel)
    if not args.rehearse:
        check(kernel, "the prefill program holds no Pallas kernel: "
              "attention(implementation='auto') took the XLA path")
        check(engine.paged_attention_impl == "kernel" and decode_kernel,
              f"the default engine's decode program holds no Pallas "
              f"kernel (paged_attention resolved to "
              f"{engine.paged_attention_impl!r})")

    vocab = engine.params["embed"].shape[0]
    body = {"prompt": PROMPT, "max_tokens": 16, "temperature": 0.0}
    with AppRunner(app=app) as runner:
        def post(path, payload):
            status, _, data = runner.request("POST", path, payload,
                                             timeout=300)
            check(status in (200, 201), f"POST {path} -> {status}: "
                                        f"{data[:300]!r}")
            return data

        first = json.loads(post("/chat", body))["data"]
        again = json.loads(post("/chat", body))["data"]
        ids = first["tokens"]
        check(len(ids) == 16 and all(0 <= t < vocab for t in ids),
              f"/chat tokens malformed: {ids}")
        check(again["tokens"] == ids,
              f"same greedy prompt, different ids: {ids} / "
              f"{again['tokens']}")
        completion = json.loads(post("/v1/completions", {
            **body, "model": preset}))
        check(completion["usage"]["completion_tokens"] == 16
              and completion["choices"][0]["finish_reason"] == "length",
              f"/v1/completions malformed: {completion}")
        events = [json.loads(line[6:]) for line in
                  post("/chat", {**body, "stream": True}).decode()
                  .splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        streamed = [e["token"] for e in events]
        check(streamed == ids,
              f"streamed ids differ from the buffered ones: {streamed}")

        status, health = runner.get_json("/.well-known/health")
        tpu = health["data"]["checks"]["tpu"]
        platforms = sorted({d["platform"]
                            for d in tpu["details"]["devices"]})
        check(status == 200 and tpu["status"] == "UP"
              and platforms == [args.platform],
              f"health does not show an UP {args.platform} device: {tpu}")
        status, _, metrics = runner.request(
            "GET", "/metrics", port=runner.metrics_port)
        gauges = sorted({line.split("{")[0].split(" ")[0]
                         for line in metrics.decode().splitlines()
                         if line.startswith("app_engine_")})
        check(status == 200 and "app_engine_tokens_per_second" in gauges,
              f"app_engine_* gauges missing from /metrics: {gauges}")
        recompiles = engine.stats["recompiles"]
    say("default.serve", greedy_ids=ids, streamed=len(streamed),
        health=tpu["status"], health_platforms=platforms,
        engine_gauges=len(gauges), recompiles_after_warmup=recompiles)
    check(recompiles == 0, f"{recompiles} recompiles after warm-up")
    return engine.params


# ------------------------------------------------------- phase 3: paged

def judge(name: str, got, ref) -> dict:
    """The judging rule of the module docstring on one logits pair
    [..., V]; returns what to report, raises on a failure."""
    import numpy as np
    got = np.asarray(got, np.float32).reshape(-1, got.shape[-1])
    ref = np.asarray(ref, np.float32).reshape(-1, ref.shape[-1])
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{name}: non-finite logits")
    err = float(np.abs(got - ref).max())
    check(err <= LOGIT_ATOL, f"{name}: max |logit diff| {err:.4f} "
                             f"> {LOGIT_ATOL:.4f}")
    ids, ref_ids = got.argmax(-1), ref.argmax(-1)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    differ = np.flatnonzero(ids != ref_ids)
    bad = [int(i) for i in differ if margin[i] >= 2 * LOGIT_ATOL]
    check(not bad, f"{name}: greedy id differs from the reference at "
                   f"rows {bad} where its top-2 margin is "
                   f"{[float(margin[i]) for i in bad]}")
    return {"max_logit_diff": round(err, 5), "ids": ids.tolist(),
            "ids_differ_under_margin": [int(i) for i in differ]}


def isolated_flips(name: str, values, limit: float) -> list:
    """The positions of ``values`` (one a decode step) over ``limit``,
    if they can be a router's flips; a failure if not. A sparse-expert
    family's own allowance: where two of a token's expert scores nearly
    tie, rounding flips its last expert and THAT position's logits move
    by their own spread (1.45 on the chip at Kanana-2's widths, 1.44 at
    the toy size on the CPU in bf16, its neighbours at 0.03-0.09:
    PERF.md section 2). A flip is one position; a fault of decode with
    history does not heal — once a step reads a wrong row every later
    step does — so it shows as a run of positions to the END. Positions
    over the limit pass as flips only if they are at most a quarter of
    the steps and not both of the last two."""
    over = [i for i, v in enumerate(values) if v > limit]
    last_two = {len(values) - 2, len(values) - 1}
    check(len(over) <= len(values) // 4 and not last_two <= set(over),
          f"{name}: {[round(float(v), 4) for v in values]} a position is "
          f"over {limit} at {over}, which is no isolated flip of a router")
    return over


def judge_forced(name: str, got, ref) -> dict:
    """:func:`judge` over teacher-forced decode steps [T, V], one row a
    token; a position :func:`isolated_flips` passes is reported, every
    other row is judged as ever."""
    import numpy as np
    diffs = np.abs(got - ref).max(-1)
    over = isolated_flips(f"{name}: max |logit diff|", diffs, LOGIT_ATOL)
    kept = [i for i in range(len(diffs)) if i not in over]
    return {**judge(name, got[kept], ref[kept]),
            "logit_diff_by_position": [round(float(d), 4) for d in diffs],
            "flipped_positions": over}


def step_logits(eng, vocab: int, expect_kernel: bool | None,
                xla_has_kernels: bool = False) -> dict:
    """Drive the engine's OWN paged step functions (what its jitted
    programs wrap) over a zero pool of the engine's own shape with a
    fixed token script: a 64-row chunk, a chunk against that history,
    a decode step, a two-branch tree verify. Every engine gets the same
    inputs, so the logits compare across implementations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.serving.spec import build_draft_tree

    rng = np.random.default_rng(21)
    b, mp = 2, eng._pages_per_slot
    tables = jnp.asarray(np.arange(b * mp, dtype=np.int32).reshape(b, mp))
    kp = jax.tree.map(jnp.zeros_like, eng.k_cache)
    vp = jax.tree.map(jnp.zeros_like, eng.v_cache)

    def toks(*shape):
        return jnp.asarray(rng.integers(0, vocab, shape), jnp.int32)

    tree = build_draft_tree(0, [[1, 2, 3], [1, 4], [5, 6]])
    n = tree.n_nodes
    script = [  # name, step function, tokens, arguments after the
        #         pools, valid rows of the logits (None = all)
        ("chunk", eng._paged_chunk_fn, toks(b, 64),
         (tables, jnp.asarray([0, 0]), jnp.asarray([64, 40])), None),
        ("chunk_history", eng._paged_chunk_fn, toks(b, 64),
         (tables, jnp.asarray([64, 40]), jnp.asarray([64, 64])), None),
        ("decode", eng._paged_decode_fn, toks(b),
         (tables, jnp.asarray([128, 104])), None),
        ("tree_verify", eng._paged_verify_fn, toks(b, n),
         (tables, jnp.asarray([129, 105]), jnp.asarray([n, n - 2]),
          jnp.asarray([tree.depths] * b, jnp.int32),
          jnp.asarray([tree.masks] * b, jnp.int32)), [n, n - 2]),
    ]
    out = {}
    for name, fn, tokens, rest, rows in script:
        if fn is None:      # a family with no tree-verify step
            continue
        if expect_kernel is not None:
            kernel = has_kernel(fn, eng.params, tokens, kp, vp, *rest)
            # ``xla_has_kernels``: XLA's grouped matmul is a Mosaic
            # kernel on the TPU too, so a sparse-expert family's XLA-
            # attention program still holds one; only presence where a
            # kernel is expected can be checked there
            check(kernel == expect_kernel or (kernel and xla_has_kernels),
                  f"{name}: program holds a Pallas kernel = {kernel}, "
                  f"expected {expect_kernel}")
        # a fourth value is the step's device-counted routing facts
        logits, kp, vp, *facts = jax.jit(fn, donate_argnums=(2, 3))(
            eng.params, tokens, kp, vp, *rest)
        out[name] = np.asarray(logits, np.float32) if rows is None else \
            np.concatenate([np.asarray(logits[i, :r], np.float32)
                            for i, r in enumerate(rows)])
        if facts:
            out["routing_facts"] = np.asarray(facts[0]).tolist()
    return out


def serve_paged(eng, vocab: int) -> dict:
    """The engine-level run: a 150-token prompt (three chunks of the
    64-wide bucket), plain decode, then the same prompt speculatively.
    Seeded random weights at a 128k vocabulary never repeat an n-gram,
    so prompt-lookup would draft nothing: the speculative run drafts
    from an oracle instead — the plain run's own continuation beside a
    wrong branch — which makes the verify pass, acceptance and KV
    compaction run on the chip and pins what they must return."""
    from gofr_tpu.serving.engine import SamplingParams
    from gofr_tpu.serving.spec import build_draft_tree

    prompt = [(7 * i + 3) % 251 for i in range(150)]
    greedy = SamplingParams(temperature=0.0, max_new_tokens=12)
    eng.start()
    try:
        plain = eng.submit_sync(prompt, greedy)
        check(plain.error is None, f"plain run failed: {plain.error}")
        want = list(plain.generated)
        check(len(want) == 12 and all(0 <= t < vocab for t in want),
              f"malformed ids {want}")
        chunks = eng.stats["prefill_calls"]

        def oracle(req):
            # the engine's own budget rule: the bonus token always
            # lands, so at most remaining - 1 drafts can be kept
            done = len(req.generated)
            depth = min(eng.config.spec_draft, len(want) - done - 1)
            if depth <= 0 or req.prompt_tokens != prompt:
                return []
            right = want[done:done + depth]
            wrong = [(t + 1) % vocab for t in right]
            return build_draft_tree(req.generated[-1], [right, wrong])

        eng._draft_proposals = oracle
        spec = eng.submit_sync(prompt, greedy)
        check(spec.error is None, f"speculative run failed: {spec.error}")
        stats = dict(eng.stats)
    finally:
        eng.stop()
    check(chunks >= 3, f"the 150-token prompt took {chunks} prefill "
                       f"dispatches, not a chunk walk")
    check(stats["spec_passes"] > 0, "no speculative verify pass ran")
    check(stats["recompiles"] == 0,
          f"{stats['recompiles']} recompiles after warm-up")
    return {"greedy_ids": want, "speculative_ids": list(spec.generated),
            "prefill_dispatches": chunks, "prefix_hits": stats["prefix_hits"],
            "spec_passes": stats["spec_passes"],
            "spec_accepted": stats["spec_accepted"]}


def phase_paged(args, params) -> None:
    from gofr_tpu.models.llama import LlamaConfig
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import llama_engine

    c = LlamaConfig.tiny() if args.rehearse else LlamaConfig.llama3_1b()
    # small max_seq and few warm-up buckets (widths untouched) keep a
    # cold run within a few minutes of compiling
    for kv_dtype in ("bf16", "int8"):
        results = {}
        for impl in ("xla", "interpret" if args.rehearse else "auto"):
            t0 = time.perf_counter()
            eng = llama_engine(params, c, EngineConfig(
                max_batch=4, max_seq=512, prefill_buckets=(64,),
                prefill_batch=2, decode_steps_per_pass=4, seed=0,
                page_size=64, kv_dtype=kv_dtype, paged_attention=impl,
                speculative=True, spec_draft=3, spec_branches=2,
                spec_adaptive=False))
            resolved = eng.paged_attention_impl
            check(resolved == {"auto": "kernel"}.get(impl, impl),
                  f"paged_attention={impl!r} resolved to {resolved!r}")
            eng.warmup(prompt_lens=(64,), chunked=True)
            warm_s = time.perf_counter() - t0
            kernel = {"kernel": True, "xla": False}.get(resolved)
            results[resolved] = (step_logits(eng, c.vocab_size, kernel),
                                 serve_paged(eng, c.vocab_size))
            say("paged.engine", kv_dtype=kv_dtype, resolved=resolved,
                programs_hold_kernel=kernel, setup_s=round(warm_s, 1),
                **results[resolved][1])
            del eng          # one pool in HBM at a time
            gc.collect()
        (ref_logits, ref_run), (got_logits, got_run) = results.values()
        verdict = {name: judge(f"paged/{kv_dtype}/{name}",
                               got_logits[name], ref_logits[name])
                   for name in ref_logits}
        agree = sum(1 for a, b in zip(got_run["greedy_ids"],
                                      ref_run["greedy_ids"]) if a == b)
        say("paged.verdict", kv_dtype=kv_dtype, logit_atol=LOGIT_ATOL,
            engine_ids_agree=f"{agree}/12 with the xla engine",
            speculative_matches_plain=(got_run["speculative_ids"]
                                       == got_run["greedy_ids"]),
            **verdict)


# ------------------------------------- phase 3b: the latent page pool

def latent_configs(args) -> dict:
    """phase name -> (the family's config for it, the fewest of the 12
    greedy ids that must equal the XLA engine's): Kanana-2-30B-A3B's
    published widths (one dense and one expert layer with all 128
    experts; 11 of 12 on the chip), and Xing4.0-29B-A4B's (two dense
    and one expert layer with all 64, four mHC streams, q-LoRA, YaRN;
    5 of 12 on the chip, so no count is held there: the teacher-forced
    logits and the served ids' distance from the twin's best judge
    it, as they judge both legs: :func:`phase_latent`)."""
    from gofr_tpu.models.deepseek import DeepseekConfig
    if args.rehearse:
        return {"latent": (DeepseekConfig.tiny(), 6),
                "latent_mhc": (DeepseekConfig.tiny_mhc(), 0)}
    return {"latent": (DeepseekConfig(num_hidden_layers=2), 6),
            "latent_mhc": (DeepseekConfig(
                vocab_size=131072, hidden_size=3584, num_hidden_layers=3,
                first_k_dense_replace=2, q_lora_rank=768,
                intermediate_size=9216, moe_intermediate_size=1024,
                n_routed_experts=64, n_shared_experts=1,
                num_experts_per_tok=4, routed_scaling_factor=2.0,
                rope_theta=10000.0, max_position_embeddings=262144,
                rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                              "beta_slow": 1, "mscale": 1,
                              "mscale_all_dim": 1,
                              "original_max_position_embeddings": 4096},
                hc_mult=4), 0)}


def forced_logits(eng, prompt: list, ids: list):
    """The logits that produce each of ``ids`` when the engine's OWN
    paged step functions are fed ``prompt`` and then ``ids`` themselves
    (teacher forcing): the prompt in 64-row chunks over a zero pool,
    then one decode step a token. float32 [len(ids), V]. Every engine
    gets the same tokens, so decode WITH HISTORY compares across
    implementations token by token, wherever the engines' own greedy
    ids part. :func:`step_logits`' shapes (two rows, the same here), so
    no program is compiled for it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, mp = 2, eng._pages_per_slot
    tables = jnp.asarray(np.arange(b * mp, dtype=np.int32).reshape(b, mp))
    kp = jax.tree.map(jnp.zeros_like, eng.k_cache)
    vp = jax.tree.map(jnp.zeros_like, eng.v_cache)
    chunk = jax.jit(eng._paged_chunk_fn, donate_argnums=(2, 3))
    decode = jax.jit(eng._paged_decode_fn, donate_argnums=(2, 3))
    for start in range(0, len(prompt), 64):
        part = prompt[start:start + 64]
        tokens = np.zeros((b, 64), np.int32)
        tokens[:, :len(part)] = part
        logits, kp, vp = chunk(
            eng.params, jnp.asarray(tokens), kp, vp, tables,
            jnp.asarray([start] * b), jnp.asarray([len(part)] * b))
    rows = [logits[0]]
    for j, token in enumerate(ids[:-1]):
        logits, kp, vp, *_ = decode(
            eng.params, jnp.asarray([token] * b, jnp.int32), kp, vp, tables,
            jnp.asarray([len(prompt) + j] * b))
        rows.append(logits[0])
    return np.stack([np.asarray(r, np.float32) for r in rows])


def phase_latent(args, phase: str, c, min_agree: int) -> None:
    """One config of the ``deepseek_v3`` family (:func:`latent_configs`):
    the latent kernel against its XLA twin on logits over chunk,
    chunk-with-history and decode, then on the logits of twelve decode
    steps teacher-forced along the KERNEL engine's own greedy ids
    through both engines' step functions — which also says how far
    below the XLA twin's best each id the kernel engine served lies —
    and the two engines' ids: at least ``min_agree`` equal."""
    import jax
    import numpy as np

    from gofr_tpu.models.deepseek import deepseek_init
    from gofr_tpu.serving.engine import EngineConfig, SamplingParams
    from gofr_tpu.serving.glue import deepseek_engine

    params = deepseek_init(jax.random.key(2), c)
    prompt = [(7 * i + 3) % 251 for i in range(150)]
    greedy = SamplingParams(temperature=0.0, max_new_tokens=12)
    results = {}
    # the kernel engine first: both are then forced along ITS ids
    for impl in ("interpret" if args.rehearse else "kernel", "xla"):
        t0 = time.perf_counter()
        eng = deepseek_engine(params, c, EngineConfig(
            max_batch=4, max_seq=512, prefill_buckets=(64,),
            prefill_batch=2, decode_steps_per_pass=4, seed=0,
            page_size=64, paged_attention=impl))
        check(eng.paged_attention_impl == impl,
              f"paged_attention={impl!r} resolved to "
              f"{eng.paged_attention_impl!r}")
        check(eng.v_cache.size == 0, "the latent pool has a V side")
        eng.warmup(prompt_lens=(64,), chunked=True)
        warm_s = time.perf_counter() - t0
        logits = step_logits(
            eng, c.vocab_size, {"kernel": True, "xla": False}.get(impl),
            xla_has_kernels=True)
        eng.start()
        try:
            run = eng.submit_sync(prompt, greedy)
            stats = dict(eng.stats)
        finally:
            eng.stop()
        check(run.error is None, f"{phase} run failed: {run.error}")
        ids = list(run.generated)
        check(len(ids) == 12 and all(0 <= t < c.vocab_size for t in ids),
              f"malformed ids {ids}")
        check(stats["prefill_calls"] >= 3, "the 150-token prompt took "
              f"{stats['prefill_calls']} prefill dispatches, not a walk")
        check(stats["recompiles"] == 0,
              f"{stats['recompiles']} recompiles after warm-up")
        forced = forced_logits(
            eng, prompt, next(iter(results.values()))[1] if results else ids)
        results[impl] = (logits, ids, forced)
        facts = logits.pop("routing_facts", None)
        if c.hc_mult is not None:
            # [touched, assignments, streams, row error as float32 bits]
            row_err = float(np.asarray(facts[3:], np.int32).view(
                np.float32)[0])
            check(facts[2] == c.hc_mult and 0 < row_err < 0.5,
                  f"stream facts {facts}: row error {row_err}")
            facts = [*facts[:3], row_err]
        say(f"{phase}.engine", resolved=impl, setup_s=round(warm_s, 1),
            greedy_ids=ids, prefill_dispatches=stats["prefill_calls"],
            routing_facts=facts,
            pool=list(eng.k_cache.shape), kv_bytes=eng._kv_bytes_total)
        del eng
        gc.collect()
    (got_logits, got_ids, got_forced), (ref_logits, ref_ids, ref_forced) = \
        results.values()
    # the kernel ENGINE's run (its fused scan, its tables and lengths)
    # under the XLA twin's step function, id by id
    below = ref_forced.max(-1) - ref_forced[np.arange(len(got_ids)), got_ids]
    agree = sum(1 for a, b in zip(got_ids, ref_ids) if a == b)
    say(f"{phase}.forced", engine_ids_agree=f"{agree}/12 with the xla engine",
        ids_part_at=next((i for i, (a, b) in enumerate(zip(got_ids, ref_ids))
                          if a != b), None),
        served_below_twins_best=[round(float(v), 4) for v in below])
    verdict = {name: judge(f"{phase}/{name}", got_logits[name],
                           ref_logits[name])
               for name in ("chunk", "chunk_history", "decode")}
    # decode with history, token by token: the same judging rule over
    # all twelve positions, wherever the two engines' ids part
    verdict["forced"] = judge_forced(f"{phase}/forced", got_forced,
                                     ref_forced)
    # two logit rows within LOGIT_ATOL put their best tokens within
    # twice that of each other, so a served id further below the twin's
    # best is a router's flip or a fault, told apart as above
    flips = isolated_flips(f"{phase}: the kernel engine's ids below the "
                           f"xla twin's best", below, 2 * LOGIT_ATOL)
    say(f"{phase}.verdict", logit_atol=LOGIT_ATOL,
        served_flipped_positions=flips, **verdict)
    # greedy ids of seeded weights part at a near-tie and, fed different
    # tokens from there on, need not meet again: the count is held only
    # where the chip has shown it holds
    check(agree >= min_agree, f"engine ids agree on {agree}/12 only: "
                              f"{got_ids} against {ref_ids}")


# ------------------------------------------------------ phase 4: tp = 4

def phase_sharded(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.parallel import create_mesh, llama_param_specs
    from gofr_tpu.parallel.sharding import _match_specs
    from gofr_tpu.serving.engine import EngineConfig, SamplingParams
    from gofr_tpu.serving.glue import llama_engine

    # (the rehearsal's tiny shape needs four kv heads to split)
    c = LlamaConfig.tiny().scaled(dim=128, n_heads=8, n_kv_heads=4) \
        if args.rehearse else LlamaConfig.llama3_1b()
    devices = jax.devices()[:4]
    # weights are made on the host so that nothing but the sharded
    # engine's share lands on any chip before the memory check
    with jax.default_device(jax.devices("cpu")[0]):
        params = llama_init(jax.random.key(0), c)
    cfg = EngineConfig(max_batch=4, max_seq=512, prefill_buckets=(64,),
                       prefill_batch=2, seed=0)
    prompts = [[(5 * i + j) % 251 for i in range(40 + 7 * j)]
               for j in range(3)]
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, c.vocab_size, (2, 64)), jnp.int32)
    kv_len = jnp.asarray([64, 33], jnp.int32)

    def run(mesh):
        eng = llama_engine(params if mesh is not None
                           else jax.device_put(params, devices[0]),
                           c, cfg, mesh=mesh)
        eng.warmup(prompt_lens=(64,))
        logits, _ = jax.jit(eng._prefill_fn)(eng.params, tokens, kv_len)
        evidence = None
        if mesh is not None:
            specs = _match_specs(eng.params, llama_param_specs(mesh))
            leaves = jax.tree.leaves_with_path(eng.params)
            flat = dict(jax.tree.leaves_with_path(
                specs, is_leaf=lambda s: not isinstance(s, dict)))
            sharded = [jax.tree_util.keystr(path) for path, leaf in leaves
                       if any(ax is not None for ax in flat[path])]
            for path, leaf in leaves:
                if jax.tree_util.keystr(path) not in sharded:
                    continue
                shards = {s.data.shape for s in leaf.addressable_shards}
                check(len(leaf.sharding.device_set) == 4
                      and leaf.size == 4 * int(np.prod(shards.pop())),
                      f"{jax.tree_util.keystr(path)} is not split over "
                      f"four devices: {leaf.sharding}")
            kv = eng.k_cache    # the pool [L, Hg, Np, pg, W]: head
            #                     groups over tp, a device's own share
            check(eng.paged_attention_impl == "view"
                  and len(kv.sharding.device_set) == 4
                  and {s.data.shape[1] for s in kv.addressable_shards}
                  == {kv.shape[1] // 4},
                  f"the page pool is not split over four devices: "
                  f"{kv.sharding}")
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices] \
                if not args.rehearse else None
            evidence = {"sharded_leaves": sharded,
                        "kv_shard_shape": list(
                            kv.addressable_shards[0].data.shape),
                        "bytes_in_use": in_use}
            if in_use is not None:
                check(max(in_use) <= 3 * min(in_use),
                      f"device memory is lopsided: {in_use}")
        eng.start()
        try:
            reqs = [eng.submit_sync(p, SamplingParams(
                temperature=0.0, max_new_tokens=8)) for p in prompts]
        finally:
            eng.stop()
        check(all(r.error is None for r in reqs),
              [r.error for r in reqs])
        return np.asarray(logits, np.float32), \
            [list(r.generated) for r in reqs], evidence

    got, got_ids, evidence = run(create_mesh({"tp": 4}, devices))
    say("sharded.tp4", attention="xla over the pool's dense view (glue: "
        "kernels are single-device)",
        greedy_ids=got_ids, **evidence)
    ref, ref_ids, _ = run(None)
    say("sharded.single", greedy_ids=ref_ids)
    say("sharded.verdict", logit_atol=LOGIT_ATOL,
        engine_ids_agree=sum(a == b for a, b in zip(got_ids, ref_ids)),
        **judge("tp4/prefill", got, ref))


# ------------------------------------------------------------------- main

def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true",
                        help="sandbox dry run: CPU, tiny shapes, "
                             "interpret kernels; never the success line")
    parser.add_argument("--phases", default=None,
                        help="comma-separated one-chip phases to run "
                             "alone: default, paged, latent, latent_mhc")
    args = parser.parse_args()
    only = set(args.phases.split(",")) if args.phases else None
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    args.platform = "cpu" if args.rehearse else "tpu"
    sys.path.insert(0, REPO)
    import gofr_tpu  # noqa: F401 — the script alone, without the
    #                  program, stops here and prints no result

    phase, device = "device", None
    try:
        device = phase_device(args)   # exits 2 with no accelerator
        if args.chips == 4:
            phase = "sharded"
            phase_sharded(args)
        else:
            if only is None or only & {"default", "paged"}:
                phase = "default"    # it makes the weights "paged" serves
                params = phase_default(args)
                gc.collect()     # the default engine's cache leaves HBM
                if only is None or "paged" in only:
                    phase = "paged"
                    phase_paged(args, params)
                del params
                gc.collect()
            for phase, (config, min_agree) in latent_configs(args).items():
                if only is None or phase in only:
                    phase_latent(args, phase, config, min_agree)
                    gc.collect()
    except Exception as exc:
        import traceback
        traceback.print_exc()
        print(json.dumps({"ok": False, "phase": phase,
                          "error": f"{type(exc).__name__}: {exc}"[:2000],
                          "device": device}), flush=True)
        return 1
    say("compile_cache", entries=len(os.listdir(args.cache_dir)))
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    elif only is not None:
        print(json.dumps({"phases_passed": sorted(only), "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
