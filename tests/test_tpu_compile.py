"""The main path's kernels, compiled for the real chip without the chip.

The TPU's compiler is installed wherever the tests run and compiles
for a chip that is described, not attached (on-chip-measurement guide
§2). Interpret mode — every other kernel test in this suite — cannot
see what Mosaic refuses: the head_dim-64 page ("Slice shape along
dimension 3 must be aligned to tiling (128), but is 64") and the
``[page, 1]`` int8 scale column ("…but is 1") both passed every
interpret-mode test and were refused here. These cases keep the
kernels of the serving path compiling for v5e at the Llama-3.2-1B
widths (Hq 32 / Hkv 8 / head_dim 64 / page 64), about two seconds a
case, at no chip time. A compile that passes is not a chip run:
``chip_smoke.py`` is.

The last cases compile the ENGINE's own step programs — the paged decode
step, one bucket prefill, one chunk prefill — for a two-layer model at
SmolLM2-1.7B's and Mistral-7B's published widths (with one layer the
layer slice folds away and proves nothing). Two things are held:

- their names against ``benchmarks/data/trace_names.json``: the
  benchmark finds the step programs and the attention kernels in the
  profiler's trace by the names the compiler gives them today
  (``jit__decode_sample``, ``jit_fused``, ``%closed_call.N = ...
  custom-call(``). A ``name=`` on a ``pallas_call``, a
  ``jax.named_scope`` around one, or a renamed jitted function changes
  those names; this fails then, here, instead of two roofline metrics
  reading nothing on the chip;
- the page pool's ONE physical layout: row-major ``{4,3,2,1,0}`` from
  program entry to exit, no copy of the pool or of a layer's slice of
  it, no temp that follows the pool's size. A pool written by ROWS is
  laid out token-major by XLA and copied for the kernel in every
  program and every layer-step — a third of the device's time in
  PR 25's traces (``ops/paged_kv.pool_write``). The dense DECODE
  program writes through the kernel instead (the decode walk lays a
  slot's fresh row into the pool, aliased in and out): it holds no
  gather or scatter of a page set at all — ``pool_write`` of one row
  moved four page sets of every compiled slot a layer-step, a quarter
  of a chat decode pass (PERF.md section 6, PR 31).

The topology is described in a fixture, never at import: only one
process may load the TPU's library, and every test worker imports
every test file (on-chip-measurement guide §2).
"""

import importlib.util
import os
import re
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
# libtpu admits one process at a time behind /tmp/libtpu_lockfile; no
# chip is attached here, so parallel test workers may all load it
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from gofr_tpu.ops.flash_attention import flash_attention
from gofr_tpu.ops.paged_attention import (paged_chunk_attention_pallas,
                                          paged_decode_attention_pallas,
                                          paged_tree_attention_pallas)
from gofr_tpu.ops.paged_kv import head_pack, scale_width

B, HQ, HKV, PAGE, N_PAGES, MAX_PAGES = 4, 32, 8, 64, 512, 16
REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e, with the persistent compile cache
    off while this file's tests run: a compile for a described chip is
    written to it but cannot be read back without one — the next run
    would warn ("Error reading persistent compilation cache entry") and
    compile again."""
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, chip):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _pool(hd, quantized, chip):
    """One layer's pool as the engine lays it out (ops/paged_kv.py)."""
    pack = head_pack(HKV, hd)
    shape = (HKV // pack, N_PAGES, PAGE, pack * hd)
    if not quantized:
        return _shape(shape, jnp.bfloat16, chip)
    return {"q": _shape(shape, jnp.int8, chip),
            "s": _shape((*shape[:2], 1, scale_width(pack, PAGE)),
                        jnp.float32, chip)}


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(chip):
    q = _shape((B, 1024, HQ, 64), jnp.bfloat16, chip)
    kv = _shape((B, 1024, HKV, 64), jnp.bfloat16, chip)
    _compiles_to_kernel(
        lambda q, k, v, n: flash_attention(q, k, v, kv_lengths=n),
        q, kv, kv, _shape((B,), jnp.int32, chip))


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("kind,hd", [("decode", 64), ("decode", 128),
                                     ("chunk", 64), ("tree", 64),
                                     ("layer", 64)])
def test_paged_kernel_compiles_for_v5e(kind, hd, quantized, chip):
    pool = _pool(hd, quantized, chip)
    tables = _shape((B, MAX_PAGES), jnp.int32, chip)
    lens = _shape((B,), jnp.int32, chip)
    if kind == "layer":         # the whole pool and a traced layer index
        whole = jax.tree.map(lambda x: _shape((4, *x.shape), x.dtype, chip),
                             pool)
        _compiles_to_kernel(
            lambda q, k, v, t, n, li: paged_decode_attention_pallas(
                q, k, v, t, n, layer=li),
            _shape((B, HQ, hd), jnp.bfloat16, chip), whole, whole, tables,
            lens, _shape((), jnp.int32, chip))
    elif kind == "decode":
        _compiles_to_kernel(paged_decode_attention_pallas,
                            _shape((B, HQ, hd), jnp.bfloat16, chip),
                            pool, pool, tables, lens)
    elif kind == "chunk":       # a 256-row prefill chunk: two q blocks
        _compiles_to_kernel(paged_chunk_attention_pallas,
                            _shape((B, 256, HQ, hd), jnp.bfloat16, chip),
                            pool, pool, tables, lens, lens)
    else:                       # an 8-node draft tree
        _compiles_to_kernel(paged_tree_attention_pallas,
                            _shape((B, 8, HQ, hd), jnp.bfloat16, chip),
                            pool, pool, tables, lens, lens,
                            _shape((B, 8), jnp.int32, chip))


#: the benchmark's engines (benchmarks/configs/): 32 slots of 128 pages
#: of 64 rows; SmolLM2-1.7B 32 kv heads of 64 (16 head groups a page),
#: Mistral-7B 8 of 128
ENGINE_SLOTS, ENGINE_SLOT_PAGES = 32, 128
ENGINE_HEADS = {64: (32, 32), 128: (32, 8)}


@pytest.mark.parametrize("writes", [False, True], ids=["reads", "writes"])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("hd", sorted(ENGINE_HEADS))
def test_decode_walk_compiles_at_the_engines_geometry(hd, quantized, writes,
                                                      chip):
    """The decode walk's fold is sized from (head groups, page, row
    width, dtype): at the engine's real geometry its double buffer
    must fit the scoped VMEM a kernel gets without asking (16 MiB on
    v5e — the compile raises past it), with room for the fold's
    float32 scores beside it. ``writes``: the entry the model step
    calls, the step's fresh rows going into the pool — inside the walk
    for a plain pool (the aliased pool, a block read by one DMA and
    written by another in one cell, a dynamic block offset), in front
    of it for an int8 one."""
    from gofr_tpu.ops.paged_attention import (
        FOLD_BYTES, _fold_pages, paged_decode_append_attention_pallas)
    hq, hkv = ENGINE_HEADS[hd]
    pack = head_pack(hkv, hd)
    shape = (4, hkv // pack, N_PAGES, PAGE, pack * hd)
    pool = _shape(shape, jnp.bfloat16, chip)
    if quantized:
        pool = {"q": _shape(shape, jnp.int8, chip),
                "s": _shape((*shape[:3], 1, scale_width(pack, PAGE)),
                            jnp.float32, chip)}
    pages = _fold_pages(shape[1], PAGE, shape[-1], 1 if quantized else 2,
                        ENGINE_SLOT_PAGES)
    fold = 2 * shape[1] * pages * PAGE * shape[-1] * (1 if quantized else 2)
    assert FOLD_BYTES // 2 < fold <= FOLD_BYTES and 2 * fold <= 8 << 20
    rows = [_shape((ENGINE_SLOTS, hkv, hd), jnp.bfloat16, chip)] * 2
    _compiles_to_kernel(
        (lambda q, kn, vn, k, v, t, n, li:
         paged_decode_append_attention_pallas(q, kn, vn, k, v, t, n,
                                              layer=li)) if writes else
        (lambda q, kn, vn, k, v, t, n, li: paged_decode_attention_pallas(
            q, k, v, t, n, layer=li)),
        _shape((ENGINE_SLOTS, hq, hd), jnp.bfloat16, chip), *rows, pool,
        pool, _shape((ENGINE_SLOTS, ENGINE_SLOT_PAGES), jnp.int32, chip),
        _shape((ENGINE_SLOTS,), jnp.int32, chip),
        _shape((), jnp.int32, chip))


# ------------------------------ the names the benchmark's trace reader uses
@pytest.fixture(scope="module")
def trace_reader():
    """benchmarks/harness/trace.py, by path: the classification the
    benchmark itself applies to a program's and an operation's name."""
    spec = importlib.util.spec_from_file_location(
        "bench_trace", REPO / "benchmarks" / "harness" / "trace.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: published widths of the benchmark's two configurations
#: (benchmarks/configs/): SmolLM2-1.7B packs two kv heads a pool row
#: (Hg 16, head_dim 64), Mistral-7B-v0.3 is the unpacked path (Hg 8,
#: head_dim 128)
WIDTHS = {
    "smollm2": dict(vocab_size=49152, dim=2048, n_heads=32, n_kv_heads=32,
                    ffn_dim=8192, rope_theta=130000.0,
                    tie_embeddings=True),
    "mistral": dict(vocab_size=32768, dim=4096, n_heads=32, n_kv_heads=8,
                    ffn_dim=14336, rope_theta=1e6, tie_embeddings=False),
}
#: pages of the pool the programs are compiled for: a layer's slice
#: (128 MiB and up) then fits no fast memory the compiler could stage
#: it in, as a deployment's does not
POOL_PAGES = 1024


def _engine(widths):
    """The benchmark's builder on a two-layer model, kernels by name:
    no chip is attached, so nothing may be left to ``auto``. Weights
    are zeros of the right shapes — only shapes are compiled."""
    if widths in LATENT_WIDTHS:
        return _latent_engine(widths)
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import llama_engine
    c = LlamaConfig(n_layers=2, max_seq=8192, norm_eps=1e-5,
                    **WIDTHS[widths])
    params = jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: llama_init(jax.random.key(0), c)))
    return llama_engine(
        params, c,
        EngineConfig(max_batch=B, max_seq=2048, prefill_buckets=(128,),
                     prefill_batch=4, kv_layout="paged",
                     paged_attention="kernel", page_size=PAGE, kv_pages=64,
                     eos_id=-1, autoprof=False),
        implementation="pallas")


#: the latent, sparse family's two configurations
#: (benchmarks/configs/kanana-2-30b-a3b-6l.json: the class's defaults;
#: xing4-29b-a4b-8l.json: four mHC residual streams, q-LoRA, YaRN)
LATENT_WIDTHS = {
    "kanana": dict(num_hidden_layers=3),
    "xing4": dict(
        vocab_size=131072, hidden_size=3584, num_hidden_layers=3,
        first_k_dense_replace=2, q_lora_rank=768, intermediate_size=9216,
        moe_intermediate_size=1024, n_routed_experts=64, n_shared_experts=1,
        num_experts_per_tok=4, routed_scaling_factor=2.0, rope_theta=10000.0,
        max_position_embeddings=262144, hc_mult=4,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096}),
}


def _latent_engine(widths):
    """``deepseek_engine`` at a configuration's published widths, three
    layers (Kanana-2: one dense and two expert layers; Xing4.0: two
    dense and one), every expert of each. The weights are SHAPES: three
    such layers are gigabytes, and only shapes are compiled."""
    from gofr_tpu.models.deepseek import DeepseekConfig, deepseek_init
    from gofr_tpu.serving.engine import EngineConfig
    from gofr_tpu.serving.glue import deepseek_engine
    c = DeepseekConfig(**LATENT_WIDTHS[widths])
    params = jax.eval_shape(lambda: deepseek_init(jax.random.key(0), c))
    return deepseek_engine(
        params, c,
        EngineConfig(max_batch=B, max_seq=2048, prefill_buckets=(128,),
                     prefill_batch=4, kv_layout="paged",
                     paged_attention="kernel", page_size=PAGE, kv_pages=64,
                     eos_id=-1, autoprof=False))


def _step_program(engine, kind, pages, chip):
    """(jitted function, its arguments as shapes on the chip) for one
    of the engine's step programs, in the order ``Engine.warmup``
    passes them, over a pool of ``pages`` pages."""
    def like(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    def sh(shape, dtype):
        return _shape(shape, dtype, chip)

    def pool(x):
        return sh((*x.shape[:2], pages, *x.shape[3:]), x.dtype)

    params = like(engine.params)
    kc, vc = pool(engine.k_cache), pool(engine.v_cache)
    slot_pages = engine._pages_per_slot
    if kind == "decode":
        return engine._decode, (
            params, sh((B,), jnp.int32), sh((B,), bool),
            like(engine._dev_zero), kc, vc, sh((B, slot_pages), jnp.int32),
            sh((B,), jnp.int32), sh((B,), bool), sh((), jnp.int32),
            sh((B,), jnp.float32), sh((B,), jnp.float32),
            sh((B,), jnp.int32), like(engine._dev_decode_key))
    sampling = (sh((), jnp.int32), sh((1,), jnp.float32),
                sh((1,), jnp.float32), sh((1,), jnp.int32),
                like(engine._prefill_base_key))
    if kind == "bucket":
        return engine._get_prefill(128, 1), (
            params, sh((1, 128), jnp.int32), sh((1,), jnp.int32), kc, vc,
            sh((1, slot_pages), jnp.int32), *sampling)
    return engine._get_chunk_prefill(), (
        params, sh((1, 128), jnp.int32), kc, vc,
        sh((1, slot_pages), jnp.int32), sh((1,), jnp.int32),
        sh((1,), jnp.int32), *sampling)


@pytest.fixture(scope="module")
def compiled(chip):
    """``compiled(widths, kind, pages)`` -> (optimised HLO text, temp
    bytes, the pool's shape) of one engine step program, each compiled
    once for the file (about 20 s apiece)."""
    engines, programs = {}, {}

    def get(widths, kind, pages=POOL_PAGES):
        if (widths, kind, pages) not in programs:
            if widths not in engines:
                engines[widths] = _engine(widths)
            fn, args = _step_program(engines[widths], kind, pages, chip)
            exe = fn.lower(*args).compile()
            programs[widths, kind, pages] = (
                exe.as_text(), exe.memory_analysis().temp_size_in_bytes,
                (*engines[widths].k_cache.shape[:2], pages,
                 *engines[widths].k_cache.shape[3:]))
        return programs[widths, kind, pages]
    return get


@pytest.mark.parametrize("kind,program", [
    ("decode", "decode"), ("bucket", "prefill"), ("chunk", "prefill")])
def test_trace_names_find_the_engines_programs_and_kernels(
        kind, program, compiled, trace_reader):
    names = trace_reader.load_names()
    text, _, _ = compiled("smollm2", kind)
    # the profiler names an execution jit_<function>(<fingerprint>)
    module = re.match(r"HloModule (\S+?),", text).group(1)
    assert trace_reader.classify(module, names["programs"]) == program
    # ... and an operation by its HLO line, as the compiled text has it
    kernels = [line.strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels, "no Pallas kernel in the engine's step program"
    for line in kernels:
        assert trace_reader.classify(line, names["kernels"]) \
            == "attention", line[:160]


@pytest.mark.parametrize("widths", sorted(LATENT_WIDTHS))
def test_latent_programs_keep_one_attention_kernel_class(widths, compiled,
                                                         trace_reader):
    """The latent kernel is the one Pallas call of the decode and chunk
    programs that the trace names ``%closed_call``: XLA's grouped
    matmul is a Mosaic kernel too, under its own name
    (``%ragged-dot-...``), and must not read as attention. The stream
    mixes of a multi-stream residual are plain XLA: a Pallas call there
    would read as attention and spoil the cell's attention roofline."""
    names = trace_reader.load_names()
    for kind, program in (("decode", "decode"), ("chunk", "prefill"),
                          ("bucket", "prefill")):
        text, _, _ = compiled(widths, kind)
        module = re.match(r"HloModule (\S+?),", text).group(1)
        assert trace_reader.classify(module, names["programs"]) == program
        kernels = [line.strip() for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        classes = {line.split(" = ")[0].rstrip(".0123456789"):
                   trace_reader.classify(line, names["kernels"])
                   for line in kernels}
        assert all(k.startswith("%ragged-dot") for k, cls in classes.items()
                   if cls is None), classes
        # the bucket program attends on XLA (PERF.md section 7)
        assert ("attention" in classes.values()) == (kind != "bucket"), \
            classes
        # one latent kernel a layer scan (dense layers, expert layers)
        assert sum(cls == "attention" for cls in (
            trace_reader.classify(line, names["kernels"])
            for line in kernels)) == (0 if kind == "bucket" else 2)


# -------------------------------------- the pool's one physical layout
#: results that hand the pool on or update it in place (a custom-call
#: that returns the pool is held to its aliasing below)
POOL_CARRIERS = {"parameter", "tuple", "get-tuple-element", "bitcast",
                 "while", "conditional", "call", "scatter", "custom-call"}
_RESULT = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\](?:\{([\d,]*))?")


def _pool_shaped_results(text, pool_shape):
    """(instruction, opcode, dims, layout) for every result in the
    optimised HLO that has the pool's shape or one layer's slice of it;
    a fusion's opcode is its root's (``fusion:scatter``)."""
    roots, computation = {}, None
    for line in text.splitlines():
        if line.startswith("%") and line.rstrip().endswith("{"):
            computation = line.split()[0]
        m = _RESULT.match(line)
        if m and "ROOT" in line.split("=")[0]:
            roots[computation] = m.group(3)
    whole = ",".join(map(str, pool_shape))
    layer = ",".join(map(str, pool_shape[1:]))
    shapes = {whole, layer, "1," + layer}
    # a one-head pool (a latent row, Hg 1) is handed on with its unit
    # dim squeezed away — a bitcast: the same buffer under another shape
    shapes |= {",".join(d for d in x.split(",") if d != "1")
               for x in (whole, layer)}
    found = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        name, types, op = m.groups()
        if op == "fusion":
            op = "fusion:" + roots.get(
                re.search(r"calls=(%[\w.-]+)", line).group(1), "?")
        for dims, layout in _ARRAY.findall(types):
            if dims in shapes:
                found.append((name, op, dims, layout))
    return found


@pytest.mark.parametrize("widths", [*sorted(WIDTHS),
                                    *sorted(LATENT_WIDTHS)])
@pytest.mark.parametrize("kind", ["decode", "bucket", "chunk"])
def test_pool_keeps_one_layout_and_is_never_copied(kind, widths, compiled):
    text, temp, pool_shape = compiled(widths, kind)
    results = _pool_shaped_results(text, pool_shape)
    # the dense decode program writes through the decode walk, the pool
    # aliased in and out of the kernel; every other program scatters pages
    kernels = {name for name, op, _, _ in results if op == "custom-call"}
    assert bool(kernels) == (kind == "decode" and widths in WIDTHS), kernels
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(1) in kernels:
            assert "output_to_operand_aliasing" in line, line[:200]
    assert kernels or any(op in ("scatter", "fusion:scatter")
                          for _, op, _, _ in results), \
        "the program writes no pool?"
    copies = [r for r in results
              if r[1] not in POOL_CARRIERS | {"fusion:scatter"}]
    assert not copies, f"the pool, or a layer of it, is copied: {copies}"
    relaid = [r for r in results if r[3] and r[3] != ",".join(
        map(str, reversed(range(r[2].count(",") + 1))))]
    assert not relaid, f"the pool leaves row-major: {relaid}"
    # a temp that follows the slab is fine; one that follows the pool
    # is the relayout
    _, temp_twice, _ = compiled(widths, kind, 2 * POOL_PAGES)
    assert temp_twice <= temp, (temp, temp_twice)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_dense_decode_moves_no_page_set(widths, compiled, trace_reader):
    """Decode's one fresh row a slot reaches the pool inside the decode
    walk: the compiled dense decode program holds no ``gather`` or
    ``scatter`` (nor a fusion of one: fused computations are in the
    text) of a slot-by-page-set ``[B, Hg, page, W]`` or of the pool —
    ``pool_write`` in front of the walk was two of each a layer — and
    its one Pallas kernel still reads as ``attention`` in the trace."""
    text, _, pool_shape = compiled(widths, "decode")
    _, hg, _, page, width = pool_shape
    moved = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or m.group(3) not in ("gather", "scatter"):
            continue
        for dims, _ in _ARRAY.findall(m.group(2)):
            shape = tuple(int(d) for d in dims.split(",") if d)
            if shape == tuple(pool_shape) or shape[-3:] == (hg, page, width):
                moved.append((m.group(1), m.group(3), shape))
    assert not moved, f"page sets are moved around the kernel: {moved}"
    kernels = [line.strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1, kernels
    assert trace_reader.classify(
        kernels[0], trace_reader.load_names()["kernels"]) == "attention"
