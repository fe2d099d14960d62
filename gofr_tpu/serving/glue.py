"""Model -> Engine glue: build engines from model families.

Single-device and mesh-sharded serving share one engine: pass
``mesh=`` to shard the model Megatron-style (``parallel/sharding.py``
specs) and the KV cache over the mesh's ``tp`` axis on the kv-head
dim. The decode step stays ONE donated jitted call — XLA inserts the
all-gathers/reduce-scatters over ICI; nothing in the engine hot loop
changes. This is the serving analog of the reference's horizontal
scale-out behind its service client (reference
pkg/gofr/service/new.go:68); on TPU the "replicas" are mesh shards in
a single SPMD program, coordinated by the runtime rather than HTTP.

``EngineConfig.kv_dtype="int8"`` and the pool's lane packing need NO
glue here: ``make_cache`` describes the model-dtype K/V rows and the
engine builds the pool in its final representation
(``engine._alloc_pool``). The paged model fns below take whole pools
and route writes through ``ops.paged_kv.pool_write``, which is
pytree-aware — so native decode, chunked prefill, prefix-cache
reattach and speculative verify all ride the quantized layout
unchanged.
"""

from __future__ import annotations

from typing import Any

from ..models.llama import (
    LlamaConfig,
    llama_decode_step,
    llama_init,
    llama_prefill_chunk,
    llama_prefill_last,
    make_empty_cache,
)
from .engine import Engine, EngineConfig


def _kv_sharding(mesh: Any):
    """NamedSharding for [L, B, S, Hkv, hd] caches / prompt-KV slabs:
    kv heads over ``tp``, everything else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tp = "tp" if "tp" in mesh.axis_names else None
    return NamedSharding(mesh, P(None, None, None, tp, None))


def llama_engine(params: Any, model_config: LlamaConfig,
                 engine_config: EngineConfig | None = None, *,
                 mesh: Any = None,
                 metrics: Any = None, logger: Any = None,
                 tracer: Any = None,
                 implementation: str = "auto",
                 quantize: str | None = None) -> Engine:
    engine_config = engine_config or EngineConfig()
    c = model_config
    if quantize is not None:
        if quantize not in ("int8", "int4"):
            raise ValueError(f"quantize must be None, 'int8' or "
                             f"'int4', got {quantize!r}")
        # weight-only quantization: int8 halves / int4 quarters the
        # HBM param stream in the memory-bound decode (ops/quant.py);
        # the model functions detect quantized leaves per-matrix, and
        # the sharding specs descend into the {'q','s'} leaves
        # (parallel/sharding.py _match_specs), so both compose with
        # mesh serving
        from ..ops.quant import quantize_llama_int4, quantize_llama_int8
        params = (quantize_llama_int8(params) if quantize == "int8"
                  else quantize_llama_int4(params))

    constrain_kv = None
    if mesh is not None:
        import jax
        from ..parallel.sharding import llama_param_specs, shard_params
        if implementation == "auto":
            # the Pallas kernels are single-device: GSPMD refuses them
            # ("Mosaic kernels cannot be automatically partitioned.
            # Please wrap the call in a shard_map" — the v5e compiler,
            # described 2x2 mesh). Until they are shard_mapped over the
            # head axis (ROADMAP B1) a sharded engine is built on XLA
            # attention, chosen here by name, not found out in warmup.
            implementation = "xla"
        params = shard_params(params, mesh, llama_param_specs(mesh))
        kv_sharding = _kv_sharding(mesh)

        def constrain_kv(t):
            # pin cache outputs to the input sharding so the donated
            # buffers round-trip in place across passes
            return jax.lax.with_sharding_constraint(t, kv_sharding)

    def prefill_fn(params, tokens, kv_lengths):
        # last-position logits only: a serving prefill never needs the
        # [S, vocab] head matmul (larger than the whole backbone at
        # short S) for positions it won't sample from
        logits, (k, v) = llama_prefill_last(
            params, tokens, c, kv_lengths=kv_lengths,
            implementation=implementation)
        if constrain_kv is not None:
            k, v = constrain_kv(k), constrain_kv(v)
        return logits, (k, v)

    def decode_fn(params, tokens, k_cache, v_cache, lengths):
        logits, kc, vc = llama_decode_step(params, tokens, k_cache,
                                           v_cache, lengths, c)
        if constrain_kv is not None:
            kc, vc = constrain_kv(kc), constrain_kv(vc)
        return logits, kc, vc

    def prefill_chunk_fn(params, tokens, k_cache, v_cache, offsets,
                         chunk_lengths):
        logits, kc, vc = llama_prefill_chunk(
            params, tokens, k_cache, v_cache, offsets, chunk_lengths, c,
            implementation=implementation)
        if constrain_kv is not None:
            kc, vc = constrain_kv(kc), constrain_kv(vc)
        return logits, kc, vc

    def spec_verify_fn(params, tokens, k_cache, v_cache, offsets,
                       chunk_lengths, tree_depths=None, tree_masks=None):
        logits, kc, vc = llama_prefill_chunk(
            params, tokens, k_cache, v_cache, offsets, chunk_lengths, c,
            implementation=implementation, return_all_logits=True,
            tree_depths=tree_depths, tree_masks=tree_masks)
        if constrain_kv is not None:
            kc, vc = constrain_kv(kc), constrain_kv(vc)
        return logits, kc, vc

    def make_cache(batch, max_seq):
        kc, vc = make_empty_cache(c, batch, max_seq=max_seq)
        if mesh is not None:
            import jax
            kc = jax.device_put(kc, _kv_sharding(mesh))
            vc = jax.device_put(vc, _kv_sharding(mesh))
        return kc, vc

    paged_decode_fn = None
    paged_chunk_fn = None
    paged_verify_fn = None
    if mesh is None:
        # native paged serving: rows written through the block table,
        # ragged paged-attention kernels read pages in place — no
        # per-pass view materialisation on decode, chunked prefill,
        # prefix reattachment or speculative verify. (The mesh path
        # keeps the view: the kernels are single-device until they are
        # shard_mapped, ROADMAP B1, and the view path already shards.)
        from ..models.llama import (llama_decode_step_paged,
                                    llama_prefill_chunk_paged)
        impl = {"kernel": "pallas", "interpret": "interpret",
                "xla": "xla"}.get(engine_config.paged_attention, "auto")

        def paged_decode_fn(params, tokens, k_pool, v_pool, tables,
                            lengths):
            return llama_decode_step_paged(params, tokens, k_pool,
                                           v_pool, tables, lengths, c,
                                           implementation=impl)

        def paged_chunk_fn(params, tokens, k_pool, v_pool, tables,
                           offsets, chunk_lengths):
            return llama_prefill_chunk_paged(
                params, tokens, k_pool, v_pool, tables, offsets,
                chunk_lengths, c, implementation=impl)

        def paged_verify_fn(params, tokens, k_pool, v_pool, tables,
                            offsets, chunk_lengths, tree_depths=None,
                            tree_masks=None):
            return llama_prefill_chunk_paged(
                params, tokens, k_pool, v_pool, tables, offsets,
                chunk_lengths, c, implementation=impl,
                return_all_logits=True, tree_depths=tree_depths,
                tree_masks=tree_masks)

    return Engine(params, engine_config, prefill_fn=prefill_fn,
                  decode_fn=decode_fn, make_cache=make_cache,
                  prefill_chunk_fn=prefill_chunk_fn,
                  spec_verify_fn=spec_verify_fn,
                  paged_decode_fn=paged_decode_fn,
                  paged_chunk_fn=paged_chunk_fn,
                  paged_verify_fn=paged_verify_fn,
                  metrics=metrics, logger=logger, tracer=tracer)


def moe_engine(params: Any, model_config, engine_config: EngineConfig | None = None,
               *, metrics: Any = None, logger: Any = None,
               tracer: Any = None,
               implementation: str = "auto") -> Engine:
    from ..models.moe import moe_decode_step, moe_prefill_last
    import jax.numpy as jnp
    engine_config = engine_config or EngineConfig()
    c = model_config

    def prefill_fn(params, tokens, kv_lengths):
        logits, caches, _router = moe_prefill_last(
            params, tokens, c, kv_lengths=kv_lengths,
            implementation=implementation)
        return logits, caches

    def decode_fn(params, tokens, k_cache, v_cache, lengths):
        return moe_decode_step(params, tokens, k_cache, v_cache,
                               lengths, c)

    def make_cache(batch, max_seq):
        shape = (c.n_layers, batch, max_seq, c.n_kv_heads, c.head_dim)
        return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)

    return Engine(params, engine_config, prefill_fn=prefill_fn,
                  decode_fn=decode_fn, make_cache=make_cache,
                  metrics=metrics, logger=logger, tracer=tracer)


def deepseek_engine(params: Any, model_config,
                    engine_config: EngineConfig | None = None, *,
                    mesh: Any = None,
                    metrics: Any = None, logger: Any = None,
                    tracer: Any = None) -> Engine:
    """The ``deepseek_v3`` family (models/deepseek.py) on the paged
    serving path: bucket prefill with materialised attention, chunk
    prefill and decode with absorbed attention straight against the
    latent page pool, experts computed sparsely. The family states its
    cache row through ``make_cache`` — one latent vector a token, a V
    side of no lanes — and the engine's pool, writers and capacity maths
    follow from that statement.

    What the family does not support is refused here, by name, not
    found out in warm-up."""
    from ..models.deepseek import (deepseek_decode_step_paged,
                                   deepseek_prefill_chunk_paged,
                                   deepseek_prefill_last,
                                   make_latent_cache, step_fact_readers)
    from ..ops.latent_attention import check_latent_layout
    from ..ops.paged_kv import pool_from_cache_shape
    from dataclasses import replace
    from ..ops.attention import is_tpu
    c = model_config
    cfg = engine_config or EngineConfig()
    if cfg.paged_attention == "auto":
        # the engine's own "auto" falls back to the dense view off the
        # TPU, which this family has no step for
        cfg = replace(cfg,
                      paged_attention="kernel" if is_tpu() else "xla")
    refused = [
        (mesh is not None,
         "mesh=: the latent kernel and the grouped expert matmul are "
         "single-device programs; neither is shard_mapped yet"),
        (cfg.kv_dtype != "bf16",
         f"kv_dtype={cfg.kv_dtype!r}: a latent row's 512 compressed lanes "
         f"and 64 rope lanes have no int8 scale layout yet"),
        (cfg.speculative,
         "speculative=True: the latent kernel has no tree-verify mask"),
        (cfg.paged_attention == "view",
         "paged_attention='view': the family has no dense-view decode "
         "step; use 'auto', 'kernel', 'interpret' or 'xla'"),
    ]
    for bad, why in refused:
        if bad:
            raise ValueError(f"deepseek_engine does not support {why}")
    impl = {"kernel": "pallas", "interpret": "interpret",
            "xla": "xla"}.get(cfg.paged_attention, "auto")
    if impl == "pallas":
        # a row the compiled kernel cannot take fails HERE, by name
        check_latent_layout(
            pool_from_cache_shape(make_latent_cache(c, 1, cfg.page_size)[0]),
            c.kv_lora_rank)

    def prefill_fn(params, tokens, kv_lengths):
        return deepseek_prefill_last(params, tokens, c,
                                     kv_lengths=kv_lengths)

    def make_cache(batch, max_seq):
        return make_latent_cache(c, batch, max_seq)

    def paged_decode_fn(params, tokens, pool, v_pool, tables, lengths):
        return deepseek_decode_step_paged(params, tokens, pool, v_pool,
                                          tables, lengths, c,
                                          implementation=impl)

    def paged_chunk_fn(params, tokens, pool, v_pool, tables, offsets,
                       chunk_lengths):
        return deepseek_prefill_chunk_paged(
            params, tokens, pool, v_pool, tables, offsets, chunk_lengths,
            c, implementation=impl)

    return Engine(params, cfg, prefill_fn=prefill_fn,
                  make_cache=make_cache,
                  paged_decode_fn=paged_decode_fn,
                  paged_chunk_fn=paged_chunk_fn,
                  decode_facts=step_fact_readers(c),
                  metrics=metrics, logger=logger, tracer=tracer)


def demo_llama_engine(engine_config: EngineConfig | None = None,
                      seed: int = 0, **kw) -> Engine:
    """Tiny random-weight engine for tests and examples."""
    import jax
    c = LlamaConfig.tiny()
    params = llama_init(jax.random.key(seed), c)
    return llama_engine(params, c,
                        engine_config or EngineConfig(max_batch=4, max_seq=128),
                        implementation="xla", **kw)
