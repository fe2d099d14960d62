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
"""

import os

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

try:
    _CHIP = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
except Exception as exc:  # no TPU compiler in this installation
    pytest.skip(f"cannot describe a v5e topology here: {exc!r}",
                allow_module_level=True)

B, HQ, HKV, PAGE, N_PAGES, MAX_PAGES = 4, 32, 8, 64, 512, 16


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one — the next run would
    warn ("Error reading persistent compilation cache entry") and
    compile again. Keep these out of it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_CHIP)


def _pool(hd, quantized):
    """One layer's pool as the engine lays it out (ops/paged_kv.py)."""
    pack = head_pack(HKV, hd)
    shape = (HKV // pack, N_PAGES, PAGE, pack * hd)
    if not quantized:
        return _shape(shape, jnp.bfloat16)
    return {"q": _shape(shape, jnp.int8),
            "s": _shape((*shape[:2], 1, scale_width(pack, PAGE)),
                        jnp.float32)}


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e():
    q = _shape((B, 1024, HQ, 64), jnp.bfloat16)
    kv = _shape((B, 1024, HKV, 64), jnp.bfloat16)
    _compiles_to_kernel(
        lambda q, k, v, n: flash_attention(q, k, v, kv_lengths=n),
        q, kv, kv, _shape((B,), jnp.int32))


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("kind,hd", [("decode", 64), ("decode", 128),
                                     ("chunk", 64), ("tree", 64)])
def test_paged_kernel_compiles_for_v5e(kind, hd, quantized):
    pool = _pool(hd, quantized)
    tables = _shape((B, MAX_PAGES), jnp.int32)
    lens = _shape((B,), jnp.int32)
    if kind == "decode":
        _compiles_to_kernel(paged_decode_attention_pallas,
                            _shape((B, HQ, hd), jnp.bfloat16),
                            pool, pool, tables, lens)
    elif kind == "chunk":       # a 256-row prefill chunk: two q blocks
        _compiles_to_kernel(paged_chunk_attention_pallas,
                            _shape((B, 256, HQ, hd), jnp.bfloat16),
                            pool, pool, tables, lens, lens)
    else:                       # an 8-node draft tree
        _compiles_to_kernel(paged_tree_attention_pallas,
                            _shape((B, 8, HQ, hd), jnp.bfloat16),
                            pool, pool, tables, lens, lens,
                            _shape((B, 8), jnp.int32))
