"""Mosaic compile rehearsal: the serving path's Pallas kernels compiled for a
described (not attached) TPU v5e chip at smollm-360m widths.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: blocks that do not tile, or more VMEM than a kernel may hold.  These
tests lower each kernel with ``interpret=False`` against one device of a
``v5e:2x2`` topology description and compile it, so such faults fail here
instead of on the chip.  Nothing runs; results are covered by the parity
tests.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers each
import every test module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ops import (flash_decode, paged_flash_decode,
                               paged_flash_packed_chunk,
                               serving_probe_spec_step, serving_probe_step)

SLOTS = 8            # serving batch (engine slots)
CACHE = 2048         # virtual KV positions per slot
BS = 16              # page size (tokens)
CHUNK = 64           # packed-chunk tokens
PACK = 4             # segments per packed chunk
SPEC = 4             # verify tokens per slot (speculative probe chain)
WINDOW = 4           # probe smoothing window


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from a persistent
    # cache without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def widths():
    cfg = get_config("smollm-360m")
    return cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model


def _compiled_has_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_serving_probe_step_compiles(sds, widths):
    f = widths[3]
    f32, i32 = jnp.float32, jnp.int32
    args = (sds((SLOTS, f), f32), sds((SLOTS, f), f32),
            sds((SLOTS,), jnp.bool_), sds((SLOTS, f), f32),
            sds((SLOTS,), f32), sds((SLOTS, WINDOW), f32),
            sds((SLOTS,), i32), sds((SLOTS,), jnp.bool_), sds((SLOTS,), i32),
            sds((), f32), sds((), f32))
    _compiled_has_kernel(serving_probe_step.lower(*args, burn_in=2,
                                                  interpret=False))


def test_serving_probe_spec_step_compiles(sds, widths):
    f = widths[3]
    f32, i32 = jnp.float32, jnp.int32
    args = (sds((SLOTS, SPEC, f), f32), sds((SLOTS, SPEC, f), f32),
            sds((SLOTS, SPEC), jnp.bool_), sds((SLOTS,), i32),
            sds((SLOTS, f), f32), sds((SLOTS,), f32),
            sds((SLOTS, WINDOW), f32), sds((SLOTS,), i32),
            sds((SLOTS,), jnp.bool_), sds((SLOTS,), i32),
            sds((), f32), sds((), f32))
    _compiled_has_kernel(serving_probe_spec_step.lower(*args, burn_in=2,
                                                       interpret=False))


def _pool(sds, kv, d, kv_dtype, slots=SLOTS, nb=CACHE // BS):
    pages = slots * nb + 1                      # + the NULL page
    dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    kp = sds((pages, kv, BS, d), dt)
    scales = ((sds((pages, kv, BS, 1), jnp.float32),) * 2
              if kv_dtype == "int8" else (None, None))
    return nb, kp, scales


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_flash_decode_compiles(sds, widths, kv_dtype):
    """At the serving shapes above and at the reasoning benchmark cell's:
    16 rows, 253 block-table entries (caches to 4042 positions)."""
    h, kv, d, _ = widths
    for slots, nb in ((SLOTS, CACHE // BS), (16, 253)):
        nb, kp, (ks, vs) = _pool(sds, kv, d, kv_dtype, slots, nb)
        lowered = paged_flash_decode.lower(
            sds((slots, h, d), jnp.float32), kp, kp,
            sds((slots, nb), jnp.int32), sds((slots, nb * BS), jnp.bool_),
            ks, vs, interpret=False, return_partials=True)
        _compiled_has_kernel(lowered)


@pytest.mark.parametrize("d,bs", [(32, 8), (32, 16), (32, 32), (64, 8),
                                  (80, 16), (128, 16)])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_flash_decode_compiles_at_other_head_dims(sds, d, bs,
                                                        kv_dtype):
    """Every lane layout of a page: 128 / d positions a lane row where the
    page fills whole 8-row tiles (d 32 at 32-token pages, d 64 at 16),
    else d padded to 128 lanes.  Packing d 32 into 16-token pages made a
    4-row DMA slice, which Mosaic refuses."""
    slots, nb = 4, 40
    dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    kp = sds((slots * nb + 1, 5, bs, d), dt)
    scales = ((sds((slots * nb + 1, 5, bs, 1), jnp.float32),) * 2
              if kv_dtype == "int8" else ())
    lowered = paged_flash_decode.lower(
        sds((slots, 15, d), jnp.float32), kp, kp,
        sds((slots, nb), jnp.int32), sds((slots, nb * bs), jnp.bool_),
        *scales, interpret=False, return_partials=True)
    _compiled_has_kernel(lowered)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_flash_packed_chunk_compiles(sds, widths, kv_dtype):
    h, kv, d, _ = widths
    nb, kp, (ks, vs) = _pool(sds, kv, d, kv_dtype)
    lowered = paged_flash_packed_chunk.lower(
        sds((CHUNK, h, d), jnp.float32), kp, kp,
        sds((CHUNK,), jnp.int32), sds((PACK, nb), jnp.int32),
        sds((PACK, nb * BS), jnp.bool_), ks, vs, interpret=False)
    _compiled_has_kernel(lowered)


def test_flash_decode_compiles(sds, widths):
    h, kv, d, _ = widths
    k = sds((SLOTS, kv, CACHE, d), jnp.bfloat16)
    lowered = flash_decode.lower(
        sds((SLOTS, h, d), jnp.bfloat16), k, k,
        sds((SLOTS, CACHE), jnp.bool_), bs=512, interpret=False)
    _compiled_has_kernel(lowered)
