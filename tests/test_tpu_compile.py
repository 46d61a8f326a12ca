"""The communication kernels compile for a TPU v5e chip, with no chip.

Each case lowers one kernel wrapper of ``kernels/ops.py`` with the compiled
Pallas implementation (``impl="pallas"``, i.e. ``interpret=False``) for a
described ``v5e:2x2`` topology, at the 32 MiB f32 bucket the gradient sync
uses and at one ragged length, and checks that the TPU compiler accepted
the kernel (``tpu_custom_call`` in the compiled program).  Interpret-mode
tests cannot see what the TPU compiler refuses, e.g. a block shape that
breaks the (8, 128) tiling rule.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

BUCKET = 8 * 1024 * 1024          # 32 MiB of f32, the default bucket
RAGGED = 3 * 1024 * 1024 + 17     # not a tile multiple


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _quantize_ef(n, sh):
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sh)
    return (lambda g, e: ops.quantize_ef(g, e, impl="pallas"), f32, f32)


def _quantize_tiles(n, sh):
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sh)
    return (lambda x: ops.quantize_tiles(x, impl="pallas"), f32)


def _dequant_accum(w):
    def make(n, sh):
        ntiles = -(-n // ops.TILE)
        q = jax.ShapeDtypeStruct((w, n), jnp.int8, sharding=sh)
        s = jax.ShapeDtypeStruct((w, ntiles), jnp.float32, sharding=sh)
        return (lambda q, s: ops.dequant_accum(q, s, impl="pallas"), q, s)
    return make


def _topk_ef(n, sh):
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sh)
    return (lambda g, e: ops.topk_ef(g, e, impl="pallas"), f32, f32)


KERNELS = {"quantize_ef": _quantize_ef, "quantize_tiles": _quantize_tiles,
           "dequant_accum_w1": _dequant_accum(1),
           "dequant_accum_w4": _dequant_accum(4), "topk_ef": _topk_ef}


@pytest.mark.parametrize("n", [BUCKET, RAGGED], ids=["bucket", "ragged"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, n):
    fn, *shapes = KERNELS[kernel](n, one_chip)
    assert "tpu_custom_call" in _compile_text(fn, *shapes)
