"""The data-plane kernels compile for a TPU v5e at full size (DESIGN.md §9).

Nothing runs: each kernel is lowered for one chip of a ``v5e:2x2`` topology
that is described, not attached, and compiled by the TPU compiler installed
with JAX. That catches what the CPU backend and interpret-mode Pallas
cannot, such as a kernel the chip's compiler refuses, at no chip time.
The topology is described inside a fixture, and the persistent compilation
cache is off around these compiles (an entry written for a described chip
cannot be read back without one).
"""
import os

import numpy as np
import pytest

from repro.mv import dataplane as dp

ROWS = 1 << 23  # ~8.4e6 rows: one base table of the chip smoke run
INDEX = 1 << 20  # sorted-unique probe index length (a pow2 bucket)


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.config.update("jax_enable_x64", True)  # int64/uint64 table columns
    yield desc
    jax.config.update("jax_enable_x64", prev_x64)
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


# kernel name -> (argument (shape, dtype) list, static extras); the shapes
# and dtypes are the ones the refresh path calls each kernel with
_F32 = ((ROWS,), np.float32)
_I64 = ((ROWS,), np.int64)
KERNELS = {
    "hash": ([_I64], ()),
    "pid": ([_I64], (8,)),
    "map_mul": ([_F32], ()),
    "encode": ([_F32], ()),
    "encode_w": ([_F32, _I64], ()),
    "probe": ([((INDEX,), np.int64), _I64, ((), np.int64)], ()),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_xla_kernel_compiles_for_v5e(one_chip, name):
    import jax

    shapes, static = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    out = dp._jk()[name].lower(*args, *static).compile().out_info
    out = out if isinstance(out, tuple) else (out,)
    assert out[0].shape == (ROWS,)  # map_mul adds a scalar flag


def test_pallas_map_multiply_compiles_to_tpu_custom_call(one_chip):
    """The 32-bit Pallas kernel the v5e compiler accepts: the map's
    multiply, built here as ``dataplane._pk`` builds it (one _BLOCK-row
    block per grid step)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def map_mul_kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...] * jnp.float32(1.0001)

    spec = pl.BlockSpec((dp._BLOCK,), lambda i: (i,))
    call = pl.pallas_call(
        map_mul_kernel, grid=(ROWS // dp._BLOCK,), in_specs=[spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct((ROWS,), np.float32),
    )
    x = jax.ShapeDtypeStruct((ROWS,), np.float32, sharding=one_chip)
    assert "tpu_custom_call" in jax.jit(call).lower(x).compile().as_text()
