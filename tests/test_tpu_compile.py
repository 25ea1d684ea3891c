"""Main-path TPU programs compiled for a described TPU v5e (no chip needed).

Interpret-mode tests check what the Pallas kernels compute; only the TPU
compiler checks that Mosaic lowers them and that they fit in VMEM, and
only it sizes a whole program against the chip's HBM.  Each gated kernel
is compiled at the largest slice its availability gate admits, so a gate
that admits a shape the chip refuses fails here.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cluster_tools_tpu.ops import _backend, pallas_cc, pallas_flood
from cluster_tools_tpu.runtime.executor import DEVICE_BATCH

V5E_HBM_BYTES = 16 * 10**9  # Google Cloud "TPU v5e": 16 GB of HBM per chip
BLOCK = (50, 512, 512)  # runtime/config.py default block_shape


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2.  Described here, in a fixture, so
    only the worker that runs this file loads the TPU compiler library."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables compiled for a described chip cannot be read back from
    # the persistent cache without one: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flood_args(sh, shape):
    return (_spec(sh, shape, jnp.float32), _spec(sh, shape, jnp.int32),
            _spec(sh, shape, jnp.bool_))


_FLOOD_SLICE = (BLOCK[0], pallas_flood._MAX_SLICE_ELEMS // 512, 512)
_CC_TILE = pallas_cc.pallas_cc_tile(BLOCK)

KERNELS = {
    # whole-slice CC at the production block, the largest its gate admits
    "cc_slices": lambda sh: pallas_cc.cc_slices.lower(
        _spec(sh, BLOCK, jnp.bool_)),
    # the largest tile pallas_cc_tile picks
    "cc_tiles": lambda sh: pallas_cc.cc_tiles.lower(
        _spec(sh, BLOCK, jnp.bool_), tile_hw=_CC_TILE),
    "flood_slices": lambda sh: pallas_flood.flood_slices.lower(
        *_flood_args(sh, _FLOOD_SLICE)),
    # the largest flood tile the tiled gate admits
    "flood_tiles_warm": lambda sh: pallas_flood.flood_tiles_warm.lower(
        *_flood_args(sh, _FLOOD_SLICE), tile_hw=_FLOOD_SLICE[1:]),
}


def test_gates_admit_the_compiled_shapes():
    assert _CC_TILE == (256, 512)
    assert BLOCK[1] * BLOCK[2] <= pallas_cc._MAX_SLICE_ELEMS
    # the production block's slice is too large for the whole-slice flood
    assert BLOCK[1] * BLOCK[2] > pallas_flood._MAX_SLICE_ELEMS
    assert _FLOOD_SLICE[1] % 8 == 0


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_to_mosaic(one_chip, name):
    compiled = KERNELS[name](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the default device batch, and 8, the TPU default it replaced
@pytest.mark.parametrize("batch", sorted({DEVICE_BATCH, 8}))
def test_block_watershed_program_fits_hbm(one_chip, batch):
    """The per-batch DT-watershed program WatershedTask dispatches, at the
    production block and a device batch of ``batch`` blocks, with the
    sweep and CC formulations the TPU backend selects."""
    from cluster_tools_tpu.tasks.watershed import (
        WatershedTask,
        _fused_ws_kernel,
    )

    conf = WatershedTask.default_task_config()
    params = tuple(sorted(WatershedTask._kernel_params(conf).items()))
    shape = (batch,) + BLOCK
    with _backend.force_sweep_mode("assoc"), _backend.force_cc_mode("coarse"):
        fn = _fused_ws_kernel(params, BLOCK, False, False, None)
        compiled = fn.lower(
            _spec(one_chip, shape, jnp.float32),
            _spec(one_chip, shape, jnp.bool_),
            _spec(one_chip, (batch, 3), jnp.int32),
        ).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
