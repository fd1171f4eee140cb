"""The live path's Pallas kernels compile for a TPU v5e at ResNet56 width.

Each case compiles for one chip of a described (not attached) v5e:2x2
topology, so it needs the TPU compiler but no chip, and checks that the
kernel reached the compiled program as a Mosaic custom call. The topology
is described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fedavg_reduce as fr
from repro.kernels import quantize as qz

RESNET56_PARAMS = 868_123  # the small tier's flat update length
N_CLIENTS = 7  # the geo_distributed deployment's silos
BLOCK = 256  # qsgd's default quantisation block
Q_ROWS = -(-RESNET56_PARAMS // (BLOCK * qz.ROW_TILE)) * qz.ROW_TILE
T_PAD = -(-RESNET56_PARAMS // fr.COL_TILE) * fr.COL_TILE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


CASES = {
    "quantize_blocks": (qz.quantize_blocks,
                        [((Q_ROWS, BLOCK), jnp.float32)]),
    "dequantize_blocks": (qz.dequantize_blocks,
                          [((Q_ROWS, BLOCK), jnp.int8),
                           ((Q_ROWS, 1), jnp.float32)]),
    "fedavg_reduce": (fr.fedavg_reduce,
                      [((N_CLIENTS, T_PAD), jnp.float32),
                       ((N_CLIENTS,), jnp.float32)]),
    "fedavg_accumulate": (fr.fedavg_accumulate,
                          [((T_PAD,), jnp.float32), ((T_PAD,), jnp.float32),
                           ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, args = CASES[name]
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    compiled = fn.lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
