"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling
rules, so these tests hand each kernel to the TPU compiler for a
described — not attached — ``v5e:2x2`` topology at the widths of the
paper's default deployment: the CAL road network (1,889,815 users after
1,000 facilities are split off), k = 10, batches of Q = 16 queries.
Nothing runs; a kernel the chip's compiler refuses fails here.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.grid_raycast import grid_raycast_cells_batch
from repro.kernels.rank_count import rank_count_kernel_call
from repro.kernels.raycast import (
    raycast_count_batch_kernel_call,
    raycast_count_kernel_call,
)

N_USERS = 1_889_815
NP = -(-N_USERS // 1024) * 1024  # users padded to the 1024-user block
Q = 16
MP = 128  # DenseBackend.prepare_batch pad: scenes hold 49-100 triangles
N_FAC = 1_000
G = 64
L = 128  # one lane tile of per-cell candidate slots
N_OCC = 1_594  # user-occupied cells of the 64x64 grid over CAL
BLOCK = 256  # auto_cell_block at CAL occupancy (mean 1,186 users/cell)
N_BLOCKS = 8_297  # padded cell runs of the bucketing at that block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


def test_raycast_count_batch_compiles_for_v5e(one_chip):
    users = _spec(one_chip, (NP,))
    planes = _spec(one_chip, (Q, 3, MP))
    _assert_mosaic(
        raycast_count_batch_kernel_call.lower(
            users, users, planes, planes, planes, bu=1024, bm=MP, interpret=False
        )
    )


def test_grid_raycast_cells_batch_compiles_for_v5e(one_chip):
    users = _spec(one_chip, (N_BLOCKS * BLOCK,))
    _assert_mosaic(
        grid_raycast_cells_batch.lower(
            users,
            users,
            _spec(one_chip, (N_BLOCKS,), jnp.int32),
            _spec(one_chip, (Q, N_OCC, 3, 3, L)),
            block=BLOCK,
            interpret=False,
        )
    )


def test_raycast_count_compiles_for_v5e(one_chip):
    users = _spec(one_chip, (NP,))
    planes = _spec(one_chip, (3, MP))
    _assert_mosaic(
        raycast_count_kernel_call.lower(
            users, users, planes, planes, planes, bu=1024, bm=MP, interpret=False
        )
    )


def test_rank_count_compiles_for_v5e(one_chip):
    users = _spec(one_chip, (NP,))
    facilities = _spec(one_chip, (1024,))  # 1,000 padded to one block
    _assert_mosaic(
        rank_count_kernel_call.lower(
            users, users, facilities, facilities, users,
            bu=1024, bm=1024, interpret=False,
        )
    )
