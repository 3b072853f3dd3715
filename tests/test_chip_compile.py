"""Compile the keyed main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler builds for a described topology and
refuses what the chip's compiler would refuse (integer contractions the MXU
lacks, layouts, fast-memory limits), which interpret mode cannot show.  The
sizes are the ones ``chip_smoke.py`` runs: about 2^16 cells per chunk
against 8 shards of 2^18 rows.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.keyed import kernels as kk
from repro.kernels import hash_table as ht
from repro.kernels import segment_reduce as sr

CELLS = 2**16          # distinct (key, window) cells in one smoke chunk
SHARD_ROWS = 2**18     # rows of one shard's table
TABLE_ROWS = 8 * SHARD_ROWS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile cannot be read back without a chip: keep these
    # programs out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _batched_lookup(dev):
    cells = tuple(_i32((CELLS,), dev) for _ in range(5))
    table = tuple(_i32((TABLE_ROWS,), dev) for _ in range(5))
    return jax.jit(ht.batched_table_lookup).lower(
        cells, table, _i32((TABLE_ROWS,), dev)
    )


def _lookup(dev):
    cells = tuple(_i32((CELLS,), dev) for _ in range(4))
    table = tuple(_i32((SHARD_ROWS,), dev) for _ in range(4))
    return jax.jit(ht.table_lookup).lower(
        cells, table, _i32((SHARD_ROWS,), dev)
    )


def _reduce_by_cell(dev):
    return kk._device_segment_path.lower(
        _i32((CELLS,), dev), _i32((CELLS, 2), dev), num_cells=CELLS
    )


def _segment_sum(dev):
    return jax.jit(sr.segment_sum, static_argnums=2).lower(
        _i32((8192, 2), dev), _i32((8192,), dev), 4096
    )


def _scatter_add(dev):
    return jax.jit(sr.scatter_add).lower(
        _i32((8192, 2), dev), _i32((8192,), dev), _i32((8192, 2), dev)
    )


PROGRAMS = {
    "batched_table_lookup": (_batched_lookup, True),
    "table_lookup": (_lookup, True),
    "reduce_by_cell": (_reduce_by_cell, False),
    "segment_sum": (_segment_sum, True),
    "scatter_add": (_scatter_add, True),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_compiles_for_v5e(one_chip, name):
    """Each program compiles for the chip; a Pallas kernel compiles as a
    Mosaic custom call, never as the interpreter's XLA loop."""
    build, is_kernel = PROGRAMS[name]
    compiled = build(one_chip).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == is_kernel
