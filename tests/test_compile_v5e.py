"""Compile the served path's executors for a described TPU v5e.

Nothing runs: each test lowers a program at the size ``chip_smoke.py``
serves (2^25 rows per chip of u64 keys and 6 int32 value columns) and
compiles it for a ``v5e:2x2`` topology that is described, not attached.
That catches what the CPU runs cannot: programs the TPU compiler refuses,
intermediates tiled past the chip's 16 GB of HBM, and the collectives of
the four-chip mesh.

The topology is described only inside the module-scoped fixture (never at
import): one process may hold the TPU library at a time.  ``use_kernel``
is passed explicitly because ``jax.default_backend()`` is still the CPU.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from repro.core import plans
from repro.core.schema import TableSchema
from repro.core.state import TableState, empty_tombstones
from repro.core.table import DistributedHashTable, table_mesh

ROWS_PER_CHIP = 1 << 25  # chip_smoke.py's one-chip table
WRITE_BUCKET = 512
TOMBSTONES = 4096
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Executables compiled for a described chip cannot be read back from a
    # persistent cache without one; keep the cache out of these compiles.
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cached)


def _served_state(devices, depth: int):
    """Abstract state of a served table: build at size plus ``depth``
    write-bucket deltas, with shardings on a mesh of ``devices``."""
    mesh = table_mesh(devices)
    n = len(devices) * ROWS_PER_CHIP
    table = DistributedHashTable(
        mesh,
        ("d",),
        hash_range=n,
        schema=TableSchema("uint64", 6),
        use_kernel=False,
        max_deltas=4,
        tombstone_capacity=TOMBSTONES,
    )
    sharding = table.key_sharding()

    def rows(m, cols, dtype):
        return jax.ShapeDtypeStruct((m, cols), dtype, sharding=sharding)

    base = jax.eval_shape(
        lambda k, v: table._build_values_jit(k, v, hash_range=n),
        rows(n, 2, jnp.uint32),
        rows(n, 6, jnp.int32),
    )
    local_cap, stride = table._delta_bucket_geometry(WRITE_BUCKET)
    delta = jax.eval_shape(
        lambda k, v, s: table._build_delta_jit(
            k, v, s, local_cap=local_cap, stride=stride
        ),
        rows(WRITE_BUCKET, 2, jnp.uint32),
        rows(WRITE_BUCKET, 6, jnp.int32),
        base.hash_splits,
    )
    state = TableState(
        base=base,
        deltas=(delta,) * depth,
        tombstones=jax.eval_shape(lambda: empty_tombstones(TOMBSTONES, 2)),
        table=table,
    )
    state = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)
        ),
        state,
        plans.state_specs(state),
    )
    return table, state


def _queries(table, m: int):
    return jax.ShapeDtypeStruct((m, 2), jnp.uint32, sharding=table.key_sharding())


def _fits_one_chip(compiled) -> int:
    m = compiled.memory_analysis()
    need = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert need < HBM_BYTES, f"{need} bytes needed on one v5e chip"
    return need


@pytest.mark.parametrize("depth", [0, 4])
def test_one_chip_query_executor_fits(topo, depth):
    table, state = _served_state(topo.devices[:1], depth)
    compiled = plans.exec_query.lower(table, state, _queries(table, 256)).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("depth", [0, 4])
def test_one_chip_retrieve_executor_fits(topo, depth):
    table, state = _served_state(topo.devices[:1], depth)
    compiled = plans.exec_retrieve.lower(
        table, state, _queries(table, 64), out_capacity=1024, seg_capacity=1024
    ).compile()
    _fits_one_chip(compiled)
    assert "tpu_custom_call" not in compiled.as_text()  # the XLA gather


def test_one_chip_live_count_fits(topo):
    """Tombstone matching over the whole base, as folds and compactions
    mask it: keys must stay 1-D lanes, since an ``(N, 2)`` intermediate
    is tiled to 64 times its size and no longer fits."""
    table, state = _served_state(topo.devices[:1], 4)
    _fits_one_chip(plans.exec_live_count.lower(table, state).compile())


def test_four_chip_retrieve_has_two_all_to_alls(topo):
    table, state = _served_state(topo.devices, 4)
    compiled = plans.exec_retrieve.lower(
        table, state, _queries(table, 64), out_capacity=1024, seg_capacity=1024
    ).compile()
    assert compiled.as_text().count("all-to-all(") == 2
    _fits_one_chip(compiled)
