"""Device stages: the executors' and the build's ``jax.named_scope`` names.

Every instruction of the compiled read, join and build programs must read
as one of ``repro.obs.tracing.STAGES`` (``hlo_stages`` reads them from the
optimized HLO's ``op_name`` metadata), so that a profiler trace's device
ops name the stage of the table's code that emitted them.  Scopes change
only metadata: the results are those of the unscoped program.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plans
from repro.core.plans import JoinPlan
from repro.core.schema import TableSchema
from repro.core.table import DistributedHashTable, join_to_pairs, table_mesh
from repro.obs.tracing import STAGES, hlo_stages, stage

READ_STAGES = {"route", "locate", "gather", "return", "expand"}
BUILD_STAGES = {"build.partition", "build.exchange", "build.sort", "build.offsets"}
_ENTRY_LINE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(")
UNSCOPED_OPS = ("parameter", "constant", "tuple", "copy")


def _entry_stages(text: str) -> dict:
    """``{instruction: (opcode, stage)}`` of the entry computation."""
    stages = hlo_stages(text)
    entry = text[text.index("\nENTRY") + 1 :]
    entry = entry[: entry.index("\n}")]
    out = {}
    for line in entry.splitlines()[1:]:
        m = _ENTRY_LINE.match(line)
        assert m is not None, line[:120]
        out[m.group(1)] = (m.group(2), stages[m.group(1)])
    return out


def _table(devices, n=4096):
    table = DistributedHashTable(
        table_mesh(devices), ("d",), hash_range=n, schema=TableSchema("uint32", 4)
    )
    rng = np.random.default_rng(3)
    keys = np.repeat(rng.choice(1 << 20, size=n // 4, replace=False).astype(np.uint32), 4)
    values = rng.integers(0, 1000, size=(n, 4)).astype(np.int32)
    return table, keys, values


def _compiled_text(kind, devices):
    table, keys, values = _table(devices)
    if kind == "build":
        sharding = table.key_sharding()
        k = table.schema.pack_keys(keys, sharding)
        v = table.schema.pack_values(values, sharding)
        lowered = type(table)._build_values_jit.lower(table, k, v, hash_range=keys.shape[0])
        return lowered.compile().as_text()
    state = table.init(keys, values)
    if kind.endswith("_deleted"):  # a tombstone index to sort and match
        state = table.delete(state, keys[:64])
        kind = kind[: -len("_deleted")]
    q = keys[:1024]
    if kind == "exec_query":
        plan = table.plan_query(num_queries=1024)
    elif kind == "exec_retrieve":
        plan = table.plan_retrieve(num_queries=1024, out_capacity=8192, seg_capacity=8192)
    else:
        plan = table.plan_join(num_queries=1024, out_capacity=8192, seg_capacity=8192)
    return plan.lower(state, q).compile().as_text()


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize(
    "kind", ["exec_join", "exec_retrieve", "exec_query", "build", "exec_query_deleted"]
)
def test_every_entry_instruction_has_a_stage(kind, ndev):
    devices = jax.devices()
    if len(devices) < ndev:
        pytest.skip(f"needs {ndev} (fake) devices")
    entry = _entry_stages(_compiled_text(kind, devices[:ndev]))
    unmapped = {
        name: op for name, (op, st) in entry.items() if st == "other" and op not in UNSCOPED_OPS
    }
    assert not unmapped
    seen = {st for _, st in entry.values()} - {"other"}
    want = BUILD_STAGES if kind == "build" else READ_STAGES
    if kind.startswith("exec_query"):
        want = {"route", "locate", "return"}
    assert seen <= want
    assert {"build.sort", "build.offsets"} <= seen or kind != "build"
    assert {"route", "locate", "return"} <= seen or kind == "build"


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize("kind", ["exec_join", "exec_retrieve"])
def test_no_loop_over_the_output_slots(kind, ndev):
    """Loops are searches of the probes (route's split search, locate's
    bucket bisection): gather and expand fill their output slots by a
    scatter and a prefix sum, with no search loop over every slot."""
    devices = jax.devices()
    if len(devices) < ndev:
        pytest.skip(f"needs {ndev} (fake) devices")
    text = _compiled_text(kind, devices[:ndev])
    stages = hlo_stages(text)
    whiles = re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\swhile\(", text, re.M)
    loops = {stages[w] for w in whiles}
    assert "locate" in loops
    assert loops <= {"route", "locate"}


def test_hlo_stages_takes_the_outermost_scope_and_inherits():
    @jax.jit
    def f(x, y):
        with stage("route"):
            k = (x * 7 + 3) % x.shape[0]
        with stage("locate"):

            def body(i, acc):
                with stage("gather"):  # inner scope: the outer stage wins
                    return acc + jnp.take(y, (k + i) % y.shape[0])

            acc = jax.lax.fori_loop(0, 4, body, jnp.zeros_like(x))
        return jnp.sort(acc) + jnp.take(y, k)  # unscoped: "other"

    x = jnp.arange(1024, dtype=jnp.int32)
    text = f.lower(x, jnp.arange(77, dtype=jnp.int32)).compile().as_text()
    stages = hlo_stages(text)
    whiles = [n for n in stages if n.startswith("while")]
    assert whiles and all(stages[n] == "locate" for n in whiles)
    # the loop body's instructions (fusions inside it too) read as locate
    body = re.search(r"body=%?([\w.\-]+)", text).group(1)
    block = text[text.index(f"%{body} ") :]
    block = block[: block.index("\n}")]
    names = re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=", block, re.M)
    assert names and {stages[n] for n in names} == {"locate"}
    assert "route" in stages.values() and "other" in stages.values()
    assert "gather" not in stages.values()


def test_stage_rejects_unknown_names():
    assert set(STAGES) == READ_STAGES | BUILD_STAGES
    with pytest.raises(ValueError):
        stage("probe")


def test_join_plan_compile_matches_the_call():
    devices = jax.devices()[:1]
    table, keys, values = _table(devices)
    state = table.init(keys, values)
    q = keys[::4][:512]
    plan = table.plan_join(num_queries=512, out_capacity=4096, seg_capacity=4096)
    assert isinstance(plan, JoinPlan)
    compiled = plan.compile(state)
    assert compiled.kind == "join" and compiled.num_queries == 512
    assert compiled.signature == plans.state_signature(state)
    want = plan(state, q)
    got = compiled(state, table.schema.pack_keys(q))
    assert int(got.num_dropped) == 0
    np.testing.assert_array_equal(join_to_pairs(got), join_to_pairs(want))
    assert len(join_to_pairs(got)) == 4 * 512
