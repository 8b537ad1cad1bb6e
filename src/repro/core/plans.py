"""Plan/execute API — pure, cache-keyed callables over versioned state.

The eager table methods each hid a jit boundary and, for ``retrieve``/
``inner_join`` with unplanned capacities, a device→host sync inside the
call.  A *plan* hoists every static decision — output and segment
capacities, query count, schema — to plan-build time:

    plan = table.plan_retrieve(state, queries)        # counts round, syncs once
    plan = table.plan_retrieve(num_queries=n,         # or fully explicit:
                               out_capacity=4096, seg_capacity=512)
    result = plan(state2, queries2)                   # pure; zero host syncs

The returned callables are ``(state, queries) -> result`` pytree functions:
they accept any :class:`~repro.core.state.TableState` (or bare
``DistributedHashGraph``) with compatible shapes, and compose under an
outer ``jax.jit`` —

    @jax.jit
    def program(keys, new_keys, dead_keys, queries):
        state = table.init(keys)
        state = state.insert(new_keys)
        state = state.delete(dead_keys)
        return plan(state, queries)

— with no recompilation across calls: execution is cache-keyed by (table,
static capacities, state structure) through ``jax.jit``'s cache, so
repeated calls with shifting data reuse one compiled program per delta
depth.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import multi_hashgraph
from repro.core.hashgraph import HashGraph
from repro.core.multi_hashgraph import (
    DistributedHashGraph,
    ShardJoin,
    ShardRetrieval,
)
from repro.core.state import TableState, Tombstones, as_state
from repro.obs.tracing import process_tracer, stage
from repro.utils.compat import shard_map


# ---------------------------------------------------------------------------
# shard_map spec builders — structure mirrors the pytrees, metadata copied
# from the live values so treedefs match exactly.
# ---------------------------------------------------------------------------


def dhg_specs(dhg: DistributedHashGraph) -> DistributedHashGraph:
    """Partition specs for one graph: local CSR sharded, splits replicated."""
    ax = tuple(dhg.axis_names)
    shard0 = P(ax)  # stack local shards along dim 0 in the global view
    local = HashGraph(
        offsets=shard0,
        keys=shard0,
        values=shard0,
        table_size=dhg.local.table_size,
        seed=dhg.local.seed,
        sorted_within_bucket=dhg.local.sorted_within_bucket,
        fingerprints=shard0 if dhg.local.fingerprints is not None else None,
    )
    return DistributedHashGraph(
        local=local,
        hash_splits=P(),  # identical on every device
        num_dropped=P(),
        hash_range=dhg.hash_range,
        seed=dhg.seed,
        local_range_cap=dhg.local_range_cap,
        axis_names=ax,
        bucket_stride=dhg.bucket_stride,
    )


def state_specs(state: TableState) -> TableState:
    """Partition specs for a whole :class:`TableState` pytree."""
    return TableState(
        base=dhg_specs(state.base),
        deltas=tuple(dhg_specs(d) for d in state.deltas),
        tombstones=Tombstones(
            keys=P(),
            epochs=P(),
            expires=P(),
            count=P(),
            num_dropped=P(),
            now=P(),
        ),
        table=state.table,
        coherent=state.coherent,
    )


def _fused(table, state: TableState) -> bool:
    """Single-route layered execution?  Requires the partition-coherence
    invariant (every delta on the base's splits); ``table.fused_routing=
    False`` forces the per-layer legacy path (parity tests, A/B benches).
    Static — both inputs are jit cache keys."""
    if table.fused_routing is False:
        return False
    return state.coherent or len(state.deltas) == 0


# ---------------------------------------------------------------------------
# jitted executors — the pure (state, queries) -> result programs plans bind.
# ``table`` is a static arg (identity-hashed config), so each (table, caps,
# state structure) triple compiles once and is reused by every plan call.
# ---------------------------------------------------------------------------


def _in_spec(table):
    return P(tuple(table.axis_names))


def _tombstone_index(st: TableState):
    """The sorted tombstone index every read masks with (stage ``locate``)."""
    with stage("locate"):
        return st.tombstones.index()


@partial(jax.jit, static_argnums=(0,), static_argnames=("dest_offset",))
def exec_query(
    table, state: TableState, queries: jax.Array, *, dest_offset: int = 0
) -> jax.Array:
    """Merged multiplicity per query over base + deltas − tombstones.

    ``dest_offset`` (static, default 0 — the guarded hot path) counts
    replica ``r`` of hot-key-replicated rows; ``table.query`` sums rounds
    over ``r = 0..R-1`` to merge replica counts (non-replicated keys count
    0 on every round but the first).
    """

    def body(st, q):
        return multi_hashgraph.query_layers_sharded(
            st.layers,
            q,
            tombstones=_tombstone_index(st),
            fused=_fused(table, st),
            capacity_slack=table.capacity_slack,
            paper_faithful_probe=table.paper_faithful_probe,
            max_probe=table.max_probe,
            dest_offset=dest_offset,
        )

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state), _in_spec(table)),
        out_specs=_in_spec(table),
        check_vma=False,
    )(state, queries)


@partial(jax.jit, static_argnums=(0,))
def exec_join_size(table, state: TableState, queries: jax.Array) -> jax.Array:
    """Global join cardinality over the versioned stack (replicated ())."""

    def body(st, q):
        return multi_hashgraph.join_size_layers_sharded(
            st.layers,
            q,
            tombstones=st.tombstones.index(),
            fused=_fused(table, st),
            capacity_slack=table.capacity_slack,
            paper_faithful_probe=table.paper_faithful_probe,
            max_probe=table.max_probe,
        )

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state), _in_spec(table)),
        out_specs=P(),
        check_vma=False,
    )(state, queries)


@partial(
    jax.jit,
    static_argnums=(0,),
    static_argnames=("out_capacity", "seg_capacity", "per_layer_counts"),
)
def exec_retrieve(
    table,
    state: TableState,
    queries: jax.Array,
    *,
    out_capacity: int,
    seg_capacity: int,
    per_layer_counts: bool = False,
) -> ShardRetrieval:
    """Merged CSR retrieval over the versioned stack.

    ``per_layer_counts=True`` fills the result's ``layer_counts`` provenance
    field (``(Nq, L)`` per-layer result counts); on the fused path the
    breakdown ships inside the same single all-to-all as the values, so the
    collective budget is unchanged (CI-asserted).
    """
    ax = tuple(table.axis_names)
    out_specs = ShardRetrieval(
        offsets=P(ax),
        values=P(ax),
        counts=P(ax),
        num_dropped=P(),
        layer_counts=P(ax) if per_layer_counts else None,
        exchange_rows=P(ax),
    )

    def body(st, q):
        return multi_hashgraph.retrieve_layers_sharded(
            st.layers,
            q,
            seg_capacity=seg_capacity,
            out_capacity=out_capacity,
            capacity_slack=table.capacity_slack,
            use_kernel=table.use_kernel,
            tombstones=_tombstone_index(st),
            fused=_fused(table, st),
            per_layer_counts=per_layer_counts,
        )

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state), _in_spec(table)),
        out_specs=out_specs,
        check_vma=False,
    )(state, queries)


@partial(
    jax.jit, static_argnums=(0,), static_argnames=("out_capacity", "seg_capacity")
)
def exec_join(
    table,
    state: TableState,
    queries: jax.Array,
    *,
    out_capacity: int,
    seg_capacity: int,
) -> ShardJoin:
    """Materialized inner join over the versioned stack."""
    ax = tuple(table.axis_names)
    out_specs = ShardJoin(
        query_idx=P(ax),
        values=P(ax),
        num_results=P(ax),
        num_dropped=P(),
        exchange_rows=P(ax),
    )

    def body(st, q):
        return multi_hashgraph.inner_join_layers_sharded(
            st.layers,
            q,
            seg_capacity=seg_capacity,
            out_capacity=out_capacity,
            capacity_slack=table.capacity_slack,
            use_kernel=table.use_kernel,
            tombstones=_tombstone_index(st),
            fused=_fused(table, st),
        )

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state), _in_spec(table)),
        out_specs=out_specs,
        check_vma=False,
    )(state, queries)


@partial(jax.jit, static_argnums=(0,))
def exec_plan_caps(table, state: TableState, queries: jax.Array):
    """The one counts round sizing both capacities: ((), ()) int32."""

    def body(st, q):
        return multi_hashgraph.plan_caps_sharded(
            st.layers,
            q,
            capacity_slack=table.capacity_slack,
            tombstones=_tombstone_index(st),
            fused=_fused(table, st),
        )

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state), _in_spec(table)),
        out_specs=(P(), P()),
        check_vma=False,
    )(state, queries)


@partial(jax.jit, static_argnums=(0,))
def exec_live_count(table, state: TableState) -> jax.Array:
    """Global live (non-tombstoned, non-sentinel) row count: replicated ().

    The counts round behind compaction sizing: ``compact()`` sizes the
    rebuild from the rows that will actually survive instead of the
    all-rows worst case, so steady-state insert/delete/compact cycles keep
    the base arrays flat.
    """

    def body(st):
        from repro.core.hashgraph import is_empty_key, match_epochs_sorted

        ts_keys, ts_epochs = st.tombstones.index()
        live = jnp.int32(0)
        for epoch, layer in enumerate(st.layers):
            k = layer.local.keys
            dead = is_empty_key(k)
            if ts_keys.shape[0]:
                dead = dead | (match_epochs_sorted(k, ts_keys, ts_epochs) >= epoch)
            live = live + jnp.sum(~dead).astype(jnp.int32)
        return jax.lax.psum(live, tuple(table.axis_names))

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state),),
        out_specs=P(),
        check_vma=False,
    )(state)


@partial(jax.jit, static_argnums=(0,))
def exec_layer_live(table, state: TableState) -> jax.Array:
    """Per-layer global live row counts: replicated ``(num_layers,)`` int32.

    The per-layer breakdown of :func:`exec_live_count` (same masking, not
    summed across layers), feeding stats-driven fold scheduling: a delta
    whose live fraction has collapsed is cold — mostly superseded or
    expired rows — and is the cheapest capacity to reclaim with
    ``fold_oldest``.  Index 0 is the base; index ``i>0`` is delta ``i-1``.
    """

    def body(st):
        from repro.core.hashgraph import is_empty_key, match_epochs_sorted

        ts_keys, ts_epochs = st.tombstones.index()
        per_layer = []
        for epoch, layer in enumerate(st.layers):
            k = layer.local.keys
            dead = is_empty_key(k)
            if ts_keys.shape[0]:
                dead = dead | (match_epochs_sorted(k, ts_keys, ts_epochs) >= epoch)
            per_layer.append(jnp.sum(~dead).astype(jnp.int32))
        return jax.lax.psum(jnp.stack(per_layer), tuple(table.axis_names))

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(state_specs(state),),
        out_specs=P(),
        check_vma=False,
    )(state)


# ---------------------------------------------------------------------------
# AOT executor handles — lowered/compiled executables a serving front end can
# call with zero live tracing.
# ---------------------------------------------------------------------------


def state_signature(state: TableState) -> tuple:
    """Structural identity of a state for executor-handle keying.

    Two states with equal signatures (same pytree structure — delta depth,
    coherence, static graph metadata — and identical leaf shapes/dtypes)
    execute through the same compiled program; the signature is exactly the
    dynamic part of ``jax.jit``'s cache key, so an AOT executable compiled
    against one is callable with the other.
    """
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return (treedef, tuple((tuple(x.shape), jnp.result_type(x).name) for x in leaves))


@dataclasses.dataclass(frozen=True)
class CompiledPlan:
    """An AOT-compiled ``(state, queries) -> result`` executable.

    Built by :meth:`QueryPlan.compile`, :meth:`RetrievePlan.compile` or
    :meth:`JoinPlan.compile` —
    the ``jit(...).lower(...).compile()`` idiom: the trace/compile cost is
    paid at *construction*, and calls run the XLA executable directly (the
    jit dispatch cache is never consulted, so a warmed serving path does
    zero live tracing by construction).  Calls require the exact structure
    the plan was lowered for: a state matching :func:`state_signature` and
    a query batch of ``num_queries`` packed keys.
    """

    compiled: object  # jax.stages.Compiled
    kind: str  # "query" | "retrieve" | "join"
    num_queries: int
    signature: tuple  # state_signature the executable was lowered against

    def __call__(self, state, queries):
        return self.compiled(state, queries)


def _proto_queries(table, num_queries: int) -> jax.Array:
    """An all-sentinel query batch with the schema's packed shape."""
    from repro.core.hashgraph import EMPTY_KEY

    lanes = table.schema.key_lanes
    shape = (num_queries,) if lanes == 1 else (num_queries, lanes)
    return jnp.full(shape, EMPTY_KEY, jnp.uint32)


# ---------------------------------------------------------------------------
# Plans — small frozen descriptors binding a table to resolved statics.
# ---------------------------------------------------------------------------


class _PlanBase:
    def _count_exchange_slots(self, st, q) -> None:
        """Add this call's exchange slots (static, from shapes) to the
        process registry's ``exchange_slots_total{phase}``; a call traced
        inside an outer ``jax.jit`` counts nothing."""
        if isinstance(q, jax.core.Tracer):
            return
        table = self.table
        rounds = 1 if _fused(table, st) else len(st.layers)
        slots = multi_hashgraph.exchange_slots(
            q.shape[0], table.num_devices, table.capacity_slack, self.seg_capacity, rounds
        )
        registry = process_tracer().registry
        for phase, n in zip(multi_hashgraph.EXCHANGE_PHASES, slots):
            registry.counter(
                "exchange_slots_total",
                labels={"phase": phase},
                help="Slots shipped by the all-to-alls of retrieve and join plan calls.",
            ).inc(n)

    def _prep(self, state, queries):
        st = as_state(self.table, state)
        q = self.table.schema.pack_keys(queries)
        if self.num_queries is not None and q.shape[0] != self.num_queries:
            raise ValueError(
                f"plan was built for {self.num_queries} queries, got {q.shape[0]}"
            )
        return st, q

    def _proto_q(self, queries):
        if queries is not None:
            return self.table.schema.pack_keys(queries)
        if self.num_queries is None:
            raise ValueError("plan has no num_queries; pass a queries sample")
        return _proto_queries(self.table, self.num_queries)


@dataclasses.dataclass(frozen=True)
class QueryPlan(_PlanBase):
    """``(state, queries) -> (Nq,) int32`` merged multiplicities."""

    table: object
    num_queries: Optional[int] = None

    def __call__(self, state, queries) -> jax.Array:
        with process_tracer().span("plan.query"):
            st, q = self._prep(state, queries)
            return exec_query(self.table, st, q)

    def join_size(self, state, queries) -> jax.Array:
        """Global join cardinality under the same plan (replicated ())."""
        st, q = self._prep(state, queries)
        return exec_join_size(self.table, st, q)

    def lower(self, state, queries=None):
        """AOT-lower the query executor against ``state``'s structure.

        ``queries`` defaults to an all-sentinel batch of ``num_queries``
        keys.  Returns a ``jax.stages.Lowered``; ``.compile()`` it (or use
        :meth:`compile`) to get the executable — tracing happens here, not
        on the first live request.
        """
        st = as_state(self.table, state)
        return exec_query.lower(self.table, st, self._proto_q(queries))

    def compile(self, state, queries=None) -> CompiledPlan:
        """AOT-compile: a :class:`CompiledPlan` callable with zero live
        tracing for any state matching ``state_signature(state)``."""
        st = as_state(self.table, state)
        q = self._proto_q(queries)
        return CompiledPlan(
            compiled=exec_query.lower(self.table, st, q).compile(),
            kind="query",
            num_queries=q.shape[0],
            signature=state_signature(st),
        )


@dataclasses.dataclass(frozen=True)
class RetrievePlan(_PlanBase):
    """``(state, queries) -> ShardRetrieval`` with capacities fixed."""

    table: object
    num_queries: Optional[int]
    out_capacity: int
    seg_capacity: int
    per_layer_counts: bool = False

    def __call__(self, state, queries) -> ShardRetrieval:
        with process_tracer().span("plan.retrieve"):
            st, q = self._prep(state, queries)
            self._count_exchange_slots(st, q)
            return exec_retrieve(
                self.table,
                st,
                q,
                out_capacity=self.out_capacity,
                seg_capacity=self.seg_capacity,
                per_layer_counts=self.per_layer_counts,
            )

    def lower(self, state, queries=None):
        """AOT-lower the retrieve executor (capacities baked in) against
        ``state``'s structure; see :meth:`QueryPlan.lower`."""
        st = as_state(self.table, state)
        return exec_retrieve.lower(
            self.table,
            st,
            self._proto_q(queries),
            out_capacity=self.out_capacity,
            seg_capacity=self.seg_capacity,
            per_layer_counts=self.per_layer_counts,
        )

    def compile(self, state, queries=None) -> CompiledPlan:
        """AOT-compile: see :meth:`QueryPlan.compile`."""
        st = as_state(self.table, state)
        q = self._proto_q(queries)
        return CompiledPlan(
            compiled=self.lower(st, q).compile(),
            kind="retrieve",
            num_queries=q.shape[0],
            signature=state_signature(st),
        )


@dataclasses.dataclass(frozen=True)
class JoinPlan(_PlanBase):
    """``(state, queries) -> ShardJoin`` with capacities fixed."""

    table: object
    num_queries: Optional[int]
    out_capacity: int
    seg_capacity: int

    def __call__(self, state, queries) -> ShardJoin:
        with process_tracer().span("plan.join"):
            st, q = self._prep(state, queries)
            self._count_exchange_slots(st, q)
            return exec_join(
                self.table,
                st,
                q,
                out_capacity=self.out_capacity,
                seg_capacity=self.seg_capacity,
            )

    def lower(self, state, queries=None):
        """AOT-lower the join executor (capacities baked in) against
        ``state``'s structure; see :meth:`QueryPlan.lower`."""
        st = as_state(self.table, state)
        return exec_join.lower(
            self.table,
            st,
            self._proto_q(queries),
            out_capacity=self.out_capacity,
            seg_capacity=self.seg_capacity,
        )

    def compile(self, state, queries=None) -> CompiledPlan:
        """AOT-compile: see :meth:`QueryPlan.compile`."""
        st = as_state(self.table, state)
        q = self._proto_q(queries)
        return CompiledPlan(
            compiled=self.lower(st, q).compile(),
            kind="join",
            num_queries=q.shape[0],
            signature=state_signature(st),
        )
