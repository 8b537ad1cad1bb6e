"""High-level mesh-facing API for the distributed HashGraph.

Wraps the shard_map internals of ``repro.core.multi_hashgraph`` behind a
simple object: callers hold *global* jax arrays (sharded over a mesh) and
get back global arrays; all paper phases run inside one jitted shard_map.

The current API is **plan/execute over versioned state** (see
``repro.core.plans`` / ``repro.core.state``):

    table = DistributedHashTable(mesh, ("d",), hash_range=1 << 20)
    state = table.init(keys)                   # TableState (versioned)
    state = state.insert(new_keys)             # functional delta insert
    state = state.delete(dead_keys)            # tombstone delete
    plan = table.plan_retrieve(state, queries)  # capacities sized up front
    result = plan(state, queries)              # pure, jit-composable
    state = state.compact()                    # fold deltas + tombstones

The key width and payload shape are set by a :class:`~repro.core.schema.
TableSchema`: the default (uint32 keys, one int32 value column) is the
paper's layout and the exact PR-1 API; ``TableSchema("uint64", C)`` stores
keys as ``(N, 2)`` packed uint32 lanes (``schema.pack_u64``) and values as
``(N, C)`` int32 columns, threaded through every phase of the pipeline.

The pre-plan eager methods (``build``/``query``/``retrieve``/``inner_join``
…) remain as thin deprecation shims over the plan executors, accepting
either a bare ``DistributedHashGraph`` (their old state type) or a
``TableState``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.utils.compat import shard_map

from repro.core import hashing, multi_hashgraph, partition, plans
from repro.core.hashgraph import (
    EMPTY_KEY,
    HashGraph,
    is_empty_key,
    match_epochs_sorted,
)
from repro.core.multi_hashgraph import (
    DistributedHashGraph,
    ShardJoin,
    ShardRetrieval,
)
from repro.core.plans import JoinPlan, QueryPlan, RetrievePlan
from repro.core.schema import TableSchema
from repro.core.state import TableState, as_state, empty_tombstones
from repro.utils import cdiv as _cdiv


def table_mesh(devices=None) -> Mesh:
    """The 1-D device mesh a :class:`DistributedHashTable` shards over.

    ``devices`` defaults to every device of the process.  The axis is
    ``Auto``: eager mutations (tombstone pushes, write padding) combine
    arrays already placed on the mesh with freshly made ones, which an
    ``Explicit`` axis (``jax.make_mesh``'s default) refuses outside a mesh
    context.
    """
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), ("d",), axis_types=(AxisType.Auto,))


def _dhg_out_specs(
    axis_names: Sequence[str],
    hash_range: int,
    local_cap: int,
    seed: int,
    bucket_stride: int = 1,
    fingerprint: bool = False,
):
    ax = tuple(axis_names)
    shard0 = P(ax)  # stack local shards along dim 0 in the global view
    local = HashGraph(
        offsets=shard0,
        keys=shard0,
        values=shard0,
        table_size=local_cap,
        seed=seed,
        sorted_within_bucket=True,
        fingerprints=shard0 if fingerprint else None,
    )
    return DistributedHashGraph(
        local=local,
        hash_splits=P(),  # identical on every device
        num_dropped=P(),
        hash_range=hash_range,
        seed=seed,
        local_range_cap=local_cap,
        axis_names=ax,
        bucket_stride=bucket_stride,
    )


@dataclasses.dataclass(eq=False)  # identity hash — required for jit static self
class DistributedHashTable:
    """Factory for jitted build/mutate/plan closures over a fixed mesh.

    ``schema`` selects key width and payload columns (default: the paper's
    uint32 keys + one int32 column).  ``use_kernel`` routes the retrieval
    gather through the Pallas ``csr_gather`` kernel (None: the XLA gather,
    which is what runs on TPU — Mosaic does not lower the kernels yet).
    ``max_deltas`` bounds the insert delta ring and ``tombstone_capacity``
    the delete buffer of the versioned state (see
    :class:`~repro.core.state.TableState`).

    ``coherent_deltas`` (default True) builds every insert delta on the
    base's *frozen* ``hash_splits`` — the partition-coherence invariant
    that lets one exchange round serve the whole layer stack (single-route
    layered execution).  ``False`` restores the pre-coherence behavior
    (each delta gets its own narrowed hash range and splits), producing
    mixed-split states that execute on the per-layer legacy path.
    ``fused_routing=False`` forces the legacy path even on coherent states
    (A/B benchmarking, parity tests); ``None`` auto-selects by state.

    ``skew_guard`` (default True) protects coherent inserts from dispatch
    overflow: a batch whose key distribution diverges from the base's
    balanced splits can overflow the per-(source, destination) exchange
    slots of the frozen-splits delta build (rows dropped, counted in
    ``num_dropped``).  The guard predicts the overflow host-side from the
    batch's histogram against the base's splits and, when it would fire,
    falls back to an *incoherent* (legacy-routed) delta whose own balanced
    splits absorb the skew — trading the fused routing invariant for zero
    dropped rows.  Fallbacks are tallied in ``skew_fallbacks`` (surfaced
    by ``serve_table`` server stats).  Eager inserts only: under an outer
    ``jax.jit`` the histogram cannot be read back, so the guard is skipped.

    ``replicate_hot_keys`` (R > 1 enables) handles the skew no split choice
    can fix: a batch dominated by ONE key value hashes to one owner, so
    duplicates beyond the dispatch slot drop no matter how the range is
    partitioned.  Eager coherent inserts detect such hot keys host-side
    (occurrence count above the per-(source, dest) dispatch slot) and
    spread each hot key's rows round-robin over ``min(R, D)`` consecutive
    owners (``dest_offsets`` in the delta build); detected keys are tallied
    in the ``hot_keys`` registry and eager ``query`` transparently sums one
    extra routed round per replica rank to merge the counts (exact for
    non-replicated keys, which count 0 off their owner).  A full
    ``compact()`` re-concentrates rows on the hash owner (the rebuild
    routes purely by hash) — re-detection on the next skewed insert
    re-spreads them; retrieve/join of replicated rows sees only the
    ``r = 0`` replica for now (counts are the serving-cache need).
    """

    mesh: jax.sharding.Mesh
    axis_names: tuple
    hash_range: int
    seed: int = hashing.DEFAULT_SEED
    capacity_slack: float = 1.25
    range_slack: float = 1.5
    num_bins: Optional[int] = None
    paper_faithful_probe: bool = False
    max_probe: int = 64
    schema: Optional[TableSchema] = None
    use_kernel: Optional[bool] = None
    max_deltas: int = 8
    tombstone_capacity: int = 1024
    coherent_deltas: bool = True
    fused_routing: Optional[bool] = None
    skew_guard: bool = True
    fingerprint: Optional[bool] = None
    replicate_hot_keys: int = 0

    def __post_init__(self):
        self.axis_names = tuple(self.axis_names)
        if self.schema is None:
            self.schema = TableSchema()
        # Probe fingerprint lane (None = auto): on for multi-lane keys, where
        # the fingerprint bisection halves the bytes of the wide-span sorted
        # search; off for 1-lane keys (the key array is already one lane).
        # Applied uniformly to base, delta, fold and compact builds so every
        # layer of a state shares one probe layout.
        if self.fingerprint is None:
            self.use_fingerprint = self.schema.key_lanes > 1
        else:
            self.use_fingerprint = bool(self.fingerprint)
        self.num_devices = 1
        for a in self.axis_names:
            self.num_devices *= self.mesh.shape[a]
        from repro.utils import cdiv

        self.local_range_cap = int(
            cdiv(self.hash_range, self.num_devices) * self.range_slack
        )
        # Diagnostics counter (not part of the static jit identity): inserts
        # routed to an incoherent delta by the skew guard.
        self.skew_fallbacks = 0
        # Hot-key registry: packed key tuple -> replica count R.  Host-side
        # bookkeeping only (queries read max(R) to size the merge rounds);
        # not part of the jit identity.
        self.hot_keys = {}
        # Compact-sizing memo, keyed by state signature (the ExecutorGrid
        # idiom): structurally identical states reuse the derived
        # (capacity, rebuild_rows) pair instead of re-running the
        # exec_live_count device round trip per fold cycle.
        self._sizing_memo = {}

    # -- sharding helpers ----------------------------------------------------
    def key_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis_names))

    def _in_spec(self):
        return P(self.axis_names)

    def _pack_queries(self, queries) -> jax.Array:
        return self.schema.pack_keys(queries)

    def _local_cap_for(self, hash_range: int) -> int:
        return int(_cdiv(hash_range, self.num_devices) * self.range_slack)

    def _out_specs(
        self,
        hash_range: Optional[int] = None,
        local_cap: Optional[int] = None,
        bucket_stride: int = 1,
    ):
        hr = self.hash_range if hash_range is None else hash_range
        return _dhg_out_specs(
            self.axis_names,
            hr,
            self._local_cap_for(hr) if local_cap is None else local_cap,
            self.seed,
            bucket_stride,
            fingerprint=self.use_fingerprint,
        )

    # -- build ----------------------------------------------------------------
    def build(self, keys, values=None) -> DistributedHashGraph:
        """Build a (build-once) distributed graph from a global key array.

        ``keys``: ``(N,)`` uint32 for the 1-lane schema, ``(N, 2)`` packed
        uint32 (``schema.pack_u64``) for uint64; ``N % devices == 0``.
        ``values``: optional ``(N,)`` / ``(N, C)`` int32 payload matching
        ``schema.value_cols`` (default: global row ids, 1-column only).

        .. deprecated:: use :meth:`init`, which returns a versioned
           :class:`TableState` supporting insert/delete/compact.  ``build``
           returns the bare ``DistributedHashGraph`` for older call sites.
        """
        sharding = self.key_sharding()
        keys = self.schema.pack_keys(keys, sharding)
        if values is None:
            if self.schema.value_cols != 1:
                raise ValueError(
                    f"schema has {self.schema.value_cols} value columns; "
                    "pass explicit values (the row-id default is 1-column)"
                )
            return self._build_jit(keys, hash_range=self.hash_range)
        return self._build_values_jit(
            keys, self.schema.pack_values(values, sharding), hash_range=self.hash_range
        )

    def init(self, keys, values=None) -> TableState:
        """Build and wrap into a versioned :class:`TableState`.

        The state starts with an empty delta ring and a zero-capacity
        tombstone buffer (pure-read states pay no masking cost); the buffer
        grows to ``tombstone_capacity`` slots on the first ``delete``.
        ``state.insert`` / ``state.delete`` / ``state.compact`` are
        functional (each returns a new state) and composable under an outer
        ``jax.jit``.
        """
        return TableState(
            base=self.build(keys, values),
            deltas=(),
            tombstones=empty_tombstones(0, self.schema.key_lanes),
            table=self,
        )

    def _build_body(self, k, v, hash_range, num_bins, capacity):
        return multi_hashgraph.build_sharded(
            k,
            hash_range=hash_range,
            axis_names=self.axis_names,
            values=v,
            num_bins=num_bins,
            capacity_slack=self.capacity_slack,
            range_slack=self.range_slack,
            seed=self.seed,
            capacity=capacity,
            fingerprint=self.use_fingerprint,
        )

    def _num_bins_for(self, hash_range: int) -> Optional[int]:
        # A user-pinned bin count is sized for the table's hash range; delta
        # builds over a narrowed range fall back to the auto choice.
        return self.num_bins if hash_range == self.hash_range else None

    @partial(jax.jit, static_argnums=0, static_argnames=("hash_range", "capacity"))
    def _build_jit(
        self, keys: jax.Array, *, hash_range: int, capacity: Optional[int] = None
    ):
        return shard_map(
            lambda k: self._build_body(
                k, None, hash_range, self._num_bins_for(hash_range), capacity
            ),
            mesh=self.mesh,
            in_specs=(self._in_spec(),),
            out_specs=self._out_specs(hash_range),
            check_vma=False,
        )(keys)

    @partial(jax.jit, static_argnums=0, static_argnames=("hash_range", "capacity"))
    def _build_values_jit(
        self,
        keys: jax.Array,
        values: jax.Array,
        *,
        hash_range: int,
        capacity: Optional[int] = None,
    ):
        return shard_map(
            lambda k, v: self._build_body(
                k, v, hash_range, self._num_bins_for(hash_range), capacity
            ),
            mesh=self.mesh,
            in_specs=(self._in_spec(), self._in_spec()),
            out_specs=self._out_specs(hash_range),
            check_vma=False,
        )(keys, values)

    # -- functional mutation (versioned state) --------------------------------
    def _delta_hash_range(self, num_keys: int) -> int:
        """Hash range for a *legacy* (incoherent) delta graph: sized to the
        batch, not the table.

        Pre-coherence behavior (``coherent_deltas=False``): each delta owns
        its own splits and bucket space, so a small insert does not pay the
        base table's O(hash_range / devices) offsets array — at the price of
        one routing round per delta on every later query.
        """
        return min(self.hash_range, max(256, 2 * num_keys))

    def _delta_bucket_geometry(self, num_keys: int) -> tuple[int, int]:
        """(local_range_cap, bucket_stride) for a partition-coherent delta.

        Coherent deltas share the base's hash range and splits (routing
        identity), but a small batch must not pay the base's
        O(hash_range / D) offsets array — so the bucket map is *strided*:
        ``stride`` consecutive base bucket slots fold into one delta bucket,
        keeping the delta's offsets at O(batch) while build and query keep
        using the identical deterministic map.  Striding only lengthens
        bucket lists; the sorted-bucket binary search absorbs it.
        """
        target = max(128, _cdiv(2 * num_keys, self.num_devices))
        stride = max(1, _cdiv(self.local_range_cap, target))
        return _cdiv(self.local_range_cap, stride), stride

    @partial(
        jax.jit, static_argnums=0, static_argnames=("local_cap", "stride", "capacity")
    )
    def _build_delta_jit(
        self,
        keys: jax.Array,
        values: jax.Array,
        splits: jax.Array,
        *,
        local_cap: int,
        stride: int,
        capacity: Optional[int] = None,
    ):
        """Build one delta graph on the base's frozen splits (no phase-1
        histogram/psum round — the splits ARE the partitioning)."""

        def body(k, v, sp):
            return multi_hashgraph.build_sharded(
                k,
                hash_range=self.hash_range,
                axis_names=self.axis_names,
                values=v,
                capacity_slack=self.capacity_slack,
                seed=self.seed,
                capacity=capacity,
                hash_splits=sp,
                local_range_cap=local_cap,
                bucket_stride=stride,
                fingerprint=self.use_fingerprint,
            )

        return shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self._in_spec(), self._in_spec(), P()),
            out_specs=self._out_specs(local_cap=local_cap, bucket_stride=stride),
            check_vma=False,
        )(keys, values, splits)

    @partial(
        jax.jit, static_argnums=0, static_argnames=("local_cap", "stride", "capacity")
    )
    def _build_delta_offsets_jit(
        self,
        keys: jax.Array,
        values: jax.Array,
        splits: jax.Array,
        offsets: jax.Array,
        *,
        local_cap: int,
        stride: int,
        capacity: Optional[int] = None,
    ):
        """Hot-key variant of :meth:`_build_delta_jit`: per-row destination
        offsets spread each hot key's rows over R consecutive owners.  A
        separate jitted program so the offset-free insert path keeps its
        jaxpr byte-identical."""

        def body(k, v, sp, offs):
            return multi_hashgraph.build_sharded(
                k,
                hash_range=self.hash_range,
                axis_names=self.axis_names,
                values=v,
                capacity_slack=self.capacity_slack,
                seed=self.seed,
                capacity=capacity,
                hash_splits=sp,
                local_range_cap=local_cap,
                bucket_stride=stride,
                fingerprint=self.use_fingerprint,
                dest_offsets=offs,
            )

        return shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self._in_spec(), self._in_spec(), P(), self._in_spec()),
            out_specs=self._out_specs(local_cap=local_cap, bucket_stride=stride),
            check_vma=False,
        )(keys, values, splits, offsets)

    def _hot_key_offsets(self, keys: jax.Array):
        """Host-side hot-key detection: per-row destination offsets, or None.

        A key is *hot* when its occurrence count in this batch exceeds the
        per-(source, destination) dispatch slot of the coherent delta build
        — beyond that, drops are guaranteed if every occurrence funnels to
        the single hash owner (the failure no split choice fixes).  Hot
        keys' rows get offsets ``occurrence_rank % R`` so the build spreads
        them over ``R = min(replicate_hot_keys, D)`` consecutive owners;
        all other rows keep offset 0.  Detected keys are registered in
        ``hot_keys`` for the query-side merge.  Eager call sites only.
        """
        d = self.num_devices
        n = keys.shape[0]
        slot = multi_hashgraph.default_capacity(n // d, d, self.capacity_slack)
        kn = np.asarray(keys)
        rows = kn if kn.ndim == 2 else kn[:, None]
        uniq, inv, counts = np.unique(
            rows, axis=0, return_inverse=True, return_counts=True
        )
        hot = (counts > slot) & ~np.all(uniq == np.uint32(EMPTY_KEY), axis=1)
        if not np.any(hot):
            return None
        r = max(2, min(self.replicate_hot_keys, d))
        offs = np.zeros(n, np.int32)
        for u in np.nonzero(hot)[0]:
            idx = np.nonzero(inv == u)[0]
            offs[idx] = np.arange(idx.shape[0], dtype=np.int32) % r
            self.hot_keys[tuple(int(x) for x in uniq[u])] = r
        return jnp.asarray(offs)

    def _coherent_dispatch_overflows(
        self, keys: jax.Array, splits, offsets=None
    ) -> bool:
        """Predict per-(source, destination) slot overflow of a coherent
        delta build for this batch (the delta-dispatch skew check).

        Replays the exact routing the frozen-splits build would use — hash,
        destination by the base's splits (plus the hot-key ``offsets`` when
        replication spread the batch), EMPTY sentinels round-robin — and
        histograms it per (source shard, destination) pair against the same
        ``default_capacity`` slot size the build would allocate.  The
        histogram and comparison run on device; only the one-boolean
        verdict crosses to host.  Eager call sites only.
        """
        d = self.num_devices
        n = keys.shape[0]
        n_local = n // d
        capacity = multi_hashgraph.default_capacity(
            n_local, d, self.capacity_slack
        )
        if offsets is None:
            offsets = jnp.zeros(n, jnp.int32)
        verdict = self._skew_verdict_jit(
            keys, jnp.asarray(splits), offsets, capacity=capacity
        )
        return bool(verdict)

    @partial(jax.jit, static_argnums=0, static_argnames=("capacity",))
    def _skew_verdict_jit(
        self,
        keys: jax.Array,
        splits: jax.Array,
        offsets: jax.Array,
        *,
        capacity: int,
    ) -> jax.Array:
        d = self.num_devices
        n = keys.shape[0]
        n_local = n // d
        h = hashing.hash_to_buckets(keys, self.hash_range, seed=self.seed)
        dest = (partition.destination_of(h, splits) + offsets) % d
        rows = jnp.arange(n, dtype=jnp.int32)
        dest = jnp.where(is_empty_key(keys), (rows % n_local) % d, dest)
        pair = (rows // n_local) * d + dest  # (source shard, destination)
        per_pair = jnp.zeros(d * d, jnp.int32).at[pair].add(1)
        return jnp.any(per_pair > capacity)

    def insert(
        self, state, keys, values=None, *, auto_compact: bool = False
    ) -> TableState:
        """Functional insert: a new state with one more delta graph.

        ``keys``/``values`` follow the :meth:`build` contract (global
        arrays, ``N % devices == 0``).  Raises when the delta ring is full —
        call :meth:`compact` first, or pass ``auto_compact=True`` to fold
        the state automatically whenever
        :meth:`~repro.core.state.TableState.should_compact` fires (ring
        full, tombstone load, or tombstone overflow; host-syncing — eager
        use only).  With ``values=None`` the default payload is the row id
        *within this batch* (0..N-1).

        With ``coherent_deltas`` (the default) the delta is built on the
        base's frozen ``hash_splits``, preserving the partition-coherence
        invariant that keeps every later query/retrieve/plan at one routing
        round regardless of delta depth.  A batch skewed enough to overflow
        the frozen-splits dispatch falls back to an incoherent delta instead
        of dropping rows (``skew_guard``; counted in ``skew_fallbacks``).
        """
        st = as_state(self, state)
        if auto_compact and st.should_compact():
            st = self.compact(st)
        if len(st.deltas) >= self.max_deltas:
            raise RuntimeError(
                f"delta ring full ({self.max_deltas} deltas); call compact() "
                "to fold deltas into the base before inserting more"
            )
        sharding = self.key_sharding()
        keys = self.schema.pack_keys(keys, sharding)
        if values is None:
            if self.schema.value_cols != 1:
                raise ValueError(
                    f"schema has {self.schema.value_cols} value columns; "
                    "pass explicit values (the row-id default is 1-column)"
                )
            values = np.arange(keys.shape[0], dtype=np.int32)
        values = self.schema.pack_values(values, sharding)
        coherent_build = self.coherent_deltas
        tracing = any(
            isinstance(x, jax.core.Tracer)
            for x in jax.tree_util.tree_leaves((keys, st.base.hash_splits))
        )
        offsets = None
        if coherent_build and not tracing and self.replicate_hot_keys > 1:
            # One-key skew no split choice fixes: spread each hot key's
            # rows over R consecutive owners before the guard re-checks.
            offsets = self._hot_key_offsets(keys)
        if coherent_build and self.skew_guard:
            if not tracing and self._coherent_dispatch_overflows(
                keys, st.base.hash_splits, offsets
            ):
                # Skewed batch: the frozen-splits dispatch would drop rows.
                # A legacy-routed delta re-balances its own splits instead.
                coherent_build = False
                self.skew_fallbacks += 1
        if coherent_build:
            local_cap, stride = self._delta_bucket_geometry(keys.shape[0])
            if offsets is not None:
                delta = self._build_delta_offsets_jit(
                    keys,
                    values,
                    st.base.hash_splits,
                    offsets,
                    local_cap=local_cap,
                    stride=stride,
                )
            else:
                delta = self._build_delta_jit(
                    keys,
                    values,
                    st.base.hash_splits,
                    local_cap=local_cap,
                    stride=stride,
                )
            coherent = st.coherent
        else:
            delta = self._build_values_jit(
                keys, values, hash_range=self._delta_hash_range(keys.shape[0])
            )
            coherent = False  # mixed-split stack: per-layer routing from now on
        return dataclasses.replace(
            st, deltas=st.deltas + (delta,), coherent=coherent
        )

    def delete(self, state, keys) -> TableState:
        """Functional delete: tombstone every current occurrence of ``keys``.

        The tombstones are stamped with the current epoch, hiding matches in
        the base and in every delta inserted so far; keys re-inserted
        *after* the delete are visible again.  ``keys`` is a replicated
        (unsharded) array of any length; overflow past
        ``tombstone_capacity`` is counted in ``state.num_dropped``.
        """
        st = as_state(self, state)
        if st.tombstones.capacity == 0:
            # Legacy states lifted from a bare graph carry a zero-capacity
            # buffer (zero masking cost); grow it on first delete.
            st = dataclasses.replace(
                st,
                tombstones=empty_tombstones(
                    self.tombstone_capacity, self.schema.key_lanes
                ),
            )
        keys = self.schema.pack_keys(keys)
        return dataclasses.replace(
            st, tombstones=st.tombstones.push(keys, epoch=len(st.deltas))
        )

    def upsert(
        self,
        state,
        keys,
        values=None,
        *,
        ttl: Optional[int] = None,
        auto_compact: bool = False,
    ) -> TableState:
        """Functional insert-or-replace: after it, ``keys`` map to exactly
        ``values`` (KV semantics over the multiset core).

        One delete + one insert through the existing delta/tombstone
        machinery: prior versions of every key are tombstoned at the
        current epoch (hiding layers ``0..d``) and the new rows land in a
        fresh delta at epoch ``d + 1`` — so reads resolve the newest
        version with the fused 2-all-to-all budget unchanged, and
        last-writer-wins / read-your-writes hold by construction.  Within
        a batch, later occurrences of a duplicate key win (host-side
        keep-last dedup; under an outer ``jax.jit`` the dedup is skipped —
        keep traced batches duplicate-free).

        ``ttl`` schedules expiry: a pending tombstone at the *new* epoch
        with ``expires = now + ttl``, invisible until the logical clock
        (``state.advance``) reaches it, then masking the upserted row
        exactly like a delete.  Each upsert refreshes its key's lifetime —
        the old version's pending entries keep pointing at epochs the
        delete already hides.

        Unlike :meth:`insert`, ``keys`` need not be device-aligned: the
        batch is EMPTY-padded to the device multiple (padding rows are
        routed round-robin and never tombstoned, so they cost no
        tombstone slots).  ``auto_compact`` mirrors :meth:`insert`.
        Overflowing ``tombstone_capacity`` is counted in
        ``state.num_dropped`` — compaction restores exactness.
        """
        st = as_state(self, state)
        if auto_compact and st.should_compact():
            st = self.compact(st)
        keys = self.schema.pack_keys(keys)
        if values is None:
            if self.schema.value_cols != 1:
                raise ValueError(
                    f"schema has {self.schema.value_cols} value columns; "
                    "pass explicit values (the row-id default is 1-column)"
                )
            values = jnp.arange(keys.shape[0], dtype=jnp.int32)
        else:
            values = self.schema.pack_values(values)
        tracing = any(
            isinstance(x, jax.core.Tracer)
            for x in jax.tree_util.tree_leaves((keys, values))
        )
        if not tracing:
            # Keep-last dedup: KV semantics demand ONE winner per key per
            # batch (two surviving rows would both clear the epoch-d
            # tombstone and double the count).  EMPTY rows drop here too.
            kn = np.asarray(keys)
            vn = np.asarray(values)
            rows = kn if kn.ndim == 2 else kn[:, None]
            _, first = np.unique(rows[::-1], axis=0, return_index=True)
            keep = np.sort(rows.shape[0] - 1 - first)
            keep = keep[~np.all(rows[keep] == np.uint32(EMPTY_KEY), axis=1)]
            keys = jnp.asarray(kn[keep])
            values = jnp.asarray(vn[keep])
        if keys.shape[0] == 0:
            return st
        real = keys  # unpadded: tombstoning EMPTY pads would burn slots
        pad = (-keys.shape[0]) % self.num_devices
        if pad:
            keys = jnp.concatenate(
                [keys, jnp.full((pad,) + keys.shape[1:], EMPTY_KEY, jnp.uint32)]
            )
            values = jnp.concatenate(
                [values, jnp.full((pad,) + values.shape[1:], -1, jnp.int32)]
            )
        st = self.delete(st, real)  # hide prior versions: epoch d
        st = self.insert(st, keys, values)  # the new version: epoch d + 1
        if ttl is not None:
            st = dataclasses.replace(
                st,
                tombstones=st.tombstones.push(
                    real,
                    epoch=len(st.deltas),
                    expires=st.tombstones.now + jnp.int32(ttl),
                ),
            )
        return st

    def compact(self, state, *, capacity: Optional[int] = None) -> TableState:
        """Fold base + deltas − tombstones into a fresh base; reset the ring.

        Pure rebuild (jit-composable): every layer's stored rows are masked
        to the EMPTY sentinel where tombstoned, concatenated live-rows-first,
        and pushed through the standard four-phase build.

        Sizing: with ``capacity=None`` on the eager path, one counts round
        (``plans.exec_live_count``) measures the live (non-tombstoned) row
        total and sizes both the post-exchange row budget and the rebuild's
        per-destination slots from it — so steady-state insert/delete/compact
        cycles keep the base arrays *flat* instead of growing by the
        all-rows worst case every fold.  Under an outer ``jax.jit`` the
        live count cannot be read back, so the worst-case sizing applies
        (pass an explicit ``capacity`` to pin it).  ``capacity`` overrides
        the per-destination slot size of the rebuild exchange either way.

        The derived sizing is memoized per state *signature* (structure,
        not data — the ``ExecutorGrid`` idiom): a background maintenance
        loop cycling through identical insert/delete/fold structures pays
        the ``exec_live_count`` round trip once per structure, not once
        per compaction.  A memo hit with a drifted live count only risks
        a *smaller-than-ideal* budget, and any live row it truncates is
        tallied into ``num_dropped`` — never silent.
        """
        st = as_state(self, state)
        # Per-DEVICE concatenated row count: layer arrays are global views,
        # the rebuild exchange sees one shard of each.
        n_cat = sum(layer.local.keys.shape[0] for layer in st.layers)
        n_cat_local = _cdiv(n_cat, self.num_devices)
        rebuild_rows = None
        if capacity is None:
            tracing = any(
                isinstance(x, jax.core.Tracer)
                for x in jax.tree_util.tree_leaves(st)
            )
            if not tracing:
                sig = plans.state_signature(st)
                cached = self._sizing_memo.get(sig)
                if cached is not None:
                    capacity, rebuild_rows = cached
                else:
                    live = int(plans.exec_live_count(self, st))
                    live_local = _cdiv(live, self.num_devices)
                    # Post-deal per-device row budget: balanced live share
                    # plus the slack margin (skew beyond it is truncated —
                    # counted in num_dropped, never silent).
                    rebuild_rows = max(64, int(live_local * self.capacity_slack) + 8)
                    rebuild_rows = min(_cdiv(rebuild_rows, 8) * 8, n_cat_local)
                    capacity = multi_hashgraph.default_capacity(
                        rebuild_rows, self.num_devices, self.capacity_slack
                    ) + _cdiv(rebuild_rows, self.num_devices)
                    if len(self._sizing_memo) >= 128:  # bounded, like the grid
                        self._sizing_memo.clear()
                    self._sizing_memo[sig] = (capacity, rebuild_rows)
            else:
                # Balanced share of the worst case (all rows live) plus a
                # full round-robin allowance for the sentinel rows.
                capacity = multi_hashgraph.default_capacity(
                    n_cat_local, self.num_devices, self.capacity_slack
                ) + _cdiv(n_cat_local, self.num_devices)
        capacity = _cdiv(capacity, 8) * 8
        new_base = self._compact_jit(st, capacity=capacity, rebuild_rows=rebuild_rows)
        # Tombstone carry: effective entries (deletes + expired TTLs) are
        # applied by the rebuild and spent, but *pending* TTL entries masked
        # nothing yet — their rows survive into the new base, so the entries
        # must survive too (clamped to epoch 0 by the remap).  Eagerly with
        # nothing pending the buffer resets to the zero-capacity form (reads
        # pay no masking); traced compacts keep the capacity-preserving
        # remap — shape-stable, and correct either way.
        ts = st.tombstones
        lanes = self.schema.key_lanes
        if ts.capacity == 0:
            new_ts = empty_tombstones(0, lanes, now=ts.now)
        else:
            ts_tracing = any(
                isinstance(x, jax.core.Tracer)
                for x in jax.tree_util.tree_leaves(ts)
            )
            pending = ts_tracing or bool(
                np.any(
                    (np.asarray(ts.epochs) >= 0)
                    & (int(ts.now) < np.asarray(ts.expires))
                )
            )
            if pending:
                from repro.core.maintenance import _remap_tombstones

                new_ts = _remap_tombstones(ts, len(st.deltas))
            else:
                new_ts = empty_tombstones(0, lanes, now=ts.now)
        return TableState(
            base=new_base,
            deltas=(),
            tombstones=new_ts,
            table=self,
        )

    @partial(
        jax.jit, static_argnums=0, static_argnames=("capacity", "rebuild_rows")
    )
    def _compact_jit(
        self,
        state: TableState,
        *,
        capacity: int,
        rebuild_rows: Optional[int] = None,
    ):
        from repro.core import exchange

        def body(st):
            ts_keys, ts_epochs = st.tombstones.index()
            keys_parts, vals_parts = [], []
            for epoch, layer in enumerate(st.layers):
                k = layer.local.keys
                hidden = match_epochs_sorted(k, ts_keys, ts_epochs) >= epoch
                dead = is_empty_key(k) | hidden
                dead_b = dead[:, None] if k.ndim == 2 else dead
                keys_parts.append(jnp.where(dead_b, jnp.uint32(EMPTY_KEY), k))
                vals_parts.append(layer.local.values)
            keys_cat = jnp.concatenate(keys_parts, axis=0)
            vals_cat = jnp.concatenate(vals_parts, axis=0)
            # Pre-balance: the base layer is hash-partitioned, so rebuilding
            # directly would route every device's live rows to ONE owner and
            # the per-pair slot would need to hold a whole device's rows.  A
            # deterministic round-robin all_to_all first deals every D-th
            # row to each peer — STRIDED, not contiguous: live rows cluster
            # at the front of the bucket-sorted shards, so contiguous chunks
            # would re-concentrate them on one receiver — making both the
            # receivers' live loads and the rebuild's destination
            # distribution uniform (~n/D per pair).
            d = self.num_devices
            chunk = _cdiv(keys_cat.shape[0], d)
            pad = chunk * d - keys_cat.shape[0]
            if pad:
                keys_cat = jnp.concatenate(
                    [
                        keys_cat,
                        jnp.full((pad,) + keys_cat.shape[1:], EMPTY_KEY, jnp.uint32),
                    ]
                )
                vals_cat = jnp.concatenate(
                    [vals_cat, jnp.full((pad,) + vals_cat.shape[1:], -1, jnp.int32)]
                )

            def deal(x):
                # row i -> peer i % D (strided deal), then one all_to_all
                stripes = x.reshape(chunk, d, *x.shape[1:]).swapaxes(0, 1)
                mixed = exchange.all_to_all_hierarchical(stripes, self.axis_names)
                return mixed.reshape(d * chunk, *x.shape[1:])

            keys_cat = deal(keys_cat)
            vals_cat = deal(vals_cat)
            # Live rows first: exchange-capacity drops hit sentinels before
            # any real key (pack order within a destination is stable; the
            # row index is the second sort key, as in
            # exchange.pack_by_destination).
            _, order = jax.lax.sort(
                (
                    is_empty_key(keys_cat).astype(jnp.int32),
                    jnp.arange(keys_cat.shape[0], dtype=jnp.int32),
                ),
                num_keys=2,
                is_stable=False,
            )
            keys_cat = keys_cat[order]
            vals_cat = vals_cat[order]
            trunc_live = jnp.int32(0)
            if rebuild_rows is not None and rebuild_rows < keys_cat.shape[0]:
                # Live-count sizing: the post-deal rows beyond the budget are
                # (statistically) all sentinels; any live row lost to skew is
                # tallied into num_dropped below, never silently.
                trunc_live = jnp.sum(
                    ~is_empty_key(keys_cat[rebuild_rows:])
                ).astype(jnp.int32)
                keys_cat = keys_cat[:rebuild_rows]
                vals_cat = vals_cat[:rebuild_rows]
            built = self._build_body(
                keys_cat,
                vals_cat,
                self.hash_range,
                self.num_bins,
                capacity,
            )
            if rebuild_rows is not None:
                built = dataclasses.replace(
                    built,
                    num_dropped=built.num_dropped
                    + jax.lax.psum(trunc_live, self.axis_names),
                )
            return built

        return shard_map(
            body,
            mesh=self.mesh,
            in_specs=(plans.state_specs(state),),
            out_specs=self._out_specs(),
            check_vma=False,
        )(state)

    # -- plan builders ---------------------------------------------------------
    def plan_query(self, num_queries: Optional[int] = None) -> QueryPlan:
        """A pure ``(state, queries) -> counts`` callable (no capacities).

        Also exposes ``.join_size(state, queries)`` for the replicated join
        cardinality under the same plan.
        """
        return QueryPlan(self, num_queries)

    def plan_caps(self, state, queries) -> tuple[int, int]:
        """One counts round sizing retrieval exactly: ``(seg, out)`` ints.

        Blocks on a device→host read of two scalars — call at plan time,
        never inside a jitted program (pass explicit capacities there).
        """
        st = as_state(self, state)
        q = self._pack_queries(queries)
        seg_need, out_need = plans.exec_plan_caps(self, st, q)
        return int(seg_need), int(out_need)

    def _resolve_caps(self, state, queries, out_capacity, seg_capacity):
        """Static output sizing, lane-aligned, count-first.

        Any ``None`` capacity triggers the combined counts planning round
        (:func:`repro.core.multi_hashgraph.plan_caps_sharded`):
        ``out_capacity`` is sized *exactly* (rounded to the lane multiple)
        and ``seg_capacity`` is rounded up to a power of two — at most 2×
        the exact width while quantizing the static shape so repeated calls
        with shifting duplicate structure reuse a bounded set of compiled
        programs.  The planning round blocks on a device→host read; under
        an outer ``jax.jit`` pass explicit capacities instead.
        """
        if out_capacity is None or seg_capacity is None:
            seg_need, out_need = self.plan_caps(state, queries)
            if out_capacity is None:
                out_capacity = out_need
            if seg_capacity is None:
                seg_capacity = (
                    max(8, 1 << (seg_need - 1).bit_length()) if seg_need > 0 else 8
                )
        out_cap = max(8, _cdiv(out_capacity, 8) * 8)
        seg_cap = max(8, _cdiv(seg_capacity, 8) * 8)
        return out_cap, seg_cap

    def _plan_statics(
        self, name, state, queries, num_queries, out_capacity, seg_capacity
    ):
        """Shared plan-builder resolution: ``(num_queries, out_cap, seg_cap)``.

        Capacities left ``None`` are sized by the counts round against the
        sample ``(state, queries)`` (the only host sync; the returned plan
        itself never syncs).  With both capacities explicit no sample is
        needed and plan construction is free of device work.
        """
        if out_capacity is None or seg_capacity is None:
            if state is None or queries is None:
                raise ValueError(
                    f"{name} needs a (state, queries) sample to size "
                    "capacities, or explicit out_capacity and seg_capacity"
                )
            out_capacity, seg_capacity = self._resolve_caps(
                state, queries, out_capacity, seg_capacity
            )
        else:
            out_capacity = max(8, _cdiv(out_capacity, 8) * 8)
            seg_capacity = max(8, _cdiv(seg_capacity, 8) * 8)
        if num_queries is None and queries is not None:
            num_queries = self._pack_queries(queries).shape[0]
        return num_queries, out_capacity, seg_capacity

    def plan_retrieve(
        self,
        state=None,
        queries=None,
        *,
        num_queries: Optional[int] = None,
        out_capacity: Optional[int] = None,
        seg_capacity: Optional[int] = None,
        per_layer_counts: bool = False,
    ) -> RetrievePlan:
        """Build a pure ``(state, queries) -> ShardRetrieval`` callable.

        Capacity contract: see :meth:`_plan_statics`.  ``per_layer_counts``
        fills the result's ``layer_counts`` provenance field (same single
        all-to-all on the fused path).
        """
        return RetrievePlan(
            self,
            *self._plan_statics(
                "plan_retrieve", state, queries, num_queries, out_capacity, seg_capacity
            ),
            per_layer_counts=per_layer_counts,
        )

    def plan_join(
        self,
        state=None,
        queries=None,
        *,
        num_queries: Optional[int] = None,
        out_capacity: Optional[int] = None,
        seg_capacity: Optional[int] = None,
    ) -> JoinPlan:
        """Build a pure ``(state, queries) -> ShardJoin`` callable.

        Capacity contract: see :meth:`_plan_statics`.
        """
        return JoinPlan(
            self,
            *self._plan_statics(
                "plan_join", state, queries, num_queries, out_capacity, seg_capacity
            ),
        )

    # -- eager shims over the plan executors -----------------------------------
    def query(self, state, queries) -> jax.Array:
        """Multiplicity of each global query key. Returns (Nq,) int32.

        With hot-key replication active (keys in the ``hot_keys``
        registry), one extra routed round per replica rank merges the
        counts of rows spread off their hash owner — non-replicated keys
        count 0 on every round but the first, so the sum is exact for
        every key.  Without registered hot keys this is the single fused
        round, jaxpr-unchanged.

        .. deprecated:: thin shim over :meth:`plan_query`; accepts a bare
           ``DistributedHashGraph`` or a ``TableState``.
        """
        st = as_state(self, state)
        q = self._pack_queries(queries)
        total = plans.exec_query(self, st, q)
        rounds = max(self.hot_keys.values(), default=1)
        for r in range(1, rounds):
            total = total + plans.exec_query(self, st, q, dest_offset=r)
        return total

    def contains(self, state, queries) -> jax.Array:
        return self.query(state, queries) > 0

    def join_size(self, state, queries) -> jax.Array:
        """Global inner-join cardinality (scalar, replicated).

        .. deprecated:: thin shim over ``plan_query().join_size``.
        """
        return plans.exec_join_size(
            self, as_state(self, state), self._pack_queries(queries)
        )

    def retrieve(
        self,
        state,
        queries,
        *,
        out_capacity: Optional[int] = None,
        seg_capacity: Optional[int] = None,
        per_layer_counts: bool = False,
    ) -> ShardRetrieval:
        """All stored values for every occurrence of every query key.

        Returns a :class:`ShardRetrieval` whose fields are *global* arrays
        sharded over the mesh — each device holds the CSR over its own query
        shard: block ``d`` of ``offsets`` (``n_local+1`` rows) indexes block
        ``d`` of ``values`` (``out_capacity`` rows; ``(out_capacity, C)``
        for multi-column schemas).  Use :func:`retrieval_to_lists` for a
        host-side per-query view.

        ``out_capacity`` bounds each device's total result count and
        ``seg_capacity`` the results any one owner shard returns to one
        querying shard; both are static.  Either left ``None`` is sized by
        the count-first planning round (exact for ``out_capacity``, next
        power of two for ``seg_capacity``); the planning round blocks on a
        device→host read, so under an outer ``jax.jit`` pass explicit
        capacities (or use :meth:`plan_retrieve`).  Overflow is reported in
        ``num_dropped`` (replicated scalar) — never silently truncated.

        ``per_layer_counts=True`` additionally returns the per-layer count
        breakdown in ``.layer_counts`` (``(Nq, L)``, base first) — layer
        provenance for versioned reads, shipped in the same all-to-all on
        the fused path.

        .. deprecated:: thin shim over :meth:`plan_retrieve`.
        """
        st = as_state(self, state)
        q = self._pack_queries(queries)
        out_cap, seg_cap = self._resolve_caps(st, q, out_capacity, seg_capacity)
        return plans.exec_retrieve(
            self,
            st,
            q,
            out_capacity=out_cap,
            seg_capacity=seg_cap,
            per_layer_counts=per_layer_counts,
        )

    def inner_join(
        self,
        state,
        queries,
        *,
        out_capacity: Optional[int] = None,
        seg_capacity: Optional[int] = None,
    ) -> ShardJoin:
        """Materialized inner join: global ``(query_idx, value)`` match pairs.

        Each device emits its pairs into block ``d`` of the global
        ``query_idx``/``values`` arrays, with its valid-pair count in
        ``num_results[d]`` (pairs beyond it are ``-1`` padding).
        ``query_idx`` is the global query row id.  Same capacity/overflow
        contract as :meth:`retrieve`.

        .. deprecated:: thin shim over :meth:`plan_join`.
        """
        st = as_state(self, state)
        q = self._pack_queries(queries)
        out_cap, seg_cap = self._resolve_caps(st, q, out_capacity, seg_capacity)
        return plans.exec_join(
            self, st, q, out_capacity=out_cap, seg_capacity=seg_cap
        )

    # -- dynamic output buffers (ROADMAP: auto-retry on overflow) --------------
    def _auto_retry(
        self, exec_fn, state, queries, out_capacity, seg_capacity, max_retries
    ):
        """Re-run ``exec_fn`` with doubled caps while ``num_dropped > 0``.

        Bails early when doubling stops shrinking ``num_dropped`` — drops
        from the *dispatch* stage depend on ``capacity_slack``, not on the
        output caps, so no amount of doubling (and recompiling) fixes them.
        """
        st = as_state(self, state)
        q = self._pack_queries(queries)
        out_cap, seg_cap = self._resolve_caps(st, q, out_capacity, seg_capacity)
        res = exec_fn(self, st, q, out_capacity=out_cap, seg_capacity=seg_cap)
        dropped = int(res.num_dropped)
        for _ in range(max_retries):
            if dropped == 0:
                break
            out_cap, seg_cap = out_cap * 2, seg_cap * 2
            res = exec_fn(self, st, q, out_capacity=out_cap, seg_capacity=seg_cap)
            prev, dropped = dropped, int(res.num_dropped)
            if dropped >= prev:
                break  # not a capacity problem (e.g. route drops)
        return res

    def retrieve_auto(
        self,
        state,
        queries,
        *,
        out_capacity: Optional[int] = None,
        seg_capacity: Optional[int] = None,
        max_retries: int = 4,
    ) -> ShardRetrieval:
        """:meth:`retrieve` with bounded capacity-doubling retries.

        Re-runs with doubled ``out_capacity``/``seg_capacity`` while
        ``num_dropped > 0``, at most ``max_retries`` times (each retry is a
        fresh static shape, hence a recompile — the price of a guaranteed
        fit).  Returns the last attempt either way; callers still check
        ``num_dropped`` (nonzero only if the bound was exhausted or the
        drops are not capacity-fixable).
        """
        return self._auto_retry(
            plans.exec_retrieve, state, queries, out_capacity, seg_capacity, max_retries
        )

    def inner_join_auto(
        self,
        state,
        queries,
        *,
        out_capacity: Optional[int] = None,
        seg_capacity: Optional[int] = None,
        max_retries: int = 4,
    ) -> ShardJoin:
        """:meth:`inner_join` with bounded capacity-doubling retries."""
        return self._auto_retry(
            plans.exec_join, state, queries, out_capacity, seg_capacity, max_retries
        )


# ---------------------------------------------------------------------------
# Host-side views — vectorized numpy block slicing (no per-query Python loop)
# ---------------------------------------------------------------------------


def retrieval_to_lists(result: ShardRetrieval) -> list:
    """Host-side view of a :class:`ShardRetrieval`: one np.ndarray per query.

    Queries are sharded contiguously (device ``d`` owns rows
    ``d*n_local : (d+1)*n_local``), so global query ``i``'s values sit in
    device ``i // n_local``'s block of ``values`` at that block's local CSR
    offsets.  Multi-column schemas yield ``(k_i, C)`` arrays per query.

    Vectorized: per-shard valid prefixes are concatenated (``D`` slices) and
    one ``np.split`` at the per-query offset boundaries yields the views —
    no O(num_queries) Python loop.
    """
    counts = np.asarray(result.counts)
    offsets = np.asarray(result.offsets)
    values = np.asarray(result.values)
    num_queries = counts.shape[0]
    # len(offsets) = D*(n_local+1), len(counts) = D*n_local  =>  D:
    d = offsets.shape[0] - counts.shape[0]
    n_local = num_queries // d
    out_cap = values.shape[0] // d
    off2 = offsets.reshape(d, n_local + 1)
    flat = np.concatenate(
        [values[s * out_cap : s * out_cap + off2[s, -1]] for s in range(d)],
        axis=0,
    )
    # Per-query lengths from the (capacity-clamped) offsets, matching the
    # CSR exactly even when overflow truncated a tail.
    lens = np.diff(off2, axis=1).reshape(-1)
    return np.split(flat, np.cumsum(lens)[:-1])


def _retrieval_to_lists_loop(result: ShardRetrieval) -> list:
    """Reference implementation of :func:`retrieval_to_lists` (per-query
    Python loop) — kept for the vectorization parity tests."""
    counts = np.asarray(result.counts)
    offsets = np.asarray(result.offsets)
    values = np.asarray(result.values)
    num_queries = counts.shape[0]
    d = offsets.shape[0] - counts.shape[0]
    n_local = num_queries // d
    out_cap = values.shape[0] // d
    per_query = []
    for i in range(num_queries):
        shard, local = divmod(i, n_local)
        off = offsets[shard * (n_local + 1) + local]
        end = offsets[shard * (n_local + 1) + local + 1]
        per_query.append(values[shard * out_cap + off : shard * out_cap + end])
    return per_query


def join_to_pairs(result: ShardJoin) -> "np.ndarray":
    """Host-side view of a :class:`ShardJoin`: an (M, 1 + C) array of rows
    ``(query_idx, *value_columns)`` — ``(M, 2)`` for the 1-column schema.

    Vectorized: a single boolean mask (slot < per-shard ``num_results``)
    selects valid pairs from all shards at once.
    """
    qi = np.asarray(result.query_idx)
    vals = np.asarray(result.values)
    if vals.ndim == 1:
        vals = vals[:, None]
    nres = np.asarray(result.num_results)
    d = nres.shape[0]
    out_cap = qi.shape[0] // d
    mask = np.arange(out_cap)[None, :] < nres[:, None]
    qi_sel = qi.reshape(d, out_cap)[mask]
    vals_sel = vals.reshape(d, out_cap, -1)[mask]
    return np.concatenate([qi_sel[:, None], vals_sel], axis=1).astype(np.int32)


def _join_to_pairs_loop(result: ShardJoin) -> "np.ndarray":
    """Reference implementation of :func:`join_to_pairs` (per-shard loop) —
    kept for the vectorization parity tests."""
    qi = np.asarray(result.query_idx)
    vals = np.asarray(result.values)
    if vals.ndim == 1:
        vals = vals[:, None]
    nres = np.asarray(result.num_results)
    d = nres.shape[0]
    out_cap = qi.shape[0] // d
    parts = []
    for s in range(d):
        m = int(nres[s])
        parts.append(
            np.concatenate(
                [
                    qi[s * out_cap : s * out_cap + m, None],
                    vals[s * out_cap : s * out_cap + m],
                ],
                axis=1,
            )
        )
    ncols = 1 + vals.shape[1]
    return (
        np.concatenate(parts, axis=0)
        if parts
        else np.zeros((0, ncols), np.int32)
    )
