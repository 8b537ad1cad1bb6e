"""Multi-device HashGraph — Alg. 2 of the paper, on a TPU mesh.

Every function here runs *inside* ``shard_map`` over the device axes named
in ``axis_names`` (the hash table treats the whole mesh — e.g. ``("pod",
"data", "model")`` — as a flat 1-D device space; the exchange itself is
hierarchical per axis, see ``repro.core.exchange``).

Build (:func:`build_sharded`) follows the paper's four phases:

1. **Partitioning** — local coarse-bin histogram, ``psum``, balanced splits
   (``repro.core.partition``).
2. **Reorganization** — counting-sort keys by destination device.
3. **Movement** — capacity-padded hierarchical all-to-all.
4. **Creation** — single-device HashGraph per shard over its hash range.

Query (:func:`query_sharded`) is the paper's query: route query keys with
the *same* splits, intersect against the local table, route counts back.

Static-shape note: a device's hash-range width ``splits[d+1]-splits[d]`` is
data-dependent, but XLA needs a static local table size.  We allocate
``local_range_cap = ceil(HR/D) * range_slack`` buckets and clamp rebased
hash values into the last bucket.  Both build and query clamp through the
same deterministic map, so matching is exact even when clamping fires
(clamped buckets just get longer lists — HashGraph's collision handling
absorbs this, the paper's headline robustness property).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import exchange, hashing, hashgraph, partition
from repro.core.hashgraph import EMPTY_KEY, HashGraph
from repro.utils import cdiv


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("local", "hash_splits", "num_dropped"),
    meta_fields=("hash_range", "seed", "local_range_cap", "axis_names", "bucket_stride"),
)
@dataclasses.dataclass(frozen=True)
class DistributedHashGraph:
    """Per-device shard of the distributed table (inside shard_map).

    ``bucket_stride`` coarsens the rebased-hash → local-bucket map:
    ``bucket = clip((h - lo) // stride, 0, local_range_cap - 1)``.  The base
    graph uses stride 1 (one bucket per hash value slot); delta graphs built
    on the base's *frozen* splits shrink their offsets arrays by striding
    instead of narrowing the hash range, which keeps routing identical
    across the layer stack (the partition-coherence invariant behind
    single-route layered execution).  Striding only lengthens bucket lists;
    the sorted-bucket binary search absorbs it exactly like split clamping.
    """

    local: HashGraph  # this device's CSR over its hash range
    hash_splits: jax.Array  # (D+1,) int32 — identical on all devices
    num_dropped: jax.Array  # () int32 — capacity overflow during build
    hash_range: int
    seed: int
    local_range_cap: int
    axis_names: tuple
    bucket_stride: int = 1


def default_capacity(n_local: int, num_devices: int, slack: float) -> int:
    """Per-destination slot size: balanced share × slack, lane-aligned."""
    base = cdiv(n_local, num_devices)
    cap = int(base * slack) + 8
    return cdiv(cap, 8) * 8


def _rebase_buckets(
    h: jax.Array,
    is_pad: jax.Array,
    lo: jax.Array,
    local_cap: int,
    stride: int,
) -> jax.Array:
    """Rebased hash → local bucket id, sentinel keys → trash bucket.

    Split off from the hashing so the fused layered paths hash a routed
    batch once and rebase per layer (layers share ``hash_range``/``seed``
    but may differ in ``local_cap``/``stride``).
    """
    rebased = h - lo
    if stride != 1:
        rebased = rebased // jnp.int32(stride)
    rebased = jnp.clip(rebased, 0, local_cap - 1)
    return jnp.where(is_pad, jnp.int32(local_cap), rebased)


def _local_buckets(
    keys: jax.Array,
    lo: jax.Array,
    hash_range: int,
    local_cap: int,
    seed: int,
    stride: int = 1,
) -> jax.Array:
    """Rebased hash → local bucket id, sentinel keys → trash bucket."""
    h = hashing.hash_to_buckets(keys, hash_range, seed=seed)
    is_pad = hashgraph.is_empty_key(keys)
    return _rebase_buckets(h, is_pad, lo, local_cap, stride)


def build_sharded(
    keys: jax.Array,
    *,
    hash_range: int,
    axis_names: Sequence[str],
    values: Optional[jax.Array] = None,
    num_bins: Optional[int] = None,
    capacity_slack: float = 1.25,
    range_slack: float = 1.5,
    seed: int = hashing.DEFAULT_SEED,
    capacity: Optional[int] = None,
    hash_splits: Optional[jax.Array] = None,
    local_range_cap: Optional[int] = None,
    bucket_stride: int = 1,
    fingerprint: Optional[bool] = None,
    dest_offsets: Optional[jax.Array] = None,
) -> DistributedHashGraph:
    """Build the distributed HashGraph from this device's local ``keys``.

    ``values`` (payload, e.g. original global row ids for joins) ride along
    through the exchange.  ``keys`` may contain EMPTY sentinels (compaction
    rebuilds ship tombstoned rows masked to EMPTY): sentinels are excluded
    from the balanced-split histogram and the overflow count, spread
    round-robin over destinations, and land in the owner's trash bucket.
    ``capacity`` overrides the per-destination slot size (compaction passes
    an allowance for the sentinel rows).

    ``hash_splits`` *freezes* the partitioning: phase 1 (histogram → psum →
    balanced splits) is skipped entirely and the given split points route
    the exchange.  This is how delta graphs stay partition-coherent with
    their base — same hash range, same seed, same owners — so one query
    dispatch serves the whole layer stack.  ``local_range_cap`` /
    ``bucket_stride`` size the local bucket space (deltas stride the base's
    bucket map down to O(batch) offsets instead of paying the base's
    O(hash_range / D) arrays).  ``fingerprint`` selects the probe
    fingerprint lane for the local CSR (None = auto by key width, see
    :func:`repro.core.hashgraph.build_from_buckets`); the fingerprints are
    derived owner-side from the routed keys, so the exchange itself is
    unchanged.  Call inside ``shard_map``.

    ``dest_offsets`` (hot-key replication) shifts each row's destination by
    a per-row device offset — ``(hash owner + offset) % D`` — so a single
    hot key's rows spread across ``R`` owners instead of funnelling into
    one device's dispatch slot.  Off-owner rows land in the receiving
    device's *clamped* bucket (``_rebase_buckets`` clips out-of-range
    buckets), where the exact key compare of every probe path still finds
    them; readers recover the full count by summing query rounds routed
    with each ``dest_offset`` (see ``query_sharded``).
    """
    axis_names = tuple(axis_names)
    keys = keys.astype(jnp.uint32)
    n_local = keys.shape[0]
    num_devices = exchange.device_count(axis_names)
    if values is None:
        # Globalize the default payload: original row id within this shard,
        # offset by the shard's rank so values are unique across devices.
        values = exchange.my_rank(axis_names) * n_local + jnp.arange(
            n_local, dtype=jnp.int32
        )
    is_pad = hashgraph.is_empty_key(keys)

    # ---- Phase 1: partitioning --------------------------------------------
    h = hashing.hash_to_buckets(keys, hash_range, seed=seed)
    if hash_splits is None:
        bins_g = num_bins or partition.choose_num_bins(hash_range, num_devices)
        hist = partition.local_bin_histogram(h, bins_g, hash_range, valid=~is_pad)
        ghist = jax.lax.psum(hist, axis_names)
        splits = partition.balanced_hash_splits(ghist, num_devices, hash_range)
    else:
        splits = hash_splits.astype(jnp.int32)  # frozen: no collective round

    # ---- Phase 2: reorganization ------------------------------------------
    dest = partition.destination_of(h, splits)
    if dest_offsets is not None:
        dest = (dest + dest_offsets.astype(jnp.int32)) % num_devices
    # Sentinels route round-robin (all EMPTY rows hash identically — sending
    # them by hash would funnel every one to a single owner's slot).
    dest = jnp.where(
        is_pad, jnp.arange(n_local, dtype=jnp.int32) % num_devices, dest
    )

    # ---- Phase 3: movement -------------------------------------------------
    if capacity is None:
        capacity = default_capacity(n_local, num_devices, capacity_slack)
    (rkeys, rvalues), route = exchange.dispatch(
        (keys, values),
        dest,
        axis_names,
        capacity,
        fills=(jnp.uint32(EMPTY_KEY), jnp.int32(-1)),
        count_mask=~is_pad,
    )

    # ---- Phase 4: local HashGraph creation ---------------------------------
    if local_range_cap is None:
        local_cap = int(cdiv(hash_range, num_devices) * range_slack)
    else:
        local_cap = int(local_range_cap)
    rank = exchange.my_rank(axis_names)
    lo = splits[rank]
    buckets = _local_buckets(rkeys, lo, hash_range, local_cap, seed, bucket_stride)
    local = hashgraph.build_from_buckets(
        rkeys,
        buckets,
        local_cap,
        rvalues,
        seed=seed,
        sort_within_bucket=True,
        fingerprint=fingerprint,
    )
    return DistributedHashGraph(
        local=local,
        hash_splits=splits,
        num_dropped=jax.lax.psum(route.num_dropped, axis_names),
        hash_range=hash_range,
        seed=seed,
        local_range_cap=local_cap,
        axis_names=axis_names,
        bucket_stride=bucket_stride,
    )


def _route_queries_once(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    capacity_slack: float,
    dest_offset: int = 0,
) -> tuple[jax.Array, exchange.Route, jax.Array, jax.Array, jax.Array, int]:
    """The one exchange round of the query hot path (paper §3.3 phase 1).

    Hash local queries and dispatch them to their owning shards by the
    *build* splits of ``dhg``.  On a partition-coherent layer stack this
    single round serves every layer: the owner-side hash of the received
    keys is layer-independent (same hash range and seed), and each layer
    rebases it into its own bucket space via :func:`_rebase_buckets`.

    ``dest_offset`` (static) routes every query ``r`` devices past its hash
    owner — the read side of hot-key replication (``build_sharded``'s
    ``dest_offsets``): replica ``r`` of a hot key lives on device
    ``(owner + r) % D``, and a non-replicated key simply counts 0 there
    (the exact key compare finds nothing), so summing rounds over
    ``r = 0..R-1`` merges replica counts exactly.  The default 0 is guarded
    to keep the hot path's jaxpr byte-identical.

    Returns ``(rq, route, rh, is_pad, lo, capacity)`` — received queries
    (EMPTY-padded), the reverse route, their owner-side hash values, the
    padding mask, this owner's split base, and the per-(src, dst) slot
    capacity.
    """
    axis_names = dhg.axis_names
    queries = queries.astype(jnp.uint32)
    num_devices = exchange.device_count(axis_names)

    h = hashing.hash_to_buckets(queries, dhg.hash_range, seed=dhg.seed)
    dest = partition.destination_of(h, dhg.hash_splits)
    if dest_offset:
        dest = (dest + jnp.int32(dest_offset)) % num_devices
    capacity = default_capacity(queries.shape[0], num_devices, capacity_slack)
    (rq,), route = exchange.dispatch(
        (queries,), dest, axis_names, capacity, fills=(jnp.uint32(EMPTY_KEY),)
    )
    lo = dhg.hash_splits[exchange.my_rank(axis_names)]
    rh = hashing.hash_to_buckets(rq, dhg.hash_range, seed=dhg.seed)
    is_pad = hashgraph.is_empty_key(rq)
    return rq, route, rh, is_pad, lo, capacity


def _route_queries(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    capacity_slack: float,
    dest_offset: int = 0,
) -> tuple[jax.Array, exchange.Route, jax.Array, int]:
    """Single-graph routing preamble: :func:`_route_queries_once` plus this
    graph's own bucket rebase.

    Every per-layer query path (count, retrieve, planning, query-side
    HashGraph) routes through this one function: the planning round's
    correctness depends on using the exact same capacity and slot layout as
    retrieval.  Returns ``(rq, route, rbuckets, capacity)``.
    """
    rq, route, rh, is_pad, lo, capacity = _route_queries_once(
        dhg, queries, capacity_slack, dest_offset
    )
    rbuckets = _rebase_buckets(
        rh, is_pad, lo, dhg.local_range_cap, dhg.bucket_stride
    )
    return rq, route, rbuckets, capacity


def _routed_fingerprints(
    layers: Sequence[DistributedHashGraph], rq: jax.Array
) -> Optional[jax.Array]:
    """Probe fingerprints of a routed query batch, or None if no layer
    carries a fingerprint lane.

    Hashed once per exchange round and shared by every layer's locate —
    the fused stack pays one ``fingerprint32`` per routed batch, not per
    layer.  Layers without the lane simply ignore the precomputed values
    (``query_locate`` drops ``qfp`` for plain tables), so mixed stacks
    stay correct.
    """
    if any(layer.local.fingerprints is not None for layer in layers):
        return hashing.fingerprint32(rq)
    return None


def _tombstone_epochs(
    rq: jax.Array, tombstones: Optional[tuple[jax.Array, jax.Array]]
) -> Optional[jax.Array]:
    """Newest tombstone epoch per routed key, or None without tombstones.

    ``tombstones`` is the *sorted* ``(keys, epochs)`` index of the versioned
    table (``Tombstones.index()``): the lookup is one binary search per key
    — O(R log T) per routed batch instead of the old O(R·T) broadcast
    compare.  Computed once per routing round and shared by every layer's
    mask (a tombstone with epoch ``e`` hides layers ``0..e``).
    """
    if tombstones is None:
        return None
    ts_keys, ts_epochs = tombstones
    return hashgraph.match_epochs_sorted(rq, ts_keys, ts_epochs)


def _mask_counts(
    counts: jax.Array,
    rq: jax.Array,
    tombstones: Optional[tuple[jax.Array, jax.Array]],
    layer_epoch: int,
    match_e: Optional[jax.Array] = None,
) -> jax.Array:
    """Zero counts of padding slots and of rows hidden by tombstones.

    ``tombstones`` is the sorted ``(keys, epochs)`` index
    (``Tombstones.index()``); a row is hidden from the layer with epoch
    ``layer_epoch`` iff a matching tombstone with epoch >= ``layer_epoch``
    exists (deleted at or after this layer's creation).  ``match_e``
    short-circuits the lookup with a precomputed per-key epoch (the fused
    layered paths resolve it once per routed batch).
    """
    counts = jnp.where(hashgraph.is_empty_key(rq), 0, counts)
    if match_e is None:
        match_e = _tombstone_epochs(rq, tombstones)
    if match_e is not None:
        counts = jnp.where(match_e >= layer_epoch, 0, counts)
    return counts


def query_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    capacity_slack: float = 1.25,
    paper_faithful_probe: bool = False,
    max_probe: int = 64,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    layer_epoch: int = 0,
    dest_offset: int = 0,
) -> jax.Array:
    """Multiplicity of each local query key in the distributed table.

    Phases (paper §3.3 "Querying Multi-GPU HashGraph"): route queries by the
    *build* splits, count against the local shard, route counts back.
    ``tombstones`` (the sorted ``Tombstones.index()`` pair) / ``layer_epoch``
    mask rows deleted from this layer of a versioned table (see
    :func:`_mask_counts`).  ``dest_offset`` counts replica ``r`` of
    hot-key-replicated rows (see :func:`_route_queries_once`).  Returns an
    int32 array aligned with ``queries``.
    """
    axis_names = dhg.axis_names
    rq, route, rbuckets, _ = _route_queries(
        dhg, queries, capacity_slack, dest_offset
    )
    if paper_faithful_probe:
        counts = hashgraph.query_count_probe(
            dhg.local, rq, max_probe=max_probe, buckets=rbuckets
        )
    else:
        counts = hashgraph.query_count_sorted(dhg.local, rq, buckets=rbuckets)
    # Padding slots probe the trash bucket; force their count to zero anyway.
    counts = _mask_counts(counts, rq, tombstones, layer_epoch)
    return exchange.combine(counts, route, axis_names, fill=jnp.int32(0))


def query_layers_sharded(
    layers: Sequence[DistributedHashGraph],
    queries: jax.Array,
    *,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    fused: Optional[bool] = None,
    capacity_slack: float = 1.25,
    paper_faithful_probe: bool = False,
    max_probe: int = 64,
    dest_offset: int = 0,
) -> jax.Array:
    """Merged multiplicity over a versioned stack of layers.

    ``layers`` is ``(base, delta_1, ..., delta_L)`` — layer ``i`` has epoch
    ``i``, so a tombstone stamped with epoch ``e`` hides layers ``0..e`` and
    leaves later inserts visible (delete-then-reinsert works).

    ``fused`` selects single-route execution: one dispatch all-to-all and
    one count return serve the whole stack (valid only when every layer
    shares the base's splits — the ``TableState.coherent`` invariant; the
    caller asserts it).  ``fused=False`` is the per-layer legacy path for
    mixed-split stacks (L dispatches, L returns).  ``None`` auto-selects
    fused only for the trivially coherent single-layer stack.
    """
    layers = tuple(layers)
    if fused is None:
        fused = len(layers) == 1
    if not fused:
        total = jnp.zeros(queries.shape[0], jnp.int32)
        for epoch, layer in enumerate(layers):
            total = total + query_sharded(
                layer,
                queries,
                tombstones=tombstones,
                layer_epoch=epoch,
                capacity_slack=capacity_slack,
                paper_faithful_probe=paper_faithful_probe,
                max_probe=max_probe,
                dest_offset=dest_offset,
            )
        return total

    base = layers[0]
    rq, route, rh, is_pad, lo, _ = _route_queries_once(
        base, queries, capacity_slack, dest_offset
    )
    match_e = _tombstone_epochs(rq, tombstones)
    rfp = _routed_fingerprints(layers, rq)
    total = jnp.zeros(rq.shape[0], jnp.int32)
    for epoch, layer in enumerate(layers):
        rb = _rebase_buckets(rh, is_pad, lo, layer.local_range_cap, layer.bucket_stride)
        if paper_faithful_probe:
            c = hashgraph.query_count_probe(
                layer.local, rq, max_probe=max_probe, buckets=rb
            )
        else:
            c = hashgraph.query_count_sorted(layer.local, rq, buckets=rb, qfp=rfp)
        total = total + _mask_counts(c, rq, tombstones, epoch, match_e)
    # One merged return trip carries the whole stack's counts.
    return exchange.combine(total, route, base.axis_names, fill=jnp.int32(0))


def contains_sharded(
    dhg: DistributedHashGraph, queries: jax.Array, **kw
) -> jax.Array:
    """Membership test for each local query key."""
    return query_sharded(dhg, queries, **kw) > 0


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("offsets", "values", "counts", "num_dropped", "layer_counts"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class ShardRetrieval:
    """Per-device CSR of retrieved values (inside shard_map).

    Local query ``i``'s values are ``values[offsets[i]:offsets[i+1]]``.
    ``num_dropped`` is a *global* (psum'd) overflow indicator: zero iff no
    static capacity anywhere in the pipeline truncated results.  When
    positive it is an unnormalized tally (stage drops can double-count the
    same missing result), not an exact loss count — treat any nonzero value
    as "rerun with larger ``seg_capacity``/``out_capacity``".  Never
    silently truncated.

    ``layer_counts`` is the optional per-layer provenance breakdown
    (``retrieve(..., per_layer_counts=True)``): an ``(n_local_queries, L)``
    int32 array with ``layer_counts[i].sum() == counts[i]`` — query ``i``'s
    result count split by layer epoch (base first).  ``None`` unless
    requested; on the fused path it rides home inside the same single
    all-to-all as the values (the bitcast packing trick of
    ``exchange.combine_ragged``), so requesting it adds no collective round.
    """

    offsets: jax.Array  # (n_local_queries + 1,) int32
    values: jax.Array  # (out_capacity,) int32
    counts: jax.Array  # (n_local_queries,) int32
    num_dropped: jax.Array  # () int32, global
    layer_counts: Optional[jax.Array] = None  # (n_local_queries, L) int32


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("query_idx", "values", "num_results", "num_dropped"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class ShardJoin:
    """Per-device materialized join pairs (inside shard_map).

    ``(query_idx[j], values[j])`` for ``j < num_results[0]`` are the match
    pairs produced by this device's queries; ``query_idx`` is the *global*
    query row id (rank * n_local + local index).  Same ``num_dropped``
    contract as :class:`ShardRetrieval`.
    """

    query_idx: jax.Array  # (out_capacity,) int32, -1 beyond num_results
    values: jax.Array  # (out_capacity,) int32
    num_results: jax.Array  # (1,) int32 — this device's valid pair count
    num_dropped: jax.Array  # () int32, global


def _use_kernel_default(use_kernel: Optional[bool]) -> bool:
    """Resolve the kernel-path flag: the XLA gather unless asked otherwise.

    The Pallas gathers (``kernels.ops.csr_gather*``) do not lower for TPU:
    compiled for v5e, Mosaic refuses their dynamic gather from a flattened
    VMEM ref ("Only 2D gather is supported") at every table size, and each
    would hold the owner's whole values column in VMEM.  So every backend
    defaults to ``hashgraph.csr_gather``; ``use_kernel=True`` selects the
    kernels (interpret mode off the chip, where their tests run).
    """
    return bool(use_kernel)


def _csr_gather_any(starts, counts, table, capacity: int, use_kernel: bool):
    """CSR gather via the Pallas kernel or the jnp idiom (the default).

    Same ``(offsets, row_idx, gathered, num_dropped)`` contract either way.
    """
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.csr_gather(starts, counts, table, capacity=capacity)
    return hashgraph.csr_gather(starts, counts, table, capacity)


def _retrieve_runs(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    seg_capacity: int,
    capacity_slack: float,
    use_kernel: bool,
    tombstones: Optional[tuple[jax.Array, jax.Array]],
    layer_epoch: int,
):
    """One layer's owner-side gather + return trip.

    Pass 1 (count): route queries to owning shards by the build splits and
    locate each routed query's contiguous match run in the local CSR.
    Pass 2 (gather): each owner prefix-sums the run lengths *per source
    block* and gathers the matched values into one static segment per source
    (the HashGraph build idiom applied to results) — a single fused Pallas
    launch over all sources on the kernel path — then a reverse all-to-all
    returns segments and run lengths to the querying shard.

    Returns ``(counts, starts, seg_flat, dropped)`` in the querier's local
    row order: row ``i``'s values are
    ``seg_flat[starts[i] : starts[i] + counts[i]]``.
    """
    axis_names = dhg.axis_names
    num_devices = exchange.device_count(axis_names)

    rq, route, rbuckets, capacity = _route_queries(dhg, queries, capacity_slack)
    run_starts, run_counts = hashgraph.query_locate(dhg.local, rq, buckets=rbuckets)
    run_counts = _mask_counts(run_counts, rq, tombstones, layer_epoch)

    # Owner side: one packed segment of matched values per source device.
    starts_b = run_starts.reshape(num_devices, capacity)
    counts_b = run_counts.reshape(num_devices, capacity)
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        # Fused launch: one grid over (sources, capacity tiles) instead of
        # one pallas_call per source block.
        _, _, seg_values, owner_dropped = kernel_ops.csr_gather_batched(
            starts_b, counts_b, dhg.local.values, capacity=seg_capacity
        )
    else:
        _, _, seg_values, seg_dropped = jax.vmap(
            lambda s, c: hashgraph.csr_gather(s, c, dhg.local.values, seg_capacity)
        )(starts_b, counts_b)
        owner_dropped = jnp.sum(seg_dropped)

    # Querier side: segments + run lengths come home.
    counts, starts, seg_flat = exchange.combine_ragged(
        seg_values, run_counts, route, axis_names
    )
    return counts, starts, seg_flat, owner_dropped + route.num_dropped


def _layer_run_descriptors(
    layers: Sequence[DistributedHashGraph],
    rq: jax.Array,
    rh: jax.Array,
    is_pad: jax.Array,
    lo: jax.Array,
    tombstones: Optional[tuple[jax.Array, jax.Array]],
) -> tuple[jax.Array, jax.Array, tuple]:
    """Owner-side batched locate across a partition-coherent layer stack.

    One binary-search locate per layer against the *same* routed batch
    (compute only — no communication), with each layer's run starts offset
    into the concatenated value-table address space.  Tombstone epochs are
    resolved once for the batch and reused by every layer's mask.

    Returns ``(starts, counts, tables)``: ``(L, R)`` stacked descriptors
    (``R`` = routed slots) addressing ``jnp.concatenate(tables)``.
    """
    match_e = _tombstone_epochs(rq, tombstones)
    rfp = _routed_fingerprints(layers, rq)
    starts_l, counts_l, tables = [], [], []
    off = 0
    for epoch, layer in enumerate(layers):
        rb = _rebase_buckets(rh, is_pad, lo, layer.local_range_cap, layer.bucket_stride)
        s, c = hashgraph.query_locate(layer.local, rq, buckets=rb, qfp=rfp)
        c = _mask_counts(c, rq, tombstones, epoch, match_e)
        starts_l.append(s + off)
        counts_l.append(c)
        tables.append(layer.local.values)
        off += layer.local.values.shape[0]
    return jnp.stack(starts_l), jnp.stack(counts_l), tuple(tables)


def _csr_gather_layers_ref(starts, counts, tables, capacity: int):
    """jnp reference of ``kernels.ops.csr_gather_layers``: a vmapped
    ``hashgraph.csr_gather`` over the *same* interleaved descriptors (the
    packing order has exactly one definition —
    ``kernels.ops.interleave_layer_runs``)."""
    from repro.kernels.ops import interleave_layer_runs

    starts_i, counts_i, table_cat = interleave_layer_runs(starts, counts, tables)
    _, _, seg_values, seg_dropped = jax.vmap(
        lambda a, b: hashgraph.csr_gather(a, b, table_cat, capacity)
    )(starts_i, counts_i)
    return seg_values, jnp.sum(seg_dropped)


def _retrieve_parts_fused(
    layers: tuple,
    queries: jax.Array,
    *,
    seg_capacity: int,
    out_capacity: int,
    capacity_slack: float,
    use_kernel: bool,
    tombstones: Optional[tuple[jax.Array, jax.Array]],
    per_layer: bool = False,
):
    """Single-route merged retrieval over a partition-coherent layer stack.

    One dispatch all-to-all routes the queries for *every* layer at once
    (all layers share the base's splits); owner-side, the per-layer locates
    run back-to-back on the routed batch and one fused gather packs each
    routed query's runs — layer-minor, epoch order — into a single segment
    per source device; one ragged return ships segments + per-slot totals
    home.  Collective rounds per retrieve: 2, independent of delta depth
    (previously ``~3·L``).

    ``per_layer=True`` additionally returns the per-layer count breakdown
    (``(n_local, L)``): the owner's per-layer run-length planes are bitcast
    into the same fused return buffer (``exchange.combine_ragged``'s
    ``layer_counts``), so provenance costs zero extra collective rounds.
    """
    base = layers[0]
    nlayers = len(layers)
    axis_names = base.axis_names
    num_devices = exchange.device_count(axis_names)
    n_local = queries.shape[0]
    rank = exchange.my_rank(axis_names)

    rq, route, rh, is_pad, lo, capacity = _route_queries_once(
        base, queries, capacity_slack
    )
    starts_lr, counts_lr, tables = _layer_run_descriptors(
        layers, rq, rh, is_pad, lo, tombstones
    )
    # (L, D*cap) -> (L, D, cap): the gather's source axis is the dispatching
    # device, its row axis the slot-major/layer-minor interleaved runs.
    starts_lsn = starts_lr.reshape(nlayers, num_devices, capacity)
    counts_lsn = counts_lr.reshape(nlayers, num_devices, capacity)
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        seg_values, owner_dropped = kernel_ops.csr_gather_layers(
            starts_lsn, counts_lsn, tables, capacity=seg_capacity
        )
    else:
        seg_values, owner_dropped = _csr_gather_layers_ref(
            starts_lsn, counts_lsn, tables, seg_capacity
        )

    # One ragged return: per-slot totals over the stack reconstruct, on the
    # querier, exactly the interleaved offsets the owner packed with.
    slot_totals = jnp.sum(counts_lr, axis=0)
    layer_breakdown = None
    if per_layer:
        counts, starts, seg_flat, layer_breakdown = exchange.combine_ragged(
            seg_values, slot_totals, route, axis_names, layer_counts=counts_lr
        )
    else:
        counts, starts, seg_flat = exchange.combine_ragged(
            seg_values, slot_totals, route, axis_names
        )
    offsets, slot_rows, values, out_dropped = _csr_gather_any(
        starts, counts, seg_flat, out_capacity, use_kernel
    )
    num_dropped = jax.lax.psum(
        owner_dropped + route.num_dropped + out_dropped, axis_names
    )
    return offsets, slot_rows, values, counts, num_dropped, rank, n_local, layer_breakdown


def _retrieve_parts(
    layers: Sequence[DistributedHashGraph],
    queries: jax.Array,
    *,
    seg_capacity: int,
    out_capacity: int,
    capacity_slack: float = 1.25,
    use_kernel: Optional[bool] = None,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    fused: Optional[bool] = None,
    per_layer: bool = False,
):
    """Merged two-pass retrieval over a layer stack; returns the local CSR.

    ``fused=True`` (valid only for partition-coherent stacks — the
    ``TableState.coherent`` invariant) takes
    :func:`_retrieve_parts_fused`: one exchange round for the whole stack.
    ``fused=False`` is the legacy per-layer path for mixed-split stacks:
    :func:`_retrieve_runs` per layer (base epoch 0, delta ``i`` epoch
    ``i``), then one querier-side gather compacts all layers' returned runs
    into the output CSR — the per-layer ``(start, count)`` run descriptors
    are interleaved query-major, so the standard ``csr_gather`` produces
    the merged values array directly and every L-th offset is the per-query
    merged offset.  ``None`` auto-selects fused only for the trivially
    coherent single-layer stack.

    ``use_kernel`` selects the Pallas ``csr_gather`` kernel for both gather
    stages (None/False: the XLA gather, see :func:`_use_kernel_default`).
    Both paths produce identical outputs (same per-query epoch-order value
    runs), including the ``per_layer`` count breakdown (fused: shipped in
    the same all-to-all; legacy: stacked from the per-layer return trips).
    """
    layers = tuple(layers)
    nlayers = len(layers)
    use_kernel = _use_kernel_default(use_kernel)
    if fused is None:
        fused = nlayers == 1
    if fused:
        return _retrieve_parts_fused(
            layers,
            queries,
            seg_capacity=seg_capacity,
            out_capacity=out_capacity,
            capacity_slack=capacity_slack,
            use_kernel=use_kernel,
            tombstones=tombstones,
            per_layer=per_layer,
        )

    axis_names = layers[0].axis_names
    n_local = queries.shape[0]
    rank = exchange.my_rank(axis_names)

    counts_l, starts_l, segs_l = [], [], []
    dropped = jnp.int32(0)
    for epoch, layer in enumerate(layers):
        counts, starts, seg_flat, drop = _retrieve_runs(
            layer,
            queries,
            seg_capacity=seg_capacity,
            capacity_slack=capacity_slack,
            use_kernel=use_kernel,
            tombstones=tombstones,
            layer_epoch=epoch,
        )
        counts_l.append(counts)
        starts_l.append(starts + epoch * seg_flat.shape[0])
        segs_l.append(seg_flat)
        dropped = dropped + drop

    seg_all = segs_l[0] if nlayers == 1 else jnp.concatenate(segs_l, axis=0)
    counts_il = jnp.stack(counts_l, axis=1).reshape(n_local * nlayers)
    starts_il = jnp.stack(starts_l, axis=1).reshape(n_local * nlayers)
    offsets_il, slot_rows, values, out_dropped = _csr_gather_any(
        starts_il, counts_il, seg_all, out_capacity, use_kernel
    )
    offsets = offsets_il[::nlayers]  # every L-th interleaved offset
    counts = counts_il.reshape(n_local, nlayers).sum(axis=1).astype(jnp.int32)
    query_idx = jnp.where(slot_rows >= 0, slot_rows // nlayers, jnp.int32(-1))
    # Overflow indicator, not an exact loss count: the stages can
    # double-count one missing result (owner segment + querier output), and
    # route drops count lost query *rows* whose result count is unknown.
    # Zero iff nothing anywhere was truncated.
    num_dropped = jax.lax.psum(dropped + out_dropped, axis_names)
    layer_breakdown = (
        jnp.stack(counts_l, axis=1).astype(jnp.int32) if per_layer else None
    )
    return offsets, query_idx, values, counts, num_dropped, rank, n_local, layer_breakdown


def retrieve_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    seg_capacity: int,
    out_capacity: int,
    capacity_slack: float = 1.25,
    use_kernel: Optional[bool] = None,
) -> ShardRetrieval:
    """All stored values for every occurrence of every local query key.

    Returns this device's :class:`ShardRetrieval` CSR over its ``queries``.
    Call inside ``shard_map``.
    """
    return retrieve_layers_sharded(
        (dhg,),
        queries,
        seg_capacity=seg_capacity,
        out_capacity=out_capacity,
        capacity_slack=capacity_slack,
        use_kernel=use_kernel,
    )


def retrieve_layers_sharded(
    layers: Sequence[DistributedHashGraph],
    queries: jax.Array,
    *,
    seg_capacity: int,
    out_capacity: int,
    capacity_slack: float = 1.25,
    use_kernel: Optional[bool] = None,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    fused: Optional[bool] = None,
    per_layer_counts: bool = False,
) -> ShardRetrieval:
    """Merged retrieval over a versioned layer stack (base + deltas).

    Per-query values concatenate layer runs in epoch order; tombstoned rows
    are masked before the gather, so they consume no output capacity.
    ``fused`` selects single-route execution over a partition-coherent
    stack (see :func:`_retrieve_parts`).  ``per_layer_counts`` fills the
    result's ``layer_counts`` provenance field (``(n_local, L)``); on the
    fused path the planes ride the same single all-to-all as the values.
    Call inside ``shard_map``.
    """
    offsets, _, values, counts, num_dropped, _, _, layer_counts = _retrieve_parts(
        layers,
        queries,
        seg_capacity=seg_capacity,
        out_capacity=out_capacity,
        capacity_slack=capacity_slack,
        use_kernel=use_kernel,
        tombstones=tombstones,
        fused=fused,
        per_layer=per_layer_counts,
    )
    return ShardRetrieval(
        offsets=offsets,
        values=values,
        counts=counts,
        num_dropped=num_dropped,
        layer_counts=layer_counts,
    )


def inner_join_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    seg_capacity: int,
    out_capacity: int,
    capacity_slack: float = 1.25,
    use_kernel: Optional[bool] = None,
) -> ShardJoin:
    """Materialized inner join ``build ⋈ queries`` as global-row match pairs.

    Call inside ``shard_map``.
    """
    return inner_join_layers_sharded(
        (dhg,),
        queries,
        seg_capacity=seg_capacity,
        out_capacity=out_capacity,
        capacity_slack=capacity_slack,
        use_kernel=use_kernel,
    )


def inner_join_layers_sharded(
    layers: Sequence[DistributedHashGraph],
    queries: jax.Array,
    *,
    seg_capacity: int,
    out_capacity: int,
    capacity_slack: float = 1.25,
    use_kernel: Optional[bool] = None,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    fused: Optional[bool] = None,
) -> ShardJoin:
    """Materialized inner join against a versioned layer stack.

    Call inside ``shard_map``.
    """
    _, query_idx, values, counts, num_dropped, rank, n_local, _ = _retrieve_parts(
        layers,
        queries,
        seg_capacity=seg_capacity,
        out_capacity=out_capacity,
        capacity_slack=capacity_slack,
        use_kernel=use_kernel,
        tombstones=tombstones,
        fused=fused,
    )
    globl = rank.astype(jnp.int32) * n_local + query_idx
    query_idx = jnp.where(query_idx >= 0, globl, jnp.int32(-1))
    num_results = jnp.minimum(jnp.sum(counts), out_capacity).astype(jnp.int32)
    return ShardJoin(
        query_idx=query_idx,
        values=values,
        num_results=num_results[None],
        num_dropped=num_dropped,
    )


def _plan_block_totals(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    capacity_slack: float,
    tombstones: Optional[tuple[jax.Array, jax.Array]],
    layer_epoch: int,
) -> jax.Array:
    """Owner-side result totals per source device for one layer: (D,) int32.

    Entry ``s`` is the number of values this owner will return to source
    ``s`` — exactly the quantity both capacity plans are built from.  Routes
    queries exactly like :func:`_retrieve_runs` pass 1 (same splits, same
    slack, so the same slot layout).
    """
    num_devices = exchange.device_count(dhg.axis_names)
    rq, _, rbuckets, capacity = _route_queries(dhg, queries, capacity_slack)
    _, run_counts = hashgraph.query_locate(dhg.local, rq, buckets=rbuckets)
    run_counts = _mask_counts(run_counts, rq, tombstones, layer_epoch)
    return jnp.sum(run_counts.reshape(num_devices, capacity), axis=1)


def plan_seg_capacity_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    capacity_slack: float = 1.25,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    layer_epoch: int = 0,
) -> jax.Array:
    """Count-only planning round: the exact ``seg_capacity`` retrieval needs.

    ``pmax`` of the owner-side per-source totals across the mesh: the
    smallest segment width for which no owner→querier return segment
    overflows.  This is the ROADMAP "ragged all-to-all" counts round — a
    cheap reduction instead of shipping ``seg_capacity``-padded value
    segments sized by worst-case guesses.  Returns a replicated () int32.

    Call inside ``shard_map``.
    """
    block_totals = _plan_block_totals(
        dhg,
        queries,
        capacity_slack=capacity_slack,
        tombstones=tombstones,
        layer_epoch=layer_epoch,
    )
    return jax.lax.pmax(jnp.max(block_totals).astype(jnp.int32), dhg.axis_names)


def plan_out_capacity_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    capacity_slack: float = 1.25,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    layer_epoch: int = 0,
) -> jax.Array:
    """Count-first output sizing: the exact ``out_capacity`` retrieval needs.

    ``psum`` of the owner-side per-source totals gives, per querying device,
    the total number of values it will receive; the max over devices is the
    smallest output CSR that fits every shard.  Same counts round as
    :func:`plan_seg_capacity_sharded` — ``retrieve`` never needs a
    worst-case output guess.  Returns a replicated () int32.

    Call inside ``shard_map``.
    """
    block_totals = _plan_block_totals(
        dhg,
        queries,
        capacity_slack=capacity_slack,
        tombstones=tombstones,
        layer_epoch=layer_epoch,
    )
    per_device = jax.lax.psum(block_totals, dhg.axis_names)  # (D,) replicated
    return jnp.max(per_device).astype(jnp.int32)


def plan_caps_sharded(
    layers: Sequence[DistributedHashGraph],
    queries: jax.Array,
    *,
    capacity_slack: float = 1.25,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
    fused: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """One counts round sizing both retrieval capacities over a layer stack.

    Returns replicated ``(seg_capacity, out_capacity)`` () int32 — the exact
    per-segment and per-device output widths a merged
    :func:`retrieve_layers_sharded` needs to drop nothing.  ``fused`` must
    match the execution path being planned for: fused retrieval packs *all*
    layers' runs into one segment per source (seg sized by the per-source
    totals summed over layers, one routing round), the legacy path one
    segment per (layer, source) pair (per-layer max, L rounds).  Call
    inside ``shard_map``.
    """
    layers = tuple(layers)
    axis_names = tuple(layers[0].axis_names)
    if fused is None:
        fused = len(layers) == 1
    if fused:
        base = layers[0]
        num_devices = exchange.device_count(axis_names)
        rq, _, rh, is_pad, lo, capacity = _route_queries_once(
            base, queries, capacity_slack
        )
        _, counts_lr, _ = _layer_run_descriptors(
            layers, rq, rh, is_pad, lo, tombstones
        )
        block_totals = jnp.sum(
            counts_lr.reshape(len(layers), num_devices, capacity), axis=(0, 2)
        )
        seg = jax.lax.pmax(jnp.max(block_totals).astype(jnp.int32), axis_names)
        out = jnp.max(jax.lax.psum(block_totals, axis_names)).astype(jnp.int32)
        return seg, out

    seg_need = jnp.int32(0)
    out_vec = jnp.int32(0)
    for epoch, layer in enumerate(layers):
        block_totals = _plan_block_totals(
            layer,
            queries,
            capacity_slack=capacity_slack,
            tombstones=tombstones,
            layer_epoch=epoch,
        )
        seg_need = jnp.maximum(seg_need, jnp.max(block_totals))
        out_vec = out_vec + block_totals
    seg = jax.lax.pmax(seg_need.astype(jnp.int32), axis_names)
    out = jnp.max(jax.lax.psum(out_vec, axis_names)).astype(jnp.int32)
    return seg, out


def build_query_hashgraph_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    *,
    capacity_slack: float = 1.25,
) -> HashGraph:
    """Paper-literal query phase 1: a *second* HashGraph from the query set,
    sharing the build table's splits (used by the list-intersection path and
    the build-vs-query benchmark)."""
    rq, _, rbuckets, _ = _route_queries(dhg, queries, capacity_slack)
    return hashgraph.build_from_buckets(
        rq,
        rbuckets,
        dhg.local_range_cap,
        seed=dhg.seed,
        sort_within_bucket=True,
        fingerprint=dhg.local.fingerprints is not None,
    )


def join_size_sharded(
    dhg: DistributedHashGraph,
    queries: jax.Array,
    **kw,
) -> jax.Array:
    """Global inner-join cardinality |build ⋈ query| (paper's intersection).

    Sum of per-query multiplicities, ``psum``-reduced across the mesh.
    """
    counts = query_sharded(dhg, queries, **kw)
    return jax.lax.psum(jnp.sum(counts), dhg.axis_names)


def join_size_layers_sharded(
    layers: Sequence[DistributedHashGraph],
    queries: jax.Array,
    **kw,
) -> jax.Array:
    """Global inner-join cardinality against a versioned layer stack."""
    counts = query_layers_sharded(layers, queries, **kw)
    return jax.lax.psum(jnp.sum(counts), tuple(layers[0].axis_names))


def fold_layers_local(
    layers: Sequence[DistributedHashGraph],
    *,
    tombstones: Optional[tuple[jax.Array, jax.Array]] = None,
) -> DistributedHashGraph:
    """Merge a partition-coherent layer prefix into one graph — NO exchange.

    The incremental-compaction primitive: ``layers`` is the oldest prefix
    ``(base, delta_1, ..., delta_k)`` of a coherent stack.  Because every
    delta was built on the base's frozen ``hash_splits``, each device
    already owns exactly the rows of its hash range *in every layer* — so
    the fold is a purely local rebuild: mask tombstoned rows to the EMPTY
    sentinel (per layer epoch, same rule as ``compact``), concatenate the
    local rows, re-bucket through the base's deterministic map, and
    counting-sort one fresh local CSR.  Zero collective rounds (the full
    ``compact`` pays a round-robin pre-balance all-to-all plus the build
    exchange) — which is what lets a serving loop run folds in the
    background without ever touching the read path's collective budget.

    ``tombstones`` is the *sorted* index pair (``Tombstones.index()``); a
    tombstone with epoch ``e`` hides layer ``i`` (0-based position in
    ``layers``) iff ``e >= i``.  The caller is responsible for remapping
    the surviving tombstones of the wider stack (epochs ``> k`` shift down
    by ``k`` — see ``repro.core.maintenance``).

    Invalid for mixed-split stacks: rows of an incoherent delta live on
    devices chosen by the *delta's* splits, so a local fold would break the
    routing invariant.  Call inside ``shard_map``.
    """
    layers = tuple(layers)
    base = layers[0]
    keys_parts, vals_parts = [], []
    dropped = base.num_dropped
    for epoch, layer in enumerate(layers):
        k = layer.local.keys
        dead = hashgraph.is_empty_key(k)
        if tombstones is not None and tombstones[0].shape[0]:
            hidden = (
                hashgraph.match_epochs_sorted(k, tombstones[0], tombstones[1])
                >= epoch
            )
            dead = dead | hidden
        dead_b = dead[:, None] if k.ndim == 2 else dead
        keys_parts.append(jnp.where(dead_b, jnp.uint32(EMPTY_KEY), k))
        vals_parts.append(layer.local.values)
        if epoch:
            dropped = dropped + layer.num_dropped
    keys_cat = jnp.concatenate(keys_parts, axis=0)
    vals_cat = jnp.concatenate(vals_parts, axis=0)
    rank = exchange.my_rank(base.axis_names)
    buckets = _local_buckets(
        keys_cat,
        base.hash_splits[rank],
        base.hash_range,
        base.local_range_cap,
        base.seed,
        base.bucket_stride,
    )
    local = hashgraph.build_from_buckets(
        keys_cat,
        buckets,
        base.local_range_cap,
        vals_cat,
        seed=base.seed,
        sort_within_bucket=True,
        fingerprint=base.local.fingerprints is not None,
    )
    return DistributedHashGraph(
        local=local,
        hash_splits=base.hash_splits,
        num_dropped=dropped,
        hash_range=base.hash_range,
        seed=base.seed,
        local_range_cap=base.local_range_cap,
        axis_names=base.axis_names,
        bucket_stride=base.bucket_stride,
    )
