"""Phases 2–3 of Alg. 2 — capacity-padded, hierarchical all-to-all exchange.

The paper reorganizes each device's keys into per-destination contiguous
partitions (a counting sort, Alg. 2 lines 19-34) and then issues a ragged
NCCL all-to-all (line 38).  XLA collectives are static-shape, so the TPU
adaptation packs each destination partition into a fixed ``capacity`` slot
padded with a sentinel — precisely the MoE token-dispatch trick, which is
why :func:`dispatch` / :func:`combine` here also back the MoE layer in
``repro.models.moe`` (the paper's technique as a first-class framework
primitive).

On a multi-axis mesh the exchange is *hierarchical*: one dense
``lax.all_to_all`` per mesh axis, transposing a ``(A, B, ..., capacity)``
partition grid one axis at a time.  This maps onto per-axis ICI rings
instead of emulating NVSwitch's flat crossbar (DESIGN.md §2).

Everything in this module runs *inside* ``shard_map`` — arrays are the
per-device shards.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.utils.compat import axis_size as _axis_size


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("perm", "slot", "keep", "num_dropped"),
    meta_fields=("num_dest", "capacity"),
)
@dataclasses.dataclass(frozen=True)
class Route:
    """Bookkeeping to reverse a dispatch (answers → original local order)."""

    perm: jax.Array  # (N,) argsort-by-destination permutation
    slot: jax.Array  # (N,) flat slot index in the packed buffer (kept rows)
    keep: jax.Array  # (N,) bool, False for capacity-dropped rows
    num_dropped: jax.Array  # () int32 — overflow diagnostics
    num_dest: int
    capacity: int


def axis_sizes(axis_names: Sequence[str]) -> tuple[int, ...]:
    return tuple(_axis_size(a) for a in axis_names)


def device_count(axis_names: Sequence[str]) -> int:
    n = 1
    for a in axis_names:
        n *= _axis_size(a)
    return n


def my_rank(axis_names: Sequence[str]) -> jax.Array:
    """Row-major composite rank over ``axis_names`` (major axis first)."""
    rank = jnp.int32(0)
    for a in axis_names:
        rank = rank * _axis_size(a) + jax.lax.axis_index(a)
    return rank


def pack_by_destination(
    payloads: Sequence[jax.Array],
    dest: jax.Array,
    num_dest: int,
    capacity: int,
    fills: Sequence,
    count_mask: jax.Array = None,
) -> tuple[list[jax.Array], Route]:
    """Counting-sort ``payloads`` by destination into a (num_dest*capacity,) buffer.

    Mirrors Alg. 2 lines 19-31: the stable argsort by destination *is* the
    ``BuffCounter``/``BuffOffset`` counting sort (same output, no atomics).
    Rows beyond ``capacity`` per destination are dropped and counted
    (``num_dropped``) — Phase 1's balanced split keeps this at zero for any
    sane slack; callers assert on it in tests.  ``count_mask`` marks the
    rows whose loss matters: True rows count toward ``num_dropped`` when
    dropped, False rows (padding a caller routes only for load spreading,
    e.g. compaction rebuilds) drop silently.
    """
    n = dest.shape[0]
    dest = dest.astype(jnp.int32)
    # Stable argsort by destination: the row index as the second sort key
    # gives a stable sort's order, and an unstable sort compiles in about
    # half the time of a stable one on TPU.
    sdest, perm = jax.lax.sort(
        (dest, jnp.arange(n, dtype=jnp.int32)), num_keys=2, is_stable=False
    )
    # First row of each destination partition in the sorted order.
    starts = jnp.searchsorted(sdest, jnp.arange(num_dest, dtype=jnp.int32), side="left")
    rank_in_part = jnp.arange(n, dtype=jnp.int32) - starts[sdest]
    keep = rank_in_part < capacity
    slot = sdest * capacity + jnp.where(keep, rank_in_part, 0)
    scatter_idx = jnp.where(keep, slot, num_dest * capacity)  # OOB -> dropped
    packed = []
    for p, fill in zip(payloads, fills):
        p = jnp.asarray(p)
        buf = jnp.full((num_dest * capacity,) + p.shape[1:], fill, dtype=p.dtype)
        packed.append(buf.at[scatter_idx].set(p[perm], mode="drop"))
    counted = ~keep if count_mask is None else (~keep & count_mask[perm])
    route = Route(
        perm=perm,
        slot=slot,
        keep=keep,
        num_dropped=jnp.sum(counted).astype(jnp.int32),
        num_dest=num_dest,
        capacity=capacity,
    )
    return packed, route


def all_to_all_hierarchical(
    x: jax.Array, axis_names: Sequence[str]
) -> jax.Array:
    """Dense all-to-all of ``x`` of shape (D, capacity, ...) over ≥1 mesh axes.

    ``D`` must equal the product of the axis sizes, partitions ordered
    row-major by ``axis_names`` (major first — matching :func:`my_rank`).
    One ``lax.all_to_all`` per axis; after all hops, row ``r`` holds the
    partition sent by device ``r``.
    """
    sizes = axis_sizes(axis_names)
    d = 1
    for s in sizes:
        d *= s
    if x.shape[0] != d:
        raise ValueError(f"leading dim {x.shape[0]} != prod(axis sizes) {d}")
    rest = x.shape[1:]
    x = x.reshape(*sizes, *rest)
    for i, a in enumerate(axis_names):
        x = jax.lax.all_to_all(x, a, split_axis=i, concat_axis=i, tiled=True)
    return x.reshape(d, *rest)


def dispatch(
    payloads: Sequence[jax.Array],
    dest: jax.Array,
    axis_names: Sequence[str],
    capacity: int,
    fills: Sequence,
    count_mask: jax.Array = None,
) -> tuple[list[jax.Array], Route]:
    """Send each payload row to device ``dest[row]``.

    Returns per-device received buffers of shape ``(D * capacity,)`` —
    row-major by *source* device — plus the :class:`Route` to send answers
    back.  Padding rows carry the corresponding ``fills`` sentinel.
    ``count_mask`` restricts overflow accounting to the rows it marks
    (see :func:`pack_by_destination`).
    """
    num_dest = device_count(axis_names)
    packed, route = pack_by_destination(
        payloads, dest, num_dest, capacity, fills, count_mask=count_mask
    )
    received = []
    for buf in packed:
        b = buf.reshape(num_dest, capacity, *buf.shape[1:])
        b = all_to_all_hierarchical(b, axis_names)
        received.append(b.reshape(num_dest * capacity, *buf.shape[1:]))
    return received, route


def combine(
    answers: jax.Array,
    route: Route,
    axis_names: Sequence[str],
    fill,
) -> jax.Array:
    """Inverse of :func:`dispatch` for per-slot answers.

    ``answers`` is laid out like the received buffers ``(D*capacity,)``;
    the reverse all-to-all restores the sender's packed layout, then the
    route unpacks to the original local row order.  Dropped rows get
    ``fill``.
    """
    d, cap = route.num_dest, route.capacity
    rest = answers.shape[1:]
    back = all_to_all_hierarchical(answers.reshape(d, cap, *rest), axis_names)
    back = back.reshape(d * cap, *rest)
    keep = route.keep.reshape((-1,) + (1,) * len(rest))
    ans_sorted = jnp.where(keep, back[route.slot], fill)
    out = jnp.empty_like(ans_sorted)
    return out.at[route.perm].set(ans_sorted)


def combine_ragged(
    seg_values: jax.Array,
    slot_counts: jax.Array,
    route: Route,
    axis_names: Sequence[str],
    layer_counts: jax.Array = None,
):
    """Inverse of :func:`dispatch` for *variable-fanout* answers (retrieval).

    :func:`combine` returns exactly one answer per dispatched row; retrieval
    returns ``count[i]`` values for row ``i``.  The owner packs, for each
    source device ``s``, the concatenation of its block's answer runs (slot
    order) into ``seg_values[s]`` of static width ``seg_capacity`` and
    reports per-slot run lengths in ``slot_counts`` (laid out like the
    received buffers, ``(D*capacity,)``).  Every device runs this
    symmetrically: one reverse all-to-all ships the segments home, a second
    ships the counts, and the exclusive prefix sum of the returned counts
    reconstructs — without any extra communication — the exact offsets the
    owner used when packing.

    Returns ``(counts, starts, values)`` in the dispatcher's original row
    order:

    * ``counts`` — ``(N,)`` int32 result count per row (0 for capacity-dropped
      rows).
    * ``starts`` — ``(N,)`` int32 start of row ``i``'s run inside ``values``;
      row ``i``'s answers are ``values[starts[i] : starts[i]+counts[i]]``.
    * ``values`` — ``(D*seg_capacity,)`` returned segments, row-major by
      owner device (``(D*seg_capacity, C)`` when ``seg_values`` carries
      trailing payload columns ``(D, seg_capacity, C)``).

    Segment overflow (a block's runs exceeding ``seg_capacity``) is the
    *owner's* to report (see ``multi_hashgraph.retrieve_sharded``); this
    routine never hides it — the counts it returns are the true run lengths.

    The whole return trip is **one** collective round: per peer, the int32
    slot counts are bitcast into the segment's dtype and concatenated onto
    the flattened value segment, so a single all-to-all ships both (split
    and bitcast back on arrival).  Non-32-bit payloads fall back to two
    rounds.

    ``layer_counts`` extends the same trick to the *per-layer* count
    breakdown of a fused layered retrieval: an optional ``(L, D*capacity)``
    int32 array of per-layer run lengths (laid out like ``slot_counts``,
    one plane per layer) is bitcast and concatenated onto the same packed
    buffer — still ONE all-to-all — and a fourth output ``per_layer`` of
    shape ``(N, L)`` is returned, giving each dispatched row its result
    count split by layer (zero for dropped rows).  The caller remains
    responsible for ``slot_counts`` equalling the plane sum; this routine
    ships both independently.
    """
    d, cap = route.num_dest, route.capacity
    seg_cap = seg_values.shape[1]
    rest = seg_values.shape[2:]
    counts_i32 = slot_counts.astype(jnp.int32).reshape(d, cap)
    nlayers = 0
    if layer_counts is not None:
        nlayers = layer_counts.shape[0]
        # (L, D*cap) -> (D, L*cap): each destination's planes pack together.
        planes = (
            layer_counts.astype(jnp.int32)
            .reshape(nlayers, d, cap)
            .swapaxes(0, 1)
            .reshape(d, nlayers * cap)
        )
    if seg_values.dtype.itemsize == 4:
        # Fused return: values and counts share one 32-bit lane buffer.
        vals_flat = seg_values.reshape(d, -1)
        cast = (
            (lambda c: c)
            if vals_flat.dtype == jnp.int32
            else (lambda c: jax.lax.bitcast_convert_type(c, vals_flat.dtype))
        )
        parts = [vals_flat, cast(counts_i32)]
        if nlayers:
            parts.append(cast(planes))
        back = all_to_all_hierarchical(jnp.concatenate(parts, axis=1), axis_names)
        split = vals_flat.shape[1]
        back_vals = back[:, :split].reshape(d, seg_cap, *rest)
        back_counts = back[:, split : split + cap]
        back_planes = back[:, split + cap :]
        if back_counts.dtype != jnp.int32:
            back_counts = jax.lax.bitcast_convert_type(back_counts, jnp.int32)
            back_planes = jax.lax.bitcast_convert_type(back_planes, jnp.int32)
    else:  # pragma: no cover - no 64-bit payloads in the current stack
        back_counts = all_to_all_hierarchical(counts_i32, axis_names)
        back_vals = all_to_all_hierarchical(seg_values, axis_names)
        back_planes = (
            all_to_all_hierarchical(planes, axis_names) if nlayers else None
        )
    # Owner o packed my block by the exclusive cumsum of my slots' counts —
    # recompute the identical offsets from the returned counts.
    block_off = jnp.cumsum(back_counts, axis=1) - back_counts
    flat_counts = back_counts.reshape(-1)
    flat_off = block_off.reshape(-1)
    owner = route.slot // cap
    starts_packed = owner * seg_cap + flat_off[route.slot]
    counts_sorted = jnp.where(route.keep, flat_counts[route.slot], 0)
    starts_sorted = jnp.where(route.keep, starts_packed, 0)
    counts = jnp.empty_like(counts_sorted).at[route.perm].set(counts_sorted)
    starts = jnp.empty_like(starts_sorted).at[route.perm].set(starts_sorted)
    values = back_vals.reshape(d * seg_cap, *rest)
    if not nlayers:
        return counts, starts, values
    # Per-layer breakdown: owner o's plane for my slot j sits at
    # back_planes[o, l*cap + j]; unsort exactly like the totals.
    bp = back_planes.reshape(d, nlayers, cap)
    pl_sorted = bp[owner[:, None], jnp.arange(nlayers)[None, :], (route.slot % cap)[:, None]]
    pl_sorted = jnp.where(route.keep[:, None], pl_sorted, 0)
    per_layer = jnp.empty_like(pl_sorted).at[route.perm].set(pl_sorted)
    return counts, starts, values, per_layer
