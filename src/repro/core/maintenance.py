"""Incremental background compaction — fold the oldest layers, off the read path.

``compact()`` folds the *whole* stack (all deltas + tombstones) into a
fresh base through a full four-phase rebuild: a pre-balance all-to-all,
the build exchange, and a re-histogram.  That is the right periodic
flattening pass, but it is exactly what a serving loop must not run
inline — the pause is proportional to the whole table.

:func:`fold_oldest` is the incremental alternative: merge only the ``k``
oldest delta layers into the base.  On a partition-coherent stack (the
default — every delta built on the base's frozen ``hash_splits``) this is
a *layer-local* rebuild (``multi_hashgraph.fold_layers_local``): each
device already owns its hash range's rows in every layer, so the fold is
pure local compute — **zero collective rounds** (regression-tested) and a
pause proportional to the folded layers only, not the table.  The
remaining deltas and the surviving tombstones shift down by ``k`` epochs
and the stack keeps serving unchanged.

:class:`CompactionPolicy` decides *when*: delta-depth, tombstone-load and
dropped-rows triggers over a cheap :class:`TableStats` snapshot.  It
generalizes ``TableState.should_compact()`` (which is now a thin shim over
it) and is shared with the ``repro.serve_table`` server, which runs the
policy against its shadow state between write batches — readers never see
a fold, only the atomically published result.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import multi_hashgraph, plans
from repro.core.hashgraph import EMPTY_KEY
from repro.core.state import TableState, Tombstones
from repro.obs.tracing import process_tracer
from repro.utils.compat import shard_map


# ---------------------------------------------------------------------------
# Cheap state snapshot for policy decisions and server metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TableStats:
    """Host-side snapshot of a :class:`TableState`'s maintenance signals.

    Static structure (delta depth, allocated rows) comes for free; the
    device reads are three scalars (tombstone fill, tombstone overflow,
    total drops) — cheap enough to poll between update batches, never call
    inside a jitted program.
    """

    delta_depth: int  # live deltas (static)
    base_rows: int  # base local CSR rows × devices (allocated, static)
    delta_rows: int  # sum of delta CSR rows (allocated, static)
    tombstone_count: int  # used tombstone slots
    tombstone_capacity: int  # allocated tombstone slots (static)
    tombstone_dropped: int  # deletes lost to tombstone capacity
    num_dropped: int  # total drops across builds + tombstones
    tombstone_expired: int = 0  # entries already effective at the clock

    @property
    def tombstone_load(self) -> float:
        """Tombstone fill fraction (0.0 on a zero-capacity buffer)."""
        if not self.tombstone_capacity:
            return 0.0
        return self.tombstone_count / self.tombstone_capacity

    @property
    def expired_load(self) -> float:
        """Expired-entry fill fraction — the TTL-eviction pressure signal.

        Every expired entry names rows that reads already mask but whose
        slots (table rows + the tombstone slot itself) only a fold/compact
        reclaims; this is the fraction :class:`CompactionPolicy`'s
        eviction trigger watches.
        """
        if not self.tombstone_capacity:
            return 0.0
        return self.tombstone_expired / self.tombstone_capacity


def collect_stats(state: TableState) -> TableStats:
    """Read a :class:`TableStats` snapshot off ``state`` (host-syncing)."""
    ts = state.tombstones
    if ts.capacity:
        expired = int(
            np.count_nonzero(
                (np.asarray(ts.epochs) >= 0)
                & (int(ts.now) >= np.asarray(ts.expires))
            )
        )
    else:
        expired = 0
    return TableStats(
        delta_depth=len(state.deltas),
        base_rows=int(state.base.local.keys.shape[0]),
        delta_rows=sum(int(d.local.keys.shape[0]) for d in state.deltas),
        tombstone_count=int(ts.count),
        tombstone_capacity=ts.capacity,
        tombstone_dropped=int(ts.num_dropped),
        num_dropped=int(state.num_dropped),
        tombstone_expired=expired,
    )


def collect_layer_live(state: TableState) -> tuple:
    """Per-layer ``(live_rows, allocated_rows)`` pairs, base first.

    One jitted counts round (:func:`repro.core.plans.exec_layer_live`) —
    the signal behind stats-driven fold sizing (``fold_k=None``): a delta
    whose live fraction has decayed (rows superseded by upserts, deleted,
    or TTL-expired) is *cold* and folds away almost for free, so the
    policy folds the longest cold prefix first.  Host-syncing; call
    eagerly between batches, never inside ``jax.jit``.
    """
    live = [int(x) for x in plans.exec_layer_live(state.table, state)]
    alloc = [int(layer.local.keys.shape[0]) for layer in state.layers]
    return tuple(zip(live, alloc))


# ---------------------------------------------------------------------------
# Compaction policy — when to fold, and how much
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """Trigger thresholds for (incremental) compaction.

    * ``max_delta_depth`` — fold when the delta ring reaches this depth
      (``None`` disables; servers default it to ``table.max_deltas`` so an
      insert never hits the ring-full error).
    * ``tombstone_load`` — fold when the tombstone buffer's fill fraction
      reaches this value.
    * ``tombstone_overflow`` — fold when deletes were lost to tombstone
      capacity (``num_dropped > 0`` on the buffer); only a *full* fold
      frees every tombstone slot, so :meth:`fold_amount` escalates.
    * ``max_dropped`` — fold when total dropped rows exceed this
      (``None`` disables).
    * ``fold_k`` — how many of the oldest deltas an incremental
      maintenance pass merges (:func:`fold_oldest`'s ``k``).  ``None``
      selects **stats-driven** sizing: the caller passes the per-layer
      live-row measurement (:func:`collect_layer_live`) to
      :meth:`fold_amount`, which folds the longest prefix of *cold*
      deltas (live rows at or below ``cold_live_ratio`` of the hottest
      delta's) — cold layers are mostly superseded/expired rows, so
      folding them first reclaims the most capacity per unit of fold
      pause.
    * ``cold_live_ratio`` — fraction of the hottest delta's live count
      at or below which a delta counts as cold for the stats-driven fold
      (``fold_k=None``).
    * ``expired_load`` — TTL-eviction trigger: escalate to a full compact
      when the fraction of tombstone entries already *expired* (effective
      at the clock — rows reads mask but whose capacity is still held)
      reaches this value.  ``None`` disables; irrelevant without TTLs
      (plain deletes also count as expired entries, but the plain
      ``tombstone_load`` trigger fires first at the default settings).
    """

    max_delta_depth: Optional[int] = None
    tombstone_load: float = 0.5
    tombstone_overflow: bool = True
    max_dropped: Optional[int] = None
    fold_k: Optional[int] = 2
    cold_live_ratio: float = 0.5
    expired_load: Optional[float] = None

    def due(self, stats: TableStats) -> bool:
        """Is a state with these stats due for compaction?"""
        if (
            self.max_delta_depth is not None
            and stats.delta_depth >= self.max_delta_depth
        ):
            return True
        return self.escalates(stats)

    def escalates(self, stats: TableStats) -> bool:
        """Does this state need a FULL compaction (not an incremental fold)?

        True under tombstone or dropped-row pressure: partial folds only
        free tombstones with epochs inside the folded prefix and *carry*
        the folded layers' drop tally into the new base, so both pressures
        want the full rebuild — and that holds even at delta depth 0
        (tombstones and drops fold away only through ``compact()``).  The
        ``expired_load`` eviction trigger escalates for the same reason:
        only the live-count-sized full rebuild returns the capacity that
        expired rows hold.
        """
        if self.tombstone_overflow and stats.tombstone_dropped > 0:
            return True
        if (
            stats.tombstone_capacity
            and stats.tombstone_load >= self.tombstone_load
        ):
            return True
        if (
            self.expired_load is not None
            and stats.tombstone_capacity
            and stats.expired_load >= self.expired_load
        ):
            return True
        return self.max_dropped is not None and stats.num_dropped > self.max_dropped

    def fold_amount(self, stats: TableStats, layer_live=None) -> int:
        """How many oldest layers to fold for a state with these stats.

        Incremental (``fold_k``) by default; :meth:`escalates` promotes to
        every delta (callers run the full ``compact()`` there, which also
        handles the depth-0 tombstone-only case an oldest-k fold cannot).

        With ``fold_k=None`` the size is derived from ``layer_live`` (the
        :func:`collect_layer_live` measurement, base first): fold the
        longest prefix of deltas that are *cold* — live rows at or below
        ``cold_live_ratio`` of the hottest delta's live count.  Coldness
        is relative to the stack's peak, not to allocated rows: allocation
        carries the capacity slack and lane rounding, so even a fully-live
        delta sits well under 1.0 of its allocation, while peak-relative
        comparison is scale- and slack-free (an all-dead stack folds
        entirely, a uniformly-hot stack folds the minimum).  Always at
        least one delta, so a due fold makes progress even when every
        delta is hot.  Without a measurement the stats-driven mode
        degrades to a minimal fold of 1.
        """
        if self.escalates(stats):
            return stats.delta_depth
        if not stats.delta_depth:
            return 0
        if self.fold_k is not None:
            return min(max(1, self.fold_k), stats.delta_depth)
        k = 1
        if layer_live is not None:
            # layer_live[0] is the base; deltas start at index 1.  Extend
            # the folded prefix while the next-oldest delta is cold.
            deltas = layer_live[1:]
            peak = max((live for live, _ in deltas), default=0)
            if peak == 0:
                k = len(deltas)  # nothing live anywhere: fold them all
            else:
                for j, (live, _alloc) in enumerate(deltas, start=1):
                    if live <= self.cold_live_ratio * peak:
                        k = j
                    else:
                        break
        return min(max(1, k), stats.delta_depth)


# ---------------------------------------------------------------------------
# fold metrics — one recording helper shared by every fold driver
# ---------------------------------------------------------------------------


def allocated_rows(state: TableState) -> int:
    """Total allocated CSR rows (base + deltas) — static, no device sync."""
    return int(state.base.local.keys.shape[0]) + sum(
        int(d.local.keys.shape[0]) for d in state.deltas
    )


def record_fold(
    metrics,
    *,
    kind: str,
    seconds: float,
    rows_before: int,
    rows_after: int,
) -> None:
    """Fold pause-time + reclaimed-rows into a metrics registry.

    ``kind`` is ``"fold"`` (incremental) or ``"full"`` (compact
    escalation).  Reclaimed rows are clamped at zero: an incremental fold
    *grows* the base by the folded deltas' rows by design — only the full
    rebuild reclaims — and a negative "reclaimed" count would poison the
    counter's monotonicity.  One recording site per fold; drivers
    (``TableServer._apply_fold``, ``KVCache.maintain``) call this rather
    than passing a registry down into :func:`fold_oldest`, so a fold is
    never double-counted.
    """
    if metrics is None:
        return
    metrics.counter(
        "maintenance_folds_total",
        labels={"kind": kind},
        help="Fold/compact passes by kind (fold=incremental, full=rebuild).",
    ).inc()
    metrics.histogram(
        "maintenance_fold_seconds",
        labels={"kind": kind},
        help="Fold pause time (the write-path stall a fold costs).",
    ).observe(seconds)
    reclaimed = max(0, int(rows_before) - int(rows_after))
    metrics.counter(
        "maintenance_reclaimed_rows_total",
        help="Allocated CSR rows returned by folds/compactions.",
    ).inc(reclaimed)
    metrics.gauge(
        "maintenance_last_reclaimed_rows",
        help="Rows reclaimed by the most recent fold (0 when it grew).",
    ).set(reclaimed)


# ---------------------------------------------------------------------------
# fold_oldest — the incremental fold
# ---------------------------------------------------------------------------


def _remap_tombstones(ts: Tombstones, k: int) -> Tombstones:
    """Shift a tombstone buffer past a fold of the ``k`` oldest deltas.

    A tombstone with epoch ``e`` hides layers ``0..e``.  After the fold,
    layers ``0..k`` are one new base with the masking already applied:
    *effective* tombstones with ``e <= k`` are spent (and MUST be
    discarded — kept, they would wrongly hide folded rows of later
    epochs), tombstones with ``e > k`` keep hiding the surviving deltas
    at ``e - k``.  TTL entries still **pending** at the current clock
    (``now < expires``) were NOT applied by the fold (they masked
    nothing — ``index()`` resolves them to epoch ``-1``), so they must
    survive regardless of their stamped epoch: a pending entry with
    ``e <= k`` now guards rows living in the folded base and is clamped
    to epoch ``0``.  Survivors are repacked to the front so ``push``
    keeps appending densely; the overflow tally and the clock are
    preserved (lost deletes stay lost until a caller decides to trust a
    full rebuild).  Pure and traceable.
    """
    spent = ts.now >= ts.expires  # effective (delete or expired TTL)
    keep = (ts.epochs > k) | ((ts.epochs >= 0) & ~spent)
    order = jnp.argsort(~keep, stable=True)  # survivors first
    kept = keep[order]
    keys = ts.keys[order]
    kept_b = kept[:, None] if keys.ndim == 2 else kept
    new_epochs = jnp.maximum(ts.epochs[order] - k, jnp.int32(0))
    return Tombstones(
        keys=jnp.where(kept_b, keys, jnp.uint32(EMPTY_KEY)),
        epochs=jnp.where(kept, new_epochs, jnp.int32(-1)),
        expires=jnp.where(kept, ts.expires[order], jnp.int32(0)),
        count=jnp.sum(keep).astype(jnp.int32),
        num_dropped=ts.num_dropped,
        now=ts.now,
    )


@partial(jax.jit, static_argnums=(0,), static_argnames=("k",))
def exec_fold(table, state: TableState, *, k: int):
    """Jitted layer-local fold: ``(new_base, remapped_tombstones)``.

    Collective-free by construction (``fold_layers_local`` never leaves
    the device) — the property the serving smoke test asserts on this
    executor's jaxpr.
    """

    def body(st):
        new_base = multi_hashgraph.fold_layers_local(
            st.layers[: k + 1], tombstones=st.tombstones.index()
        )
        return new_base, _remap_tombstones(st.tombstones, k)

    return shard_map(
        body,
        mesh=table.mesh,
        in_specs=(plans.state_specs(state),),
        out_specs=(
            plans.dhg_specs(state.base),
            Tombstones(
                keys=P(), epochs=P(), expires=P(),
                count=P(), num_dropped=P(), now=P(),
            ),
        ),
        check_vma=False,
    )(state)


def fold_oldest(state: TableState, k: int, *, metrics=None) -> TableState:
    """Merge the ``k`` oldest delta layers into the base; keep the rest.

    ``metrics`` (a :class:`~repro.obs.registry.MetricsRegistry`) records
    the fold's pause time and reclaimed rows via :func:`record_fold` —
    for *direct* callers only; the server and cache drivers time their
    folds themselves and must not also pass a registry here.

    The incremental counterpart of ``state.compact()``: the new state has
    ``depth - k`` deltas, the surviving tombstones shifted down ``k``
    epochs, and answers every query identically (oracle-tested against the
    full compaction).  On a coherent stack the fold is layer-local — zero
    collective rounds, pause proportional to the folded layers only — so a
    server can run it against a shadow state while readers keep hitting
    the previous snapshot.

    The folded base's row allocation grows by the folded deltas' rows
    (tombstoned rows become sentinels but keep their slots); a periodic
    full ``compact()`` (live-count sized) re-flattens it.  Mixed-split
    (incoherent) stacks cannot fold locally and fall back to the full
    ``compact()``.  ``k <= 0`` is the identity; ``k`` is clamped to the
    delta depth.
    """
    k = min(int(k), len(state.deltas))
    if k <= 0:
        return state
    table = state.table
    with process_tracer().span("table.fold") as span:
        rows_before = allocated_rows(state)
        if state.coherent:
            new_base, new_ts = exec_fold(table, state, k=k)
            out = TableState(
                base=new_base,
                deltas=state.deltas[k:],
                tombstones=new_ts,
                table=table,
                coherent=True,
            )
        else:
            out = table.compact(state)
    record_fold(
        metrics,
        kind="fold" if state.coherent else "full",
        seconds=span.seconds,
        rows_before=rows_before,
        rows_after=allocated_rows(out),
    )
    return out
