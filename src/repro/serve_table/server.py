"""TableServer — snapshot-swapped reads over a mutating distributed table.

The serving loop the ROADMAP's "background compaction" item asks for:

* **Readers** always execute against the last *published*
  :class:`~repro.serve_table.snapshot.Snapshot` — an immutable
  ``TableState`` behind a wait-free reference read — through the
  :class:`~repro.serve_table.batcher.MicroBatcher` (pow2-bucketed static
  shapes, cached plan executors).  Reads never block on mutation or
  compaction: a fold can take as long as it likes, the read path keeps
  hitting the previous snapshot until the new one is swapped in.
* A **writer loop** pops queued insert/delete batches, applies them to a
  private *shadow* state (``TableState`` mutations are functional — the
  published snapshot is never touched), and publishes the result with a
  fresh seqno.
* **Incremental background compaction**: between write batches the writer
  evaluates a :class:`~repro.core.maintenance.CompactionPolicy` against
  the shadow's stats and runs :func:`~repro.core.maintenance.fold_oldest`
  — a layer-local, zero-collective fold of the oldest deltas — either
  inline (``maintain()``) or on a worker thread (``fold_async()``) while
  reads keep flowing.  Policy escalations (tombstone pressure) run the
  full live-count-sized ``compact()`` instead, which also re-flattens the
  base arrays that incremental folds let grow.

Threading contract: one writer driver (either the embedded ``start()``
thread or an external caller invoking ``step()``/``maintain()``) plus any
number of reader threads.  Readers never wait on writers or folds: the
snapshot fetch is a wait-free reference read, and the only reader-side
lock is the micro-batcher's own batch lock (readers serialize against
*each other* for the duration of a fused batch — shared plan caches —
which costs nothing real since jax execution is dispatch-serialized
anyway).  Writer state (shadow, queue) is mutex-guarded; while a
background fold is in flight the writer defers new applications (writes
queue up) so the fold's rebase is trivially consistent.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import maintenance, plans
from repro.core.hashgraph import EMPTY_KEY
from repro.core.maintenance import CompactionPolicy, TableStats
from repro.core.state import empty_tombstones
from repro.obs.registry import MetricsRegistry, RegistrySnapshot
from repro.serve_table.batcher import BatcherStats, MicroBatcher
from repro.serve_table.snapshot import Snapshot, SnapshotRegistry


@dataclasses.dataclass(frozen=True)
class ServerStats:
    """One coherent sample of the server's counters and state signals."""

    seqno: int  # last published snapshot
    pending_writes: int  # queued, not yet applied
    writes_applied: int  # insert/delete batches applied to the shadow
    reads: int  # individual read requests served
    read_batches: int  # coalesced read executions
    folds: int  # incremental fold_oldest passes
    full_compacts: int  # full compact() escalations
    fold_seconds_total: float
    last_fold_seconds: float
    fold_in_flight: bool  # a background fold is currently running
    skew_fallbacks: int  # inserts routed incoherent by the skew guard
    last_error: Optional[str]  # last write-application failure (None = healthy)
    batcher: BatcherStats
    shadow: TableStats  # maintenance signals of the writer's state
    warmup: Optional[object] = None  # WarmupStats once warm() ran, else None


class TableServer:
    """Serve reads from published snapshots while a writer loop mutates.

    ``keys``/``values`` build the initial table (the ``table.init``
    contract).  ``policy`` defaults to folding ``fold_k`` oldest layers
    whenever the delta ring reaches ``table.max_deltas`` (so an insert can
    never hit the ring-full error) or tombstone pressure escalates to a
    full compaction.  ``window`` is the latency/throughput knob: the
    writer applies at most ``window`` queued mutation batches per step
    before publishing, and readers using :meth:`query_many` /
    :meth:`retrieve_many` choose their own coalescing width.
    """

    def __init__(
        self,
        table,
        keys,
        values=None,
        *,
        policy: Optional[CompactionPolicy] = None,
        batcher: Optional[MicroBatcher] = None,
        window: int = 8,
        write_bucket: Optional[int] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        self.table = table
        self.write_bucket: Optional[int] = None
        if write_bucket is not None:
            wb = int(write_bucket)
            if wb < 1 or wb & (wb - 1):
                raise ValueError("write_bucket must be a power of two")
            if wb % table.num_devices:
                raise ValueError(
                    "write_bucket must be a multiple of the device count"
                )
            self.write_bucket = wb
        state = table.init(*self._pad_insert(keys, values))
        if self.write_bucket is not None:
            # Shape-stable serving pre-grows the tombstone buffer (init
            # leaves it at zero capacity until the first delete): one
            # tombstone structure for the state's whole life means one AOT
            # executor per (bucket, depth) instead of two.
            state = dataclasses.replace(
                state,
                tombstones=empty_tombstones(
                    table.tombstone_capacity, table.schema.key_lanes
                ),
            )
        self.registry = SnapshotRegistry(state)
        self.policy = policy or CompactionPolicy(
            max_delta_depth=table.max_deltas
        )
        # ONE MetricsRegistry per server: the batcher, the AOT grid, any
        # front ends, and the maintenance recorder all write here, so
        # metrics()/render_prometheus export the whole stack coherently.
        # (Attribute named metrics_registry because metrics() is the
        # snapshot API.)
        self.metrics_registry = metrics if metrics is not None else MetricsRegistry()
        self.batcher = batcher or MicroBatcher(table)
        self.batcher.bind_registry(self.metrics_registry)
        self.window = max(1, int(window))
        self._shadow = state
        self._writes: deque = deque()
        self._lock = threading.Lock()  # queue + shadow swaps
        # Serializes every shadow mutation (step application vs background
        # fold): a fold holds it for its whole duration, so a step that was
        # already mid-application when fold_async was called finishes first
        # and the fold reads the post-step shadow — applied writes are never
        # discarded.  Readers never touch it.
        self._writer_mutex = threading.Lock()
        self._fold_thread: Optional[threading.Thread] = None
        self._writer_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._last_error: Optional[str] = None
        self._fold_error: Optional[str] = None
        self._skew_base = table.skew_fallbacks
        reg = self.metrics_registry
        self._c_reads = reg.counter(
            "serve_reads_total", help="Individual read requests served."
        )
        self._c_read_batches = reg.counter(
            "serve_read_batches_total", help="Coalesced read executions."
        )
        self._c_writes_applied = reg.counter(
            "serve_writes_applied_total",
            help="Insert/delete/upsert batches applied to the shadow.",
        )
        # Same instruments maintenance.record_fold targets (get-or-create).
        self._c_folds = reg.counter(
            "maintenance_folds_total", labels={"kind": "fold"}
        )
        self._c_full_compacts = reg.counter(
            "maintenance_folds_total", labels={"kind": "full"}
        )
        self._g_last_fold = reg.gauge(
            "serve_last_fold_seconds", help="Duration of the most recent fold."
        )

    # -- write path (admission) ----------------------------------------------
    def _host_rows(self, keys, values):
        """One mutation batch staged on the host: canonical packed keys and
        payload (row ids when ``values`` is None)."""
        schema = self.table.schema
        keys = schema.host_keys(keys)
        if values is None:
            values = schema.default_values(keys.shape[0])
        return keys, schema.host_values(values)

    def _pad_insert(self, keys, values, bucket: Optional[int] = None):
        """Device-align one mutation batch: EMPTY-pad keys, -1-pad values.

        The build/insert contract wants ``N % devices == 0``; sentinel rows
        route round-robin, land in trash buckets, and are invisible to
        every read — the same padding idiom as the exchange.  With
        ``bucket`` the batch is padded all the way to that fixed size, so
        every delta it builds shares one geometry (the AOT grid contract).
        Padding happens on the host; the padded rows then go straight to
        the table's key sharding.
        """
        keys, values = self._host_rows(keys, values)
        n = keys.shape[0]
        pad = (-n) % self.table.num_devices if bucket is None else bucket - n
        if pad:
            kshape = (pad,) + keys.shape[1:]
            vshape = (pad,) + values.shape[1:]
            keys = np.concatenate([keys, np.full(kshape, EMPTY_KEY, np.uint32)])
            values = np.concatenate([values, np.full(vshape, -1, np.int32)])
        sharding = self.table.key_sharding()
        return jax.device_put(keys, sharding), jax.device_put(values, sharding)

    def submit_insert(self, keys, values=None) -> None:
        """Queue one insert batch (applied by the writer loop).

        With ``write_bucket`` set, the batch is chunked to the bucket size
        and each chunk EMPTY-padded up to it: every queued insert then
        builds a delta of identical geometry, which is what lets
        :meth:`warm` enumerate (and AOT-compile) every state structure the
        writer can reach.
        """
        keys, values = self._host_rows(keys, values)
        n = keys.shape[0]
        wb = self.write_bucket
        if wb is None:
            ops = [self._pad_insert(keys, values)]
        else:
            ops = [
                self._pad_insert(keys[i : i + wb], values[i : i + wb], bucket=wb)
                for i in range(0, max(1, n), wb)
            ]
        with self._lock:
            for k, v in ops:
                self._writes.append(("insert", k, v, None))

    def submit_delete(self, keys) -> None:
        """Queue one delete batch (applied by the writer loop).

        Batches are chunked to at most half the tombstone capacity so the
        per-op policy check between chunks can escalate (freeing the
        buffer) before any chunk could overflow it — one oversized batch
        must not silently lose deletes.  Residual overflow under an
        unusually permissive policy still surfaces in
        ``stats().shadow.tombstone_dropped``.
        """
        keys = self.table.schema.host_keys(keys)
        chunk = max(1, self.table.tombstone_capacity // 2)
        with self._lock:
            for i in range(0, max(1, keys.shape[0]), chunk):
                self._writes.append(("delete", keys[i : i + chunk], None, None))

    def submit_upsert(self, keys, values=None, *, ttl: Optional[int] = None) -> None:
        """Queue one insert-or-replace batch (KV semantics; see
        :meth:`DistributedHashTable.upsert`).

        The batch is keep-last deduplicated at admission (one winner per
        key) and chunked like inserts; each chunk applies as one
        delete-prior-versions + one bucket-padded delta build, so with
        ``write_bucket`` set every upsert delta shares the warmed insert
        geometry — AOT reads never retrace.  ``ttl`` schedules expiry of
        the new version at ``now + ttl`` on the server's logical clock
        (:meth:`advance`).
        """
        kn, vn = self._host_rows(keys, values)
        # Keep-last dedup at admission: KV semantics demand one winner per
        # key per batch, and deduping host-side keeps the applied chunks
        # disjoint (cross-chunk duplicates would re-tombstone fresh rows).
        rows = kn if kn.ndim == 2 else kn[:, None]
        _, first = np.unique(rows[::-1], axis=0, return_index=True)
        keep = np.sort(rows.shape[0] - 1 - first)
        keep = keep[~np.all(rows[keep] == np.uint32(EMPTY_KEY), axis=1)]
        if keep.shape[0] == 0:
            return
        keys = kn[keep]
        values = vn[keep]
        chunk = self.write_bucket or max(1, keys.shape[0])
        chunk = min(chunk, max(1, self.table.tombstone_capacity // 2))
        with self._lock:
            for i in range(0, keys.shape[0], chunk):
                self._writes.append(
                    ("upsert", keys[i : i + chunk], values[i : i + chunk], ttl)
                )

    def advance(self, now) -> None:
        """Advance the serving logical clock to ``now``; publish.

        TTL expiry is resolved against this clock at read time, so
        advancing it is how upserted rows age out of every later read.
        The clock is a *data* field of the state (no structure change —
        AOT executors keep matching); monotone by contract.  Blocks
        briefly on the shadow-mutation mutex (a fold in flight finishes
        first).
        """
        with self._writer_mutex:
            self._shadow = self._shadow.advance(now)
            self.registry.publish(self._shadow)

    def pending(self) -> int:
        return len(self._writes)

    def step(self) -> int:
        """Apply up to ``window`` queued mutations to the shadow; publish.

        Returns the number of batches applied (0 while a background fold
        is in flight — writes stay queued, reads stay live).  Runs the
        compaction policy *before* every mutation, so neither the delta
        ring (inserts) nor the tombstone buffer (delete runs) can overflow
        mid-stream while the policy's triggers are enabled.
        """
        # Non-blocking acquire keeps the documented contract even when a
        # fold wins the race between the flag check and the mutex: the
        # writes stay queued and the caller gets 0 instead of parking for
        # the whole fold.
        if self.fold_in_flight or not self._writer_mutex.acquire(blocking=False):
            return 0
        try:
            applied = 0
            # Lazy per-window stats: the device-read signals (tombstone
            # fill/overflow, drop tallies) are collected once per window and
            # re-read only after the ops that can move them (deletes,
            # folds); the delta-depth trigger is tracked host-side.  An idle
            # step() never touches the device.
            stats = None
            while applied < self.window:
                with self._lock:
                    if not self._writes:
                        break
                    op = self._writes.popleft()
                try:
                    if stats is None:
                        stats = self._shadow.stats()
                    if self.policy.due(stats):
                        self._fold_shadow()
                        stats = self._shadow.stats()
                    kind, keys, values, ttl = op
                    if kind == "insert":
                        self._shadow = self.table.insert(self._shadow, keys, values)
                        stats = dataclasses.replace(
                            stats, delta_depth=len(self._shadow.deltas)
                        )
                    elif kind == "upsert":
                        self._apply_upsert(keys, values, ttl)
                        stats = None  # delta depth AND tombstones moved
                    else:
                        self._shadow = self.table.delete(self._shadow, keys)
                        stats = None  # tombstone signals moved: re-read
                except Exception as e:
                    # An acknowledged write must never vanish: requeue it at
                    # the front, surface the error in stats, and re-raise
                    # (the embedded loop stops loudly; an external driver
                    # sees the exception directly).
                    with self._lock:
                        self._writes.appendleft(op)
                    self._last_error = f"{type(e).__name__}: {e}"
                    if applied:
                        self.registry.publish(self._shadow)
                    raise
                self._c_writes_applied.inc()
                applied += 1
            if applied:
                self.registry.publish(self._shadow)
            return applied
        finally:
            self._writer_mutex.release()

    def _apply_upsert(self, keys, values, ttl) -> None:
        """Apply one (deduped, unpadded) upsert chunk to the shadow.

        The delete-then-insert of ``table.upsert``, with the insert padded
        to ``write_bucket`` when set — the upsert delta then shares the
        warmed insert geometry, so the state signature stays inside the
        AOT grid and reads never retrace.  Only *real* keys are
        tombstoned (padding sentinels would burn buffer slots).
        """
        shadow = self.table.delete(self._shadow, keys)  # epoch d
        k_pad, v_pad = self._pad_insert(keys, values, bucket=self.write_bucket)
        shadow = self.table.insert(shadow, k_pad, v_pad)  # epoch d + 1
        if ttl is not None:
            shadow = dataclasses.replace(
                shadow,
                tombstones=shadow.tombstones.push(
                    keys,
                    epoch=len(shadow.deltas),
                    expires=shadow.tombstones.now + jnp.int32(ttl),
                ),
            )
        self._shadow = shadow

    # -- maintenance (off the read path) --------------------------------------
    def maintain(self) -> bool:
        """Fold the shadow now if the policy says it is due; publish.

        Synchronous variant for deterministic drivers; the background
        variant is :meth:`fold_async`.  Returns True iff a fold ran.
        """
        if self.fold_in_flight or not self._writer_mutex.acquire(blocking=False):
            return False
        try:
            if not self.policy.due(self._shadow.stats()):
                return False
            ran = self._fold_counts()
            self._fold_shadow()
            if self._fold_counts() == ran:
                return False  # due but nothing actionable: no phantom publish
            self.registry.publish(self._shadow)
            return True
        finally:
            self._writer_mutex.release()

    def _fold_shadow(self) -> None:
        stats = self._shadow.stats()
        escalate = self.policy.escalates(stats)
        layer_live = None
        if self.policy.fold_k is None and not escalate and stats.delta_depth:
            # Stats-driven sizing: one counts round measures per-layer live
            # rows and the policy folds the longest cold prefix first.
            layer_live = maintenance.collect_layer_live(self._shadow)
        k = self.policy.fold_amount(stats, layer_live)
        if not escalate and not k:
            return
        # An incoherent shadow (skew-guard fallback) cannot fold locally —
        # fold_oldest would full-compact anyway; route it here so the pause
        # is attributed to full_compacts, not folds.
        if escalate or k >= stats.delta_depth or not self._shadow.coherent:
            # Escalation: the full rebuild frees every tombstone (valid even
            # at delta depth 0) and re-flattens the base arrays that
            # incremental folds let grow.
            self._apply_fold(self.table.compact, full=True)
        else:
            self._apply_fold(lambda s: maintenance.fold_oldest(s, k), full=False)

    def _fold_counts(self) -> tuple:
        return (self._c_folds.value, self._c_full_compacts.value)

    def _apply_fold(self, fold_fn, *, full: bool) -> None:
        """Run one timed fold of the shadow and attribute the counter."""
        t0 = time.perf_counter()
        rows_before = maintenance.allocated_rows(self._shadow)
        self._shadow = fold_fn(self._shadow)
        if full and self.write_bucket is not None:
            # compact() resets the tombstone buffer to zero capacity when
            # nothing was pending; shape-stable serving re-grows it
            # immediately (clock preserved) so the state structure — and
            # with it the AOT executor keys — stays fixed.  With pending
            # TTL entries compact() already returned the capacity-preserving
            # remap, which must NOT be overwritten (the entries guard rows
            # that survived into the new base).
            ts = self._shadow.tombstones
            if ts.capacity != self.table.tombstone_capacity:
                self._shadow = dataclasses.replace(
                    self._shadow,
                    tombstones=empty_tombstones(
                        self.table.tombstone_capacity,
                        self.table.schema.key_lanes,
                        now=ts.now,
                    ),
                )
        dt = time.perf_counter() - t0
        # One recording site per fold: pause time, counter by kind, and
        # reclaimed rows all land in the shared registry.
        maintenance.record_fold(
            self.metrics_registry,
            kind="full" if full else "fold",
            seconds=dt,
            rows_before=rows_before,
            rows_after=maintenance.allocated_rows(self._shadow),
        )
        self._g_last_fold.set(dt)

    def fold_async(self, k: Optional[int] = None) -> threading.Thread:
        """Start one background fold of the shadow; reads keep flowing.

        The fold runs on a worker thread holding the shadow-mutation mutex
        for its whole duration: a ``step()`` that was mid-application when
        the fold started finishes first (the fold then reads the post-step
        shadow — acknowledged writes are never discarded), later steps
        defer until the fold lands (writes queue), and the folded state is
        published atomically on completion.  Reads never touch the mutex.
        Returns the thread (join it or poll :attr:`fold_in_flight`).
        """
        if self.fold_in_flight:
            raise RuntimeError("a background fold is already in flight")

        def run():
            try:
                with self._writer_mutex:
                    ran_before = self._fold_counts()
                    if k is None:
                        # Policy-driven: same decision tree as inline
                        # maintenance (including the depth-0
                        # tombstone-pressure escalation).
                        self._fold_shadow()
                    else:
                        kk = min(k, len(self._shadow.deltas))
                        if kk <= 0:
                            return
                        if self._shadow.coherent and kk < len(self._shadow.deltas):
                            self._apply_fold(
                                lambda s: maintenance.fold_oldest(s, kk), full=False
                            )
                        else:  # fold-all or incoherent: full rebuild either way
                            self._apply_fold(self.table.compact, full=True)
                    if self._fold_counts() != ran_before:
                        self.registry.publish(self._shadow)
            except Exception as e:
                # A dead fold thread must never be silent: the failure is
                # surfaced on stats().last_error and re-raised by drain().
                # The published snapshot stays at the last good seqno and
                # the read path keeps serving it.
                self._fold_error = f"{type(e).__name__}: {e}"
                self._last_error = self._fold_error

        t = threading.Thread(target=run, name="serve-table-fold", daemon=True)
        self._fold_thread = t
        t.start()
        return t

    @property
    def fold_in_flight(self) -> bool:
        t = self._fold_thread
        return t is not None and t.is_alive()

    # -- read path (never blocks on writes/folds) ------------------------------
    def current(self) -> Snapshot:
        """The snapshot reads execute against right now."""
        return self.registry.current()

    def query_many(self, requests) -> tuple[list, int]:
        """Merged multiplicities per request against the current snapshot.

        Returns ``(results, seqno)`` — one int32 array per request plus
        the seqno of the snapshot that served them (every key of every
        request in the batch observes that one consistent version).
        """
        snap = self.registry.current()
        out = self.batcher.query_many(snap.state, requests)
        self._c_reads.inc(len(requests))
        self._c_read_batches.inc()
        return out, snap.seqno

    def retrieve_many(self, requests, *, per_layer_counts: bool = False):
        """Stored values per request key against the current snapshot.

        Returns ``(results, seqno)``; see
        :meth:`MicroBatcher.retrieve_many` for the result shape.
        """
        snap = self.registry.current()
        out = self.batcher.retrieve_many(
            snap.state, requests, per_layer_counts=per_layer_counts
        )
        self._c_reads.inc(len(requests))
        self._c_read_batches.inc()
        return out, snap.seqno

    def query(self, keys) -> np.ndarray:
        """Single-request convenience wrapper over :meth:`query_many`."""
        return self.query_many([keys])[0][0]

    # -- AOT warmup ---------------------------------------------------------------
    def warm(self, **kwargs):
        """AOT-compile the read-executor grid before admitting traffic.

        Thin wrapper over :func:`repro.serve_table.aot.warm_server` (see it
        for the knobs); requires ``write_bucket``.  After this, live reads
        whose (bucket, state structure) fall inside the warmed grid run
        pre-compiled XLA executables — zero tracing, zero compilation —
        and coverage is visible in ``stats().warmup``.
        """
        from repro.serve_table.aot import warm_server

        return warm_server(self, **kwargs)

    # -- embedded writer loop ---------------------------------------------------
    def start(self, poll_interval: float = 0.001) -> None:
        """Run the writer loop on a daemon thread until :meth:`stop`.

        A write that fails to apply stops the loop (the failed batch stays
        at the head of the queue) and surfaces as ``stats().last_error`` —
        never a silently dead thread.
        """
        if self._writer_thread is not None and self._writer_thread.is_alive():
            raise RuntimeError("writer loop already running")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    applied = self.step()
                except Exception:
                    self._stop.set()  # error is in stats().last_error
                    return
                if not applied:
                    time.sleep(poll_interval)

        self._writer_thread = threading.Thread(
            target=loop, name="serve-table-writer", daemon=True
        )
        self._writer_thread.start()

    def stop(self) -> None:
        """Stop the writer loop (queued writes stay queued)."""
        self._stop.set()
        if self._writer_thread is not None:
            self._writer_thread.join()
            self._writer_thread = None

    def drain(self, timeout: float = 60.0) -> None:
        """Block until every queued write has been applied and published.

        Works with the embedded writer loop (waits) or without one (drives
        :meth:`step` inline); in-flight background folds are joined.

        Never exits silently with work still queued:

        * raises :class:`TimeoutError` (with the number of still-pending
          batches) if the queue has not emptied by ``timeout``;
        * raises :class:`RuntimeError` promptly — not at timeout — if the
          embedded writer it is waiting on stops (explicit :meth:`stop`,
          or a write failure killing the loop) or a background fold
          crashed, carrying ``last_error`` when one is recorded.
        """
        deadline = time.monotonic() + timeout
        embedded = (
            self._writer_thread is not None and self._writer_thread.is_alive()
        )
        while True:
            if self._fold_error is not None:
                raise RuntimeError(
                    f"background fold failed: {self._fold_error}"
                )
            pending = self.pending()
            if not pending and not self.fold_in_flight and self._settled():
                return
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"drain timed out with {pending} pending "
                    f"batch{'es' if pending != 1 else ''}"
                    + (" and a fold in flight" if self.fold_in_flight else "")
                )
            if self.fold_in_flight:
                t = self._fold_thread
                if t is not None:
                    t.join(
                        timeout=min(0.05, max(0.0, deadline - time.monotonic()))
                    )
                continue
            writer_alive = (
                self._writer_thread is not None and self._writer_thread.is_alive()
            )
            if embedded and (self._stop.is_set() or not writer_alive):
                # The writer this drain was parked on is gone: stop() was
                # called, or a failing write batch killed the loop.  Waiters
                # unblock immediately instead of spinning to the timeout.
                why = (
                    f"writer failed: {self._last_error}"
                    if self._last_error
                    else "server stopped"
                )
                raise RuntimeError(
                    f"drain unblocked ({why}) with {pending} pending "
                    f"batch{'es' if pending != 1 else ''}"
                )
            if writer_alive:
                time.sleep(0.0005)
            else:
                self.step()

    def _settled(self) -> bool:
        """True once applied work is *published*, not merely dequeued.

        ``pending()`` drops to 0 the moment the writer pops the last op —
        before the mutation lands and the snapshot swaps.  Briefly taking
        the shadow-mutation mutex proves no step/fold is mid-application
        (both publish before releasing it), closing the drain-returns-early
        race.
        """
        if not self._writer_mutex.acquire(timeout=0.01):
            return False
        try:
            return not self.pending() and not self.fold_in_flight
        finally:
            self._writer_mutex.release()

    # -- metrics ----------------------------------------------------------------
    def stats(self) -> ServerStats:
        """A coherent host-side sample of every serving counter.

        The view is a thin wrapper over ONE registry snapshot (a single
        lock acquisition observes every counter at the same instant — no
        field-by-field tearing between, say, ``reads`` and
        ``read_batches``); the shadow's :class:`TableStats` is the usual
        few-scalar device read on top.
        """
        snap = self.metrics_registry.snapshot()
        hist_fold = snap.histogram("maintenance_fold_seconds", {"kind": "fold"})
        hist_full = snap.histogram("maintenance_fold_seconds", {"kind": "full"})
        fold_seconds = (hist_fold.sum if hist_fold else 0.0) + (
            hist_full.sum if hist_full else 0.0
        )
        return ServerStats(
            seqno=self.registry.seqno,
            pending_writes=self.pending(),
            writes_applied=int(snap.value("serve_writes_applied_total")),
            reads=int(snap.value("serve_reads_total")),
            read_batches=int(snap.value("serve_read_batches_total")),
            folds=int(snap.value("maintenance_folds_total", {"kind": "fold"})),
            full_compacts=int(
                snap.value("maintenance_folds_total", {"kind": "full"})
            ),
            fold_seconds_total=fold_seconds,
            last_fold_seconds=float(snap.value("serve_last_fold_seconds", default=0.0)),
            fold_in_flight=self.fold_in_flight,
            skew_fallbacks=self.table.skew_fallbacks - self._skew_base,
            last_error=self._last_error,
            batcher=self.batcher.stats(snapshot=snap),
            shadow=self._shadow.stats(),
            warmup=(
                self.batcher.executors.stats()
                if self.batcher.executors is not None
                else None
            ),
        )

    def metrics(self, refresh: bool = True) -> RegistrySnapshot:
        """One atomic sample of the server's whole metrics registry.

        With ``refresh`` (default) the state-derived gauges — seqno, queue
        depths, drop tallies, delta depth, the jit dispatch-cache size —
        are re-read first (costs the shadow's few-scalar device sync);
        ``refresh=False`` samples the counters as-is.  Feed the result to
        :func:`repro.obs.render_prometheus` / :func:`repro.obs.render_jsonl`
        or assert on it directly (``benchmarks.common.assert_clean_run``).
        """
        if refresh:
            reg = self.metrics_registry
            sh = self._shadow.stats()
            reg.gauge("serve_seqno", help="Last published snapshot seqno.").set(
                self.registry.seqno
            )
            reg.gauge(
                "serve_pending_writes", help="Queued, not yet applied writes."
            ).set(self.pending())
            reg.gauge(
                "serve_fold_in_flight", help="1 while a background fold runs."
            ).set(int(self.fold_in_flight))
            reg.gauge(
                "serve_delta_depth", help="Live delta layers on the shadow."
            ).set(sh.delta_depth)
            reg.gauge(
                "serve_dropped_rows",
                help="Rows lost to capacity anywhere in the stack (want 0).",
            ).set(sh.num_dropped)
            reg.gauge(
                "serve_tombstone_dropped",
                help="Deletes lost to tombstone capacity (want 0).",
            ).set(sh.tombstone_dropped)
            reg.gauge(
                "serve_skew_fallbacks",
                help="Inserts routed incoherent by the skew guard.",
            ).set(self.table.skew_fallbacks - self._skew_base)
            reg.gauge(
                "jit_dispatch_cache_size",
                help="exec_query jit cache entries (flat once warmed).",
            ).set(plans.exec_query._cache_size())
        return self.metrics_registry.snapshot()
