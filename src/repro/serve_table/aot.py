"""AOT warmup — compile the serving executor grid before the first request.

The serving front end admits reads on pow2-bucketed static shapes
(:class:`~repro.serve_table.batcher.MicroBatcher`) and writes padded to a
fixed ``write_bucket`` (:class:`~repro.serve_table.server.TableServer`), so
the set of programs live traffic can demand is *enumerable up front*: one
read executor per ``(bucket, state structure)`` pair, where the structure
is determined by the delta depth, the (uniform) delta geometry, the
tombstone buffer, and how many incremental folds have grown the base.

:func:`warm_server` walks exactly that grid at server start, building each
program through the ``jax.jit(...).lower(...).compile()`` idiom (the
offline-inference warmup pattern: per-padded-shape executables compiled
ahead of time, keyed by shape) and parks the executables in an
:class:`ExecutorGrid`.  The grid hooks into the micro-batcher: a read whose
``(bucket, state signature)`` matches a warmed entry runs the XLA
executable directly — ``jax.jit``'s dispatch cache is never consulted, so a
fully-warmed server does **zero live tracing or compilation** (asserted by
the no-retrace regression tests and the CI open-loop smoke).  Reads that
miss the grid (unwarmed depth, post-full-compact geometry, oversized write
batches) fall back to the normal plan path and are *counted*, never wrong:
``WarmupStats.coverage`` makes warmup adequacy observable.

State structures are warmed without real data: a **sentinel delta** (one
insert of ``write_bucket`` EMPTY keys) has byte-for-byte the geometry of
any real write at that bucket, so depth-``d`` prototypes are the base plus
``d`` references to it, and fold-``f`` prototypes fold the sentinel stack
``f`` times.  Prototype construction also warms the write-path executor
(``_build_delta_jit``) and the incremental fold as a side effect.
"""
from __future__ import annotations

import dataclasses
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax.numpy as jnp

from repro.core import maintenance
from repro.core.hashgraph import EMPTY_KEY
from repro.core.plans import CompiledPlan, state_signature
from repro.core.state import TableState
from repro.obs.tracing import process_tracer


@dataclasses.dataclass(frozen=True)
class WarmupStats:
    """Coverage of the AOT-warmed executor grid (one coherent sample).

    ``entries`` is the number of compiled executables held; ``aot_hits`` /
    ``aot_misses`` count live read executions served by a warmed executable
    vs falling back to the jit plan path (a nonzero miss count after warmup
    means live traffic reached a structure outside the warmed grid — wider
    ``depths``/``fold_horizon``/``buckets`` close it).
    """

    write_bucket: int
    buckets: tuple  # read bucket sizes warmed
    depths: tuple  # delta depths warmed (at fold step 0)
    fold_horizon: int  # incremental folds whose post-fold bases are warmed
    entries: int  # compiled executables held
    compile_seconds: float  # wall-clock cost of the warmup pass
    aot_hits: int  # live executions served by a warmed executable
    aot_misses: int  # live executions that fell back to the jit path
    profiles: tuple = ()  # ExecutorCost rows from the warmup profiling pass

    @property
    def coverage(self) -> float:
        total = self.aot_hits + self.aot_misses
        return self.aot_hits / total if total else 1.0


class ExecutorGrid:
    """Registry of AOT-compiled read executors, keyed by shape + structure.

    Lookup key: ``(kind, bucket, extra-statics, state_signature(state))`` —
    a hit means the compiled executable was lowered against a structurally
    identical state and runs with zero tracing.  Hit/miss counters are
    plain ints guarded by a lock (lookups come from the micro-batcher's
    locked sections and the front end's single dispatcher thread).
    """

    def __init__(self):
        self._handles = {}
        self._retrieve_caps = {}  # bucket -> (out_cap, seg_cap) warmed caps
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        # Mirror counters in a shared MetricsRegistry (None until bound).
        # The plain ints stay authoritative for THIS grid's stats; the
        # registry counters accumulate across re-warms (a replaced grid
        # binds to the same registry), matching Prometheus counter
        # semantics.
        self._hit_counter = None
        self._miss_counter = None
        self.profiles: tuple = ()  # ExecutorCost rows (warmup profiling pass)
        self._meta = {
            "write_bucket": 0,
            "buckets": (),
            "depths": (),
            "fold_horizon": 0,
            "compile_seconds": 0.0,
        }

    def bind_registry(self, registry) -> None:
        """Mirror hit/miss counts into ``registry`` (carrying current counts)."""
        with self._lock:
            self._hit_counter = registry.counter(
                "aot_hits_total", help="Reads served by an AOT-warmed executable."
            )
            self._miss_counter = registry.counter(
                "aot_misses_total",
                help="Reads that fell back to the jit plan path.",
            )
            if self._hits:
                self._hit_counter.inc(self._hits)
            if self._misses:
                self._miss_counter.inc(self._misses)

    def __len__(self) -> int:
        return len(self._handles)

    def add(self, bucket: int, handle: CompiledPlan, extra: tuple = ()) -> None:
        key = (handle.kind, bucket, extra, handle.signature)
        with self._lock:
            self._handles[key] = handle

    def query_handle(self, state, bucket: int) -> Optional[CompiledPlan]:
        """The warmed query executable for this exact structure, or None.

        Counts the hit/miss either way — the pair is the live coverage
        signal in :class:`WarmupStats`.
        """
        return self._lookup(("query", bucket, (), state_signature(state)))

    def retrieve_handle(
        self, state, bucket: int, out_cap: int, seg_cap: int, per_layer: bool
    ) -> Optional[CompiledPlan]:
        return self._lookup(
            ("retrieve", bucket, (out_cap, seg_cap, per_layer), state_signature(state))
        )

    def _lookup(self, key) -> Optional[CompiledPlan]:
        with self._lock:
            h = self._handles.get(key)
            if h is None:
                self._misses += 1
                if self._miss_counter is not None:
                    self._miss_counter.inc()
            else:
                self._hits += 1
                if self._hit_counter is not None:
                    self._hit_counter.inc()
            return h

    def _peek(self, key) -> Optional[CompiledPlan]:
        """Uncounted lookup (warmup-internal; never a coverage signal)."""
        with self._lock:
            return self._handles.get(key)

    def cost_profile(self) -> tuple:
        """The warmup profiling pass's :class:`ExecutorCost` rows."""
        return self.profiles

    def retrieve_caps(self, bucket: int) -> Optional[tuple]:
        """The (out, seg) capacities retrieve was warmed with for a bucket
        (the batcher seeds its working caps from these so warmed traffic
        lands on the compiled executables instead of re-planning)."""
        return self._retrieve_caps.get(bucket)

    def stats(self) -> WarmupStats:
        with self._lock:
            return WarmupStats(
                write_bucket=self._meta["write_bucket"],
                buckets=tuple(self._meta["buckets"]),
                depths=tuple(self._meta["depths"]),
                fold_horizon=self._meta["fold_horizon"],
                entries=len(self._handles),
                compile_seconds=self._meta["compile_seconds"],
                aot_hits=self._hits,
                aot_misses=self._misses,
                profiles=self.profiles,
            )


def _sentinel_batch(table, n: int):
    """An all-EMPTY insert batch: real geometry, no visible rows."""
    schema = table.schema
    lanes = schema.key_lanes
    kshape = (n,) if lanes == 1 else (n, lanes)
    vshape = (n,) if schema.value_cols == 1 else (n, schema.value_cols)
    keys = jnp.full(kshape, EMPTY_KEY, jnp.uint32)
    values = jnp.full(vshape, -1, jnp.int32)
    return keys, values


def warm_server(
    server,
    *,
    buckets: Optional[Sequence[int]] = None,
    depths: Optional[Sequence[int]] = None,
    fold_horizon: int = 1,
    retrieve_caps=None,
    workers: Optional[int] = None,
    profile: bool = True,
) -> WarmupStats:
    """AOT-compile the server's whole reachable read-executor grid.

    * ``buckets`` — read batch sizes to warm (pow2, device-aligned;
      default: the batcher's ``min_bucket`` and the next two doublings).
    * ``depths`` — delta depths to warm at fold step 0 (default: every
      depth the compaction policy lets the writer reach, ``0..trigger``).
    * ``fold_horizon`` — how many incremental folds ahead to warm: each
      fold grows the base by the folded deltas' rows, a new structure.
      Post-fold steps warm depths ``trigger-fold_k..trigger`` (the band a
      folding writer actually revisits).  Ignored (treated as 0) when the
      policy never folds incrementally.
    * ``retrieve_caps`` — ``(out, seg)`` pair or ``{bucket: (out, seg)}``
      to additionally warm retrieve executors; queries only by default.
    * ``workers`` — thread pool width for the XLA compile stage (tracing
      is sequential; compilation releases the GIL).  0 = fully sequential.
    * ``profile`` — run the jaxpr collective accountant over the warmed
      grid: one :class:`~repro.obs.profiling.ExecutorCost` per distinct
      (kind, depth) program structure at the smallest bucket, combining
      collective counts/bytes with the compiled executable's XLA cost
      analysis.  Surfaced on ``grid.cost_profile()`` / ``stats().warmup.
      profiles`` and as labelled registry gauges.

    Attaches the resulting :class:`ExecutorGrid` to the server's batcher
    and records coverage in ``server.stats().warmup``.  Idempotent-ish:
    re-warming replaces the grid (the server registry's AOT counters keep
    accumulating across re-warms).
    """
    if server.write_bucket is None:
        raise ValueError(
            "AOT warmup needs a shape-stable write path: construct the "
            "TableServer with write_bucket=<pow2> so every insert delta "
            "shares one geometry"
        )
    with process_tracer().span("server.warm") as span:
        grid = _warm_grid(
            server,
            buckets=buckets,
            depths=depths,
            fold_horizon=fold_horizon,
            retrieve_caps=retrieve_caps,
            workers=workers,
            profile=profile,
        )
    grid._meta["compile_seconds"] = span.seconds
    registry = getattr(server, "metrics_registry", None)
    if registry is not None:
        grid.bind_registry(registry)
        registry.gauge(
            "aot_entries", help="Compiled executables held by the AOT grid."
        ).set(len(grid))
        registry.gauge(
            "aot_compile_seconds", help="Wall-clock cost of the last warmup."
        ).set(span.seconds)
        for cost in grid.profiles:
            labels = {
                "kind": cost.kind,
                "bucket": cost.bucket,
                "depth": cost.depth,
            }
            registry.gauge(
                "executor_all_to_alls",
                labels=labels,
                help="all_to_all primitives per executor (jaxpr accountant).",
            ).set(cost.all_to_alls)
            registry.gauge(
                "executor_collective_bytes",
                labels=labels,
                help="Per-device bytes moved through collectives per call.",
            ).set(cost.total_collective_bytes)
    server.batcher.executors = grid
    # Seed the batcher's retrieve working caps so warmed buckets skip the
    # planning round and land on the compiled executables.
    for b, caps in grid._retrieve_caps.items():
        server.batcher._caps.setdefault(b, caps)
    return grid.stats()


def _warm_grid(
    server, *, buckets, depths, fold_horizon, retrieve_caps, workers, profile
) -> ExecutorGrid:
    """The body of :func:`warm_server`: prototype states, lowering,
    compilation and profiling of the grid (not yet attached)."""
    table = server.table
    state0 = server.current().state
    policy = server.policy
    trigger = policy.max_delta_depth
    if trigger is None or trigger > table.max_deltas:
        trigger = table.max_deltas
    # Stats-driven policies (fold_k=None) size each fold at runtime; warm
    # the single-step geometry (their cold-prefix walk returns >= 1) and
    # let fold_horizon cover repetition.
    pfk = 1 if policy.fold_k is None else policy.fold_k
    fold_k = min(max(1, pfk), max(1, trigger - 1))
    folds_incremental = trigger is not None and pfk < trigger
    if not folds_incremental:
        fold_horizon = 0  # escalations full-compact: geometry is data-sized

    if buckets is None:
        b0 = server.batcher.min_bucket
        buckets = (b0, b0 * 2, b0 * 4)
    buckets = tuple(sorted({server.batcher.bucket_size(int(b)) for b in buckets}))
    if depths is None:
        depths = range(0, trigger + 1)
    depths = tuple(sorted({int(d) for d in depths if 0 <= d <= table.max_deltas}))
    if isinstance(retrieve_caps, tuple):
        retrieve_caps = {b: retrieve_caps for b in buckets}
    retrieve_caps = retrieve_caps or {}

    # -- prototype states: sentinel delta, fold-grown bases -------------------
    keys, values = _sentinel_batch(table, server.write_bucket)
    delta = table.insert(state0, keys, values).deltas[-1]

    def proto(base, depth) -> TableState:
        return dataclasses.replace(
            state0, base=base, deltas=(delta,) * depth, coherent=True
        )

    protos = []  # (fold_step, depth, state)
    base = state0.base
    for f in range(fold_horizon + 1):
        dd = depths if f == 0 else tuple(
            d for d in range(max(0, trigger - fold_k), trigger + 1)
        )
        for d in dd:
            protos.append((f, d, proto(base, d)))
        if f < fold_horizon:
            # The next fold step's base: fold fold_k sentinel deltas in.
            # The stack is trigger-deep, as the one the policy folds: the
            # fold executor is traced per stack depth, so this warms the
            # very program the writer calls.
            base = maintenance.fold_oldest(proto(base, trigger), fold_k).base

    # -- lower sequentially (tracing), compile on a pool (XLA, GIL-free) ------
    grid = ExecutorGrid()
    jobs = []  # (bucket, extra, kind-lowered)
    for _, _, st in protos:
        for b in buckets:
            qp = table.plan_query(num_queries=b)
            jobs.append((b, (), "query", qp.lower(st), state_signature(st)))
            caps = retrieve_caps.get(b)
            if caps is not None:
                out_cap, seg_cap = int(caps[0]), int(caps[1])
                rp = table.plan_retrieve(
                    num_queries=b, out_capacity=out_cap, seg_capacity=seg_cap
                )
                jobs.append(
                    (b, (out_cap, seg_cap, False), "retrieve",
                     rp.lower(st), state_signature(st))
                )

    def compile_one(job):
        b, extra, kind, lowered, sig = job
        handle = CompiledPlan(
            compiled=lowered.compile(), kind=kind, num_queries=b, signature=sig
        )
        grid.add(b, handle, extra=extra)

    if workers is None:
        workers = min(8, len(jobs))
    if workers and len(jobs) > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(compile_one, jobs))
    else:
        for job in jobs:
            compile_one(job)

    for b, caps in retrieve_caps.items():
        grid._retrieve_caps[int(b)] = (int(caps[0]), int(caps[1]))

    # -- device-cost profiling: jaxpr accountant over the warmed grid ---------
    if profile:
        grid.profiles = _profile_grid(
            table, grid, protos, buckets, retrieve_caps
        )

    grid._meta.update(
        write_bucket=server.write_bucket,
        buckets=buckets,
        depths=depths,
        fold_horizon=fold_horizon,
    )
    return grid


def _profile_grid(table, grid, protos, buckets, retrieve_caps) -> tuple:
    """One :class:`ExecutorCost` per (kind, depth) structure, smallest bucket.

    The jaxpr walk is per program *structure* — collective count and bytes
    do not depend on which fold step grew the base — so fold step 0 at the
    smallest warmed bucket bounds the tracing cost while still covering
    every delta depth (the acceptance criterion: the accountant must
    re-confirm the fused 2-all-to-all budget at each depth).
    """
    from repro.core.plans import _proto_queries, state_signature
    from repro.obs.profiling import profile_executor

    b0 = buckets[0]
    q = _proto_queries(table, b0)
    costs = []
    seen = set()
    for f, d, st in protos:
        if f != 0 or d in seen:
            continue
        seen.add(d)
        sig = state_signature(st)
        handle = grid._peek(("query", b0, (), sig))
        costs.append(
            profile_executor(
                table,
                st,
                q,
                kind="query",
                compiled=None if handle is None else handle.compiled,
            )
        )
        caps = retrieve_caps.get(b0)
        if caps is not None:
            out_cap, seg_cap = int(caps[0]), int(caps[1])
            rhandle = grid._peek(
                ("retrieve", b0, (out_cap, seg_cap, False), sig)
            )
            costs.append(
                profile_executor(
                    table,
                    st,
                    q,
                    kind="retrieve",
                    compiled=None if rhandle is None else rhandle.compiled,
                    exec_kwargs={
                        "out_capacity": out_cap,
                        "seg_capacity": seg_cap,
                    },
                )
            )
    return tuple(costs)


__all__ = ["ExecutorGrid", "WarmupStats", "warm_server"]
