"""Serving benchmark — request-stream throughput, tail latency, fold pauses.

Three measurements of the serve_table engine:

1. **Request stream vs batching window**: a stream of ragged query
   requests runs through the :class:`MicroBatcher` at several coalescing
   windows (requests per fused execution).  Larger windows amortize the
   executor launch over more requests (throughput up) but every request
   in a batch waits for the whole flush (latency up) — the knob the
   README's serving section documents.  Reported: keys/sec, request p50
   and p99 latency per window.
2. **Fold vs full compact pause**: the maintenance pause a background
   thread pays on a delta-deep state — incremental
   ``fold_oldest(state, k)`` (layer-local, zero collectives) against the
   full live-count-sized ``compact()``.
3. **``--smoke``** (CI): a server applies a mixed insert/delete stream,
   then runs a background fold while the main thread keeps reading.  The
   step *asserts* zero read-path stalls: reads issued during the fold
   complete against the pre-fold seqno, at least one lands while the fold
   is in flight, and no during-fold read takes as long as the fold itself
   (reads never waited on it).  A torn read, a blocked read path, or a
   missing publish fails CI loudly.
4. **``--open-loop``**: the async front end under open-loop Poisson
   arrivals.  The server is AOT-warmed (:func:`repro.serve_table.warm_server`)
   and then offered a configurable request rate; latency is measured from
   the *intended* arrival instant to future resolution, so queueing and
   admission delay count against the server, not the generator.  Reported
   per offered rate: p50/p99/p999 latency, goodput (responses inside the
   ``--slo-ms`` budget per second), and the traced per-phase breakdown
   (admission/linger/dispatch/device/scatter) out of the observability
   registry.  Each rate also runs a **tracing-overhead control pair**: a
   read-only stream with tracing disabled vs enabled, on frozen table
   geometry, isolating what the span bookkeeping itself costs (under
   ``--smoke`` each mode runs interleaved repeats and scores its best
   p99 — single-run tails on a 1-core CI box are scheduler noise).  With
   ``--smoke`` the mixed stream (writes + a policy-triggered fold through
   the front end) additionally *asserts*, by scraping the rendered
   Prometheus export the way an external monitor would: zero live traces,
   zero dropped rows, zero AOT misses and a flat jit dispatch cache
   (:func:`benchmarks.common.assert_clean_run`), the fused two-all-to-all
   budget on every profiled executor, a generous p99 bound, and < 5%
   tracing overhead on the control pair.
"""
import argparse
import threading
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 15)
    ap.add_argument("--requests", type=int, default=256, help="stream length")
    ap.add_argument("--req-min", type=int, default=4)
    ap.add_argument("--req-max", type=int, default=256)
    ap.add_argument("--windows", type=str, default="1,4,16,64")
    ap.add_argument("--depth", type=int, default=8, help="deltas for the fold bench")
    ap.add_argument("--fold-k", type=int, default=2)
    ap.add_argument("--smoke", action="store_true", help="CI no-stall assertion run")
    ap.add_argument("--json", type=str, default=None)
    ap.add_argument(
        "--open-loop",
        action="store_true",
        help="async front end under Poisson arrivals (only this part runs)",
    )
    ap.add_argument("--rates", type=str, default="100,400,1600", help="req/s sweep")
    ap.add_argument("--duration", type=float, default=2.0, help="seconds per rate")
    ap.add_argument("--req-keys", type=int, default=8, help="keys per request")
    ap.add_argument("--slo-ms", type=float, default=50.0, help="goodput latency budget")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.open_loop:
        _open_loop(args)
        return

    if args.smoke:
        args.keys = min(args.keys, 1 << 13)
        args.requests = min(args.requests, 64)
        args.req_max = min(args.req_max, 64)
        args.windows = "1,8"
        args.depth = 4

    import jax
    import numpy as np

    from benchmarks.common import emit, time_fn, write_bench_json
    from repro.core import maintenance
    from repro.core.table import DistributedHashTable, table_mesh
    from repro.serve_table import CompactionPolicy, MicroBatcher, TableServer

    d = len(jax.devices())
    mesh = table_mesh()
    n = args.keys
    rng = np.random.default_rng(11)
    keys = rng.integers(0, n, size=n, dtype=np.uint32)
    vals = np.arange(n, dtype=np.int32)

    rows = []

    # ---- 1. request stream: throughput + latency vs batching window --------
    table = DistributedHashTable(mesh, ("d",), hash_range=n, capacity_slack=2.0)
    state = table.init(jax.numpy.asarray(keys), jax.numpy.asarray(vals))
    sizes = rng.integers(args.req_min, args.req_max + 1, size=args.requests)
    stream = [rng.choice(keys, size=s).astype(np.uint32) for s in sizes]
    total_keys = int(sizes.sum())

    for window in [int(w) for w in args.windows.split(",")]:
        batcher = MicroBatcher(table)
        # warmup pass populates the plan caches (compiles excluded from the
        # serving numbers, as in steady traffic)
        for i in range(0, len(stream), window):
            batcher.query_many(state, stream[i : i + window])
        lat = []
        t_all0 = time.perf_counter()
        for i in range(0, len(stream), window):
            t0 = time.perf_counter()
            batcher.query_many(state, stream[i : i + window])
            dt = time.perf_counter() - t0
            lat.extend([dt] * len(stream[i : i + window]))
        total_sec = time.perf_counter() - t_all0
        st = batcher.stats()
        row = {
            "part": "stream",
            "window": window,
            "keys_per_sec": total_keys / total_sec,
            "requests_per_sec": len(stream) / total_sec,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "pad_fraction": st.pad_fraction,
            "cache_hit_rate": st.cache_hits / max(1, st.cache_hits + st.cache_misses),
        }
        rows.append(row)
        emit(
            "serve_stream",
            total_sec,
            window=window,
            keys_per_sec=f"{row['keys_per_sec']:.3e}",
            p50_ms=f"{row['p50_ms']:.3f}",
            p99_ms=f"{row['p99_ms']:.3f}",
            pad_fraction=f"{row['pad_fraction']:.3f}",
        )

    # ---- 2. fold_oldest vs full compact pause -------------------------------
    deep = table.init(jax.numpy.asarray(keys), jax.numpy.asarray(vals))
    batch = max(d * 8, min(1 << 10, n // 8))
    for _ in range(args.depth):
        deep = deep.insert(
            jax.numpy.asarray(rng.integers(0, n, size=batch, dtype=np.uint32)),
            jax.numpy.asarray(np.arange(batch, dtype=np.int32)),
        )
    deep = deep.delete(jax.numpy.asarray(keys[:64]))

    sec_fold = time_fn(
        lambda: maintenance.fold_oldest(deep, args.fold_k), iters=3
    )
    sec_full = time_fn(lambda: deep.compact(), iters=3)
    rows.append(
        {
            "part": "fold",
            "depth": args.depth,
            "fold_k": args.fold_k,
            "fold_sec": sec_fold,
            "full_compact_sec": sec_full,
            "pause_ratio": sec_fold / sec_full,
        }
    )
    emit(
        "serve_fold",
        sec_fold,
        depth=args.depth,
        fold_k=args.fold_k,
        full_compact_sec=f"{sec_full:.6f}",
        pause_ratio=f"{sec_fold / sec_full:.3f}",
    )

    # ---- 3. smoke: background fold must not stall reads ---------------------
    server = None
    if args.smoke:
        policy = CompactionPolicy(max_delta_depth=64, fold_k=2)  # manual folds
        server = TableServer(table, keys, vals, policy=policy)
        oracle_keys = keys[:32]
        for _ in range(args.depth):
            server.submit_insert(
                rng.integers(0, n, size=batch, dtype=np.uint32),
                np.arange(batch, dtype=np.int32),
            )
        server.submit_delete(keys[n - 64 :])
        server.drain()
        want = np.asarray(server.query_many([oracle_keys])[0][0])

        # warm both read depths so the during-fold loop measures serving,
        # not compilation: current depth, and depth - fold_k (post-fold)
        post = maintenance.fold_oldest(server.current().state, 2)
        server.batcher.query_many(post, [oracle_keys])

        # Up to 3 attempts guard against two benign timing flukes: a fast
        # fold landing before the first read can be issued (nothing to
        # observe), and a GIL-contended single read outlasting a warm fold
        # (stall >= fold_sec without the read path actually blocking).
        # Each retry restores the folded depth with two fresh inserts.
        for attempt in range(3):
            pre_seq = server.current().seqno
            t0 = time.perf_counter()
            t = server.fold_async(k=2)
            reads_during = 0
            stall = 0.0
            while t.is_alive():
                r0 = time.perf_counter()
                counts, seq = server.query_many([oracle_keys])
                dt = time.perf_counter() - r0
                assert seq == pre_seq, (
                    f"torn read: seqno {seq} during fold of {pre_seq}"
                )
                np.testing.assert_array_equal(np.asarray(counts[0]), want)
                reads_during += 1
                stall = max(stall, dt)
            t.join()
            fold_sec = time.perf_counter() - t0
            assert server.current().seqno == pre_seq + 1, "fold did not publish"
            counts, seq = server.query_many([oracle_keys])
            assert seq == pre_seq + 1
            np.testing.assert_array_equal(np.asarray(counts[0]), want)
            if reads_during >= 1 and stall < fold_sec:
                break
            for _ in range(2):  # restore depth for the retry
                server.submit_insert(
                    rng.integers(0, n, size=batch, dtype=np.uint32),
                    np.arange(batch, dtype=np.int32),
                )
            server.drain()
            want = np.asarray(server.query_many([oracle_keys])[0][0])
        assert reads_during >= 1, "no read completed while the fold was in flight"
        assert stall < fold_sec, (
            f"a read ({stall:.3f}s) waited as long as the fold ({fold_sec:.3f}s) "
            "on every attempt: the read path blocked on compaction"
        )
        print(
            f"smoke: {reads_during} reads served during a {fold_sec * 1e3:.0f}ms "
            f"background fold (max read {stall * 1e3:.1f}ms), all at seqno "
            f"{pre_seq}, fold published {pre_seq + 1}; zero read-path stalls"
        )

    if args.json:
        write_bench_json(
            args.json,
            "serve",
            rows,
            snapshot=server.metrics() if server is not None else None,
            devices=d,
            keys=n,
        )


def _open_loop(args) -> None:
    """Async front end under open-loop Poisson arrivals (see module doc, part 4)."""
    import jax
    import numpy as np

    from benchmarks.common import assert_clean_run, emit, write_bench_json
    from repro.core import plans
    from repro.core.table import DistributedHashTable
    from repro.obs import parse_prometheus, render_prometheus
    from repro.obs.registry import HistogramSnapshot
    from repro.obs.tracing import PHASES
    from repro.serve_table import (
        AsyncFrontend,
        CompactionPolicy,
        MicroBatcher,
        TableServer,
    )

    if args.smoke:
        # Rate sized for a single-core worst case: ~4ms/fused exec on one
        # CPU core caps a flush_keys=16 front end near 250 req/s, so 100/s
        # keeps utilization < 50% and the p99 bound meaningful (a retrace
        # costs ~seconds and blows it regardless of queueing noise).
        args.keys = min(args.keys, 1 << 13)
        args.rates = "100"
        args.duration = min(args.duration, 1.5)

    d = len(jax.devices())
    n = args.keys
    rng = np.random.default_rng(23)
    seed_keys = rng.integers(0, n, size=n, dtype=np.uint32)
    seed_vals = np.arange(n, dtype=np.int32)

    write_bucket = max(8, d)
    table = DistributedHashTable(
        table_mesh(),
        ("d",),
        hash_range=n,
        capacity_slack=2.0,
        max_deltas=4,
        tombstone_capacity=max(256, 4 * write_bucket),
    )
    policy = CompactionPolicy(max_delta_depth=2, fold_k=1, tombstone_load=0.9)
    server = TableServer(
        table,
        seed_keys,
        seed_vals,
        policy=policy,
        batcher=MicroBatcher(table, min_bucket=write_bucket),
        write_bucket=write_bucket,
    )
    flush_keys = 2 * write_bucket if args.smoke else 8 * write_bucket
    warm_buckets = tuple(
        write_bucket << i for i in range((flush_keys // write_bucket).bit_length())
    )
    warm = server.warm(buckets=warm_buckets, depths=(0, 1, 2), fold_horizon=2)
    emit(
        "serve_async_warmup",
        warm.compile_seconds,
        entries=warm.entries,
        buckets=",".join(str(b) for b in warm_buckets),
        fold_horizon=warm.fold_horizon,
    )
    cache_size = getattr(plans.exec_query, "_cache_size", None)

    rows = [
        {
            "part": "open_loop_warmup",
            "entries": warm.entries,
            "compile_seconds": warm.compile_seconds,
            "buckets": list(warm.buckets),
            "depths": list(warm.depths),
            "fold_horizon": warm.fold_horizon,
        }
    ]

    # Per-executor device-cost profiles out of the warmup's jaxpr walk —
    # the per-artifact record that the routing stayed inside the paper's
    # two-all-to-all budget at every warmed delta depth.
    profiles = server.batcher.executors.cost_profile()
    for p in profiles:
        rows.append({"part": "executor_cost", **p.as_dict()})
        emit(
            "serve_async_executor_cost",
            0.0,
            kind=p.kind,
            bucket=p.bucket,
            depth=p.depth,
            all_to_alls=p.all_to_alls,
            collective_bytes=p.total_collective_bytes,
        )
    if args.smoke:
        assert profiles, "warmup produced no executor cost profiles"
        for p in profiles:
            assert p.all_to_alls == 2, (
                f"{p.kind} executor (bucket {p.bucket}, depth {p.depth}) uses "
                f"{p.all_to_alls} all-to-alls — fused 2-round budget broken"
            )

    def drive(fe, rate: float, duration: float, write_ops: dict):
        """One open-loop stream; returns (lat, failures, submitted, wall)."""
        lat: list = []
        failures: list = []
        done_lock = threading.Lock()
        t0 = time.perf_counter()
        next_t = t0
        submitted = 0
        while next_t - t0 < duration:
            now = time.perf_counter()
            if now < next_t:
                time.sleep(next_t - now)
            op = write_ops.get(submitted)
            if op is not None:
                (fe.submit_insert if op[0] == "insert" else fe.submit_delete)(
                    op[1], timeout=30.0
                )
            q = rng.choice(seed_keys, size=args.req_keys).astype(np.uint32)
            t_arr = next_t  # intended arrival: open-loop latency epoch

            def _done(fut, t=t_arr):
                dt = time.perf_counter() - t
                with done_lock:
                    if fut.exception() is None:
                        lat.append(dt)
                    else:
                        failures.append(fut.exception())

            fe.submit_query(q, timeout=30.0).add_done_callback(_done)
            submitted += 1
            next_t += rng.exponential(1.0 / rate)
        deadline = time.perf_counter() + 60.0
        while True:
            with done_lock:
                if len(lat) + len(failures) >= submitted:
                    break
            if time.perf_counter() > deadline:
                raise TimeoutError(
                    f"open loop: {submitted - len(lat) - len(failures)} "
                    "responses never resolved"
                )
            time.sleep(0.002)
        server.drain(timeout=60.0)
        return lat, failures, submitted, time.perf_counter() - t0

    def phase_breakdown(before, after) -> dict:
        """Per-phase latency stats from the delta of two registry snapshots."""
        out = {}
        for phase in PHASES:
            a = after.histogram("trace_phase_seconds", {"phase": phase})
            if a is None:
                continue
            b = before.histogram("trace_phase_seconds", {"phase": phase})
            if b is not None and b.count:
                a = HistogramSnapshot(
                    count=a.count - b.count,
                    sum=a.sum - b.sum,
                    min=a.min,
                    max=a.max,
                    bounds=a.bounds,
                    counts=tuple(x - y for x, y in zip(a.counts, b.counts)),
                )
            if not a.count:
                continue
            out[phase] = {
                "count": a.count,
                "mean_ms": a.mean * 1e3,
                "p50_ms": a.p50 * 1e3,
                "p99_ms": a.p99 * 1e3,
            }
        return out

    slo = args.slo_ms / 1e3
    for rate in [float(r) for r in args.rates.split(",")]:
        expected = max(1, int(rate * args.duration))
        # Mixed stream (smoke): writes + a policy-triggered incremental fold
        # land mid-stream through the front end — same op sequence the
        # no-retrace regression test pins down, all inside the warmed grid.
        write_ops = {}
        if args.smoke:
            fresh = rng.integers(n, 2 * n, size=4 * write_bucket, dtype=np.uint32)
            ins = [
                fresh[i * write_bucket : (i + 1) * write_bucket] for i in range(4)
            ]
            write_ops = {
                max(1, expected // 5): ("insert", ins[0]),
                max(2, 2 * expected // 5): ("insert", ins[1]),
                max(3, 3 * expected // 5): ("delete", ins[0][: write_bucket // 2]),
                max(4, 4 * expected // 5): ("insert", ins[2]),
            }

        cache0 = cache_size() if cache_size else None
        snap_before = server.metrics(refresh=False)
        with AsyncFrontend(
            server,
            linger=0.002,
            flush_keys=flush_keys,
            default_deadline=slo,
            write_backlog=32,
        ) as fe:
            lat, failures, submitted, wall = drive(
                fe, rate, args.duration, write_ops
            )
        fe.metrics()  # refresh trace_live / queue-depth gauges post-drain
        snap = server.metrics()  # ONE atomic sample, state gauges refreshed
        st = fe.stats(snapshot=snap)
        wstats = server.stats()
        row = {
            "part": "open_loop",
            "rate_offered": rate,
            "req_keys": args.req_keys,
            "submitted": submitted,
            "completed": len(lat),
            "failed": len(failures),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "p999_ms": float(np.percentile(lat, 99.9) * 1e3),
            "goodput_rps": sum(1 for x in lat if x <= slo) / wall,
            "slo_ms": args.slo_ms,
            "batches_fill": st.batches_fill,
            "batches_due": st.batches_due,
            "aot_hits": wstats.warmup.aot_hits,
            "aot_misses": wstats.warmup.aot_misses,
            "phases": phase_breakdown(snap_before, snap),
        }
        rows.append(row)
        emit(
            "serve_async_open_loop",
            wall,
            rate=rate,
            p50_ms=f"{row['p50_ms']:.3f}",
            p99_ms=f"{row['p99_ms']:.3f}",
            p999_ms=f"{row['p999_ms']:.3f}",
            goodput_rps=f"{row['goodput_rps']:.1f}",
            aot_misses=row["aot_misses"],
        )

        if args.smoke:
            assert not failures, f"{len(failures)} requests failed: {failures[:3]}"
            assert len(lat) == submitted, "lost responses"
            # The shared smoke gate off one snapshot (AOT misses, dropped
            # rows, skew fallbacks, failed requests, live traces, flat jit
            # cache) ...
            assert_clean_run(
                snap, baseline_cache_size=cache0, context=f"rate {rate:.0f}"
            )
            # ... re-asserted through the scrape path an external monitor
            # would use: render the Prometheus text and parse it back.
            scraped = parse_prometheus(render_prometheus(snap))
            assert scraped.get(("trace_live", ()), 0) == 0, (
                "Prometheus export shows live traces after drain"
            )
            assert scraped.get(("serve_dropped_rows", ()), 0) == 0, (
                "Prometheus export shows dropped rows"
            )
            assert scraped.get(("aot_misses_total", ()), 0) == 0, (
                "Prometheus export shows AOT misses"
            )
            assert wstats.folds >= 1, "mixed stream never triggered a fold"
            assert row["p99_ms"] < 500.0, (
                f"p99 {row['p99_ms']:.1f}ms over the smoke bound (500ms): "
                "retrace or read-path stall"
            )
            print(
                f"open-loop smoke: {submitted} requests at {rate:.0f}/s, "
                f"p99 {row['p99_ms']:.1f}ms, {wstats.folds} fold(s), "
                f"0 traces after warmup ({wstats.warmup.aot_hits} AOT hits)"
            )

        # ---- tracing-overhead control pair (read-only, frozen geometry) ----
        # No writes, no folds: both runs serve identical warmed executors,
        # so the only difference is the span bookkeeping itself.  A single
        # run's p99 on a 1-core CI box is scheduler-noise-dominated (the
        # fake 8-device mesh time-slices one core), so each mode runs
        # ``repeats`` times interleaved (control, traced, control, ...) and
        # scores its *best* p99 — the run least disturbed by the scheduler,
        # which is the one that isolates the bookkeeping cost.
        repeats = 3 if args.smoke else 1
        ro = {"control": [], "traced": []}
        for _ in range(repeats):
            for mode, tracing in (("control", False), ("traced", True)):
                with AsyncFrontend(
                    server,
                    linger=0.002,
                    flush_keys=flush_keys,
                    default_deadline=slo,
                    write_backlog=32,
                    tracing=tracing,
                ) as fe2:
                    run = drive(fe2, rate, args.duration, {})
                assert not run[1], f"{mode} run had failures: {run[1][:3]}"
                ro[mode].append(run)

        def best(mode, q):
            return min(
                float(np.percentile(run[0], q) * 1e3) for run in ro[mode]
            )

        c_p50, c_p99 = best("control", 50), best("control", 99)
        t_p50, t_p99 = best("traced", 50), best("traced", 99)
        row2 = {
            "part": "tracing_overhead",
            "rate_offered": rate,
            "control_p50_ms": c_p50,
            "control_p99_ms": c_p99,
            "traced_p50_ms": t_p50,
            "traced_p99_ms": t_p99,
            "overhead_p99_pct": (t_p99 / c_p99 - 1.0) * 100.0 if c_p99 else 0.0,
        }
        rows.append(row2)
        emit(
            "serve_async_tracing_overhead",
            ro["traced"][-1][3],
            rate=rate,
            control_p99_ms=f"{c_p99:.3f}",
            traced_p99_ms=f"{t_p99:.3f}",
            overhead_p99_pct=f"{row2['overhead_p99_pct']:.2f}",
        )
        if args.smoke:
            # < 5% p99 regression, with a 2ms absolute floor so scheduler
            # noise on a 1-core CI box can't fail a microsecond-level cost.
            assert t_p99 <= c_p99 * 1.05 + 2.0, (
                f"tracing overhead too high: p99 {c_p99:.2f}ms -> {t_p99:.2f}ms "
                f"({row2['overhead_p99_pct']:.1f}%, budget 5% + 2ms)"
            )
            print(
                f"tracing overhead: p99 {c_p99:.2f}ms untraced -> {t_p99:.2f}ms "
                f"traced ({row2['overhead_p99_pct']:+.1f}%)"
            )

    if args.json:
        write_bench_json(
            args.json,
            "serve_async",
            rows,
            snapshot=server.metrics(),
            devices=d,
            keys=n,
            slo_ms=args.slo_ms,
        )


if __name__ == "__main__":
    main()
