"""YCSB benchmark — the KV-cache subsystem under A–F mixed workloads.

The cloud-serving measurement for the upsert/TTL serving stack: each
workload letter (see :mod:`repro.cache.workload`) streams zipfian-skewed
reads and insert-or-replace writes through the AOT-warmed
:class:`TableServer` behind an :class:`AsyncFrontend`, closed-loop (ops
are offered as fast as the front end admits them — the throughput mode of
the YCSB client; latency percentiles are completion minus submission, so
queueing counts against the server).

Mapping onto the serving stack:

* **read**   — count-probe requests of ``--req-keys`` keys through
  ``submit_query`` (the fused 2-all-to-all read path; value
  materialization is benched separately in ``bench_retrieve``).
* **update / insert / rmw-write** — coalesced into ``write_bucket``-sized
  buffers and applied via ``submit_upsert`` (delete-prior + bucket-padded
  delta build, keep-last dedup at admission).  RMW issues the read half
  first, same keys.
* **scan** — one request per scan op: a contiguous multiget of
  ``--scan-len`` insertion-order keys (the hashed-store reading of
  YCSB-E's short ranges).

Write submissions are pre-planned, so the exact number of incremental
folds the compaction policy will run is known up front and the AOT warmup
covers every fold-grown base geometry the run can reach
(``fold_horizon``).  ``--smoke`` (CI) then *asserts* the serving
invariants: zero failed/lost requests, zero dropped rows (delta builds
and tombstone buffer), zero skew fallbacks, and zero live traces — every
read batch hits the warmed executor grid and the jit dispatch cache stays
flat across all six letters.

Output: one row per letter (throughput, read p50/p99, op counts) into
``BENCH_ycsb.json`` and ``BENCH,`` CSV lines for the orchestrator.
"""
from __future__ import annotations

import argparse
import threading
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 13, help="loaded population")
    ap.add_argument("--ops", type=int, default=4000, help="ops per workload letter")
    ap.add_argument("--theta", type=float, default=0.99, help="zipfian skew")
    ap.add_argument("--batch", type=int, default=128, help="generator op-batch size")
    ap.add_argument("--scan-len", type=int, default=16)
    ap.add_argument("--req-keys", type=int, default=8, help="keys per read request")
    ap.add_argument("--workloads", type=str, default="A,B,C,D,E,F")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--smoke", action="store_true", help="CI invariant-assertion run")
    ap.add_argument("--json", type=str, default="BENCH_ycsb.json")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.smoke:
        args.keys = min(args.keys, 1 << 10)
        args.ops = min(args.ops, 600)
        args.batch = min(args.batch, 64)
        args.scan_len = min(args.scan_len, 8)

    import jax
    import numpy as np

    from benchmarks.common import assert_clean_run, emit, write_bench_json
    from repro.cache.workload import WORKLOADS, YCSBWorkload, key_of
    from repro.core import plans
    from repro.core.table import DistributedHashTable, table_mesh
    from repro.serve_table import (
        AsyncFrontend,
        CompactionPolicy,
        MicroBatcher,
        TableServer,
    )

    d = len(jax.devices())
    n = args.keys
    letters = [w.strip().upper() for w in args.workloads.split(",") if w.strip()]

    # Write geometry: big buckets keep the fold count (one per coalesced
    # upsert submission at steady state) small enough to pre-warm every
    # fold-grown base the run reaches; one read flush bucket keeps the
    # executor grid linear in the fold horizon.
    wb = 16 * d if args.smoke else 32 * d
    wb = max(8, 1 << (wb - 1).bit_length())
    flush_keys = wb
    if args.scan_len > flush_keys:
        raise SystemExit("--scan-len must fit one flush (raise --keys tier)")

    # ---- pre-plan every letter's op script -----------------------------------
    # ('q', keys) read/scan requests; ('w', keys, values) coalesced upsert
    # submissions of at most wb keys.  Pre-planning pins the exact write-
    # submission count, which pins the fold count, which sizes the warmup.
    scripts = {}
    total_write_submits = 0
    for letter in letters:
        w = YCSBWorkload(
            WORKLOADS[letter],
            n,
            theta=args.theta,
            batch=args.batch,
            scan_len=args.scan_len,
            seed=args.seed,
        )
        script = []
        counts = {k: 0 for k in ("read", "update", "insert", "scan", "rmw")}
        buf_k, buf_v, buf_n = [], [], 0

        def flush_writes():
            nonlocal buf_k, buf_v, buf_n
            if buf_n:
                script.append(
                    ("w", np.concatenate(buf_k), np.concatenate(buf_v))
                )
                buf_k, buf_v, buf_n = [], [], 0

        for kind, keys, vals in w.batches(args.ops):
            if kind == "scan":
                counts["scan"] += keys.shape[0] // args.scan_len
                for i in range(0, keys.shape[0], args.scan_len):
                    script.append(("q", keys[i : i + args.scan_len]))
                continue
            if kind == "read" or kind == "rmw":
                counts[kind] += keys.shape[0]
                for i in range(0, keys.shape[0], args.req_keys):
                    script.append(("q", keys[i : i + args.req_keys]))
                if kind == "read":
                    continue
            else:
                counts[kind] += keys.shape[0]
            # update / insert / rmw write half: coalesce up to wb keys
            off = 0
            while off < keys.shape[0]:
                take = min(wb - buf_n, keys.shape[0] - off)
                buf_k.append(keys[off : off + take])
                buf_v.append(vals[off : off + take])
                buf_n += take
                off += take
                if buf_n == wb:
                    flush_writes()
        flush_writes()
        scripts[letter] = (script, counts)
        total_write_submits += sum(1 for op in script if op[0] == "w")

    # Exact fold forecast: the policy folds one layer per upsert submission
    # once the ring holds max_delta_depth deltas.
    max_depth = 2
    depth = folds = 0
    for _ in range(total_write_submits):
        if depth >= max_depth:
            folds += 1
            depth -= 1
        depth += 1
    fold_horizon = folds + 2  # slack for count drift

    # ---- table + server + AOT warmup ----------------------------------------
    table = DistributedHashTable(
        table_mesh(),
        ("d",),
        hash_range=max(n, 1024),
        capacity_slack=2.0,
        max_deltas=4,
        tombstone_capacity=max(256, 4 * wb),
    )
    policy = CompactionPolicy(
        max_delta_depth=max_depth, fold_k=1, tombstone_load=0.9
    )
    server = TableServer(
        table,
        key_of(np.arange(n)),
        np.arange(n, dtype=np.int32),
        policy=policy,
        batcher=MicroBatcher(table, min_bucket=wb),
        write_bucket=wb,
    )
    warm_buckets = tuple(
        wb << i for i in range((flush_keys // wb).bit_length())
    )
    warm = server.warm(
        buckets=warm_buckets, depths=(0, 1, 2), fold_horizon=fold_horizon
    )
    emit(
        "ycsb_warmup",
        warm.compile_seconds,
        entries=warm.entries,
        buckets=",".join(str(b) for b in warm_buckets),
        fold_horizon=fold_horizon,
        write_submits=total_write_submits,
    )
    cache_size = getattr(plans.exec_query, "_cache_size", None)
    cache0 = cache_size() if cache_size else None

    # ---- run phase -----------------------------------------------------------
    rows = [
        {
            "part": "warmup",
            "entries": warm.entries,
            "compile_seconds": warm.compile_seconds,
            "buckets": list(warm_buckets),
            "fold_horizon": fold_horizon,
            "write_submits_planned": total_write_submits,
        }
    ]
    for letter in letters:
        script, counts = scripts[letter]
        lat: list = []
        failures: list = []
        done_lock = threading.Lock()
        submitted = 0

        with AsyncFrontend(
            server, linger=0.002, flush_keys=flush_keys, write_backlog=32
        ) as fe:
            t0 = time.perf_counter()
            for op in script:
                if op[0] == "w":
                    fe.submit_upsert(op[1], op[2], timeout=60.0)
                    continue
                t_sub = time.perf_counter()

                def _done(fut, t=t_sub):
                    dt = time.perf_counter() - t
                    with done_lock:
                        if fut.exception() is None:
                            lat.append(dt)
                        else:
                            failures.append(fut.exception())

                fe.submit_query(op[1], timeout=60.0).add_done_callback(_done)
                submitted += 1
            deadline = time.perf_counter() + 120.0
            while True:
                with done_lock:
                    if len(lat) + len(failures) >= submitted:
                        break
                if time.perf_counter() > deadline:
                    raise TimeoutError(
                        f"workload {letter}: "
                        f"{submitted - len(lat) - len(failures)} read "
                        "responses never resolved"
                    )
                time.sleep(0.002)
            server.drain(timeout=120.0)
            wall = time.perf_counter() - t0

        fe.metrics()  # refresh trace_live / queue-depth gauges post-drain
        snap = server.metrics()  # ONE atomic registry sample per letter
        wstats = server.stats()
        row = {
            "part": "workload",
            "workload": letter,
            "ops": args.ops,
            "op_counts": counts,
            "read_requests": submitted,
            "throughput_ops_s": args.ops / wall,
            "read_p50_ms": float(np.percentile(lat, 50) * 1e3) if lat else None,
            "read_p99_ms": float(np.percentile(lat, 99) * 1e3) if lat else None,
            "wall_seconds": wall,
            "folds_total": wstats.folds,
            "full_compacts_total": wstats.full_compacts,
            "aot_misses_total": wstats.warmup.aot_misses,
            "dropped_rows": wstats.shadow.num_dropped,
        }
        rows.append(row)
        emit(
            "ycsb",
            wall,
            workload=letter,
            ops=args.ops,
            throughput_ops_s=f"{row['throughput_ops_s']:.1f}",
            read_p50_ms=(
                f"{row['read_p50_ms']:.3f}" if lat else "n/a"
            ),
            read_p99_ms=(
                f"{row['read_p99_ms']:.3f}" if lat else "n/a"
            ),
            aot_misses=row["aot_misses_total"],
        )

        if args.smoke:
            assert not failures, (
                f"workload {letter}: {len(failures)} reads failed: "
                f"{failures[:3]}"
            )
            assert len(lat) == submitted, f"workload {letter}: lost responses"
            # Shared smoke gate (zero AOT misses, zero dropped rows, zero
            # skew fallbacks, zero live traces, flat jit cache) off ONE
            # registry snapshot; only the letter-specific fold-forecast
            # check stays inline.
            assert_clean_run(
                snap,
                baseline_cache_size=cache0,
                context=f"workload {letter}",
            )
            assert wstats.full_compacts == 0, (
                f"workload {letter}: {wstats.full_compacts} full compacts — "
                "the fold forecast missed (geometry left the warmed grid)"
            )

    if args.smoke:
        wstats = server.stats()
        print(
            f"ycsb smoke: {len(letters)} workloads x {args.ops} ops, "
            f"{wstats.folds} folds inside a horizon of {fold_horizon}, "
            f"0 dropped rows, 0 live traces "
            f"({wstats.warmup.aot_hits} AOT read hits)"
        )

    if args.json:
        write_bench_json(
            args.json,
            "ycsb",
            rows,
            snapshot=server.metrics(),
            devices=d,
            keys=n,
            ops_per_workload=args.ops,
            theta=args.theta,
            write_bucket=wb,
            flush_keys=flush_keys,
        )


if __name__ == "__main__":
    main()
