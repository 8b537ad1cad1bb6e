"""Observability — per-phase latency breakdown, metrics export, traces.

One :class:`~repro.obs.registry.MetricsRegistry` per server collects
every counter, gauge, and latency histogram the serving stack produces:
the batcher, the AOT executor grid, maintenance folds, and each
:class:`AsyncFrontend`.  This example drives a short request stream and
then shows the three read sides:

* the **per-phase latency breakdown** — every traced request records
  admission / linger / dispatch / device / scatter durations into
  ``trace_phase_seconds{phase=...}`` histograms;
* the **device-cost profile** — the jaxpr-walking accountant attached to
  warmup reports collectives and bytes per compiled executor (the fused
  read path must show exactly 2 all-to-alls at every delta depth);
* the **exporters** — Prometheus text for scraping, JSONL for artifact
  stamping, and the bounded trace ring dumped as one JSON object per
  request.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/observability.py
"""
import tempfile

import numpy as np

from repro.core.table import DistributedHashTable, table_mesh
from repro.obs import PHASES, render_prometheus
from repro.serve_table import (
    AsyncFrontend,
    CompactionPolicy,
    MicroBatcher,
    TableServer,
)


def main() -> None:
    rng = np.random.default_rng(0)
    mesh = table_mesh()
    n = 1 << 12

    table = DistributedHashTable(
        mesh, ("d",), hash_range=n, max_deltas=4, tombstone_capacity=256
    )
    keys = rng.integers(0, n, size=n, dtype=np.uint32)
    server = TableServer(
        table,
        keys,
        np.arange(n, dtype=np.int32),
        policy=CompactionPolicy(max_delta_depth=2, fold_k=1),
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=8,
    )

    # Warmup profiles each compiled executor's collective footprint.
    warm = server.warm(buckets=(8, 16), depths=(0, 1, 2), fold_horizon=1)
    print(f"warmed {warm.entries} executables; per-executor device cost:")
    for p in warm.profiles:
        print(
            f"  {p.kind:5s} bucket={p.bucket:<3d} depth={p.depth}  "
            f"all_to_alls={p.all_to_alls}  "
            f"collective_bytes={p.total_collective_bytes}  "
            f"flop/byte={p.flop_per_byte}"
        )

    # ---- a traced request stream -------------------------------------------
    with AsyncFrontend(server, linger=0.002, flush_keys=16) as fe:
        futs = [
            fe.submit_query(rng.choice(keys, size=8).astype(np.uint32))
            for _ in range(48)
        ]
        fe.submit_insert(rng.integers(n, 2 * n, size=16, dtype=np.uint32))
        server.drain()
        for f in futs:
            f.result(timeout=10.0)

        # ---- per-phase latency breakdown -----------------------------------
        snap = fe.metrics()  # ONE atomic sample of the shared registry
        print("\nper-phase latency (where each request's time went):")
        for phase in PHASES:
            h = snap.histogram("trace_phase_seconds", {"phase": phase})
            print(
                f"  {phase:10s} n={h.count:<4d} mean={h.mean * 1e3:7.3f}ms  "
                f"p50={h.p50 * 1e3:7.3f}ms  p99={h.p99 * 1e3:7.3f}ms"
            )
        total = snap.histogram("request_latency_seconds")
        print(
            f"  {'total':10s} n={total.count:<4d} "
            f"mean={total.mean * 1e3:7.3f}ms  p50={total.p50 * 1e3:7.3f}ms  "
            f"p99={total.p99 * 1e3:7.3f}ms"
        )

        # ---- the trace ring: per-request records, JSONL-dumpable -----------
        recent = fe.tracer.recent()
        t = recent[-1]
        marks = t.durations()
        print(
            f"\nlast trace (id {t.trace_id}, {t.size} keys, bucket "
            f"{t.bucket}): "
            + "  ".join(f"{ph}={marks[ph] * 1e3:.3f}ms" for ph in marks)
        )
        with tempfile.NamedTemporaryFile("r", suffix=".jsonl") as f_tmp:
            wrote = fe.tracer.dump_jsonl(f_tmp.name)
            print(f"dumped {wrote} trace records to {f_tmp.name}")

    # ---- exporters ----------------------------------------------------------
    snap = server.metrics()
    text = render_prometheus(snap)
    wanted = (
        "serve_reads_total",
        "aot_hits_total",
        "aot_misses_total",
        "executor_all_to_alls",
        "frontend_completed_total",
        "maintenance_folds_total",
    )
    print("\nPrometheus export (selected lines):")
    for line in text.splitlines():
        if line.startswith(wanted):
            print(f"  {line}")
    print(
        f"\nfull export: {len(text.splitlines())} lines, "
        f"{len(snap.as_dict())} metrics — also available as "
        "render_jsonl(snap) / write_bench_json(..., snapshot=snap)"
    )


if __name__ == "__main__":
    main()
