"""Probe-path benchmark — fingerprint lane vs full-key bisection.

Sweeps the probe+gather path (query multiplicities and plan-executed
retrieve) over the schema grid the fingerprint lane targets: u32x1 (1-lane
keys, fingerprints off by default) and u64x2 (2-lane keys, fingerprints on
by default), each at delta depth 0 and 8, with the fingerprint lane forced
on and off so the two probe layouts run the identical workload.

What to expect: the fingerprint path narrows every bucket window with a
1-lane uint32 bisection before the full-key verification pass, so per
probe step it compares 4 bytes where the u64x2 full-key path compares 8
(the ``probe_lane_bytes`` column).  On TPU that is the memory-bound win;
on this CPU/interpret validation vehicle the fixed-trip bisection cost is
ALU-bound and the two paths land at parity — the committed
``BENCH_probe.json`` documents the measured ratio alongside the bytes
moved per probe step, which is the honest CPU-side scorecard.

``--smoke`` shrinks sizes for CI and **asserts** the fingerprint path is
byte-identical to the full-key path on a mixed workload (build + inserts
+ deletes, hit/miss queries, both schemas) — offsets, values, counts, and
drop counters all equal.  ``--json PATH`` writes the machine-readable
baseline.
"""
import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 20)
    ap.add_argument("--queries", type=int, default=1 << 14)
    ap.add_argument("--dup", type=int, default=4, help="average key multiplicity")
    ap.add_argument("--depths", type=str, default="0,8")
    ap.add_argument("--smoke", action="store_true", help="CI parity run")
    ap.add_argument("--json", type=str, default=None, help="write rows to PATH")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.smoke:
        args.keys = min(args.keys, 1 << 14)
        args.queries = min(args.queries, 1 << 12)
        args.depths = "0,4"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.schema import TableSchema, pack_u64
    from repro.core.table import DistributedHashTable, table_mesh

    depths = [int(x) for x in args.depths.split(",")]
    deepest = max(depths)
    d = len(jax.devices())
    mesh = table_mesh()
    n = (args.keys // d) * d
    nq = args.queries
    rng = np.random.default_rng(11)
    distinct = max(1, n // args.dup)

    def make_keys(key_dtype, size):
        raw = rng.integers(0, distinct, size=size).astype(np.uint64)
        if key_dtype == "uint64":
            # full 64-bit spread so the 2-lane compare path is real work
            return pack_u64(raw | (raw << np.uint64(32)))
        return jnp.asarray(raw.astype(np.uint32))

    rows = []
    for key_dtype in ("uint32", "uint64"):
        sch = TableSchema(key_dtype, 1)
        keys = make_keys(key_dtype, n)
        values = jnp.arange(n, dtype=jnp.int32)
        # hit/miss mix: half the queries re-draw stored content, half miss
        q_hit = make_keys(key_dtype, nq // 2)
        miss = rng.integers(distinct, 2 * distinct, size=nq - nq // 2).astype(np.uint64)
        q_miss = (
            pack_u64(miss | (miss << np.uint64(32)))
            if key_dtype == "uint64"
            else jnp.asarray(miss.astype(np.uint32))
        )
        queries = jnp.concatenate([q_hit, q_miss], axis=0)
        ins_batches = [make_keys(key_dtype, max(64, n // 256)) for _ in range(deepest)]
        dels = keys[:64]

        results = {}
        for fp in (False, True):
            table = DistributedHashTable(
                mesh,
                ("d",),
                hash_range=n,
                capacity_slack=2.0,
                schema=sch,
                max_deltas=max(deepest, 1),
                fingerprint=fp,
            )
            state = table.init(keys, values=values)
            state = state.delete(dels)
            by_depth = {0: state}
            for i, ins in enumerate(ins_batches):
                state = state.insert(ins)
                by_depth[i + 1] = state

            for depth in depths:
                st = by_depth[depth]
                plan = table.plan_retrieve(st, queries)
                res = plan(st, queries)
                assert int(res.num_dropped) == 0, "benchmark capacity sizing bug"
                results[(fp, depth)] = res
                sec_q = time_fn(table.query, st, queries, iters=3)
                sec_r = time_fn(plan, st, queries, iters=3)
                lanes = sch.key_lanes
                row = {
                    "key_dtype": key_dtype,
                    "fingerprint": fp,
                    "depth": depth,
                    "keys": n,
                    "queries": nq,
                    # bytes compared per probe step: the fingerprint layout
                    # bisects a 1-lane uint32 array; the full-key layout
                    # compares every key lane.
                    "probe_lane_bytes": 4 if fp else 4 * lanes,
                    "query_keys_per_sec": nq / sec_q,
                    "retrieve_keys_per_sec": nq / sec_r,
                    "query_sec": sec_q,
                    "retrieve_sec": sec_r,
                }
                rows.append(row)
                emit(
                    "probe",
                    sec_q,
                    key_dtype=key_dtype,
                    fingerprint=fp,
                    depth=depth,
                    query_keys_per_sec=f"{nq / sec_q:.3e}",
                    retrieve_keys_per_sec=f"{nq / sec_r:.3e}",
                )

        # Parity gate: same workload through both probe layouts must agree
        # byte-for-byte (stable sort makes even duplicate-run payload order
        # identical).  Always checked; --smoke exists to run it cheaply.
        for depth in depths:
            a, b = results[(False, depth)], results[(True, depth)]
            for field in ("offsets", "counts", "values", "num_dropped"):
                av, bv = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
                assert np.array_equal(av, bv), (
                    f"fingerprint path diverged: {key_dtype} depth={depth} {field}"
                )
        print(f"parity: {key_dtype} fingerprint path byte-identical at depths {depths}")

    for key_dtype in ("uint32", "uint64"):
        sub = {
            (r["fingerprint"], r["depth"]): r
            for r in rows
            if r["key_dtype"] == key_dtype
        }
        for depth in depths:
            ratio = sub[(False, depth)]["query_sec"] / sub[(True, depth)]["query_sec"]
            print(
                f"{key_dtype} depth={depth}: fingerprint query speedup {ratio:.2f}x "
                f"(probe lane {sub[(True, depth)]['probe_lane_bytes']}B vs "
                f"{sub[(False, depth)]['probe_lane_bytes']}B per compare)"
            )

    if args.json:
        with open(args.json, "w") as f:
            json.dump(
                {
                    "bench": "probe",
                    "devices": d,
                    "keys": n,
                    "queries": nq,
                    "dup": args.dup,
                    "note": (
                        "CPU interpret-mode numbers: fixed-trip bisection is "
                        "ALU-bound here, so fingerprint vs full-key lands at "
                        "parity; probe_lane_bytes records the per-compare "
                        "bytes-moved reduction the lane buys on the "
                        "memory-bound TPU target."
                    ),
                    "rows": rows,
                },
                f,
                indent=2,
            )
            f.write("\n")
        print(f"wrote {args.json}")

    if args.smoke:
        print("smoke: fingerprint/full-key parity asserted on mixed workload")


if __name__ == "__main__":
    main()
