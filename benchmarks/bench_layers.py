"""Layered-read benchmark — throughput vs delta-ring depth, fused vs legacy.

The single-route issue's acceptance metric: with partition-coherent deltas
and fused routing, query/retrieve cost must be ~flat in the delta depth L
(one dispatch + one return per op), where the legacy per-layer path pays
one routing round per layer (~L× the collectives, ~L× the latency).

For each depth L in ``--depths`` the same base + insert history is read
through both routings (``fused_routing=None`` vs ``False`` on otherwise
identical tables) with plan-executed retrieve (explicit caps, no planning
sync in the timed region).

``--smoke`` shrinks sizes/depths to a CI-budget run (~30s) and **asserts**
the fused path's collective count is depth-independent (a deterministic
jaxpr check — wall-clock on shared CI runners is too noisy to gate on), so
a routing-round regression fails the step loudly.  ``--json PATH`` records
the rows machine-readably (the committed ``BENCH_layers.json`` baseline).
"""
import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 17)
    ap.add_argument("--queries", type=int, default=1 << 15)
    ap.add_argument("--insert-batch", type=int, default=1 << 12)
    ap.add_argument("--depths", type=str, default="0,1,2,4,8")
    ap.add_argument("--smoke", action="store_true", help="~30s CI smoke run")
    ap.add_argument("--json", type=str, default=None, help="write rows to PATH")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.smoke:
        args.keys = min(args.keys, 1 << 14)
        args.queries = min(args.queries, 1 << 12)
        args.insert_batch = min(args.insert_batch, 1 << 9)
        args.depths = "0,2,4"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.table import DistributedHashTable, table_mesh

    depths = [int(x) for x in args.depths.split(",")]
    d = len(jax.devices())
    mesh = table_mesh()
    n, nq, batch = args.keys, args.queries, args.insert_batch
    rng = np.random.default_rng(7)

    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    queries = jnp.asarray(rng.integers(0, n, size=nq, dtype=np.uint32))
    ins_batches = [
        jnp.asarray(rng.integers(0, n, size=batch, dtype=np.uint32))
        for _ in range(max(depths))
    ]
    dels = jnp.asarray(rng.integers(0, n, size=64, dtype=np.uint32))

    rows = []
    states_by_mode = {}
    for mode, fused_routing in [("fused", None), ("legacy", False)]:
        table = DistributedHashTable(
            mesh,
            ("d",),
            hash_range=n,
            capacity_slack=2.0,
            max_deltas=max(max(depths), 1),
            fused_routing=fused_routing,
        )
        state = table.init(keys)
        state = state.delete(dels)  # tombstone masking on the timed path
        by_depth = {0: state}
        for i, ins in enumerate(ins_batches):
            state = state.insert(ins)
            by_depth[i + 1] = state
        states_by_mode[mode] = (table, by_depth)

        for depth in depths:
            st = by_depth[depth]
            plan = table.plan_retrieve(st, queries)
            res = plan(st, queries)
            assert int(res.num_dropped) == 0, "benchmark capacity sizing bug"
            sec_q = time_fn(table.query, st, queries, iters=3)
            sec_r = time_fn(plan, st, queries, iters=3)
            row = {
                "mode": mode,
                "depth": depth,
                "layers": depth + 1,
                "query_keys_per_sec": nq / sec_q,
                "retrieve_keys_per_sec": nq / sec_r,
                "query_sec": sec_q,
                "retrieve_sec": sec_r,
            }
            rows.append(row)
            emit(
                "layers",
                sec_r,
                mode=mode,
                depth=depth,
                query_keys_per_sec=f"{nq / sec_q:.3e}",
                retrieve_keys_per_sec=f"{nq / sec_r:.3e}",
            )

    if args.json:
        with open(args.json, "w") as f:
            json.dump(
                {
                    "bench": "layers",
                    "devices": d,
                    "keys": n,
                    "queries": nq,
                    "insert_batch": batch,
                    "rows": rows,
                },
                f,
                indent=2,
            )
        print(f"wrote {args.json}")

    deepest = max(depths)
    if deepest > 0:
        by = {(r["mode"], r["depth"]): r for r in rows}
        fused_ratio = (
            by[("fused", deepest)]["retrieve_sec"]
            / by[("fused", 0)]["retrieve_sec"]
        )
        legacy_ratio = (
            by[("legacy", deepest)]["retrieve_sec"]
            / by[("legacy", 0)]["retrieve_sec"]
        )
        print(
            f"retrieve slowdown at depth {deepest}: fused {fused_ratio:.2f}x, "
            f"legacy {legacy_ratio:.2f}x"
        )

    # Smoke guard (deterministic, unlike CI wall-clock): the fused path's
    # collective count must not grow with the delta depth.
    if args.smoke and deepest > 0:
        from repro.core import plans
        from repro.obs.profiling import count_primitive

        table, by_depth = states_by_mode["fused"]
        a2a = {}
        for depth in (0, deepest):
            jx = jax.make_jaxpr(
                lambda s, q: plans.exec_retrieve(
                    table, s, q, out_capacity=1024, seg_capacity=1024
                )
            )(by_depth[depth], queries)
            a2a[depth] = count_primitive(jx.jaxpr, "all_to_all")
        assert a2a[deepest] == a2a[0], (
            f"fused routing regressed: depth-{deepest} retrieve traces "
            f"{a2a[deepest]} all_to_alls vs {a2a[0]} at depth 0"
        )
        print(
            f"smoke: fused retrieve all_to_all count depth-independent "
            f"({a2a[0]} at depth 0 and depth {deepest})"
        )


if __name__ == "__main__":
    main()
