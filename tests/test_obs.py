"""Observability layer — registry, exporters, tracing, device-cost accounting.

Four layers under test:

* **Registry semantics**: get-or-create identity, one-snapshot
  consistency, histogram quantiles off log buckets (interpolated,
  clamped to observed min/max), type-conflict rejection.
* **Exporters**: the Prometheus text render must round-trip through the
  scrape-side parser (the same path the CI smoke gates use), and the
  JSONL render must emit one valid JSON object per metric with the stamp
  merged in.
* **Tracing**: phase marks -> durations, the bounded ring, the
  ``live()`` leak detector, and the disabled-tracer fast path; host
  spans (nesting, per-thread parents, compile children, the profiler
  annotation) and the spans of the table, the plans and the server.
* **Accounting + integration** (mesh): the jaxpr collective accountant
  independently re-confirms the fused two-all-to-all budget at every
  delta depth; ``TableServer.stats()`` is a registry view (no parallel
  counters to drift); the AOT warmup hit/miss discipline is asserted
  through the *metrics API* on a mixed bucket/insert/fold stream; the
  KV cache and maintenance fold recorder feed the same registry.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import maintenance, plans
from repro.core.table import DistributedHashTable
from repro.obs import (
    PHASES,
    MetricsRegistry,
    Tracer,
    collective_profile,
    process_tracer,
    parse_prometheus,
    profile_executor,
    render_jsonl,
    render_prometheus,
)
from repro.serve_table import (
    AsyncFrontend,
    CompactionPolicy,
    MicroBatcher,
    TableServer,
)

# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_counter_monotone_and_get_or_create():
    reg = MetricsRegistry()
    c1 = reg.counter("requests_total", help="x")
    c2 = reg.counter("requests_total")
    assert c1 is c2  # get-or-create: same instrument
    c1.inc()
    c1.inc(4)
    assert c2.value == 5
    with pytest.raises(ValueError):
        c1.inc(-1)
    # Distinct label sets are distinct instruments under one name.
    a = reg.counter("by_kind_total", labels={"kind": "a"})
    b = reg.counter("by_kind_total", labels={"kind": "b"})
    assert a is not b
    a.inc(2)
    snap = reg.snapshot()
    assert snap.value("by_kind_total", {"kind": "a"}) == 2
    assert snap.value("by_kind_total", {"kind": "b"}) == 0
    assert snap.value("absent_total", default=-1) == -1


def test_type_conflict_rejected():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_gauge_set_add():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(3)
    g.add(2)
    assert reg.snapshot().value("depth") == 5


def test_histogram_quantiles_single_value():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    h.observe(0.017)
    s = h.snapshot()
    # One observation: every quantile clamps to that value.
    assert s.count == 1
    assert s.p50 == pytest.approx(0.017)
    assert s.p99 == pytest.approx(0.017)
    assert s.p999 == pytest.approx(0.017)
    assert s.mean == pytest.approx(0.017)


def test_histogram_quantiles_spread():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    vals = [0.001] * 98 + [0.5, 1.0]
    for v in vals:
        h.observe(v)
    s = h.snapshot()
    assert s.count == 100
    assert s.sum == pytest.approx(sum(vals))
    assert s.min == pytest.approx(0.001)
    assert s.max == pytest.approx(1.0)
    # p50 sits in the 1ms bucket; p999 reaches into the tail.
    assert s.p50 == pytest.approx(0.001, rel=0.5)
    assert s.p999 >= 0.5
    assert s.quantile(1.0) == pytest.approx(1.0)


def test_histogram_custom_bounds_sorted():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="sorted"):
        reg.histogram("bad", bounds=(2.0, 1.0))


def test_snapshot_is_atomic_view():
    reg = MetricsRegistry()
    c = reg.counter("a_total")
    h = reg.histogram("b_seconds")
    c.inc(7)
    h.observe(0.25)
    snap = reg.snapshot()
    c.inc(100)  # after the sample: must not leak into it
    h.observe(9.0)
    assert snap.value("a_total") == 7
    assert snap.histogram("b_seconds").count == 1
    d = snap.as_dict()
    assert d["a_total"] == 7
    assert d["b_seconds"]["count"] == 1


def test_snapshot_labels_of_and_nested_dict():
    reg = MetricsRegistry()
    reg.counter("folds_total", labels={"kind": "fold"}).inc(3)
    reg.counter("folds_total", labels={"kind": "full"}).inc(1)
    snap = reg.snapshot()
    kinds = {lab["kind"] for lab in snap.labels_of("folds_total")}
    assert kinds == {"fold", "full"}
    assert snap.as_dict()["folds_total"] == {"kind=fold": 3, "kind=full": 1}


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="Requests.").inc(42)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_seconds", labels={"phase": "device"})
    for v in (0.001, 0.004, 0.25):
        h.observe(v)
    text = render_prometheus(reg)
    assert "# HELP reqs_total Requests." in text
    assert "# TYPE lat_seconds histogram" in text
    scraped = parse_prometheus(text)
    assert scraped[("reqs_total", ())] == 42
    assert scraped[("depth", ())] == 3
    assert scraped[("lat_seconds_count", (("phase", "device"),))] == 3
    assert scraped[("lat_seconds_sum", (("phase", "device"),))] == pytest.approx(
        0.255
    )
    # Cumulative buckets: monotone, +Inf bucket equals the count.
    buckets = sorted(
        (dict(lk)["le"], v)
        for (name, lk) in scraped
        if name == "lat_seconds_bucket"
        for v in [scraped[(name, lk)]]
    )
    assert scraped[("lat_seconds_bucket", (("le", "+Inf"), ("phase", "device")))] == 3
    cums = [
        v
        for (name, lk), v in scraped.items()
        if name == "lat_seconds_bucket"
    ]
    assert max(cums) == 3
    assert buckets  # at least one finite bucket rendered


def test_jsonl_render_stamped(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total").inc(2)
    reg.histogram("b_seconds").observe(0.5)
    out = render_jsonl(reg, run="unit", ts=123)
    recs = [json.loads(line) for line in out.strip().splitlines()]
    assert {r["metric"] for r in recs} == {"a_total", "b_seconds"}
    assert all(r["run"] == "unit" and r["ts"] == 123 for r in recs)
    hist = next(r for r in recs if r["metric"] == "b_seconds")
    assert hist["count"] == 1 and hist["p50"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_tracer_phases_histograms_and_ring():
    clock = FakeClock()
    reg = MetricsRegistry()
    tr = Tracer(reg, ring=2, clock=clock)
    for i in range(3):
        clock.t = i * 1.0
        t = tr.start(size=4)
        assert tr.live() == 1
        for j, phase in enumerate(PHASES):
            t.mark(phase, i * 1.0 + 0.01 * (j + 1))
        tr.finish(t)
        assert tr.live() == 0
    snap = reg.snapshot()
    for phase in PHASES:
        h = snap.histogram("trace_phase_seconds", {"phase": phase})
        assert h.count == 3
        assert h.p50 == pytest.approx(0.01, rel=1e-6)
    total = snap.histogram("request_latency_seconds")
    assert total.count == 3
    assert total.p50 == pytest.approx(0.05, rel=1e-6)
    assert snap.value("traces_recorded_total") == 3
    # Ring is bounded: only the 2 most recent traces survive.
    recent = tr.recent()
    assert [t.trace_id for t in recent] == [1, 2]


def test_trace_durations_contiguous_and_clamped():
    clock = FakeClock()
    tr = Tracer(MetricsRegistry(), clock=clock)
    t = tr.start()
    t.mark("admission", 0.1)
    t.mark("linger", 0.3)
    t.mark("dispatch", 0.2)  # clock skew: must clamp, not go negative
    d = t.durations()
    assert d["admission"] == pytest.approx(0.1)
    assert d["linger"] == pytest.approx(0.2)
    assert d["dispatch"] == 0.0
    assert t.total == pytest.approx(0.3)
    assert "device" not in d  # unmarked phases are absent, not zero


def test_tracer_abandon_and_disabled(tmp_path):
    reg = MetricsRegistry()
    tr = Tracer(reg, enabled=True)
    t = tr.start()
    tr.abandon(t)
    assert tr.live() == 0
    assert reg.snapshot().value("traces_recorded_total") == 0  # not recorded
    off = Tracer(MetricsRegistry(), enabled=False)
    assert off.start() is None
    off.finish(None)  # no-ops, no raise
    off.abandon(None)
    # dump_jsonl appends completed traces.
    t2 = tr.start(size=2)
    t2.mark("admission", t2.t0 + 0.001)
    tr.finish(t2)
    path = tmp_path / "traces.jsonl"
    assert tr.dump_jsonl(str(path)) == 1
    rec = json.loads(path.read_text().strip())
    assert rec["size"] == 2 and "admission" in rec["phases"]


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records entries."""

    def __init__(self):
        self.entered = []

    def __call__(self, name):
        log = self.entered

        class _Annotation:
            def __enter__(self):
                log.append(name)

            def __exit__(self, *exc):
                return None

        return _Annotation()


@pytest.fixture
def annotations(monkeypatch):
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def test_tracer_span_nests_records_and_annotates(annotations):
    clock = FakeClock()
    reg = MetricsRegistry()
    tr = Tracer(reg, ring=8, clock=clock)
    with tr.span("table.build") as outer:
        clock.t = 1.0
        with tr.span("table.build.pack") as inner:
            clock.t = 1.5
        clock.t = 4.0
    assert inner.seconds == pytest.approx(0.5)
    assert outer.seconds == pytest.approx(4.0)
    spans = [r.as_dict() for r in tr.recent()]
    assert [(d["span"], d["parent"]) for d in spans] == [
        ("table.build.pack", "table.build"),
        ("table.build", None),
    ]
    assert spans[1]["start"] == 0.0 and spans[1]["end"] == 4.0
    snap = reg.snapshot()
    h = snap.histogram("span_seconds", {"span": "table.build"})
    assert (h.count, h.sum) == (1, pytest.approx(4.0))
    assert snap.histogram("span_seconds", {"span": "table.build.pack"}).sum == 0.5
    assert annotations.entered == ["table.build", "table.build.pack"]
    # annotate(): a profiler annotation only, nothing recorded
    with tr.annotate("frontend.wait"):
        pass
    assert annotations.entered[-1] == "frontend.wait"
    assert len(tr.recent()) == 2


def test_tracer_span_parent_is_per_thread(annotations):
    tr = Tracer(MetricsRegistry())
    opened = threading.Event()
    release = threading.Event()

    def worker():
        with tr.span("server.fold"):
            opened.set()
            release.wait(5)

    t = threading.Thread(target=worker)
    with tr.span("server.warm"):
        t.start()
        assert opened.wait(5)
        with tr.span("plan.query"):
            pass
        release.set()
        t.join()
    parents = {r.name: r.parent for r in tr.recent()}
    assert parents == {"server.fold": None, "plan.query": "server.warm", "server.warm": None}


def test_tracer_span_attributes_compiles_to_the_innermost_span(annotations):
    tr = Tracer(MetricsRegistry())
    x = jnp.arange(7, dtype=jnp.int32)
    with tr.span("plan.join"):
        with tr.span("table.build.run"):
            jax.jit(lambda v: v * 5 + 3)(x).block_until_ready()  # a new function
    records = tr.recent()
    children = {r.name for r in records if r.parent == "table.build.run"}
    assert {"jax.trace", "jax.lower", "jax.compile"} <= children
    assert [r.name for r in records if r.parent == "plan.join"] == ["table.build.run"]
    run = next(r for r in records if r.name == "table.build.run")
    for r in records:
        if r.parent == "table.build.run":
            assert run.start <= r.start <= r.end <= run.end + 1e-3
    snap = tr.registry.snapshot()
    assert snap.histogram("span_seconds", {"span": "jax.compile"}).count >= 1


def test_tracer_span_disabled_records_nothing(annotations):
    reg = MetricsRegistry()
    tr = Tracer(reg, enabled=False)
    with tr.span("plan.join") as span:
        jax.jit(lambda v: v - 11)(jnp.arange(3)).block_until_ready()
    with tr.annotate("frontend.dispatch"):
        pass
    assert span.seconds >= 0.0  # the instruments that read it still can
    assert tr.recent() == []
    assert reg.snapshot().labels_of("span_seconds") == []
    assert annotations.entered == []


def test_table_and_plan_spans_in_the_process_tracer(mesh8, annotations):
    tracer = process_tracer()
    assert tracer.enabled is False  # off unless a caller turns it on
    table = _small_table(mesh8)
    keys = np.arange(1, 257, dtype=np.uint32)
    before = len(tracer.recent())
    tracer.enabled = True
    try:
        state = table.init(keys)
        plan = table.plan_join(num_queries=8, out_capacity=64, seg_capacity=64)
        plan(state, keys[:8])
    finally:
        tracer.enabled = False
    records = tracer.recent()[before:]
    parents = {r.name: r.parent for r in records if not r.name.startswith("jax.")}
    assert parents == {
        "table.build.pack": "table.build",
        "table.build.run": "table.build",
        "table.build": None,
        "plan.join": None,
    }
    # the build's compile happened inside the run span
    assert any(r.name == "jax.compile" and r.parent == "table.build.run" for r in records)
    assert annotations.entered[:3] == ["table.build", "table.build.pack", "table.build.run"]
    n = len(tracer.recent())
    plan(state, keys[8:16])  # disabled again: nothing recorded
    assert len(tracer.recent()) == n


def test_fold_span_feeds_the_fold_histogram(mesh8, annotations):
    table = _small_table(mesh8)
    server = TableServer(
        table,
        np.arange(1, 129, dtype=np.uint32),
        policy=CompactionPolicy(max_delta_depth=2, fold_k=1),
        write_bucket=8,
    )
    tracer = process_tracer()
    spans = tracer.registry.histogram("span_seconds", labels={"span": "server.fold"})
    count0, sum0 = spans.snapshot().count, spans.snapshot().sum
    tracer.enabled = True
    try:
        for k in (9991, 9992, 9993):
            server.submit_insert(np.array([k], dtype=np.uint32))
            server.step()  # the third step folds first
    finally:
        tracer.enabled = False
    fold = server.metrics().histogram("maintenance_fold_seconds", {"kind": "fold"})
    span = spans.snapshot()
    assert fold.count == span.count - count0 == 1
    assert fold.sum == pytest.approx(span.sum - sum0, rel=1e-12)  # one reading feeds both
    assert "serve_last_fold_seconds" not in server.metrics().types
    assert not hasattr(server.stats(), "last_fold_seconds")


# ---------------------------------------------------------------------------
# The shared CI gate
# ---------------------------------------------------------------------------


def test_assert_clean_run_gate():
    from benchmarks.common import assert_clean_run

    reg = MetricsRegistry()
    assert_clean_run(reg.snapshot())  # all-absent metrics default to 0
    reg.counter("aot_misses_total").inc()
    with pytest.raises(AssertionError, match="fell off the warmed"):
        assert_clean_run(reg.snapshot(), context="unit")
    reg2 = MetricsRegistry()
    reg2.gauge("jit_dispatch_cache_size").set(7)
    with pytest.raises(AssertionError, match="cache grew"):
        assert_clean_run(reg2.snapshot(), baseline_cache_size=5)


# ---------------------------------------------------------------------------
# Maintenance fold recorder
# ---------------------------------------------------------------------------


def test_record_fold_metrics_and_clamp():
    maintenance.record_fold(
        None, kind="fold", seconds=0.1, rows_before=10, rows_after=5
    )  # metrics=None: no-op
    reg = MetricsRegistry()
    maintenance.record_fold(
        reg, kind="fold", seconds=0.02, rows_before=100, rows_after=60
    )
    maintenance.record_fold(
        reg, kind="full", seconds=0.2, rows_before=60, rows_after=90
    )  # grew: reclaimed clamps to 0
    snap = reg.snapshot()
    assert snap.value("maintenance_folds_total", {"kind": "fold"}) == 1
    assert snap.value("maintenance_folds_total", {"kind": "full"}) == 1
    assert snap.histogram(
        "maintenance_fold_seconds", {"kind": "fold"}
    ).sum == pytest.approx(0.02)
    assert snap.value("maintenance_reclaimed_rows_total") == 40
    assert snap.value("maintenance_last_reclaimed_rows") == 0


# ---------------------------------------------------------------------------
# Collective accountant (mesh)
# ---------------------------------------------------------------------------


def _small_table(mesh8, **kw):
    kw.setdefault("hash_range", 1 << 12)
    kw.setdefault("max_deltas", 4)
    kw.setdefault("tombstone_capacity", 256)
    return DistributedHashTable(mesh8, ("d",), **kw)


def test_accountant_reconfirms_two_all_to_alls_at_every_depth(mesh8):
    """The acceptance criterion: jaxpr accounting of the fused read path
    must show exactly 2 all-to-alls regardless of delta depth."""
    table = _small_table(mesh8)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 12, size=256, dtype=np.uint32)
    state = table.init(keys, np.arange(256, dtype=np.int32))
    queries = plans._proto_queries(table, 16)
    for depth in range(3):
        counts, bytes_ = collective_profile(
            lambda s, q: plans.exec_query(table, s, q), state, queries
        )
        assert counts.get("all_to_all", 0) == 2, (
            f"depth {depth}: fused query budget broken: {counts}"
        )
        assert bytes_["all_to_all"] > 0
        state = state.insert(
            np.array([1, 2, 3, 4, 5, 6, 7, 8], dtype=np.uint32),
            np.arange(8, dtype=np.int32),
        )


def test_profile_executor_query_and_retrieve(mesh8):
    table = _small_table(mesh8)
    keys = np.arange(64, dtype=np.uint32)
    state = table.init(keys, np.arange(64, dtype=np.int32))
    queries = plans._proto_queries(table, 16)
    cost = profile_executor(table, state, queries, kind="query")
    assert cost.kind == "query" and cost.bucket == 16 and cost.depth == 0
    assert cost.all_to_alls == 2
    assert cost.all_to_all_bytes > 0
    assert cost.total_collective_bytes >= cost.all_to_all_bytes
    r = profile_executor(
        table,
        state,
        queries,
        kind="retrieve",
        exec_kwargs={"out_capacity": 64, "seg_capacity": 64},
    )
    assert r.kind == "retrieve" and r.all_to_alls == 2
    d = r.as_dict()
    assert d["all_to_alls"] == 2 and d["collective_counts"]["all_to_all"] == 2


# ---------------------------------------------------------------------------
# Server / frontend / cache integration (mesh)
# ---------------------------------------------------------------------------


def test_server_stats_is_registry_view(mesh8):
    table = _small_table(mesh8)
    rng = np.random.default_rng(5)
    seed = (rng.choice(1 << 14, size=128, replace=False) + 1).astype(np.uint32)
    server = TableServer(
        table,
        seed,
        policy=CompactionPolicy(max_delta_depth=2, fold_k=1),
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=8,
    )
    server.query_many([seed[:4]])
    server.query_many([seed[4:8], seed[8:12]])
    server.submit_insert(np.array([9991, 9992], dtype=np.uint32))
    server.step()
    server.submit_insert(np.array([9993], dtype=np.uint32))
    server.step()
    server.submit_insert(np.array([9994], dtype=np.uint32))
    server.step()  # policy folds before applying the third delta
    st = server.stats()
    snap = server.metrics()
    assert st.reads == snap.value("serve_reads_total") == 3
    assert st.read_batches == snap.value("serve_read_batches_total") == 2
    assert st.writes_applied == snap.value("serve_writes_applied_total") == 3
    assert st.folds == snap.value("maintenance_folds_total", {"kind": "fold"})
    assert st.folds >= 1
    assert st.fold_seconds_total == pytest.approx(
        snap.histogram("maintenance_fold_seconds", {"kind": "fold"}).sum
    )
    assert st.batcher.requests == snap.value("batch_requests_total")
    # Refreshed state gauges land in the same sample.
    assert snap.value("serve_seqno") == server.registry.seqno
    assert snap.value("serve_delta_depth") == len(server._shadow.deltas)
    assert snap.value("serve_dropped_rows") == 0
    assert snap.value("jit_dispatch_cache_size") == plans.exec_query._cache_size()
    # The whole sample renders and scrapes.
    scraped = parse_prometheus(render_prometheus(snap))
    assert scraped[("serve_reads_total", ())] == 3


def test_warmup_hit_miss_through_metrics_api(mesh8):
    """Satellite: AOT warmup coverage asserted via the metrics API — a
    mixed bucket/insert/fold stream against a warmed server must show
    aot_hits_total > 0, aot_misses_total == 0, and a flat jit cache."""
    table = _small_table(mesh8, hash_range=1 << 16, max_deltas=3)
    rng = np.random.default_rng(3)
    seed_keys = (rng.choice(1 << 18, size=256, replace=False) + 1000).astype(
        np.uint32
    )
    server = TableServer(
        table,
        seed_keys,
        policy=CompactionPolicy(max_delta_depth=2, fold_k=1, tombstone_load=0.9),
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=8,
    )
    warm = server.warm(
        buckets=(8, 16), depths=(0, 1, 2), fold_horizon=1,
        retrieve_caps={8: (64, 64)},
    )
    assert warm.entries > 0
    snap0 = server.metrics()
    assert snap0.value("aot_entries") == warm.entries
    assert snap0.value("aot_misses_total") == 0
    jit0 = snap0.value("jit_dispatch_cache_size")
    # Warmup profiling surfaced per-executor collective gauges at every
    # warmed depth, each inside the fused 2-all-to-all budget.
    depths_profiled = set()
    for labels in snap0.labels_of("executor_all_to_alls"):
        assert snap0.value("executor_all_to_alls", labels) == 2
        depths_profiled.add(int(labels["depth"]))
    assert depths_profiled == {0, 1, 2}
    assert warm.profiles and all(p.all_to_alls == 2 for p in warm.profiles)

    def q(keys):
        res, _ = server.query_many([np.asarray(keys, dtype=np.uint32)])
        return res[0]

    # Mixed stream: both warmed buckets, writes, a delete, one fold.
    assert q(seed_keys[:5]).tolist() == [1] * 5  # bucket 8
    assert q(seed_keys[:12]).tolist() == [1] * 12  # bucket 16
    server.submit_insert(np.array([21, 22], dtype=np.uint32))
    server.step()
    assert q([21, 22, 23]).tolist() == [1, 1, 0]
    server.submit_insert(np.array([24], dtype=np.uint32))
    server.step()
    server.submit_delete(np.array([22], dtype=np.uint32))
    server.step()
    server.submit_insert(np.array([25], dtype=np.uint32))
    server.step()  # policy folds (depth 2 -> 1): fold step 1
    assert q([21, 24, 25]).tolist() == [1, 1, 1]
    vals, _ = server.retrieve_many([np.array([21, 25], dtype=np.uint32)])
    assert [len(v) for v in vals[0]] == [1, 1]

    snap = server.metrics()
    assert snap.value("aot_hits_total") > 0
    assert snap.value("aot_misses_total") == 0, (
        "live traffic fell off the warmed grid"
    )
    assert snap.value("jit_dispatch_cache_size") == jit0, (
        "a live request traced/compiled despite AOT warmup"
    )
    assert snap.value("maintenance_folds_total", {"kind": "fold"}) == 1
    assert snap.histogram("maintenance_fold_seconds", {"kind": "fold"}).count == 1
    # Registry-backed ServerStats agrees with the raw counters.
    st = server.stats()
    assert st.warmup.aot_misses == 0 and st.warmup.aot_hits > 0


def test_frontend_tracing_end_to_end(mesh8):
    table = _small_table(mesh8)
    rng = np.random.default_rng(9)
    seed = (rng.choice(1 << 11, size=64, replace=False) + 1).astype(np.uint32)
    server = TableServer(
        table,
        seed,
        policy=CompactionPolicy(max_delta_depth=3, fold_k=1),
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=8,
    )
    with AsyncFrontend(server, linger=0.001, flush_keys=8, trace_ring=16) as fe:
        futs = [fe.submit_query(seed[i : i + 4], timeout=10) for i in range(6)]
        for f in futs:
            assert np.asarray(f.result(timeout=60).counts).tolist() == [1] * 4
        fe_snap = fe.metrics()
    assert fe.tracer.live() == 0
    assert fe_snap.value("trace_live") == 0
    assert fe_snap.value("traces_recorded_total") == 6
    assert fe_snap.value("frontend_completed_total") == 6
    assert fe_snap.value("frontend_failed_total") == 0
    for phase in PHASES:
        h = fe_snap.histogram("trace_phase_seconds", {"phase": phase})
        assert h is not None and h.count == 6, f"phase {phase} not recorded"
    assert fe_snap.histogram("request_latency_seconds").count == 6
    recent = fe.tracer.recent()
    assert recent and all(set(t.marks) == set(PHASES) for t in recent)
    assert all(t.bucket == 8 and t.seqno >= 0 for t in recent)
    # FrontendStats is the same snapshot, viewed per-instance.
    st = fe.stats()
    assert st.submitted == st.completed == 6 and st.failed == 0
    # A second frontend on the same server starts its view at zero.
    fe2 = AsyncFrontend(server, linger=0.001, flush_keys=8)
    assert fe2.stats().submitted == 0


def test_frontend_tracing_disabled_records_nothing(mesh8):
    table = _small_table(mesh8)
    seed = np.arange(1, 65, dtype=np.uint32)
    server = TableServer(
        table,
        seed,
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=8,
    )
    with AsyncFrontend(
        server, linger=0.001, flush_keys=8, tracing=False
    ) as fe:
        fut = fe.submit_query(seed[:4], timeout=10)
        assert np.asarray(fut.result(timeout=60).counts).tolist() == [1] * 4
        snap = fe.metrics()
    assert snap.value("traces_recorded_total") == 0
    # Instruments exist (pre-registered) but nothing was observed.
    assert snap.histogram("trace_phase_seconds", {"phase": "device"}).count == 0
    assert snap.value("frontend_completed_total") == 1


def test_kvcache_metrics(mesh8):
    from repro.cache.kvcache import KVCache

    table = _small_table(mesh8)
    cache = KVCache(table, default_ttl=4)
    k = np.array([11, 22, 33, 44, 55, 66, 77, 88], dtype=np.uint32)
    cache.put(k, np.arange(8, dtype=np.int32))
    assert cache.get(k[:2]).tolist() == [0, 1]
    assert cache.contains(k[:1]).tolist() == [True]
    cache.delete(k[:1])
    cache.tick(10)  # everything expires
    reclaimed = cache.evict_expired()
    assert reclaimed >= 0
    snap = cache.metrics()
    assert snap.value("kvcache_puts_total") == 1
    assert snap.value("kvcache_gets_total") == 2  # get + contains
    assert snap.value("kvcache_deletes_total") == 1
    assert snap.value("kvcache_evictions_total") >= 1
    assert snap.histogram("kvcache_put_seconds").count == 1
    assert snap.histogram("kvcache_get_seconds").count == 1
    assert snap.value("kvcache_now") == cache.now == 10
    assert snap.value("kvcache_delta_depth") == 0  # compacted
    # The shared fold recorder fed the same registry.
    assert snap.value("maintenance_folds_total", {"kind": "full"}) >= 1
    assert cache.evictions == snap.value("kvcache_evictions_total")
