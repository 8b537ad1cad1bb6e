"""Async serving front end — concurrency stress, fault injection, deadlines.

The async layer is threads + deadlines + shared snapshots, so these tests
are the PR's backbone rather than an afterthought:

* **Stress**: N reader threads hammer an :class:`AsyncFrontend` while a
  writer streams inserts/deletes and folds run in the background, for a
  wall-clock budget.  Consistency is checked on *every* response via a
  uniform-multiplicity probe set: each write batch inserts the whole set
  exactly once, so any consistent snapshot shows one count for all probe
  keys — a torn read is a non-uniform response, a stale-vs-future mix is
  a count regression across seqnos, and same-seqno responses must agree.
  No response may be lost or duplicated, and shutdown must leave zero
  threads behind.
* **Fault injection**: a writer-loop step or a background fold that
  raises mid-batch must surface on ``stats()``/``drain()`` (never hang),
  leave the published snapshot at the last good seqno, and keep the read
  path serving.
* **Deadline batcher property**: for random arrival schedules, every
  request is dispatched exactly once, by ``min(enqueue + linger,
  deadline)`` (+ one poll step), in a batch bounded by ``flush_keys`` —
  under a fake clock (deterministic) and the real timer.  Runs under
  Hypothesis when installed, otherwise over seeded random schedules.
* **No-retrace regression**: after AOT warmup, a mixed stream across all
  warmed bucket sizes — interleaved with inserts, deletes, a fold, and
  the snapshot swaps they publish — leaves both the executor grid's miss
  counter and ``jax.jit``'s compiled-function cache unchanged.
* **drain() contract**: timeout raises with the number of still-pending
  batches; ``stop()`` (or a dead writer) unblocks waiters promptly.
"""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from repro.core import maintenance, plans
from repro.core.table import DistributedHashTable
from repro.serve_table import (
    AsyncFrontend,
    CompactionPolicy,
    DeadlineBatcher,
    MicroBatcher,
    TableServer,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: seeded-random fallback
    HAVE_HYPOTHESIS = False

# Probe set: one write batch inserts ALL of these exactly once, so every
# consistent snapshot shows a single count c for the whole set (c = number
# of applied probe batches).  Sized to the write bucket so a probe insert
# is exactly one delta.
PROBES = np.array(
    [101, 202, 303, 404, 505, 606, 707, 808], dtype=np.uint32
)
WRITE_BUCKET = 8


def _make_server(
    mesh8, *, policy=None, write_bucket=WRITE_BUCKET, seed=0, pool=256
):
    table = DistributedHashTable(
        mesh8,
        ("d",),
        hash_range=1 << 16,
        max_deltas=4,
        tombstone_capacity=256,
    )
    rng = np.random.default_rng(seed)
    # Seed keys disjoint from PROBES (probe counts must start at 0).
    keys = (rng.choice(1 << 18, size=pool, replace=False) + 1000).astype(
        np.uint32
    )
    server = TableServer(
        table,
        keys,
        policy=policy
        or CompactionPolicy(max_delta_depth=2, fold_k=1, tombstone_load=0.9),
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=write_bucket,
    )
    return server, keys


# ---------------------------------------------------------------------------
# Concurrency stress: readers + writer + background folds
# ---------------------------------------------------------------------------


def _run_stress(server, pool, *, budget: float, readers: int, min_responses: int):
    """Hammer the front end; return (responses, errors, frontend stats).

    Write pattern: every writer cycle inserts the whole probe set AND
    deletes 8 fresh seed keys, keeping the live row count constant — so
    the always-escalating compaction policy rebuilds the base to the SAME
    capacity each time and the state-structure family the readers see is
    finite (depth 0..2 over two base shapes).  After the first cycle's
    one-time compiles, reads run at cache speed and the stress actually
    stresses concurrency instead of the compiler.
    """
    stop = threading.Event()
    errors: list = []
    responses: list = []  # (seqno, uniform count) per completed read
    resp_lock = threading.Lock()

    fe = AsyncFrontend(
        server, linger=0.001, flush_keys=WRITE_BUCKET, write_backlog=32
    ).start()

    def reader():
        while not stop.is_set():
            try:
                fut = fe.submit_query(PROBES, timeout=10)
                r = fut.result(timeout=120)
            except Exception as e:  # noqa: BLE001 - recorded for the assert
                errors.append(f"reader: {type(e).__name__}: {e}")
                return
            c = np.asarray(r.counts)
            if c.shape[0] != PROBES.shape[0] or not (c == c[0]).all():
                errors.append(
                    f"torn read at seqno {r.seqno}: {c.tolist()}"
                )
                return
            with resp_lock:
                responses.append((r.seqno, int(c[0])))

    def writer():
        i = 0
        max_cycles = pool.shape[0] // WRITE_BUCKET  # never re-delete a key
        while not stop.is_set():
            if i >= max_cycles:
                time.sleep(0.005)
                continue
            try:
                fe.submit_insert(PROBES, timeout=10)
                # Delete exactly as many (unique, live) seed keys as the
                # probe insert added: live count — and with it the full
                # compact's rebuilt base shape — stays constant.
                fe.submit_delete(
                    pool[i * WRITE_BUCKET : (i + 1) * WRITE_BUCKET], timeout=10
                )
                if i % 10 == 9 and not server.fold_in_flight:
                    try:
                        server.fold_async()  # background compaction
                    except RuntimeError:
                        pass  # raced another fold: fine
            except Exception as e:  # noqa: BLE001
                errors.append(f"writer: {type(e).__name__}: {e}")
                return
            i += 1
            time.sleep(0.005)

    threads = [threading.Thread(target=reader, daemon=True) for _ in range(readers)]
    threads.append(threading.Thread(target=writer, daemon=True))
    t0 = time.monotonic()
    for t in threads:
        t.start()
    # Run for the wall budget, extended (bounded) until enough responses
    # landed that the consistency assertions have teeth — the first write
    # cycle pays one-time plan compiles on this unwarmed server.
    hard_cap = t0 + max(budget * 30, 120.0)
    while time.monotonic() < t0 + budget or (
        len(responses) < min_responses and time.monotonic() < hard_cap
    ):
        if errors:
            break
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "stress worker failed to stop"
    server.drain(timeout=180)
    fe.stop()
    while server.fold_in_flight:
        time.sleep(0.005)
    return responses, errors, fe.stats()


def _assert_stress_invariants(responses, errors, stats):
    assert not errors, errors[:5]
    # No lost or duplicated responses: every admitted read resolved once.
    assert stats.failed == 0
    assert stats.completed == stats.submitted
    assert stats.queue_depth == 0 and stats.inflight == 0
    # Per-seqno consistency: same-seqno responses must agree on the count.
    by_seqno: dict = {}
    for seqno, count in responses:
        if seqno in by_seqno:
            assert by_seqno[seqno] == count, (
                f"seqno {seqno} served two different counts "
                f"({by_seqno[seqno]} vs {count})"
            )
        else:
            by_seqno[seqno] = count
    # Monotonicity: probe inserts only accumulate (deletes never touch the
    # probe set), so counts ordered by seqno never regress.
    ordered = sorted(by_seqno.items())
    counts = [c for _, c in ordered]
    assert counts == sorted(counts), f"count regression across seqnos: {ordered}"


# Full compacts only (fold_k == max_delta_depth escalates every trigger):
# the rebuilt base is live-count-sized, and the stress writer keeps the
# live count constant, so the structure family stays finite.
_STRESS_POLICY = CompactionPolicy(
    max_delta_depth=2, fold_k=2, tombstone_load=0.95
)


def test_stress_readers_writer_folds_short(mesh8):
    """CI-budget stress: 3 readers + writer + folds, ~2s of wall traffic."""
    server, pool = _make_server(mesh8, policy=_STRESS_POLICY, pool=4096)
    responses, errors, stats = _run_stress(
        server, pool, budget=2.0, readers=3, min_responses=50
    )
    _assert_stress_invariants(responses, errors, stats)
    assert len(responses) >= 50
    # Clean shutdown: no serving thread survived stop().
    leaked = {
        t
        for t in threading.enumerate()
        if t.is_alive()
        and t.name.startswith(("serve-table", "serve-frontend"))
    }
    assert not leaked, f"leaked serving threads: {leaked}"


@pytest.mark.slow
def test_stress_readers_writer_folds_long(mesh8):
    """Full-budget stress (slow): more readers, longer wall clock."""
    server, pool = _make_server(
        mesh8, policy=_STRESS_POLICY, seed=7, pool=16384
    )
    responses, errors, stats = _run_stress(
        server, pool, budget=8.0, readers=5, min_responses=300
    )
    _assert_stress_invariants(responses, errors, stats)
    assert len(responses) >= 300


# ---------------------------------------------------------------------------
# Deterministic fault injection
# ---------------------------------------------------------------------------


def test_writer_crash_surfaces_and_reads_survive(mesh8, monkeypatch):
    server, _ = _make_server(mesh8, seed=1)
    table = server.table
    seed_key = np.array([42, 43], dtype=np.uint32)
    real_insert = table.insert
    poison = {"armed": False}

    def flaky_insert(state, keys, values=None, **kw):
        if poison["armed"]:
            raise RuntimeError("injected insert failure")
        return real_insert(state, keys, values, **kw)

    monkeypatch.setattr(table, "insert", flaky_insert)
    server.start()
    try:
        server.submit_insert(seed_key)  # applies fine -> seqno 1
        server.drain(timeout=60)
        good_seqno = server.registry.seqno
        assert good_seqno >= 1

        poison["armed"] = True
        server.submit_insert(np.array([77], dtype=np.uint32))
        server.submit_insert(np.array([78], dtype=np.uint32))
        # The embedded writer must die loudly, not hang.
        deadline = time.monotonic() + 30
        while server._writer_thread.is_alive() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not server._writer_thread.is_alive(), "writer loop hung on error"

        stats = server.stats()
        assert stats.last_error and "injected insert failure" in stats.last_error
        # Published snapshot stayed at the last good seqno; the failed
        # batch was re-queued, not dropped.
        assert server.registry.seqno == good_seqno
        assert server.pending() == 2
        # Read path keeps serving the last good snapshot.
        res, seqno = server.query_many([seed_key])
        assert seqno == good_seqno and res[0].tolist() == [1, 1]
        # drain() surfaces the failure instead of hanging or lying: with
        # the embedded writer dead it re-drives step() inline, which
        # re-raises the injected error.
        with pytest.raises(RuntimeError, match="injected insert failure"):
            server.drain(timeout=5)
    finally:
        poison["armed"] = False
        server.stop()


def test_fold_crash_surfaces_and_reads_survive(mesh8, monkeypatch):
    server, _ = _make_server(
        mesh8, policy=CompactionPolicy(max_delta_depth=None), seed=2
    )
    server.submit_insert(np.array([11, 12], dtype=np.uint32))
    server.submit_insert(np.array([13, 14], dtype=np.uint32))
    while server.step():
        pass
    good_seqno = server.registry.seqno
    assert len(server._shadow.deltas) == 2

    def boom(state, k):
        raise RuntimeError("injected fold failure")

    monkeypatch.setattr("repro.core.maintenance.fold_oldest", boom)
    t = server.fold_async(1)
    t.join(timeout=30)
    assert not t.is_alive(), "fold thread hung on error"
    stats = server.stats()
    assert stats.last_error and "injected fold failure" in stats.last_error
    assert server.registry.seqno == good_seqno  # snapshot at last good seqno
    res, seqno = server.query_many([np.array([11, 13], dtype=np.uint32)])
    assert seqno == good_seqno and res[0].tolist() == [1, 1]
    with pytest.raises(RuntimeError, match="background fold failed"):
        server.drain(timeout=5)


# ---------------------------------------------------------------------------
# Deadline batcher: dispatch-exactly-once, deadline bound, bucket bound
# ---------------------------------------------------------------------------

LINGER = 0.01
FLUSH_KEYS = 16


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _check_schedule(schedule):
    """Drive a fake-clock DeadlineBatcher through one arrival schedule.

    ``schedule`` is a list of ``(arrival, size, deadline_offset)``; the
    checker polls at every arrival and every per-request obligation time,
    then asserts the three batching properties.
    """
    clock = FakeClock()
    b = DeadlineBatcher(
        flush_keys=FLUSH_KEYS, linger=LINGER, capacity=10_000, clock=clock
    )
    arrivals = sorted(
        (float(a), int(s), float(d)) for a, s, d in schedule
    )
    eps = 1e-6
    # Poll at every moment something can become due: each arrival, each
    # arrival+linger, each deadline (plus the final drain point).
    times = sorted(
        {a for a, _, _ in arrivals}
        | {a + LINGER + eps for a, _, _ in arrivals}
        | {a + d + eps for a, s, d in arrivals}
    )
    submitted = []  # (_Pending, deadline_abs)
    dispatched = []  # (request, dispatch_time, batch_index)
    it = iter(arrivals)
    nxt = next(it, None)
    batch_idx = 0
    for now in times:
        clock.t = now
        while nxt is not None and nxt[0] <= now + eps:
            a, size, doff = nxt
            req = b.submit(
                np.arange(size, dtype=np.uint32), deadline=a + doff
            )
            submitted.append((req, a + doff))
            nxt = next(it, None)
        while True:
            batch = b.poll(now)
            if batch is None:
                break
            total = sum(r.size for r in batch)
            # Bucket bound: a batch never exceeds flush_keys unless a
            # single oversized request forces it.
            assert total <= FLUSH_KEYS or len(batch) == 1
            for r in batch:
                dispatched.append((r, now, batch_idx))
            batch_idx += 1
    assert b.pending() == 0, "requests left undispatched after final poll"

    # Exactly once.
    ids = [id(r) for r, _, _ in dispatched]
    assert len(ids) == len(set(ids)) == len(submitted)
    # Deadline bound: dispatched by min(enqueue+linger, deadline), within
    # one poll step (we poll exactly at obligation times, so eps slack).
    for r, t_disp, _ in dispatched:
        bound = min(r.enqueued + LINGER, r.deadline)
        assert t_disp <= bound + 2 * eps, (
            f"request enqueued at {r.enqueued} (deadline {r.deadline}) "
            f"dispatched late at {t_disp}"
        )


def _random_schedule(seed: int):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 60))
    return [
        (
            float(rng.uniform(0, 0.05)),
            int(rng.integers(1, 12)),
            float(rng.uniform(0.0005, 0.03)),
        )
        for _ in range(n)
    ]


if HAVE_HYPOTHESIS:

    @settings(max_examples=50, deadline=None)
    @given(
        schedule=st.lists(
            st.tuples(
                st.floats(0, 0.05, allow_nan=False),
                st.integers(1, 12),
                st.floats(0.0005, 0.03, allow_nan=False),
            ),
            min_size=1,
            max_size=60,
        )
    )
    def test_deadline_batcher_property_fake_clock(schedule):
        _check_schedule(schedule)

else:

    @pytest.mark.parametrize("seed", range(25))
    def test_deadline_batcher_property_fake_clock(seed):
        _check_schedule(_random_schedule(seed))


def test_deadline_batcher_real_timer():
    """Same exactly-once/deadline/bucket properties under the real clock."""
    b = DeadlineBatcher(flush_keys=32, linger=0.02, capacity=1024)
    n = 60
    dispatched = []
    done = threading.Event()

    def consumer():
        got = 0
        while got < n:
            batch = b.next_batch(timeout=1.0)
            if batch is None:
                break
            assert sum(r.size for r in batch) <= 32 or len(batch) == 1
            t = time.monotonic()
            dispatched.extend((r, t) for r in batch)
            got += len(batch)
        done.set()

    c = threading.Thread(target=consumer, daemon=True)
    c.start()
    submitted = []
    for i in range(n):
        submitted.append(b.submit(np.arange(1 + i % 4, dtype=np.uint32)))
        time.sleep(0.001)
    assert done.wait(timeout=20), "consumer never drained the queue"
    c.join(timeout=5)

    ids = [id(r) for r, _ in dispatched]
    assert len(ids) == len(set(ids)) == n  # exactly once
    for r, t_disp in dispatched:
        # Real-timer bound: linger plus generous scheduler slack.
        assert t_disp - r.enqueued <= 0.02 + 0.5
    b.close()
    assert b.next_batch(timeout=0.1) is None  # close() wakes and exhausts


def test_deadline_batcher_urgent_deadline_pulls_flush_forward():
    """A later request with an earlier deadline flushes the whole queue."""
    clock = FakeClock()
    b = DeadlineBatcher(flush_keys=64, linger=1.0, capacity=64, clock=clock)
    b.submit(np.arange(2, dtype=np.uint32))  # relaxed: due at t=1.0
    clock.t = 0.1
    b.submit(np.arange(2, dtype=np.uint32), deadline=0.2)  # urgent
    assert b.poll(0.15) is None  # nothing due yet
    batch = b.poll(0.21)  # urgent deadline passed: both ship now
    assert batch is not None and len(batch) == 2


def test_deadline_batcher_backpressure_and_close():
    clock = FakeClock()
    b = DeadlineBatcher(flush_keys=8, linger=1.0, capacity=2, clock=clock)
    b.submit(np.arange(1, dtype=np.uint32))
    b.submit(np.arange(1, dtype=np.uint32))
    with pytest.raises(TimeoutError, match="admission queue full"):
        b.submit(np.arange(1, dtype=np.uint32), timeout=0.05)
    assert b.poll(2.0) is not None  # linger expired: frees capacity
    b.submit(np.arange(1, dtype=np.uint32))  # fits again
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(np.arange(1, dtype=np.uint32))


# ---------------------------------------------------------------------------
# No-retrace regression: warmed grid + mixed stream = zero new compiles
# ---------------------------------------------------------------------------


def test_no_retrace_after_warmup(mesh8):
    table = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 16, max_deltas=3, tombstone_capacity=256
    )
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
    assert warm.entries > 0 and warm.aot_misses == 0

    has_counter = hasattr(plans.exec_query, "_cache_size")
    jit_before = plans.exec_query._cache_size() if has_counter else None
    fold_before = maintenance.exec_fold._cache_size() if has_counter else None

    def q(keys):
        res, _ = server.query_many([np.asarray(keys, dtype=np.uint32)])
        return res[0]

    # Mixed open-loop stream: both warmed buckets, interleaved writes (and
    # the snapshot swaps they publish), a delete, and one incremental fold.
    assert q(seed_keys[:5]).tolist() == [1] * 5  # bucket 8
    assert q(seed_keys[:12]).tolist() == [1] * 12  # bucket 16
    server.submit_insert(np.array([21, 22], dtype=np.uint32))
    server.step()  # depth 1, snapshot swap
    assert q([21, 22, 23]).tolist() == [1, 1, 0]
    server.submit_insert(np.array([24], dtype=np.uint32))
    server.step()  # depth 2
    assert q(np.concatenate([[21, 22, 24], seed_keys[:9]])).tolist() == [1] * 12
    server.submit_delete(np.array([22], dtype=np.uint32))
    server.step()
    assert q([21, 22, 24]).tolist() == [1, 0, 1]
    server.submit_insert(np.array([25], dtype=np.uint32))
    server.step()  # policy folds (depth 2 -> 1) before applying: fold step 1
    assert server.stats().folds == 1
    assert q([21, 24, 25]).tolist() == [1, 1, 1]
    assert q(seed_keys[:16]).tolist() == [1] * 16  # bucket 16 post-fold
    # Warmed retrieve path too.
    vals, _ = server.retrieve_many([np.array([21, 25], dtype=np.uint32)])
    assert [len(v) for v in vals[0]] == [1, 1]

    after = server.stats().warmup
    assert after.aot_misses == 0, (
        f"live traffic fell off the warmed grid: {after}"
    )
    assert after.aot_hits >= 8
    if has_counter:
        assert plans.exec_query._cache_size() == jit_before, (
            "a live request traced/compiled despite AOT warmup"
        )
        assert maintenance.exec_fold._cache_size() == fold_before, (
            "the policy fold traced/compiled despite AOT warmup"
        )


# ---------------------------------------------------------------------------
# drain(): timeout must raise with pending count; stop() must unblock
# ---------------------------------------------------------------------------


def test_drain_timeout_raises_with_pending_count(mesh8):
    server, _ = _make_server(mesh8, seed=4)
    server.submit_insert(np.array([5], dtype=np.uint32))
    # Hold the shadow-mutation mutex: inline step() can't apply anything.
    assert server._writer_mutex.acquire(timeout=5)
    try:
        with pytest.raises(TimeoutError, match="1 pending batch"):
            server.drain(timeout=0.3)
    finally:
        server._writer_mutex.release()
    server.drain(timeout=60)  # mutex free: drains fine now
    assert server.pending() == 0
    assert server.query(np.array([5], dtype=np.uint32)).tolist() == [1]


def test_drain_unblocks_on_stop(mesh8):
    server, _ = _make_server(mesh8, seed=5)
    server.start()
    assert server._writer_mutex.acquire(timeout=5)  # writer loop can't apply
    outcome: list = []

    def drainer():
        try:
            server.drain(timeout=60)
            outcome.append("returned")
        except Exception as e:  # noqa: BLE001 - the outcome under test
            outcome.append(e)

    try:
        server.submit_insert(np.array([6], dtype=np.uint32))
        t = threading.Thread(target=drainer, daemon=True)
        t.start()
        time.sleep(0.2)
        assert t.is_alive()  # parked on the embedded writer
        t0 = time.monotonic()
        server.stop()
        t.join(timeout=10)
        assert not t.is_alive(), "drain stayed blocked after stop()"
        assert time.monotonic() - t0 < 10  # unblocked promptly, not at timeout
        assert len(outcome) == 1 and isinstance(outcome[0], RuntimeError)
        assert "1 pending batch" in str(outcome[0])
    finally:
        server._writer_mutex.release()
        server.stop()


def test_future_results_are_read_your_writes_with_wait_for(mesh8):
    """wait_for(seqno) + submit_query observes a just-applied write."""
    server, _ = _make_server(mesh8, seed=6)
    with AsyncFrontend(server, linger=0.001) as fe:
        fe.submit_insert(np.array([91, 92], dtype=np.uint32))
        server.drain(timeout=60)
        target = server.registry.seqno
        snap = server.registry.wait_for(target, timeout=30)
        assert snap.seqno >= target
        fut = fe.submit_query(np.array([91, 92, 93], dtype=np.uint32))
        r = fut.result(timeout=60)
        assert isinstance(fut, Future)
        assert r.counts.tolist() == [1, 1, 0] and r.seqno >= target
    with pytest.raises(TimeoutError):
        server.registry.wait_for(server.registry.seqno + 1, timeout=0.05)
