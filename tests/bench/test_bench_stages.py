"""The stage reduction (``bench/trace_stages.py``), on a synthetic profile and
on a small trace recorded on a TPU v5e chip (``data/stages.xplane.pb`` and
``data/stages_hlo.json``, made by ``bench/tools/record_stage_fixture.py``),
and beside the first recorded trace (``data/fixture.xplane.pb``), whose
numbers ``bench/trace_reduce.py`` reads unchanged."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.trace_reduce import reduce_file
from bench.trace_stages import reduce_stages, reduce_stages_file
from repro.obs.tracing import hlo_stages

DATA = Path(__file__).parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


STAGE_OF = {"exec_join": {"while.3": "locate", "fusion.4": "locate", "fusion.5": "gather",
                          "sort.6": "expand"}}


def profile():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            ev("bench.window", 0, 10_000),
            ev("bench.call", -200, 1_200),
            ev("plan.join", 50, 800),
            ev("plan.join", 2_000, 2_000),  # host work between the calls
            ev("PjitFunction(exec_join)", 2_800, 300),
            ev("plan.join", 5_050, 800),
            ev("plan.join", 7_000, 2_500),  # a span that dispatched nothing
        ]),
    ])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_exec_join(7)", 150, 700),
            ev("jit_exec_join(7)", 5_150, 700),
        ]),
        NS(name="XLA Ops", events=[
            # a loop and the ops of its body overlap: one stage, counted once
            ev("%while.3 = (s32[], s32[8]) while(%tuple.2), body=%body.1", 150, 400),
            ev("%fusion.4 = s32[8] fusion(%a), kind=kLoop", 200, 100),
            ev("%fusion.4 = s32[8] fusion(%a), kind=kLoop", 350, 100),
            ev("%fusion.5 = s32[8] fusion(%b)", 550, 200),
            ev("%sort.6 = s32[8] sort(%c)", 750, 50),
            ev("%copy.9 = s32[8] copy(%d)", 800, 50),  # not in the map: other
            ev("%while.3 = (s32[], s32[8]) while(%tuple.2), body=%body.1", 5_150, 400),
            ev("%fusion.5 = s32[8] fusion(%b)", 5_550, 300),
        ]),
    ])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device])


def test_synthetic_stage_seconds_count_nested_ops_once():
    s = reduce_stages(profile(), STAGE_OF)
    assert s.calls == {"exec_join": 2}
    assert s.module_s["exec_join"] == pytest.approx(1400e-9)
    stages = s.stage_s["exec_join"]
    assert stages["locate"] == pytest.approx(800e-9)  # 2 x 400, not 2 x 400 + 200
    assert stages["gather"] == pytest.approx(500e-9)
    assert stages["expand"] == pytest.approx(50e-9)
    assert stages["other"] == pytest.approx(50e-9)
    assert s.per_call_ms("exec_join", "locate") == pytest.approx(400e-6)
    assert s.per_call_ms("exec_query", "locate") is None
    assert s.other_share("exec_join") == pytest.approx(50 / 1400)
    assert sum(stages.values()) == pytest.approx(s.module_s["exec_join"])


def test_synthetic_clock_offset_and_gap_labels():
    s = reduce_stages(profile(), STAGE_OF)
    # each run started 100 ns after the plan.join span that dispatched it
    assert s.clock_offset_ms == pytest.approx(100e-6)
    # gaps are read at their middle less the offset
    assert [(label, round(secs * 1e9)) for label, secs in s.idle_gaps] == [
        ("plan.join/PjitFunction(exec_join)", 4_300),  # [850, 5150) at 2900
        ("plan.join/-", 4_150),  # [5850, 10000) at 7825
        ("bench.call", 150),  # [0, 150) at -25: neither program nor JAX span
    ]


def test_synthetic_without_window_or_device_gives_nothing():
    p = profile()
    p.planes[1].lines[0].events.pop(0)
    assert reduce_stages(p, STAGE_OF) is None
    p = profile()
    p.planes.pop()
    assert reduce_stages(p, STAGE_OF) is None


def test_first_fixture_runs_read_as_trace_reduce_reads_them():
    old = reduce_file(DATA / "fixture.xplane.pb")
    new = reduce_stages_file(DATA / "fixture.xplane.pb", {})
    for jit in ("exec_query", "exec_join"):
        ex = old.executable(jit)
        assert new.calls[jit] == ex["count"]
        assert new.module_s[jit] == pytest.approx(ex["seconds"])
        # no stage map: every op is "other", and the union is the busy time
        assert set(new.stage_s[jit]) == {"other"}
    busy = sum(sum(v.values()) for v in new.stage_s.values())
    assert busy == pytest.approx(old.busy_s, rel=1e-6)
    assert new.window_s == pytest.approx(old.window_s)
    assert new.clock_offset_ms is None  # no program span in that trace
    assert [g[1] for g in new.idle_gaps] == pytest.approx([g[1] for g in old.idle_gaps])


@pytest.fixture(scope="module")
def recorded():
    hlo = json.loads((DATA / "stages_hlo.json").read_text())
    stage_of = {jit: hlo_stages(text) for jit, text in hlo.items()}
    return stage_of, reduce_stages_file(DATA / "stages.xplane.pb", stage_of)


def test_recorded_tpu_trace_stages(recorded):
    stage_of, s = recorded
    assert s is not None and s.devices == 1
    assert set(stage_of["exec_join"].values()) == {"route", "locate", "expand"}
    assert s.calls["exec_join"] == 3 and s.calls["exec_query"] >= 2
    for jit, want in (("exec_join", {"route", "locate", "expand"}),
                      ("exec_query", {"route", "return"})):
        stages = s.stage_s[jit]
        assert set(stages) - {"other"} == want
        assert s.other_share(jit) < 0.05
        # the stages are disjoint in time within one run: they add up to at
        # most the executable's own device time
        assert sum(stages.values()) <= s.module_s[jit] * 1.001
        assert sum(stages.values()) >= 0.5 * s.module_s[jit]
    # the loop (a scope nested in its body) is counted once
    assert s.stage_s["exec_join"]["locate"] < s.module_s["exec_join"]


def test_recorded_tpu_trace_gaps_and_clock(recorded):
    _, s = recorded
    # runs start within a few ms of the span that dispatched them
    assert s.clock_offset_ms is not None and abs(s.clock_offset_ms) < 3.0
    labels = [label for label, secs in s.idle_gaps if secs > 0.003]
    # the 4 ms sleeps after the first two exec_join calls lie inside their
    # plan.join spans; the third runs on into the closing host gap
    assert labels.count("plan.join/-") == 2
    assert "bench.host_gap" in labels
