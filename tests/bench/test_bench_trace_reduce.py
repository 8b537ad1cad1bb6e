"""The trace reduction, on a synthetic profile and on a small trace recorded
on a TPU v5e chip (``data/fixture.xplane.pb``, made by
``bench/tools/record_trace_fixture.py``)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.trace_reduce import reduce_file, reduce_profile

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            ev("bench.window", 0, 1000),
            ev("bench.await_arrival", 400, 300),
            ev("PjitFunction(exec_query)", 450, 50),
            ev("instant", 10, 0),
        ]),
    ])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_exec_query(3)", 120, 100),
            ev("jit_exec_query(3)", 720, 80),
            ev("jit_exec_join(4)", 1100, 100),
        ]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 120, 50),
            ev("sort.2", 160, 60),
            ev("fusion.1", 720, 80),
            ev("fusion.9", 1100, 100),
        ]),
    ])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device])


def test_synthetic_busy_idle_and_executables():
    s = reduce_profile(profile())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(180e-9)  # union of [120,220] and [720,800]
    assert s.idle_share == pytest.approx(0.82)
    assert s.executable("exec_query") == {"count": 2, "seconds": pytest.approx(180e-9)}
    assert s.executable("exec_join") is None  # ran after the window
    assert s.device_ops[0][0] == "exec_query:fusion.1"
    assert s.device_ops[0][1] == pytest.approx(130e-9)
    assert [g[0] for g in s.idle_gaps] == [
        "PjitFunction(exec_query)",  # the program's span wins over the bench's
        "no host span",
        "no host span",
    ]
    assert [g[1] for g in s.idle_gaps] == pytest.approx([500e-9, 200e-9, 120e-9])


def test_synthetic_without_window_or_device_gives_nothing():
    p = profile()
    p.planes[1].lines[0].events.pop(0)
    assert reduce_profile(p) is None
    p = profile()
    p.planes.pop()
    assert reduce_profile(p) is None


def test_recorded_tpu_trace():
    s = reduce_file(FIXTURE)
    assert s is not None and s.devices == 1
    assert 0 < s.busy_s < s.window_s
    q, j = s.executable("exec_query"), s.executable("exec_join")
    # 3 and 2 calls ran; the device clock runs about 1 ms ahead of the
    # host's here, so the first call's module starts before the window
    assert q["count"] == 2 and j["count"] == 2
    assert q["seconds"] > 0 and j["seconds"] > 0
    assert q["seconds"] + j["seconds"] <= s.window_s
    assert 0 < len(s.device_ops) <= 10 and 0 < len(s.idle_gaps) <= 10
    # the three deliberate 4 ms host gaps are the longest idle gaps
    assert [label for label, secs in s.idle_gaps[:3]] == ["bench.host_gap"] * 3
    assert all(secs > 0.004 for _, secs in s.idle_gaps[:3])
