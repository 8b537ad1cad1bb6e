"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The window is the host span named ``bench.window`` (the drivers mark the
measured window with it).  From the device planes (``/device:TPU:<n>``)
within that window it takes:

* busy seconds: the union of the intervals in which an XLA op ran, averaged
  over the devices; idle share is ``1 - busy / window``;
* device seconds and call counts per executable, by the name of the jitted
  function the executable was compiled from (``exec_query`` and so on);
* the device operations that took most time (``breakdown.device_ops``),
  each named ``<executable>:<HLO instruction>`` with its layouts removed;
* the longest idle gaps, each labelled by the host span that covered its
  middle (``breakdown.idle_gaps``): the innermost span of the program or of
  JAX there, else the innermost span of the benchmark, else "no host span".
  Events of the Python tracer (names that start with ``$``) are not spans:
  the harness runs the profiler with that tracer off.

The device's clock is not the host's: on a v5e the device events of a
call were seen to start about 1 ms before the host span that dispatched
them, so counts at the window's edges can be off by a call.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Optional

WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_NAME_CHARS = 160


@dataclasses.dataclass(frozen=True)
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the device planes
    devices: int
    executables: dict  # module name -> {"count": int, "seconds": float}
    device_ops: list  # [[op name, seconds]], longest first, at most 10
    idle_gaps: list  # [[host span, seconds]], longest first, at most 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def executable(self, jit_name: str) -> Optional[dict]:
        """Count and seconds of the executables compiled from ``jit_name``
        (summed over the devices), or None when none ran in the window."""
        hits = [v for k, v in self.executables.items() if _jit_of(k) == jit_name]
        if not hits:
            return None
        return {
            "count": sum(v["count"] for v in hits),
            "seconds": sum(v["seconds"] for v in hits),
        }


def _jit_of(module: str) -> str:
    """``jit_exec_query(12)`` -> ``exec_query``."""
    name = _SUFFIX.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def _op_name(module: str, hlo: str) -> str:
    """``exec_join:%fusion.7 = s32[8,4] fusion(...)``, layouts removed."""
    return f"{_jit_of(module)}:{_LAYOUT.sub('', hlo)}"[:_NAME_CHARS]


def _module_at(starts, modules, t: float) -> str:
    """Name of the executable whose interval holds ``t``; ``modules`` are
    ``(start, end, name)`` sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else "?"


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _host_label(host_spans, t: float) -> str:
    """Innermost covering span at time ``t``: the program's or JAX's first,
    then the benchmark's."""
    best = {False: None, True: None}  # is-bench -> (duration, name)
    for name, a, b in host_spans:
        if a <= t < b:
            key = name.startswith(BENCH_PREFIX)
            if best[key] is None or b - a < best[key][0]:
                best[key] = (b - a, name)
    for key in (False, True):
        if best[key] is not None:
            return best[key][1]
    return "no host span"


def reduce_profile(profile, top: int = 10) -> Optional[TraceSummary]:
    """Reduce a ``jax.profiler.ProfileData``; None when the trace has no
    ``bench.window`` span or no device plane."""
    window = None
    host_spans = []
    devices = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns <= 0 or e.name.startswith("$"):
                    continue
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if e.name == WINDOW_SPAN:
                    window = (a, b)
                else:
                    host_spans.append((e.name, a, b))
    if window is None or not devices:
        return None
    lo, hi = window
    host_spans = [s for s in host_spans if s[2] > lo and s[1] < hi]
    busy_total = 0.0
    executables: dict = {}
    op_seconds: dict = {}
    gaps = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, _SUFFIX.sub("", e.name))
            for e in (lines[_MODULES_LINE].events if _MODULES_LINE in lines else ())
        )
        for a, b, name in modules:
            if lo <= a < hi:
                entry = executables.setdefault(name, {"count": 0, "seconds": 0.0})
                entry["count"] += 1
                entry["seconds"] += (b - a) * 1e-9
        starts = [m[0] for m in modules]
        intervals = []
        for e in lines[_OPS_LINE].events if _OPS_LINE in lines else ():
            iv = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
            if iv is None:
                continue
            intervals.append(iv)
            name = _op_name(_module_at(starts, modules, e.start_ns), e.name)
            op_seconds[name] = op_seconds.get(name, 0.0) + (iv[1] - iv[0]) * 1e-9
        busy = _union(intervals)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [
        [_host_label(host_spans, (a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:top]
    ]
    device_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / len(devices),
        devices=len(devices),
        executables=executables,
        device_ops=[[k, v] for k, v in device_ops],
        idle_gaps=idle_gaps,
    )


def reduce_file(path) -> Optional[TraceSummary]:
    """:func:`reduce_profile` of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))
