"""Reduce a JAX profiler trace by the program's device stages and host spans.

A companion of :mod:`bench.trace_reduce` that leaves its numbers alone.
Within the host span ``bench.window`` it takes, from the device planes:

* per executable and stage, the seconds of the **union** of the intervals
  of that stage's ops (a loop and the ops of its body overlap in time, so
  a sum would count them twice).  The stage of each op comes from
  ``stage_of``: ``{jit name: {HLO instruction name: stage}}``, as
  ``repro.obs.tracing.hlo_stages`` reads it from the executable's
  optimized HLO; ops it does not name are "other";
* the longest idle gaps, each labelled ``<program span>/<JAX span>``: the
  innermost span of the program's tracer (names under ``table.``,
  ``plan.``, ``server.``, ``frontend.``) and the innermost other host span
  (JAX's own, such as ``PjitFunction(exec_join)``) that covered the gap's
  middle, ``-`` where there was none; the benchmark's own span when
  neither was there, else "no host span";
* the offset of the device's clock from the host's: the median, over the
  executables' runs in the window, of the run's start minus the start of
  the ``plan.<kind>`` span that dispatched it (``exec_<kind>``, the
  nearest such span).  It includes the host's dispatch latency; gap labels
  are read at ``middle - offset``.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import statistics
from typing import Optional

from bench.trace_reduce import (
    _DEVICE_PLANE,
    _MODULES_LINE,
    _OPS_LINE,
    _SUFFIX,
    BENCH_PREFIX,
    WINDOW_SPAN,
    _clip,
    _jit_of,
    _module_at,
    _union,
)

PROGRAM_SPAN_ROOTS = ("table", "plan", "server", "frontend")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=")


@dataclasses.dataclass(frozen=True)
class StageSummary:
    window_s: float
    devices: int
    calls: dict  # jit name -> runs that started in the window (all devices)
    module_s: dict  # jit name -> device seconds of those runs
    stage_s: dict  # jit name -> {stage: seconds of the union of its ops}
    idle_gaps: list  # [[label, seconds]], longest first
    clock_offset_ms: Optional[float]

    def per_call_ms(self, jit_name: str, stage: str) -> Optional[float]:
        """Mean milliseconds of ``stage`` per run of ``jit_name``."""
        calls = self.calls.get(jit_name)
        if not calls:
            return None
        return 1e3 * self.stage_s.get(jit_name, {}).get(stage, 0.0) / calls

    def other_share(self, jit_name: str) -> Optional[float]:
        """Share of the stages' seconds of ``jit_name`` that no stage names."""
        stages = self.stage_s.get(jit_name)
        if not stages:
            return None
        return stages.get("other", 0.0) / sum(stages.values())


def _instruction(op: str) -> str:
    m = _INSTRUCTION.match(op)
    return m.group(1) if m else op


def _is_program_span(name: str) -> bool:
    return name.split(".", 1)[0] in PROGRAM_SPAN_ROOTS and "." in name


def _innermost(spans, t: float) -> Optional[str]:
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return best[1] if best else None


def _label(program, jax_spans, bench, t: float) -> str:
    p, j = _innermost(program, t), _innermost(jax_spans, t)
    if p is None and j is None:
        return _innermost(bench, t) or "no host span"
    return f"{p or '-'}/{j or '-'}"


def _clock_offset_ms(plan_spans, runs) -> Optional[float]:
    """Median of run start minus the nearest dispatching span's start."""
    starts: dict = {}
    for name, a, _ in plan_spans:
        starts.setdefault("exec_" + name.split(".", 1)[1], []).append(a)
    samples = []
    for jit, a in runs:
        s = sorted(starts.get(jit, ()))
        if not s:
            continue
        i = bisect.bisect_left(s, a)
        near = min(s[max(0, i - 1) : i + 1], key=lambda x: abs(a - x))
        samples.append((a - near) * 1e-6)
    return statistics.median(samples) if samples else None


def reduce_stages(profile, stage_of: dict, top: int = 10) -> Optional[StageSummary]:
    """Reduce a ``jax.profiler.ProfileData``; None when the trace has no
    ``bench.window`` span or no device plane."""
    window = None
    program, jax_spans, bench = [], [], []
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
                span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name == WINDOW_SPAN:
                    window = span[1:]
                elif e.name.startswith(BENCH_PREFIX):
                    bench.append(span)
                elif _is_program_span(e.name):
                    program.append(span)
                else:
                    jax_spans.append(span)
    if window is None or not devices:
        return None
    lo, hi = window
    calls: dict = {}
    module_s: dict = {}
    stage_iv: dict = {}  # (jit, stage) -> intervals, per device plane
    stage_s: dict = {}
    runs = []
    gaps = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, _SUFFIX.sub("", e.name))
            for e in (lines[_MODULES_LINE].events if _MODULES_LINE in lines else ())
        )
        for a, b, name in modules:
            if lo <= a < hi:
                jit = _jit_of(name)
                calls[jit] = calls.get(jit, 0) + 1
                module_s[jit] = module_s.get(jit, 0.0) + (b - a) * 1e-9
                runs.append((jit, a))
        starts = [m[0] for m in modules]
        busy = []
        stage_iv.clear()
        for e in lines[_OPS_LINE].events if _OPS_LINE in lines else ():
            iv = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
            if iv is None:
                continue
            busy.append(iv)
            jit = _jit_of(_module_at(starts, modules, e.start_ns))
            stage = stage_of.get(jit, {}).get(_instruction(e.name), "other")
            stage_iv.setdefault((jit, stage), []).append(iv)
        for (jit, stage), ivs in stage_iv.items():
            secs = sum(b - a for a, b in _union(ivs)) * 1e-9
            per = stage_s.setdefault(jit, {})
            per[stage] = per.get(stage, 0.0) + secs
        edges = [lo] + [x for iv in _union(busy) for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    plan_spans = [s for s in program if s[0].startswith("plan.")]
    offset_ms = _clock_offset_ms(plan_spans, runs)
    shift = (offset_ms or 0.0) * 1e6
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [
        [_label(program, jax_spans, bench, (a + b) / 2 - shift), (b - a) * 1e-9]
        for a, b in gaps[:top]
    ]
    return StageSummary(
        window_s=(hi - lo) * 1e-9,
        devices=len(devices),
        calls=calls,
        module_s=module_s,
        stage_s=stage_s,
        idle_gaps=idle_gaps,
        clock_offset_ms=offset_ms,
    )


def reduce_stages_file(path, stage_of: dict) -> Optional[StageSummary]:
    """:func:`reduce_stages` of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return reduce_stages(ProfileData.from_file(str(path)), stage_of)
