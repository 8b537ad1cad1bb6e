"""Request tracing — per-phase spans through the async serving pipeline.

Every ``AsyncFrontend`` submission can carry a :class:`Trace` that is
stamped at each pipeline boundary::

    admission -> linger -> dispatch -> device -> scatter

* **admission** — time spent inside ``submit_query`` getting the request
  into the deadline batcher (backpressure shows up here).
* **linger** — enqueue until the batcher flushed the request's batch
  (fill-triggered or deadline-triggered).
* **dispatch** — snapshot pin + bucket/pad + AOT executor launch.
* **device** — blocking on the device result (``block_until_ready``).
* **scatter** — host-side de-pad/slice and future resolution.

Phase durations aggregate into one registry histogram family
(``trace_phase_seconds{phase=...}``) plus an end-to-end
``request_latency_seconds``; the most recent completed traces are kept in
a bounded ring (constant memory) and can be dumped as JSONL for offline
timeline inspection.  A disabled tracer (``enabled=False``) costs one
attribute check per request and records nothing.

Host spans (:meth:`Tracer.span`) time the program's coarse steps — the
table build, a plan call, an AOT warm-up, a fold — on the profiler's
clock: while enabled a span records ``span_seconds{span=<name>}``, keeps
``(name, start, end, parent)`` in the same ring, and enters a
``jax.profiler.TraceAnnotation`` so it sits beside the device events of a
profiler trace.  JAX compile events (jaxpr trace, MLIR lowering, backend
compile or cache load) that happen inside an open span become its
children ``jax.trace``, ``jax.lower`` and ``jax.compile``.  Code with no
server of its own (the table, the plans) uses :func:`process_tracer`,
disabled by default.

Device stages carry the scope names of :data:`STAGES`
(:func:`stage`), so a profiler trace's ops read as the stage of the
table's code that emitted them.
"""
from __future__ import annotations

import collections
import contextlib
import json
import re
import threading
import time
from typing import Optional

import jax

from repro.obs.registry import MetricsRegistry

PHASES = ("admission", "linger", "dispatch", "device", "scatter")

# Device stages of the read/join executors and of the build, as
# ``jax.named_scope`` names (HLO ``op_name`` metadata).
STAGES = (
    "route",  # hash the queries, dispatch them to their owners
    "locate",  # owner-side bucket search of each routed query's run
    "gather",  # owner-side gather of the runs' values into segments
    "return",  # segments and counts home (the reverse all-to-all)
    "expand",  # querier-side expansion into the output rows
    "build.partition",  # balanced hash splits (build phase 1)
    "build.exchange",  # destinations and the all-to-all (phases 2, 3)
    "build.sort",  # the local bucket sort and payload permutation
    "build.offsets",  # the CSR bucket offsets
)

# jax.monitoring duration events recorded as children of the open span
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


def stage(name: str):
    """The ``jax.named_scope`` of device stage ``name`` (one of :data:`STAGES`)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    return jax.named_scope(name)


_HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*->.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_HLO_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_HLO_REF = re.compile(r"%([\w.\-]+)")
_TRANSFORM = re.compile(r"^[\w.\-]*\((.*)\)$")
_JAX_OP = re.compile(r"^[A-Za-z_]\w*$")  # a primitive's name, not an HLO one


def _scope_stage(op_name: str) -> Optional[str]:
    """The outermost stage among the scopes of an ``op_name`` path (its
    last component is the operation itself, e.g. the ``gather`` primitive,
    and is not a scope); transform wrappers such as ``vmap(...)`` are
    unwrapped."""
    for part in op_name.split("/")[:-1]:
        while (m := _TRANSFORM.match(part)) is not None:
            part = m.group(1)
        if part in STAGES:
            return part
    return None


def hlo_stages(hlo_text: str) -> dict:
    """Device stage of every instruction of an optimized HLO module.

    ``hlo_text`` is ``compiled.as_text()``.  An instruction's stage is the
    outermost :data:`STAGES` scope in its ``op_name`` metadata.  Inside a
    called computation (loop bodies, fusions, reducers) an instruction
    without one takes the stage of the instruction that calls it.  In the
    entry computation an instruction whose metadata names a JAX operation
    outside every stage is ``"other"``; XLA's own instructions (copies,
    materialized constants, the SPMD partitioner's) take the stage of
    their first user, else of their first operand, else are ``"other"``.
    Returns ``{instruction name: stage}``, the names as a profiler trace's
    device ops carry them.
    """
    comps: dict = {}  # name -> [(instruction, rest of line)]
    entry = None
    current = None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m is not None:
            current = comps.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is not None and current is not None:
            current.append((m.group(1), m.group(2)))
    stages: dict = {}

    def called(rest: str) -> list:
        out = _HLO_CALLS.findall(rest)
        for group in _HLO_CALL_LISTS.findall(rest):
            out += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        return out

    def own(rest: str) -> Optional[str]:
        """The stage the instruction's own metadata names: "other" for a
        JAX operation outside every stage, None for XLA's own instructions
        (no ``op_name``, or one ending in an HLO name such as the SPMD
        partitioner's ``shard_map/broadcast.37``)."""
        m = _HLO_OP_NAME.search(rest)
        if m is None:
            return None
        st = _scope_stage(m.group(1))
        if st is None and _JAX_OP.match(m.group(1).rsplit("/", 1)[-1]) and "/" in m.group(1):
            return "other"
        return st

    def visit(comp: str, inherited: str, seen: set) -> None:
        if comp in seen or comp not in comps:
            return
        seen.add(comp)
        for name, rest in comps[comp]:
            st = own(rest)
            st = inherited if st in (None, "other") else st
            stages[name] = st
            for c in called(rest):
                visit(c, st, seen)

    if entry is None:
        return stages
    seen = {entry}
    body = comps[entry]
    names = {name for name, _ in body}
    users: dict = {}
    operands: dict = {}
    for name, rest in body:
        stages[name] = own(rest)
        operands[name] = [r for r in _HLO_REF.findall(rest) if r in names and r != name]
        for ref in operands[name]:
            users.setdefault(ref, []).append(name)
    changed = True
    while changed:
        changed = False
        for name, _ in body:
            if stages[name] is None:
                near = (*users.get(name, ()), *operands[name])
                st = next((stages[n] for n in near if stages[n] not in (None, "other")), None)
                if st is not None:
                    stages[name] = st
                    changed = True
    for name, rest in body:
        stages[name] = stages[name] or "other"
        for c in called(rest):
            visit(c, stages[name], seen)
    return stages


class Trace:
    """One request's span: monotonic phase timestamps plus metadata.

    ``t0`` is the submission instant; ``marks[phase]`` is the *end* of that
    phase.  Phases are contiguous, so durations are successive differences.
    """

    __slots__ = ("trace_id", "t0", "marks", "size", "seqno", "bucket")

    def __init__(self, trace_id: int, t0: float, size: int):
        self.trace_id = trace_id
        self.t0 = t0
        self.marks: dict = {}
        self.size = size
        self.seqno = -1
        self.bucket = -1

    def mark(self, phase: str, t: float) -> None:
        self.marks[phase] = t

    def durations(self) -> dict:
        out = {}
        prev = self.t0
        for phase in PHASES:
            t = self.marks.get(phase)
            if t is None:
                continue
            out[phase] = max(0.0, t - prev)
            prev = t
        return out

    @property
    def total(self) -> float:
        last = max(self.marks.values()) if self.marks else self.t0
        return max(0.0, last - self.t0)

    def as_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "size": self.size,
            "seqno": self.seqno,
            "bucket": self.bucket,
            "total_seconds": self.total,
            "phases": self.durations(),
        }


class SpanRecord:
    """One closed host span: ``(name, start, end, parent)`` on the tracer's
    clock; ``parent`` is the name of the span it was opened in, or None."""

    __slots__ = ("name", "start", "end", "parent")

    def __init__(self, name: str, start: float, end: float, parent: Optional[str]):
        self.name = name
        self.start = start
        self.end = end
        self.parent = parent

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def as_dict(self) -> dict:
        return {
            "span": self.name,
            "start": self.start,
            "end": self.end,
            "parent": self.parent,
            "seconds": self.seconds,
        }


_open = threading.local()  # .stack: this thread's open spans, innermost last
_listening = False
_listen_lock = threading.Lock()


def _on_jax_event(event: str, duration: float, **kwargs) -> None:
    kind = _JAX_EVENTS.get(event)
    stack = getattr(_open, "stack", None)
    if kind is None or not stack:
        return
    span = stack[-1]
    end = span.tracer.clock()
    span.children.append((kind, end - duration, end))


def _listen_for_compiles() -> None:
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
            _listening = True


class _Stopwatch:
    """A disabled tracer's span: it reads the clock at both ends (callers
    such as the fold and warm-up instruments read ``seconds``) and emits
    nothing."""

    __slots__ = ("clock", "start", "end")

    def __init__(self, clock):
        self.clock = clock

    def __enter__(self):
        self.start = self.clock()
        return self

    def __exit__(self, *exc) -> None:
        self.end = self.clock()

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _Span(_Stopwatch):
    """An enabled tracer's span (see :meth:`Tracer.span`)."""

    __slots__ = ("tracer", "name", "parent", "children", "_annotation")

    def __init__(self, tracer: "Tracer", name: str):
        super().__init__(tracer.clock)
        self.tracer = tracer
        self.name = name
        self.children: list = []  # (jax event kind, start, end)

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start = self.clock()
        return self

    def __exit__(self, *exc) -> None:
        self.end = self.clock()
        self._annotation.__exit__(*exc)
        _open.stack.pop()
        records = [SpanRecord(self.name, self.start, self.end, self.parent)]
        # Nested jits report their own trace events inside the outer one's:
        # keep only the outermost interval of each kind.
        last: dict = {}
        for kind, a, b in sorted(self.children, key=lambda c: (c[1], -c[2])):
            if kind in last and b <= last[kind]:
                continue
            last[kind] = b
            records.append(SpanRecord(kind, a, b, self.name))
        self.tracer._record_spans(records)


class Tracer:
    """Factory + sink for :class:`Trace` spans, backed by a registry.

    ``start``/``finish`` bracket a request; in between the pipeline stamps
    phase marks directly on the trace object (no tracer lock touched).
    ``finish`` folds the phase durations into the registry histograms and
    appends the trace to the bounded ring.  ``live()`` counts traces
    started but not finished — the CI gate asserts it returns to zero
    after drain (a leak here means a request fell out of the pipeline).
    """

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        *,
        ring: int = 256,
        enabled: bool = True,
        clock=time.perf_counter,
    ):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.enabled = enabled
        self.clock = clock
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=max(0, ring))
        self._next_id = 0
        self._started = 0
        self._finished = 0
        self._phase_hists = {
            phase: self.registry.histogram(
                "trace_phase_seconds",
                labels={"phase": phase},
                help="Per-phase request latency through the async pipeline.",
            )
            for phase in PHASES
        }
        self._total_hist = self.registry.histogram(
            "request_latency_seconds",
            help="End-to-end submit-to-result latency.",
        )
        self._recorded = self.registry.counter(
            "traces_recorded_total", help="Completed traces folded into histograms."
        )
        self._span_hists: dict = {}

    def span(self, name: str):
        """A host span around one step of the program, as a context manager.

        Enabled, the span records ``span_seconds{span=name}`` (sum and
        count) in this tracer's registry, keeps ``(name, start, end,
        parent)`` in the ring (the parent is the innermost span open on
        this thread), and holds a ``jax.profiler.TraceAnnotation`` so a
        profiler trace shows it on the device events' clock.  JAX compile
        events inside it are recorded as its children ``jax.trace``,
        ``jax.lower`` and ``jax.compile``.  Disabled, it only reads the
        clock at its two ends and emits nothing.  Either way the context
        manager's value has ``seconds`` once the block has exited.
        """
        if not self.enabled:
            return _Stopwatch(self.clock)
        _listen_for_compiles()
        return _Span(self, name)

    def annotate(self, name: str):
        """A profiler annotation only (no registry record, no ring entry)
        while enabled; a no-op context manager while disabled."""
        if not self.enabled:
            return _NULL
        return jax.profiler.TraceAnnotation(name)

    def _record_spans(self, records: list) -> None:
        for r in records:
            hist = self._span_hists.get(r.name)
            if hist is None:
                hist = self._span_hists[r.name] = self.registry.histogram(
                    "span_seconds",
                    labels={"span": r.name},
                    help="Host spans of the program (Tracer.span).",
                )
            hist.observe(r.seconds)
        with self._lock:
            if self._ring.maxlen:
                self._ring.extend(records)

    def start(self, size: int = 1) -> Optional[Trace]:
        if not self.enabled:
            return None
        with self._lock:
            tid = self._next_id
            self._next_id += 1
            self._started += 1
        return Trace(tid, self.clock(), size)

    def finish(self, trace: Optional[Trace]) -> None:
        if trace is None:
            return
        for phase, dur in trace.durations().items():
            self._phase_hists[phase].observe(dur)
        self._total_hist.observe(trace.total)
        self._recorded.inc()
        with self._lock:
            self._finished += 1
            if self._ring.maxlen:
                self._ring.append(trace)

    def abandon(self, trace: Optional[Trace]) -> None:
        """Drop a trace whose request failed — keeps ``live()`` honest
        without polluting the latency histograms with error paths."""
        if trace is None:
            return
        with self._lock:
            self._finished += 1

    def live(self) -> int:
        with self._lock:
            return self._started - self._finished

    def recent(self) -> list:
        """Most recent completed traces and closed spans, oldest first."""
        with self._lock:
            return list(self._ring)

    def dump_jsonl(self, path: str) -> int:
        """Append the ring's traces and spans to ``path`` as JSONL; returns
        count."""
        traces = self.recent()
        with open(path, "a") as f:
            for t in traces:
                f.write(json.dumps(t.as_dict(), sort_keys=True) + "\n")
        return len(traces)


_NULL = contextlib.nullcontext()
_PROCESS = Tracer(ring=4096, enabled=False)


def process_tracer() -> Tracer:
    """The tracer of code that has no server of its own (the table build,
    the plans).  Disabled until ``process_tracer().enabled = True``."""
    return _PROCESS


__all__ = [
    "PHASES",
    "STAGES",
    "SpanRecord",
    "Trace",
    "Tracer",
    "hlo_stages",
    "process_tracer",
    "stage",
]
