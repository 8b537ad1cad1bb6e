"""Device-cost accounting — a jaxpr-walking collective/kernel accountant.

Two complementary sources, combined per compiled executor:

* **jaxpr walk** (:func:`collective_profile`): trace the executor with
  ``jax.make_jaxpr`` and count every collective primitive (``all_to_all``,
  ``psum`` …) anywhere in the nested jaxpr, summing the output aval bytes
  of each — the bytes one device moves through that collective.  This is
  exact program structure, independent of the backend: it is how the CI
  gate *independently re-confirms* the fused routing budget (exactly two
  all-to-alls per query/retrieve at every delta depth).
* **XLA cost analysis** (via :func:`~repro.utils.compat.
  compiled_cost_analysis`): FLOPs and bytes-accessed estimates from the
  compiled executable, giving a FLOP/byte arithmetic-intensity figure per
  executor.  A figure XLA does not report stays ``None`` (not measured).

``warm_server`` runs :func:`profile_executor` once per distinct program
structure in the AOT grid and stores the resulting
:class:`ExecutorCost` rows on the :class:`~repro.serve_table.aot.
ExecutorGrid`, where ``server.metrics()`` and the benches surface them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.extend import core as jcore

from repro.utils.compat import compiled_cost_analysis

# Cross-device data movement primitives to account for.  ``psum`` covers
# the replicated reductions (join_size, live counts); the all_to_alls are
# the routing rounds the paper's scalability argument rests on.
COLLECTIVE_PRIMITIVES = (
    "all_to_all",
    "all_gather",
    "psum",
    "ppermute",
    "reduce_scatter",
)


def _iter_jaxprs(v):
    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _iter_jaxprs(x)


def _aval_bytes(var) -> int:
    aval = var.aval
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * dtype.itemsize


def _walk(jaxpr, counts: dict, bytes_: dict) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in counts:
            counts[name] += 1
            bytes_[name] += sum(_aval_bytes(v) for v in eqn.outvars)
        for v in eqn.params.values():
            for sub in _iter_jaxprs(v):
                _walk(sub, counts, bytes_)


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` anywhere in a (nested) jaxpr."""
    counts = {name: 0}
    bytes_ = {name: 0}
    _walk(jaxpr, counts, bytes_)
    return counts[name]


@dataclasses.dataclass(frozen=True)
class ExecutorCost:
    """Static device-cost profile of one compiled executor.

    Collective counts/bytes come from the jaxpr walk (bytes are per-device
    output payload of each collective, summed over occurrences); ``flops``
    and ``bytes_accessed`` come from XLA's cost analysis of the compiled
    executable (``None`` — not measured — without an executable, or when
    XLA reports no such figure).
    """

    kind: str  # "query" | "retrieve" | ...
    bucket: int  # query batch size the executor was lowered for
    depth: int  # delta depth of the state structure
    collective_counts: dict  # primitive name -> occurrence count
    collective_bytes: dict  # primitive name -> summed output bytes
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None

    @property
    def all_to_alls(self) -> int:
        return self.collective_counts.get("all_to_all", 0)

    @property
    def all_to_all_bytes(self) -> int:
        return self.collective_bytes.get("all_to_all", 0)

    @property
    def total_collective_bytes(self) -> int:
        return sum(self.collective_bytes.values())

    @property
    def flop_per_byte(self) -> Optional[float]:
        if self.flops is None or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def as_dict(self) -> dict:
        return {
            "kind": self.kind,
            "bucket": self.bucket,
            "depth": self.depth,
            "all_to_alls": self.all_to_alls,
            "all_to_all_bytes": self.all_to_all_bytes,
            "collective_counts": dict(self.collective_counts),
            "collective_bytes": dict(self.collective_bytes),
            "total_collective_bytes": self.total_collective_bytes,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "flop_per_byte": self.flop_per_byte,
        }


def collective_profile(fn, *args) -> tuple:
    """``(counts, bytes)`` dicts for every collective in ``fn(*args)``.

    Traces with ``jax.make_jaxpr`` (abstract — no device execution) and
    walks the nested jaxpr.  Only primitives with nonzero occurrence are
    kept, so the dicts double as a compact "which collectives does this
    program use" fingerprint.
    """
    jx = jax.make_jaxpr(fn)(*args)
    counts = {p: 0 for p in COLLECTIVE_PRIMITIVES}
    bytes_ = {p: 0 for p in COLLECTIVE_PRIMITIVES}
    _walk(jx.jaxpr, counts, bytes_)
    counts = {k: v for k, v in counts.items() if v}
    bytes_ = {k: v for k, v in bytes_.items() if v}
    return counts, bytes_


def profile_executor(
    table,
    state,
    queries,
    *,
    kind: str,
    compiled=None,
    exec_kwargs: Optional[dict] = None,
) -> ExecutorCost:
    """Profile one executor structure: jaxpr walk + XLA cost analysis.

    ``kind`` selects the executor (``"query"`` / ``"retrieve"``);
    ``exec_kwargs`` carries its static capacities.  ``compiled`` (a
    ``jax.stages.Compiled``, e.g. out of the AOT grid) supplies the
    FLOP/bytes-accessed estimates when given.
    """
    from repro.core import plans

    kw = dict(exec_kwargs or {})
    if kind == "query":
        fn = lambda s, q: plans.exec_query(table, s, q, **kw)
    elif kind == "retrieve":
        fn = lambda s, q: plans.exec_retrieve(table, s, q, **kw)
    else:
        raise ValueError(f"unknown executor kind {kind!r}")
    counts, bytes_ = collective_profile(fn, state, queries)
    flops = bytes_accessed = None
    if compiled is not None:
        cost = compiled_cost_analysis(compiled)
        if "flops" in cost:
            flops = float(cost["flops"])
        if "bytes accessed" in cost:
            bytes_accessed = float(cost["bytes accessed"])
    return ExecutorCost(
        kind=kind,
        bucket=int(queries.shape[0]),
        depth=max(0, len(state.deltas)),
        collective_counts=counts,
        collective_bytes=bytes_,
        flops=flops,
        bytes_accessed=bytes_accessed,
    )


__all__ = [
    "COLLECTIVE_PRIMITIVES",
    "ExecutorCost",
    "collective_profile",
    "count_primitive",
    "profile_executor",
]
