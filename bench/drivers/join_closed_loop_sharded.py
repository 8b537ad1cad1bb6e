"""Closed-loop bulk probes of a table sharded over several chips.

Set-up, window and comparison are ``join_closed_loop``'s, unchanged: the
same ``DistributedHashTable.init`` and ``JoinPlan``, here over a mesh of
every chip of the cell, so each call routes its probe keys to their owners
and the rows back with two all-to-alls.  This driver adds only what the
exchange reports:

* counters, read after the window: ``exchange_rows_total.<phase>``, the
  real rows of each all-to-all summed over the window's calls (the
  program's ``ShardJoin.exchange_rows``), and ``exchange_slots_total.
  <phase>``, the slots those calls shipped (the program's counter of the
  same name in the process registry);
* work for the readers: the devices of the mesh, and the bytes a row of
  each phase carries.

A program without those counters leaves them out, and the readers that
need them then find nothing.
"""
from __future__ import annotations

import numpy as np

from bench.drivers.join_closed_loop import Driver as _ClosedLoop
from bench.drivers.join_closed_loop import control  # noqa: F401  (bench/control.py)

PHASES = ("route", "return")


def _slots() -> dict:
    """The program's ``exchange_slots_total`` per phase, or {} without it."""
    from repro.obs.tracing import process_tracer

    snap = process_tracer().registry.snapshot()
    if "exchange_slots_total" not in snap.types:
        return {}
    return {p: float(snap.value("exchange_slots_total", {"phase": p})) for p in PHASES}


class Driver(_ClosedLoop):
    def setup(self) -> None:
        super().setup()
        plan, self.rows = self.plan, []

        def counted(state, queries):
            res = plan(state, queries)
            self.rows.append(getattr(res, "exchange_rows", None))
            return res

        self.plan = counted

    def window(self) -> None:
        before = _slots()
        super().window()
        after = _slots()
        self.slots = {p: after[p] - before.get(p, 0.0) for p in after}

    def release(self) -> None:
        self.exchange = {}
        if self.slots and self.rows and all(r is not None for r in self.rows):
            rows = np.sum([np.asarray(r).sum(axis=0) for r in self.rows], axis=0)
            for p, n in zip(PHASES, rows):
                self.exchange[f"exchange_rows_total.{p}"] = float(n)
                self.exchange[f"exchange_slots_total.{p}"] = self.slots[p]
        self.rows = None
        super().release()

    def outcome(self):
        out = super().outcome()
        cfg = self.cell.config
        out.counters.update(self.exchange)
        out.work["exec_join"]["devices"] = len(self.cell.devices)
        out.work["exchange"] = {
            "bytes_per_row.route": 4 if cfg["key_dtype"] == "uint32" else 8,
            "bytes_per_row.return": 4 * cfg["value_cols"],
        }
        for p in PHASES:
            slots = self.exchange.get(f"exchange_slots_total.{p}")
            if slots:
                fill = 100.0 * self.exchange[f"exchange_rows_total.{p}"] / slots
                out.info[f"exchange_fill_pct.{p}"] = fill
        return out
