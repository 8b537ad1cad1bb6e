"""Peaks of the chip and the least bytes an operation must move.

The byte counts are computed from shapes and count what the work requires,
whatever implements it: no sort, no bisection, no padding.  A kernel's
roofline share is those bytes over (the chip's HBM bandwidth times the
kernel's device time from the trace).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def probe_min_bytes(
    probe_keys: int,
    matched_rows: int,
    *,
    key_bytes: int,
    value_bytes: int,
    directory_entry_bytes: int = 8,
    output_index_bytes: int = 4,
) -> int:
    """Least HBM bytes of one probe call that returns every matching row.

    Each probe key is read once and looks up one bucket-directory entry
    (its start and end offsets, 8 bytes);
    each matched row's key and payload are read once; each output row (its
    probe index and payload) is written once.
    """
    reads = probe_keys * (key_bytes + directory_entry_bytes)
    reads += matched_rows * (key_bytes + value_bytes)
    writes = matched_rows * (output_index_bytes + value_bytes)
    return int(reads + writes)
