"""Bytes and least times for the roofline shares, computed from shapes.

``input_roofline_pct`` is a memory-bound share: the least time the chip
needs to read the statement's referenced base columns once, at the HBM
peak, over the device's busy time for the statement.  The referenced
columns and their types are in ``queries/<t>.json``; the widths below are
what the engine holds on the device (decimal(12,2) as scaled int64, dates
as int32 days, strings as int32 dictionary codes).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

DEVICE_WIDTH_BYTES = {"bigint": 8, "decimal": 8, "double": 8,
                      "date": 4, "integer": 4, "varchar": 4}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for a ``device_kind``; an unknown device is
    an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def input_bytes(columns: dict, row_counts: dict) -> int:
    """Bytes of one pass over ``columns`` ({table: {column: type}})."""
    return sum(row_counts[table] * DEVICE_WIDTH_BYTES[ctype]
               for table, cols in columns.items()
               for ctype in cols.values())


def least_read_seconds(nbytes: float, peaks: dict) -> float:
    return nbytes / peaks["hbm_bytes_per_s"]
