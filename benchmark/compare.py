"""The comparison that decides ``correct``: a served result against the
reference's rows, value by value, exactly.

Integer, decimal, date and string values must be equal (limit 0
mismatches).  A double value is held to ``DOUBLE_REL_TOL``: float64 sums
of a few million terms agree to ~1e-12 in any order, float32 ones only to
~1e-4, so 1e-9 fails a float32 accumulation and passes a reordered
float64 one.  No template of the benchmark returns a double yet.
"""

from __future__ import annotations

import math
from decimal import Decimal

DOUBLE_REL_TOL = 1e-9


def typed_rows(columns, rows):
    """Protocol rows with decimals (shipped as text) as ``Decimal``."""
    is_dec = [c["type"].lower().startswith("decimal") for c in columns]
    return [tuple(Decimal(v) if d and v is not None else v
                  for v, d in zip(row, is_dec)) for row in rows]


def _same(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        return math.isclose(a, b, rel_tol=DOUBLE_REL_TOL, abs_tol=0.0)
    return type(a) is type(b) and a == b


def _order_key(row):
    return tuple("" if v is None else str(v) for v in row)


def mismatches(got_rows, want_rows, ordered: bool) -> int:
    """Values that differ (a missing or extra row counts each of its
    values); 0 means the answer is the reference's."""
    got, want = list(got_rows), list(want_rows)
    if not ordered:
        got, want = sorted(got, key=_order_key), sorted(want, key=_order_key)
    width = len(want[0]) if want else (len(got[0]) if got else 0)
    bad = abs(len(got) - len(want)) * max(width, 1)
    for g, w in zip(got, want):
        if len(g) != len(w):
            bad += max(len(g), len(w))
            continue
        bad += sum(not _same(a, b) for a, b in zip(g, w))
    return bad
