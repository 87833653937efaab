"""TPC-H Q1 in plain numpy: scaled-int64 sums per (returnflag, linestatus)."""

import numpy as np

from .decimals import avg_half_up, dec
from .hosttables import days


def reference(tables, params, acc=np.int64):
    """``acc`` is the accumulator type: int64 is exact; the control of
    ``benchmark/tests`` passes float32 / int32 to show the comparison fails."""
    flag, status, qty, price, disc, tax, ship = tables.columns(
        "lineitem", ["l_returnflag", "l_linestatus", "l_quantity",
                     "l_extendedprice", "l_discount", "l_tax", "l_shipdate"])
    keep = ship <= days("1998-12-01") - int(params["DELTA"])
    (fcodes, fvals), (scodes, svals) = flag, status
    key = fcodes.astype(np.int64) * len(svals) + scodes
    disc_price = price * (100 - disc)           # scale 4
    charge = disc_price * (100 + tax)           # scale 6
    rows = []
    for k in np.unique(key[keep]):
        sel = keep & (key == k)
        n = int(sel.sum())
        s = [int(v[sel].astype(acc).sum(dtype=acc))
             for v in (qty, price, disc_price, charge, disc)]
        rows.append((fvals[int(k) // len(svals)], svals[int(k) % len(svals)],
                     dec(s[0], 2), dec(s[1], 2), dec(s[2], 4), dec(s[3], 6),
                     dec(avg_half_up(s[0], n), 2),
                     dec(avg_half_up(s[1], n), 2),
                     dec(avg_half_up(s[4], n), 2), n))
    return sorted(rows, key=lambda r: (r[0], r[1]))
