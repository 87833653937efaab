"""TPC-H Q6 in plain numpy: one filtered scaled-int64 sum."""

from decimal import Decimal

import numpy as np

from .decimals import dec
from .hosttables import EPOCH, days, iso


def reference(tables, params, acc=np.int64):
    qty, price, disc, ship = tables.columns(
        "lineitem", ["l_quantity", "l_extendedprice", "l_discount",
                     "l_shipdate"])
    lo = days(params["DATE"])
    first = EPOCH.fromisoformat(params["DATE"])
    hi = days(first.replace(year=first.year + 1).isoformat())
    cents = int(Decimal(str(params["DISCOUNT"])) * 100)
    sel = (ship >= lo) & (ship < hi) & (disc >= cents - 1) \
        & (disc <= cents + 1) & (qty < int(params["QUANTITY"]) * 100)
    if not sel.any():
        return [(None,)]
    total = (price[sel] * disc[sel]).astype(acc).sum(dtype=acc)
    return [(dec(int(total), 4),)]
