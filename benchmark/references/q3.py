"""TPC-H Q3 in plain numpy: two key-indexed semi-joins, a grouped
scaled-int64 sum, top 10 by (revenue desc, orderdate)."""

import numpy as np

from .decimals import dec
from .hosttables import days, iso


def reference(tables, params, acc=np.int64):
    custkey, (segcodes, segvals) = tables.columns(
        "customer", ["c_custkey", "c_mktsegment"])
    okey, ocust, odate, oprio = tables.columns(
        "orders", ["o_orderkey", "o_custkey", "o_orderdate",
                   "o_shippriority"])
    lkey, price, disc, ship = tables.columns(
        "lineitem", ["l_orderkey", "l_extendedprice", "l_discount",
                     "l_shipdate"])
    date = days(params["DATE"])
    cust_ok = np.zeros(int(custkey.max()) + 1, dtype=bool)
    cust_ok[custkey[segcodes == segvals.index(params["SEGMENT"])]] = True
    order_sel = cust_ok[ocust] & (odate < date)
    order_row = np.full(int(okey.max()) + 1, -1, dtype=np.int64)
    order_row[okey[order_sel]] = np.nonzero(order_sel)[0]
    line_sel = (ship > date) & (order_row[lkey] >= 0)
    keys = lkey[line_sel]
    revenue = (price[line_sel] * (100 - disc[line_sel])).astype(acc)
    order = np.argsort(keys, kind="stable")
    keys, revenue = keys[order], revenue[order]
    starts = np.nonzero(np.r_[True, keys[1:] != keys[:-1]])[0] \
        if len(keys) else np.zeros(0, dtype=np.int64)
    gkeys = keys[starts]
    grev = np.add.reduceat(revenue, starts).astype(np.int64) \
        if len(keys) else np.zeros(0, dtype=np.int64)
    orow = order_row[gkeys]
    top = np.lexsort((odate[orow], -grev))[:10]
    return [(int(gkeys[i]), dec(grev[i], 4), iso(odate[orow[i]]),
             int(oprio[orow[i]])) for i in top]
