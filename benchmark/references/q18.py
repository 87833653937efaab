"""TPC-H Q18 in plain numpy: ``l_quantity`` summed per order, the orders
whose sum is over the threshold, each with its customer's name, top 100
by (totalprice desc, orderdate)."""

import numpy as np

from .decimals import dec
from .hosttables import iso


def reference(tables, params, acc=np.int64):
    custkey, (namecodes, names) = tables.columns(
        "customer", ["c_custkey", "c_name"])
    okey, ocust, total, odate = tables.columns(
        "orders", ["o_orderkey", "o_custkey", "o_totalprice",
                   "o_orderdate"])
    lkey, qty = tables.columns("lineitem", ["l_orderkey", "l_quantity"])
    per_order = np.zeros(int(max(okey.max(), lkey.max())) + 1, dtype=acc)
    np.add.at(per_order, lkey, qty.astype(acc))
    name_of = np.full(int(max(custkey.max(), ocust.max())) + 1, -1,
                      dtype=np.int64)
    name_of[custkey] = namecodes
    # scale 2 on both sides of the comparison; the joins keep an order
    # only if it has a customer (and a line, which a sum over 0 implies)
    rows = np.nonzero((per_order[okey] > int(params["QUANTITY"]) * 100)
                      & (name_of[ocust] >= 0))[0]
    top = rows[np.lexsort((odate[rows], -total[rows]))[:100]]
    return [(names[name_of[ocust[i]]], int(ocust[i]), int(okey[i]),
             iso(odate[i]), dec(total[i], 2),
             dec(int(per_order[okey[i]]), 2)) for i in top]
