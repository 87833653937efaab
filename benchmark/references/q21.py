"""TPC-H Q21 in plain numpy: the late lines (``l_receiptdate >
l_commitdate``) of the nation's suppliers on orders of status ``F`` that
another supplier also filled and on which no other supplier was late,
counted per supplier name; top 100 by (count desc, name).

Per order the distinct ``(l_orderkey, l_suppkey)`` pairs are counted
twice, over all lines and over the late ones: a supplier with two lines
in an order is one supplier, and both of its late lines count.  A late
line of supplier s passes ``EXISTS (l2 ... <> s)`` iff its order has two
suppliers or more, and ``NOT EXISTS (l3 late ... <> s)`` iff its order's
late suppliers are s alone (one late supplier: the line is late itself).

The controls each answer another question, and must fail the comparison
on the data: ``other="line"`` drops both ``<>`` (any other line will do,
the same supplier's included: lines counted where suppliers should be),
``not_exists=False`` drops the ``NOT EXISTS``, ``status_f=False`` forgets
``o_orderstatus = 'F'``."""

import numpy as np


def _per_order(orderkey, suppkey, size, other):
    """Distinct suppliers (or, for the control, lines) per order over
    the given lines."""
    if other == "line":
        return np.bincount(orderkey, minlength=size)
    width = int(suppkey.max()) + 1 if len(suppkey) else 1
    pairs = np.unique(orderkey * width + suppkey)
    return np.bincount(pairs // width, minlength=size)


def reference(tables, params, other="supplier", not_exists=True,
              status_f=True):
    skey, (scodes, snames), snation = tables.columns(
        "supplier", ["s_suppkey", "s_name", "s_nationkey"])
    nkey, (ncodes, nnames) = tables.columns(
        "nation", ["n_nationkey", "n_name"])
    okey, (ocodes, ostatus) = tables.columns(
        "orders", ["o_orderkey", "o_orderstatus"])
    lorder, lsupp, commit, receipt = tables.columns(
        "lineitem", ["l_orderkey", "l_suppkey", "l_commitdate",
                     "l_receiptdate"])

    orders = int(max(okey.max(), lorder.max())) + 1
    late = receipt > commit
    suppliers = _per_order(lorder, lsupp, orders, other)
    late_suppliers = _per_order(lorder[late], lsupp[late], orders, other)
    order_ok = np.zeros(orders, dtype=bool)
    is_f = np.array([v == "F" for v in ostatus], dtype=bool)[ocodes]
    order_ok[okey[is_f] if status_f else okey] = True

    nations = nkey[np.array([v == params["NATION"] for v in nnames],
                            dtype=bool)[ncodes]]
    name_of = np.full(int(max(skey.max(), lsupp.max())) + 1, -1,
                      dtype=np.int64)
    ours = np.isin(snation, nations)
    name_of[skey[ours]] = scodes[ours]

    rows = late & order_ok[lorder] & (name_of[lsupp] >= 0) \
        & (suppliers[lorder] >= 2)
    if not_exists:
        rows &= late_suppliers[lorder] == 1
    numwait = np.bincount(name_of[lsupp[rows]], minlength=len(snames))
    out = sorted(((snames[c], int(numwait[c]))
                  for c in np.nonzero(numwait)[0]),
                 key=lambda r: (-r[1], r[0]))
    return out[:100]
