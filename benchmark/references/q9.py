"""TPC-H Q9 in plain numpy: the parts whose name holds the colour, their
``lineitem`` rows each with its ``partsupp`` row (by the pair of keys),
its supplier's nation and its order's year; profit summed as scaled
int64 per nation and year, ordered by nation, year descending."""

import numpy as np

from .decimals import dec

_YEAR0 = 1970


def reference(tables, params, acc=np.int64, partsupp_key="pair"):
    pkey, (pcodes, pnames) = tables.columns("part", ["p_partkey", "p_name"])
    skey, snation = tables.columns("supplier", ["s_suppkey", "s_nationkey"])
    nkey, (ncodes, nnames) = tables.columns(
        "nation", ["n_nationkey", "n_name"])
    pspart, pssupp, cost = tables.columns(
        "partsupp", ["ps_partkey", "ps_suppkey", "ps_supplycost"])
    okey, odate = tables.columns("orders", ["o_orderkey", "o_orderdate"])
    lorder, lpart, lsupp, qty, price, disc = tables.columns(
        "lineitem", ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                     "l_extendedprice", "l_discount"])

    green = np.array([params["COLOR"] in v for v in pnames], dtype=bool)
    part_ok = np.zeros(int(max(pkey.max(), lpart.max())) + 1, dtype=bool)
    part_ok[pkey[green[pcodes]]] = True
    nation_of = np.full(int(max(skey.max(), lsupp.max())) + 1, -1,
                        dtype=np.int64)
    nation_of[skey] = snation
    name_of = np.full(int(nkey.max()) + 1, -1, dtype=np.int64)
    name_of[nkey] = ncodes
    year_of = np.full(int(max(okey.max(), lorder.max())) + 1, -1,
                      dtype=np.int64)
    year_of[okey] = odate.astype("datetime64[D]").astype(
        "datetime64[Y]").astype(np.int64) + _YEAR0

    rows = np.nonzero(part_ok[lpart] & (nation_of[lsupp] >= 0)
                      & (year_of[lorder] >= 0))[0]
    # partsupp by (ps_partkey, ps_suppkey): one sorted index over the pair
    # (the control ``partsupp_key="part"`` joins on ps_partkey alone, to
    # the first of a part's four rows)
    width = int(max(pssupp.max(), lsupp.max())) + 1
    if partsupp_key == "pair":
        index, probe = pspart * width + pssupp, \
            lpart[rows] * width + lsupp[rows]
    else:
        index, probe = pspart, lpart[rows]
    order = np.argsort(index, kind="stable")
    at = np.minimum(np.searchsorted(index[order], probe), len(order) - 1)
    found = index[order][at] == probe
    rows, ps = rows[found], order[at[found]]
    nation = name_of[nation_of[lsupp[rows]]]
    rows, ps, nation = rows[nation >= 0], ps[nation >= 0], nation[nation >= 0]

    # units of 10**-4: scale 2 times scale 2 on both sides of the minus
    amount = (price[rows] * (100 - disc[rows])
              - cost[ps] * qty[rows]).astype(acc)
    year = year_of[lorder[rows]]
    y0 = int(year.min()) if len(year) else 0
    years = (int(year.max()) - y0 + 1) if len(year) else 0
    total = np.zeros((len(nnames), years), dtype=acc)
    seen = np.zeros((len(nnames), years), dtype=bool)
    np.add.at(total, (nation, year - y0), amount)
    seen[nation, year - y0] = True
    out = [(nnames[n], int(y0 + y), dec(int(total[n, y]), 4))
           for n, y in zip(*np.nonzero(seen))]
    return sorted(out, key=lambda r: (r[0], -r[1]))
