"""TPC-H Q13 in plain numpy: orders per customer (outer join keeps the
customers with none), then customers per count."""

import re

import numpy as np


def reference(tables, params, acc=np.int64):
    custkey = tables.column("customer", "c_custkey")
    ocust, (ccodes, cvals) = tables.columns(
        "orders", ["o_custkey", "o_comment"])
    like = re.compile(re.escape(params["WORD1"]) + ".*"
                      + re.escape(params["WORD2"]), re.DOTALL)
    hit = np.fromiter((like.search(v) is not None for v in cvals),
                      dtype=bool, count=len(cvals))
    kept = ocust[~hit[ccodes]]
    per_cust = np.bincount(kept, minlength=int(custkey.max()) + 1)[custkey]
    counts, dist = np.unique(per_cust.astype(acc), return_counts=True)
    rows = sorted(zip(dist.tolist(), counts.tolist()), reverse=True)
    return [(int(c), int(d)) for d, c in rows]
