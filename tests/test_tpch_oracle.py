"""TPC-H correctness: every query cross-checked against a sqlite3 oracle.

Reference analog: the H2 cross-check oracle (``testing/trino-testing/.../
H2QueryRunner.java`` + ``QueryAssertions``) used by AbstractTestQueries.
The engine runs the Trino-dialect text; sqlite runs a mechanically
translated variant (date literals folded, EXTRACT/SUBSTRING rewritten).
"""

import datetime
import math
import re
import sqlite3
from decimal import Decimal

import pytest

from trino_tpu import types as T
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session

EPOCH = datetime.date(1970, 1, 1)
SCHEMA = "micro"


def _days_to_iso(d):
    return (EPOCH + datetime.timedelta(days=d)).isoformat()


@pytest.fixture(scope="module", autouse=True)
def _fresh_jax_caches():
    """Late in a full tier-1 run this module's q17 compile aborts
    inside XLA (SIGABRT in backend_compile, CPU, single process,
    ~600 compiled programs accumulated; reproduces identically on the
    pre-PR-14 tree and with a cold persistent cache, passes when the
    module runs alone). Dropping the in-process jit caches before the
    module bounds the accumulated-executable state the crash needs;
    the queries recompile from the persistent on-disk cache, so the
    cost is seconds, not a cold trace."""
    import jax

    jax.clear_caches()


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(page_rows=8192)


@pytest.fixture(scope="module")
def runner(conn):
    return LocalQueryRunner({"tpch": conn},
                            Session(catalog="tpch", schema=SCHEMA))


def load_sqlite(conn, schema: str):
    """sqlite3 loaded with the same generated data (also the oracle of
    ``chip_smoke.py``'s CPU rehearsal)."""
    db = sqlite3.connect(":memory:")
    meta = conn.metadata()
    for table in meta.list_tables(schema):
        handle = meta.get_table_handle(schema, table)
        cols = meta.get_columns(handle)
        names = [c.name for c in cols]
        db.execute(f"create table {table} ({', '.join(names)})")
        for split in conn.split_manager().get_splits(handle, 1):
            src = conn.page_source(split, cols)
            while True:
                page = src.get_next_page()
                if page is None:
                    break
                lists = [b.to_pylist() for b in page.blocks]
                for i, c in enumerate(cols):
                    if c.type == T.DATE:
                        lists[i] = [None if v is None else _days_to_iso(v)
                                    for v in lists[i]]
                    elif c.type.is_decimal:
                        lists[i] = [None if v is None else float(v)
                                    for v in lists[i]]
                rows = list(zip(*lists))
                ph = ", ".join(["?"] * len(cols))
                db.executemany(
                    f"insert into {table} values ({ph})", rows)
    db.commit()
    return db


@pytest.fixture(scope="module")
def oracle(conn):
    return load_sqlite(conn, SCHEMA)


_DATE_INTERVAL = re.compile(
    r"date\s+'(\d+-\d+-\d+)'\s*([+-])\s*interval\s+'(\d+)'\s+"
    r"(day|month|year)", re.IGNORECASE)
_DATE_LIT = re.compile(r"date\s+'(\d+-\d+-\d+)'", re.IGNORECASE)
_EXTRACT = re.compile(r"extract\s*\(\s*year\s+from\s+([a-z_0-9.]+)\s*\)",
                      re.IGNORECASE)
_SUBSTRING = re.compile(
    r"substring\s*\(\s*([a-z_0-9.]+)\s+from\s+(\d+)\s+for\s+(\d+)\s*\)",
    re.IGNORECASE)


def _shift(date_text: str, sign: str, n: int, unit: str) -> str:
    y, m, d = map(int, date_text.split("-"))
    n = n if sign == "+" else -n
    if unit == "day":
        return (datetime.date(y, m, d)
                + datetime.timedelta(days=n)).isoformat()
    months = y * 12 + (m - 1) + n * (12 if unit == "year" else 1)
    ny, nm = divmod(months, 12)
    nm += 1
    # clamp day like civil-calendar addition
    while True:
        try:
            return datetime.date(ny, nm, d).isoformat()
        except ValueError:
            d -= 1


_DEC_ARITH = re.compile(r"(\d+\.\d+)\s*([-+])\s*(\d+\.\d+)")


def to_sqlite(sql: str) -> str:
    sql = _DATE_INTERVAL.sub(
        lambda m: "'" + _shift(m.group(1), m.group(2), int(m.group(3)),
                               m.group(4).lower()) + "'", sql)
    sql = _DATE_LIT.sub(lambda m: "'" + m.group(1) + "'", sql)
    sql = _EXTRACT.sub(
        lambda m: f"CAST(strftime('%Y', {m.group(1)}) AS INTEGER)", sql)
    sql = _SUBSTRING.sub(
        lambda m: f"substr({m.group(1)}, {m.group(2)}, {m.group(3)})", sql)
    # fold literal decimal arithmetic exactly: sqlite's float '0.06 + 0.01'
    # is 0.069999..., which breaks BETWEEN bounds the engine computes in
    # exact decimals
    sql = _DEC_ARITH.sub(
        lambda m: str(Decimal(m.group(1)) + Decimal(m.group(3))
                      if m.group(2) == "+"
                      else Decimal(m.group(1)) - Decimal(m.group(3))), sql)
    return sql


def _norm(v, type_=None):
    if v is None:
        return None
    if isinstance(v, Decimal):
        return float(v)
    if type_ == T.DATE and isinstance(v, int):
        return _days_to_iso(v)
    return v


def _close(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        try:
            fa, fb = float(a), float(b)
        except (TypeError, ValueError):
            return a == b
        # abs_tol 0.011 tolerates half-up (engine decimals) vs half-even
        # (python round) on exact .5 ties at scale 2
        return math.isclose(fa, fb, rel_tol=1e-6, abs_tol=0.011)
    return a == b


def _sort_key(row):
    return tuple("\0" if v is None else
                 (f"{v:.4f}" if isinstance(v, float) else str(v))
                 for v in row)


def assert_same(engine_res, oracle_rows, ordered: bool):
    got = [tuple(_norm(v, t) for v, t in zip(row, engine_res.types))
           for row in engine_res.rows]

    def quantize(v, t):
        # engine decimals round to their declared scale (Trino: avg over
        # decimal(p,s) returns decimal(p,s)); match the oracle to it
        if v is not None and t is not None and t.is_decimal and \
                isinstance(v, float):
            return round(v, t.scale)
        return v

    want = [tuple(quantize(_norm(v), t)
                  for v, t in zip(row, engine_res.types))
            for row in oracle_rows]
    assert len(got) == len(want), \
        f"row count {len(got)} != oracle {len(want)}\n" \
        f"got={got[:5]}\nwant={want[:5]}"
    if not ordered:
        got = sorted(got, key=_sort_key)
        want = sorted(want, key=_sort_key)
    for i, (g, w) in enumerate(zip(got, want)):
        assert len(g) == len(w), f"row {i} arity"
        for j, (a, b) in enumerate(zip(g, w)):
            assert _close(a, b), \
                f"row {i} col {j}: engine={a!r} oracle={b!r}\n" \
                f"engine row={g}\noracle row={w}"


@pytest.mark.parametrize("qid", sorted(TPCH_QUERIES))
def test_tpch_query_matches_oracle(qid, runner, oracle):
    sql = TPCH_QUERIES[qid]
    res = runner.execute(sql)
    want = oracle.execute(to_sqlite(sql)).fetchall()
    ordered = "order by" in sql.lower()
    assert_same(res, want, ordered)
