"""sqlite loaded from the connector's host pages, and the four templates'
SQL in sqlite's dialect (dates as ISO text, literal arithmetic folded)."""

import datetime
import re
import sqlite3
from decimal import Decimal

from benchmark.references.hosttables import HostTables, iso

_INTERVAL = re.compile(r"date\s+'([\d-]+)'\s*([+-])\s*interval\s+'(\d+)'\s+"
                       r"(day|year)", re.IGNORECASE)
_DATE = re.compile(r"date\s+'([\d-]+)'", re.IGNORECASE)
_DEC = re.compile(r"(\d+\.\d+)\s*([-+])\s*(\d+\.\d+)")


def _shift(text, sign, n, unit):
    d = datetime.date.fromisoformat(text)
    n = int(n) if sign == "+" else -int(n)
    if unit.lower() == "day":
        return (d + datetime.timedelta(days=n)).isoformat()
    return d.replace(year=d.year + n).isoformat()


def to_sqlite(sql: str) -> str:
    sql = _INTERVAL.sub(lambda m: "'" + _shift(*m.groups()) + "'", sql)
    sql = _DATE.sub(lambda m: "'" + m.group(1) + "'", sql)
    return _DEC.sub(lambda m: str(
        Decimal(m.group(1)) + Decimal(m.group(3)) if m.group(2) == "+"
        else Decimal(m.group(1)) - Decimal(m.group(3))), sql)


def load(schema: str, columns: dict):
    """``columns``: {table: {column: type}} as the query files state."""
    tables = HostTables(schema)
    db = sqlite3.connect(":memory:")
    for table, cols in columns.items():
        names = list(cols)
        data = []
        for name in names:
            col = tables.column(table, name)
            if isinstance(col, tuple):
                codes, values = col
                data.append([values[c] for c in codes])
            elif cols[name] == "date":
                data.append([iso(v) for v in col])
            elif cols[name] == "decimal":
                data.append([int(v) / 100.0 for v in col])
            else:
                data.append(col.tolist())
        db.execute(f"create table {table} ({', '.join(names)})")
        db.executemany(
            f"insert into {table} values ({', '.join('?' * len(names))})",
            zip(*data))
    return db
