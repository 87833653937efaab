"""Base-table columns as plain numpy arrays, for the references.

The rows come from the connector's host page source (the data has no
other definition: the generator is counter-based and takes no seed).  No
JAX, no engine operator, and a connector instance of the reference's own,
so nothing the engine made (device pages, dictionaries, caches) is read.
"""

from __future__ import annotations

import datetime

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def iso(day: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


class HostTables:
    """``column(table, name)`` -> int64/int32 array; a string column is
    ``(codes, values)`` with ``values[code]`` the string.  Loaded once
    per column set and kept for the run's other instances."""

    def __init__(self, schema: str, page_rows: int = 1 << 20):
        from trino_tpu.connectors.tpch import TpchConnector

        self.schema = schema
        self.conn = TpchConnector(page_rows=page_rows)
        self._cols = {}

    def row_count(self, table: str) -> int:
        meta = self.conn.metadata()
        handle = meta.get_table_handle(self.schema, table)
        return int(meta.get_statistics(handle).row_count)

    def load(self, table: str, names):
        names = [n for n in names if (table, n) not in self._cols]
        if not names:
            return
        meta = self.conn.metadata()
        handle = meta.get_table_handle(self.schema, table)
        if handle is None:
            raise KeyError(f"no table {self.schema}.{table}")
        by_name = {c.name: c for c in meta.get_columns(handle)}
        cols = [by_name[n] for n in names]
        parts = [[] for _ in cols]
        dicts = [None] * len(cols)
        for split in self.conn.split_manager().get_splits(handle, 1):
            src = self.conn.page_source(split, cols)
            while (page := src.get_next_page()) is not None:
                for i, block in enumerate(page.blocks):
                    block = block.numpy()
                    if block.nulls is not None and block.nulls.any():
                        raise ValueError(
                            f"{table}.{names[i]} holds NULLs: the "
                            "references assume base columns have none")
                    parts[i].append(np.asarray(block.data))
                    dicts[i] = block.dictionary
        for i, n in enumerate(names):
            data = np.concatenate(parts[i])
            if dicts[i] is not None:
                self._cols[table, n] = (data, list(dicts[i].values))
            else:
                self._cols[table, n] = data

    def column(self, table: str, name: str):
        self.load(table, [name])
        return self._cols[table, name]

    def columns(self, table: str, names):
        self.load(table, names)
        return [self._cols[table, n] for n in names]
