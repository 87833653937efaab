"""``runner.kind`` ``distributed_resident``: ``DistributedQueryRunner``
with ``runner.workers`` in-process workers, one per device, whose session
is the memory connector's catalog, with the tables of ``runner.load``
loaded from the generator's catalog before it is handed over: one
``CREATE TABLE <t> AS SELECT * FROM <from>.<schema>.<t>`` each, through
the runner's normal ``execute`` — a distributed CTAS whose writer tasks
each keep their pages on their own chip.  So the load is part of set-up,
and a template's unqualified ``from lineitem`` reads the loaded table.

The configuration is tables spread over the chips.  The program's own
account of them by device (``exec.memory.resident_table_bytes_by_device``)
is required BEFORE the first load — a program that keeps none is another
deployment and ends the run at once — and read after every load: a table
of more than ``runner.workers`` pages that left a chip without a byte of
it ends the run before the window."""

import json
import time


def build(config: dict):
    from trino_tpu.exec.memory import resident_table_bytes_by_device

    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.parallel.distributed import DistributedQueryRunner
    from trino_tpu.sql.analyzer import Session

    schema, connector = config["schema"], config["connector"]
    catalog, source = connector["catalog"], connector["from"]
    workers = config["runner"]["workers"]
    session = Session(catalog=catalog, schema=schema)
    session.properties.update(config["session_properties"])
    memory = MemoryConnector(catalog, schemas=[schema])
    runner = DistributedQueryRunner(
        {source: TpchConnector(source, page_rows=connector["page_rows"]),
         catalog: memory},
        session, n_workers=workers,
        desired_splits=config["runner"]["desired_splits"])
    for table in config["runner"]["load"]:
        t0, held = time.perf_counter(), resident_table_bytes_by_device()
        (rows,), = runner.execute(
            f"create table {table} as "
            f"select * from {source}.{schema}.{table}").rows
        grown = {str(d): n - held.get(d, 0) for d, n in
                 resident_table_bytes_by_device().items()}
        by_device = {d: grown[d] for d in sorted(grown) if grown[d]}
        pages = len(memory.tables[(schema, table)].pages)
        print(json.dumps({"phase": "load", "table": table, "rows": rows,
                          "pages": pages, "device_bytes": by_device,
                          "seconds": time.perf_counter() - t0}),
              flush=True)
        if rows and not by_device:
            raise RuntimeError(f"{catalog}.{schema}.{table}: {rows} rows "
                               "loaded and no byte of them on a device")
        if pages > workers and len(by_device) < workers:
            raise RuntimeError(
                f"{catalog}.{schema}.{table}: {pages} pages on "
                f"{len(by_device)} of {workers} chips: {by_device}")
    return runner
