"""``runner.kind`` ``local_resident``: one ``LocalQueryRunner`` on one
chip whose session is the memory connector's catalog, with the tables of
``runner.load`` loaded from the generator's catalog before it is handed
over: one ``CREATE TABLE <t> AS SELECT * FROM <from>.<schema>.<t>`` each,
through the runner's normal ``execute``.  So the load is part of set-up,
and a template's unqualified ``from lineitem`` reads the loaded table.

The configuration is tables held on the device.  The program's own
account of them (``exec.memory.resident_table_bytes``) is required and
read after every load: a program that keeps no such account, or a table
that left no bytes on the device, is another deployment and ends the
run before the window."""

import json
import time


def build(config: dict):
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.exec.memory import resident_table_bytes
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    schema, connector = config["schema"], config["connector"]
    catalog, source = connector["catalog"], connector["from"]
    session = Session(catalog=catalog, schema=schema)
    session.properties.update(config["session_properties"])
    runner = LocalQueryRunner(
        {source: TpchConnector(source, page_rows=connector["page_rows"]),
         catalog: MemoryConnector(catalog, schemas=[schema])},
        session, desired_splits=config["runner"]["desired_splits"])
    for table in config["runner"]["load"]:
        t0, held = time.perf_counter(), resident_table_bytes()
        (rows,), = runner.execute(
            f"create table {table} as "
            f"select * from {source}.{schema}.{table}").rows
        device_bytes = resident_table_bytes() - held
        print(json.dumps({"phase": "load", "table": table, "rows": rows,
                          "device_bytes": device_bytes,
                          "seconds": time.perf_counter() - t0}),
              flush=True)
        if rows and device_bytes <= 0:
            raise RuntimeError(f"{catalog}.{schema}.{table}: {rows} rows "
                               "loaded and no byte of them on the device")
    return runner
