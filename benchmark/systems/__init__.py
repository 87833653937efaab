"""One file per runner kind: ``<kind>.py`` with ``build(config) ->
runner``, found by ``run.py`` through the configuration's
``runner.kind``.  What every kind shares is here."""


def connectors_and_session(config: dict):
    """The configuration's connector under its catalog name, and the
    session (catalog, schema, session properties) statements run in."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.sql.analyzer import Session

    catalog = config["connector"]["catalog"]
    conn = TpchConnector(page_rows=config["connector"]["page_rows"])
    session = Session(catalog=catalog, schema=config["schema"])
    session.properties.update(config["session_properties"])
    return {catalog: conn}, session
