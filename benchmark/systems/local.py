"""``runner.kind`` ``local``: one ``LocalQueryRunner`` on one chip."""

from benchmark.systems import connectors_and_session


def build(config: dict):
    from trino_tpu.runner import LocalQueryRunner

    connectors, session = connectors_and_session(config)
    return LocalQueryRunner(
        connectors, session,
        desired_splits=config["runner"]["desired_splits"])
