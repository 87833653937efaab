"""``runner.kind`` ``distributed``: ``DistributedQueryRunner`` with
``runner.workers`` in-process workers, one per device, coordinator and
workers as threads of the one process that holds the chips."""

from benchmark.systems import connectors_and_session


def build(config: dict):
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    connectors, session = connectors_and_session(config)
    return DistributedQueryRunner(
        connectors, session, n_workers=config["runner"]["workers"],
        desired_splits=config["runner"]["desired_splits"])
