"""The sharded resident cell, ``mesh4_q1_resident``, rehearsed on four
virtual CPU devices at ``tiny`` with stored pages of 4,096 lanes
(``lineitem``: 16 pages, four a device): the traced run is ``correct``
with ``local_scan_pct.mesh4`` and ``resident_scan_pct.mesh4`` at 100,
and its control — splits handed out by stride, whatever device they
name — reads ``local_scan_pct.mesh4`` under 100 with the answers still
right (pages crossed between devices, none was lost)."""

import argparse

import pytest

from benchmark import run
from benchmark.tests.test_rehearse import BENCH, on_tiny


def by_stride(splits, task_id, task_count, task_devices=None):
    return [s for i, s in enumerate(splits) if i % task_count == task_id]


@pytest.mark.parametrize("assignment, local_pct", [(None, 100.0),
                                                   (by_stride, 25.0)],
                         ids=["addressed", "by_stride"])
def test_local_scan_pct_of_the_rehearsed_cell(assignment, local_pct,
                                              tmp_path, monkeypatch):
    from trino_tpu.connectors import memory
    from trino_tpu.exec import local_planner

    monkeypatch.setattr(memory, "PAGE_ROWS", 4096)
    if assignment is not None:
        monkeypatch.setattr(local_planner, "splits_of_task", assignment)
    cell, = [w for w in BENCH["workloads"]
             if w["name"] == "mesh4_q1_resident"]
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=4100000227, seconds=1.0, trace=1,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True and line["failed"] == 0
    metrics = {name: m["value"] for name, m in line["metrics"].items()}
    assert metrics["local_scan_pct.mesh4"] == pytest.approx(local_pct)
    assert metrics["resident_scan_pct.mesh4"] == 100.0
    assert metrics["resident_hbm_gb.mesh4"] > 0
    assert metrics["planning_ms.mesh4"] > 0
    assert metrics["host_syncs_per_query.mesh4"] > 0
