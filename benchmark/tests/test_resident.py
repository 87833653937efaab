"""The resident-table cell's own guarantee — every row that was loaded is
in every answer — can fail: the cell's run on ``tiny`` with one resident
``lineitem`` page dropped after the load, and with one handed out twice,
reads ``correct`` false.  (The sound run is
``test_rehearse.py::test_traffic_file_runs_on_tiny[*-sf1_q6_scan]``.)"""

import argparse

import pytest

from benchmark import run
from benchmark.tests.test_rehearse import BENCH, on_tiny


def drop_a_page(data):
    data.pages = data.pages[:2] + data.pages[3:]


def hand_a_page_out_twice(data):
    data.pages.append(data.pages[1])


@pytest.mark.parametrize("tamper", [drop_a_page, hand_a_page_out_twice],
                         ids=lambda f: f.__name__)
def test_run_over_tampered_resident_pages_is_not_correct(tamper, tmp_path,
                                                         monkeypatch):
    from benchmark.systems import local_resident
    from trino_tpu.connectors import memory

    # stored pages of 8,192 lanes, so tiny's lineitem is eight pages
    monkeypatch.setattr(memory, "PAGE_ROWS", 8192)
    real = local_resident.build

    def tampered(config):
        runner = real(config)
        conn = runner.metadata.connectors[config["connector"]["catalog"]]
        data = conn.tables[(config["schema"], "lineitem")]
        assert len(data.pages) == 8
        tamper(data)
        return runner

    monkeypatch.setattr(local_resident, "build", tampered)
    cell, = [w for w in BENCH["workloads"] if w["name"] == "sf1_q6_scan"]
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=5, seconds=1.0, trace=0,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0
    assert any(c["value"] > 0 for name, c in line["compared"].items()
               if name != "failed_statements")
