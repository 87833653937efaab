"""``run.py --rehearse-cpu`` end to end on ``tiny`` for each cell's
traffic file and runner kind (the four-chip cell on four virtual CPU
devices), and the last line's keys."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def config_of(cell):
    """The cell's entry in ``configs`` and its configuration file."""
    entry, = [c for c in BENCH["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(run.ROOT, entry["file"])) as f:
        return entry, json.load(f)


def on_tiny(cell, tmp_path):
    """``(bench, cell)`` with the cell's own configuration cut to the
    ``tiny`` schema: same runner kind, workers and session properties."""
    entry, config = config_of(cell)
    config["schema"] = "tiny"
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    bench = dict(BENCH, configs=[dict(entry, name="on_tiny",
                                      file=str(path))])
    return bench, dict(cell, config="on_tiny")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
@pytest.mark.parametrize("trace", [0, 1])
def test_traffic_file_runs_on_tiny(cell, trace, tmp_path):
    """Each cell's traffic and runner kind on the tiny schema, in
    process."""
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=2147483659, seconds=1.0, trace=trace,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert len(line["device"]["per_device"]) == cell["chips"]
    wanted = BENCH["per_layer"] if trace else BENCH["end_to_end"]
    listed = {m["name"] for m in wanted
              if cell["name"] in m.get("workloads", [cell["name"]])}
    assert set(line["metrics"]) <= listed
    if not trace:
        assert set(line["metrics"]) == listed
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_command_prints_the_contract_line_last():
    cell, = [w["name"] for w in BENCH["workloads"]
             if config_of(w)[1]["schema"] == "tiny"]
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         cell, "--seed", "7", "--seconds", "1", "--trace", "0",
         "--rehearse-cpu"], capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"


def test_no_tpu_is_an_error():
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "7", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
