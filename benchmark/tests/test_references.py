"""Every numpy reference against sqlite on ``tiny`` at the TPC-H
validation parameters, and against the committed sqlite answers at SF1."""

import importlib
import json
import math
import os
from decimal import Decimal

import pytest

from benchmark import traffic
from benchmark.references.hosttables import HostTables
from benchmark.tests import sqlite_oracle

TEMPLATES = ["q1", "q3", "q6", "q13"]


def close(ref, oracle) -> bool:
    """A reference value (exact) against sqlite's (float arithmetic; an
    avg over decimal(12,2) is rounded to the cent by the reference)."""
    if isinstance(ref, Decimal):
        return math.isclose(float(ref), float(oracle), rel_tol=1e-9,
                            abs_tol=0.0051)
    return ref == oracle


def assert_rows(ref_rows, oracle_rows):
    assert len(ref_rows) == len(oracle_rows)
    for r, o in zip(ref_rows, oracle_rows):
        assert len(r) == len(o)
        assert all(close(a, b) for a, b in zip(r, o)), (r, o)


@pytest.mark.parametrize("name", TEMPLATES)
def test_reference_equals_sqlite_on_tiny(name):
    template = traffic.load_template(name)
    inst = traffic.instantiate(template, template.meta["validation"])
    db = sqlite_oracle.load("tiny", template.meta["columns"])
    oracle = db.execute(sqlite_oracle.to_sqlite(inst.sql)).fetchall()
    ref = importlib.import_module(f"benchmark.references.{name}")
    assert_rows(ref.reference(HostTables("tiny"), dict(inst.params)), oracle)


@pytest.fixture(scope="module")
def sf1_tables():
    return HostTables("sf1")


@pytest.mark.parametrize("name", TEMPLATES)
def test_reference_equals_committed_answers_at_sf1(name, sf1_tables):
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "references",
                           "sf1_validation.json")) as f:
        oracle = json.load(f)["answers"][name]
    template = traffic.load_template(name)
    ref = importlib.import_module(f"benchmark.references.{name}")
    assert_rows(ref.reference(sf1_tables, template.meta["validation"]),
                [tuple(r) for r in oracle])
