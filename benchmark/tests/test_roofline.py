import pytest

from benchmark import roofline, traffic
from benchmark.references.hosttables import HostTables


def test_input_bytes_of_q1_q3_q6_at_sf1():
    tables = HostTables("sf1")
    rows = {t: tables.row_count(t)
            for t in ("lineitem", "orders", "customer")}
    assert rows["orders"] == 1_500_000 and rows["customer"] == 150_000
    li = rows["lineitem"]
    want = {"q1": li * (4 + 4 + 8 * 4 + 4),
            "q6": li * (8 * 3 + 4),
            "q3": li * (8 * 3 + 4) + 1_500_000 * (8 * 3 + 4)
            + 150_000 * (8 + 4)}
    for name, nbytes in want.items():
        cols = traffic.load_template(name).meta["columns"]
        assert roofline.input_bytes(cols, rows) == nbytes


def test_known_and_unknown_device_kind():
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert roofline.least_read_seconds(819e9, peaks) == 1.0
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
