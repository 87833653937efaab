"""Operators layer: mean over the window's statements of the scan
operators' ``wait_s``: seconds ``get_output`` waited for a page its
producer thread had not finished, which is the part of the read-ahead
pages' host generation and upload that stayed on the statement's path
(``scan_host_s_per_query`` is all of it, hidden or not).  0.0 where no
scan reads ahead (resident tables, one-page scans); None where the
program's scans keep no such counter."""

from benchmark.span_facts import per_statement


def _wait_s(spans):
    return sum(s["attrs"].get("wait_s", 0.0) for s in spans)


def _counted(spans):
    return sum("wait_s" in s["attrs"] for s in spans)


def read(run):
    values = per_statement(run, _wait_s, _wait_s)
    if not values or not any(per_statement(run, _counted, _counted)):
        return None
    return sum(values) / len(values)
