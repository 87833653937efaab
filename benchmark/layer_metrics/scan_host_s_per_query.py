"""Operators layer: mean over the window's statements of the scan
operators' ``generate_s`` (connector page generation + coalescing
concat) + ``upload_s`` (host-to-device upload), host seconds that
overlap device work and so show in no idle gap."""

from benchmark.span_facts import per_statement


def _scan_host_s(spans):
    return sum(s["attrs"].get("generate_s", 0.0)
               + s["attrs"].get("upload_s", 0.0) for s in spans)


def read(run):
    values = per_statement(run, _scan_host_s, _scan_host_s)
    return sum(values) / len(values) if values else None
