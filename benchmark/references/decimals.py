"""Scaled-int64 decimals for the references."""

from __future__ import annotations

from decimal import Decimal


def dec(units: int, scale: int) -> Decimal:
    """``units`` of 10**-scale as an exact Decimal."""
    return Decimal(int(units)).scaleb(-scale)


def avg_half_up(total: int, count: int) -> int:
    """``total / count`` rounded half away from zero, in the units of
    ``total`` (avg over decimal(p,s) is decimal(p,s))."""
    total, count = int(total), int(count)
    sign = -1 if total < 0 else 1
    return sign * ((2 * abs(total) + count) // (2 * count))
