"""The comparison that decides ``correct``, as far as every cell shares it.

What the timed path produced is held against the plain reference by the
cell's check kind (``checks/<kind>.py``, named by ``check.kind`` in the
workload's file): ``collect`` takes it from the system while that still
stands, ``numbers`` compares it with the family's reference once the system
is freed.  ``verdict`` then holds each number to the limit of its own that
the workload's file gives it (PERF.md section 2 has the readings each was
set from): every limit names a number the check kind has to give.
"""

from __future__ import annotations


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    compared = {name: {"value": numbers[name], "limit": limits[name]} for name in limits}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared
