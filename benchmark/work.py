"""The work a scan of the slab needs, from the configuration's numbers.

Operations and bytes are reckoned from what the data files state (slab rows,
slab itemsize), never from what the program happens to dispatch: padding, a
dtype change or another kernel are then read against the same work.  A
model's work is its family's to reckon (``families/<family>.py`` ``flops``).
"""

from __future__ import annotations


def scan_flops(slab: dict) -> float:
    """One query against every row the slab holds capacity for."""
    return 2.0 * slab["capacity_rows"] * slab["dim"]


def scan_bytes(slab: dict, query_rows: int = 1) -> float:
    """Least bytes a scan moves: the slab once, at the itemsize the
    configuration states, and the queries."""
    return float(
        slab["capacity_rows"] * slab["dim"] * slab["itemsize"] + query_rows * slab["dim"] * 4
    )
