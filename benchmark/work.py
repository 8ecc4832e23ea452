"""The work a request or a chunk needs, from the configuration's numbers.

Operations and bytes are reckoned from what the data files state (model
sizes, slab rows, slab itemsize, useful tokens), never from what the program
happens to dispatch: padding, a dtype change or another kernel are then read
against the same work.
"""

from __future__ import annotations


def encoder_flops(model: dict, tokens: int) -> float:
    """Multiply-adds x 2 of one row of ``tokens`` useful tokens through the
    encoder: per layer the four attention projections (8 h^2 T), the two MLP
    products (4 h f T) and the two attention products (4 T^2 h)."""
    h = model["hidden_size"]
    f = model["intermediate_size"]
    per_layer = tokens * (8 * h * h + 4 * h * f) + 4 * tokens * tokens * h
    return float(model["num_hidden_layers"] * per_layer)


def scan_flops(slab: dict) -> float:
    """One query against every row the slab holds capacity for."""
    return 2.0 * slab["capacity_rows"] * slab["dim"]


def scan_bytes(slab: dict, query_rows: int = 1) -> float:
    """Least bytes a scan moves: the slab once, at the itemsize the
    configuration states, and the queries."""
    return float(
        slab["capacity_rows"] * slab["dim"] * slab["itemsize"] + query_rows * slab["dim"] * 4
    )
