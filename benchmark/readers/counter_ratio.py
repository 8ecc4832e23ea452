"""Reader kind ``counter_ratio``: one of the program's own monotonic
counters over another (``internals/device_counters.snapshot()``, which since
PR 25 also carries the span recorder's stage totals as ``span_ns.<stage>``
and ``span_count.<stage>``), both as differences between the window's close
and its opening.

The declaration gives ``numerator`` (keys, summed), optionally ``minus`` (keys,
taken off the numerator), and either ``denominator`` (keys, summed) or ``per``
(a count the traffic kind kept over the window, as ``program_counter`` has
it), and ``scale`` (1e-6 turns nanoseconds a span into milliseconds).  A key
the program does not have (the parent of the PR that added it; a recorder
switched off), or a denominator that did not move, returns nothing.
"""

from __future__ import annotations


def _moved(c: dict, keys: list[str]) -> float | None:
    """Sum of the keys' differences over the window.  A key absent at the
    close is unknown to this program; one absent only at the opening is a
    stage total no span had fed yet, which is zero."""
    if any(k not in c["close"] for k in keys):
        return None
    return float(sum(c["close"][k] - c["open"].get(k, 0) for k in keys))


def read(decl: dict, r: dict) -> float | None:
    c = r["counters"]
    top = _moved(c, decl["numerator"])
    off = _moved(c, decl.get("minus", []))
    if "per" in decl:
        bottom = r["window"].get(decl["per"])
    else:
        bottom = _moved(c, decl["denominator"])
    if top is None or off is None or not bottom:
        return None
    return decl.get("scale", 1.0) * (top - off) / bottom
