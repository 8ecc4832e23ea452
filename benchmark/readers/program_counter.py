"""Reader kind ``program_counter``: the program's own monotonic counters
(``internals/device_counters``), as the difference between the window's
close and its opening, optionally per chunk made searchable in the window.
"""

from __future__ import annotations


def read(decl: dict, r: dict) -> float | None:
    c = r["counters"]
    try:
        delta = sum(c["close"][k] - c["open"][k] for k in decl["counters"])
    except KeyError:
        return None
    per = decl.get("per")
    if per is None:
        return float(delta)
    n = r["window"].get(per)
    return delta / n if n else None
