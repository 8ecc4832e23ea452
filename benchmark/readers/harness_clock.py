"""Reader kind ``harness_clock``: a percentile of a series the load
generator kept on its own clock over the whole window."""

from __future__ import annotations

import numpy as np


def read(decl: dict, r: dict) -> float | None:
    series = r["window"].get("series", {}).get(decl["series"])
    if not series:
        return None
    return float(np.percentile(series, decl["percentile"]))
