"""Reader kind ``device_module``: device time of one compiled program, by
the XLA module names the declaration lists (the names the programs carry
today; the ``tracing`` issue gives them stable ``named_scope`` names).

Quantities: ``mean_ms`` (mean device duration of an execution),
``busy_pct`` (union of its executions over the traced slice) and
``roofline_pct`` (the least time the chip could take for the work the
configuration states, over ``mean_ms``).  Finds nothing, returns nothing.
"""

from __future__ import annotations

from benchmark import work


def _pick(decl: dict, r: dict) -> dict | None:
    found = [m for name, m in r["trace"]["modules"].items() if name in decl["modules"]]
    if not found or not sum(m["count"] for m in found):
        return None
    return {
        "count": sum(m["count"] for m in found),
        "total_s": sum(m["total_s"] for m in found),
        "busy_s": sum(m["busy_s"] for m in found),
    }


def read(decl: dict, r: dict) -> float | None:
    m = _pick(decl, r)
    if m is None or r["trace"]["window_s"] <= 0:
        return None
    mean_s = m["total_s"] / m["count"]
    q = decl["quantity"]
    if q == "mean_ms":
        return mean_s * 1000.0
    if q == "busy_pct":
        return 100.0 * m["busy_s"] / r["trace"]["window_s"]
    if q == "roofline_pct":
        # a scan is bound by memory bandwidth: bytes over peak bytes/s
        # exceeds FLOPs over peak FLOP/s for every slab this benchmark holds
        slab = r["config"]["slab"]
        least_s = max(
            work.scan_bytes(slab) / r["peaks"]["hbm_bytes_per_s"],
            work.scan_flops(slab) / r["peaks"]["bf16_flops_per_s"],
        )
        return 100.0 * least_s / mean_s
    raise ValueError(f"device_module: unknown quantity {q!r}")
