"""Reader kind ``device_busy``: the union of every device op's interval
over the traced slice, and what follows from it with no program's name.

Quantities: ``idle_pct``; ``step_mfu_pct`` (FLOPs the slice's work needs, from
the data files, over seconds x the chip's peak; ``over`` says whether the
seconds are the slice's or the device-busy ones); ``host_ms_per_request``
(median client latency of the slice less device-busy time per request).
"""

from __future__ import annotations

import statistics

from benchmark import doors, work


def read(decl: dict, r: dict) -> float | None:
    t = r["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    q = decl["quantity"]
    if q == "idle_pct":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    s = r["slice"]
    if q == "step_mfu_pct":
        flops = doors.model_flops(r["config"], s["useful_tokens"])
        if decl.get("scan_per_request"):
            flops += s["requests"] * work.scan_flops(r["config"]["slab"])
        if flops <= 0:
            return None
        seconds = t["busy_s"] if decl["over"] == "busy" else t["window_s"]
        return 100.0 * flops / (seconds * r["peaks"]["bf16_flops_per_s"])
    if q == "host_ms_per_request":
        if not s["requests"]:
            return None
        return statistics.median(s["latency_ms"]) - 1000.0 * t["busy_s"] / s["requests"]
    raise ValueError(f"device_busy: unknown quantity {q!r}")
