"""Find the knee of a ``retrieve_open`` cell once, on the chip: the highest
offered rate the system sustains.

    python benchmark/sweep.py <workload> <seconds> [<first rate> [<most in flight>]]
    python benchmark/sweep.py <workload> <seconds> repeat <rate>x<windows> [<rate>x<windows> ...]

One process, so that set-up is paid once: the rate doubles from the first (5
requests/s) for ``seconds`` each until completions no longer keep up with
offers or the generator's lateness grows through the window, then the last
interval is halved twice.  Prints one JSON line per rate; the workload's file
then takes four fifths of the highest rate that held, as a number.

``repeat`` plays whole windows of ``seconds`` at fixed rates instead, each
window under a seed of its own as the driver's runs are, and prints each
window's percentiles and, per rate, each percentile's spread (distance between
the quartiles over the median, by ``statistics.quantiles``): which tail a
window of that length holds at that load.  Windows of one process share its
set-up, so these spreads are a floor under those of separate runs.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    from benchmark import doors, run
    from benchmark.traffic.retrieve_open import peak_in_flight

    workload, seconds = argv[0], float(argv[1])
    repeat = [(float(a.split("x")[0]), int(a.split("x")[1])) for a in argv[3:]] if argv[2:3] == ["repeat"] else None
    rate = float(argv[2]) if len(argv) > 2 and repeat is None else 5.0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    if run.device_info()["platform"] != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    spec = run.load_cell(manifest, ROOT, workload)
    # a power of two; every rows bucket up to it is warmed
    most = spec["workload"]["max_in_flight"] if repeat is not None else int(argv[3]) if len(argv) > 3 else 64
    wl = dict(spec["workload"], max_in_flight=most)
    os.makedirs(spec["scratch_parent"], exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="sweep-", dir=spec["scratch_parent"])
    system_kind = doors.find("systems", spec["config"].get("system"), f"configs/{spec['cell']['config']}.json `system`")
    system = system_kind.System(spec["config"], 7, scratch, chips=spec["cell"]["chips"])
    try:
        system.start()
        system.fill(wl)
        grid = wl["warm_grid"]
        powers = [1 << i for i in range(most.bit_length())]
        wl["warm_grid"] = dict(grid, encoder_rows=[p for p in powers if p >= 8], search_rows=powers)
        traffic = doors.find("traffic", wl.get("kind"), f"workloads/{workload}.json `kind`").Traffic(system, wl, 7, seconds, None)
        traffic.setup()

        def probe(rate: float, n: int) -> dict:
            sched = traffic._schedule(max(1, round(rate * seconds)), seconds, stream_offset=200 + n)
            res = traffic._play(sched, record=False)
            ok = [r for r in res if r["status"] == "ok"]
            lat = np.array([(r["done"] - r["due"]) * 1000 for r in ok]) if ok else np.array([np.nan])
            half = len(ok) // 2
            row = {
                "rate": rate, "offered": len(res), "answered": len(ok),
                "answered_by_window_end": sum(1 for r in ok if r["done"] <= seconds),
                "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
                "p50_first_half_ms": float(np.percentile(lat[:half], 50)) if half else None,
                "p50_second_half_ms": float(np.percentile(lat[half:], 50)) if half else None,
                "peak_in_flight": peak_in_flight(ok),
                "lateness_p95_ms": float(np.percentile([(r["sent"] - r["due"]) * 1000 for r in res if r["sent"] is not None] or [np.nan], 95)),
            }
            # held: everything answered, nearly all of it inside the window, and the
            # second half of the window no slower than the first (no backlog growing)
            row["held"] = bool(
                row["answered"] == row["offered"]
                and row["answered_by_window_end"] >= 0.98 * row["offered"]
                and row["p50_second_half_ms"] is not None
                and row["p50_second_half_ms"] < 1.25 * row["p50_first_half_ms"] + 10.0
            )
            print(json.dumps(row), flush=True)
            return row

        if repeat is not None:
            for rate, windows in repeat:
                rows = []
                for i in range(windows):
                    traffic.seed = 1000 * round(rate) + i  # block order and words, as another --seed gives them
                    traffic.w["rate_per_s"] = rate  # the blocks stay about a second long
                    res = traffic._play(traffic._schedule(max(1, round(rate * seconds)), seconds), record=False)
                    ok = [r for r in res if r["status"] == "ok"]
                    slowest = max([wl["timeout_s"] * 1000.0, *[(r["done"] - r["due"]) * 1000 for r in ok]])
                    lat = [(r["done"] - r["due"]) * 1000 if r["status"] == "ok" else slowest for r in res]
                    first30 = [x for x, r in zip(lat, res) if r["due"] < 30.0]
                    row = {"rate": rate, "seconds": seconds, "window": i, "offered": len(res), "failed": len(res) - len(ok), "peak_in_flight": peak_in_flight(ok)}
                    row.update({f"p{p}_ms": float(np.percentile(lat, p)) for p in (50, 75, 90, 95, 99)})
                    row.update({f"p{p}_first30s_ms": float(np.percentile(first30, p)) for p in (50, 90, 95)})
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                summary = {"rate": rate, "seconds": seconds, "windows": windows}
                for name in [k for k in rows[0] if k.endswith("_ms")]:
                    vals = [r[name] for r in rows]
                    q = statistics.quantiles(vals, n=4) if len(vals) > 1 else [vals[0]] * 3
                    med = statistics.median(vals)
                    summary[name] = {"median": med, "spread_pct": 100 * (q[2] - q[0]) / med, "min": min(vals), "max": max(vals)}
                print(json.dumps(summary), flush=True)
            return 0
        n, held, broke = 0, None, None
        while broke is None and rate <= 2000:
            row = probe(rate, n)
            n += 1
            if row["held"]:
                held, rate = rate, rate * 2
            else:
                broke = rate
        for _ in range(2):
            if held is None or broke is None:
                break
            mid = (held + broke) / 2
            row = probe(mid, n)
            n += 1
            if row["held"]:
                held = mid
            else:
                broke = mid
        print(json.dumps({"highest_rate_that_held": held, "lowest_that_did_not": broke, "four_fifths": None if held is None else 0.8 * held}), flush=True)
    finally:
        system.stop()
        shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
