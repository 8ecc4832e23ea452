"""Readings for the output check's limits, on the chip, many seeds in one
process: the program's numbers (the lower readings) and the control's (the
reference in fp8 in the program's place: the upper readings).

    python benchmark/control.py <workload> <seconds> <seed> [<seed> ...]

Each seed is a whole short run of the cell through ``run.run_cell`` (its own
weights, filler, corpus and window), so what is compared is what a run
compares; the control's side is the cell's check kind's (``checks/<kind>.py``,
``numbers`` at that precision).  Its numbers go through ``check.verdict``
against the workload's committed limits, as the program's do:
``control_correct`` has to read false on every seed, and the exit code is 1
where it does not (or where the program's ``correct`` is false).  Prints one
JSON line per seed and a summary last.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    from benchmark import check, run

    workload, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    info = run.device_info()
    if info["platform"] != "tpu":
        print(f"control: needs a TPU, JAX found {info}", file=sys.stderr)
        return 2
    limits = run.load_cell(manifest, ROOT, workload)["workload"]["limits"]
    rows = []
    for seed in seeds:
        line = run.run_cell(manifest, ROOT, workload, seed, seconds, False, control="fp8")
        ctrl = line.get("control")
        # a control that gave no number has failed, and sets no upper reading
        ctrl_ok, ctrl_compared = check.verdict(ctrl, limits) if ctrl else (False, {})
        row = {
            "seed": seed,
            "correct": line["correct"],
            "control_correct": ctrl_ok,
            "control_fails": sorted(n for n, c in ctrl_compared.items() if c["value"] > c["limit"]),
            "failed": line["failed"],
            "program": {k: v["value"] for k, v in line["compared"].items()},
            "control": ctrl,
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = [n for n, limit in limits.items() if limit and rows and n in rows[0]["program"]]  # a limit of 0 is exact: no readings
    summary = {}
    for n in names:
        lower = [r["program"][n] for r in rows if r["program"]]
        upper = [r["control"][n] for r in rows if r["control"]]
        summary[n] = {"program_max": max(lower, default=None), "control_min": min(upper, default=None)}
    sound = all(r["correct"] and not r["control_correct"] for r in rows)
    print(
        json.dumps({
            "workload": workload, "seeds": len(rows), "limits": limits, "summary": summary,
            "program_correct": [r["correct"] for r in rows], "control_correct": [r["control_correct"] for r in rows],
        }),
        flush=True,
    )
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
