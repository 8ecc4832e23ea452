"""Rehearsal without the chip: every traffic kind end to end at a toy size.

    JAX_PLATFORMS=cpu python benchmark/rehearse_cpu.py [plain|engine_killed|overload|all]

The toy cells (``benchmark/rehearsal``: every workload file there is one) go
through ``run.run_cell`` exactly as a real cell does, past the device gate
that ``run.py`` keeps for itself.
What this prints are counts and a well-formed last line, never a device
metric.  ``engine_killed`` stops the engine mid-window and ``overload``
offers far more than a CPU sustains; both must still end in their line.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def toy_manifest() -> dict:
    """BENCHMARK.json with its cells swapped for the toy ones: every file
    under ``rehearsal/workloads`` is a toy cell, and stands for the cells of
    its own traffic kind, so that it reports the same metrics by the same
    files."""
    manifest = _json(os.path.join(ROOT, "BENCHMARK.json"))
    bench = os.path.join(ROOT, manifest["paths"][0])
    toys = {
        name[:-5]: _json(os.path.join(bench, "rehearsal", "workloads", name))
        for name in sorted(os.listdir(os.path.join(bench, "rehearsal", "workloads")))
        if name.endswith(".json")
    }
    toy_of = {wl["kind"]: cell for cell, wl in toys.items()}
    kind_of = {w["name"]: _json(os.path.join(bench, "workloads", w["name"] + ".json"))["kind"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({toy_of[kind_of[w]] for w in m["workloads"] if kind_of[w] in toy_of})
    manifest["data_dir"] = "benchmark/rehearsal"
    manifest["configs"] = [
        {"name": n, "file": f"benchmark/rehearsal/configs/{n}.json"} for n in sorted({wl["config"] for wl in toys.values()})
    ]
    manifest["workloads"] = [
        {"name": cell, "config": wl["config"], "traffic": wl["kind"], "chips": 1} for cell, wl in toys.items()
    ]
    return manifest


def kill_engine_after(seconds: float):
    def sabotage(system) -> None:
        def stop() -> None:
            time.sleep(seconds)
            from pathway_tpu.internals.parse_graph import G

            G.active_scheduler.stop()

        threading.Thread(target=stop, daemon=True).start()

    return sabotage


def rehearse(cell: str, seed: int, seconds: float, trace: bool, sabotage=None, edit=None) -> dict:
    from benchmark import run

    # a cell BENCHMARK.json names runs from its own files, as a run on the chip
    # would; any other is a toy cell of benchmark/rehearsal
    manifest = _json(os.path.join(ROOT, "BENCHMARK.json"))
    if cell not in {w["name"] for w in manifest["workloads"]}:
        manifest = toy_manifest()
    root = ROOT
    if edit is not None:  # a workload edited for this rehearsal only, in a scratch copy
        import shutil
        import tempfile

        tmp = tempfile.mkdtemp(prefix="rehearsal-", dir=os.path.join(ROOT, "benchmark", ".scratch"))
        shutil.copytree(os.path.join(ROOT, "benchmark", "rehearsal"), os.path.join(tmp, "data"))
        path = os.path.join(tmp, "data", "workloads", cell + ".json")
        with open(path) as f:
            w = json.load(f)
        edit(w)
        with open(path, "w") as f:
            json.dump(w, f)
        manifest["data_dir"] = os.path.relpath(os.path.join(tmp, "data"), ROOT)
        for c in manifest["configs"]:
            c["file"] = os.path.join(manifest["data_dir"], "configs", c["name"] + ".json")
    try:
        line = run.run_cell(manifest, root, cell, seed, seconds, trace, sabotage=sabotage)
    finally:
        if edit is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    missing = [k for k in REQUIRED if k not in line]
    if missing:
        raise AssertionError(f"last line lacks {missing}: {line}")
    json.dumps(line, allow_nan=False)
    return line


def main(which: str) -> int:
    os.makedirs(os.path.join(ROOT, "benchmark", ".scratch"), exist_ok=True)
    plans = {
        "plain": [("toy-cls.ingest", 3.0, False, None, None), ("toy.retrieve", 3.0, False, None, None)],
        "traced": [("toy-cls.ingest", 3.0, True, None, None), ("toy.retrieve", 3.0, True, None, None)],
        "engine_killed": [("toy.retrieve", 3.0, False, kill_engine_after(1.0), None), ("toy-cls.ingest", 3.0, False, kill_engine_after(1.0), None)],
        "overload": [("toy.retrieve", 3.0, False, None, lambda w: w.update(rate_per_s=3000, max_in_flight=4, timeout_s=2))],
    }
    names = list(plans) if which == "all" else [which]
    for name in names:
        for cell, seconds, trace, sabotage, edit in plans[name]:
            line = rehearse(cell, seed=2**31 + 12345, seconds=seconds, trace=trace, sabotage=sabotage, edit=edit)
            print(f"--- {name} {cell}: " + json.dumps(line)[:3000], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "plain"))
