"""One cell, once, in one new process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses any backend but a TPU (exit 2, no result line), and a directory in
which the program is not beside it (exit 3, no result line).  Past those two
gates everything runs under one handler: whatever happens, the last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and, last, ``compared``: each number
the output check compared, beside its limit), and the exit code is 0.  What
went wrong is on standard error and in ``correct``.

The harness holds no list of names and no default: the cell, its
configuration, its system kind, its model families, its traffic kind, its
check kind and its per-layer metrics are files found by the names in
``BENCHMARK.json`` and in the cell's own data files (``benchmark/README.md``,
``benchmark/doors.py``).  A name with no file ends the run in its line, with
``correct`` false and the missing file named under ``error``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import doors  # noqa: E402
from benchmark.system import T0, log  # noqa: E402  (T0: the process's start)


def load_cell(manifest: dict, root: str, workload: str) -> dict:
    """The cell's entry, configuration, workload file and metric
    declarations, all found by name under ``root``."""
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    bench_dir = os.path.join(root, manifest["paths"][0])
    data_dir = os.path.join(root, manifest.get("data_dir", manifest["paths"][0]))
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(data_dir, "workloads", workload + ".json")) as f:
        wl = json.load(f)

    end_to_end = [m for m in manifest["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in end_to_end}
    per_layer = []
    for m in manifest["per_layer"]:
        # without a list of its own, a metric is read wherever the metric it moves is reported
        if workload in m["workloads"] if "workloads" in m else m["moves"] in names:
            with open(os.path.join(bench_dir, "metrics", m["name"] + ".json")) as f:
                per_layer.append({**json.load(f), **m})
    return {
        "cell": cell,
        "config": config,
        "workload": wl,
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "scratch_parent": os.path.join(bench_dir, ".scratch"),
    }


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks))


def run_cell(manifest: dict, root: str, workload: str, seed: int, seconds: float, trace: bool, sabotage=None, control: str | None = None) -> dict:
    """Everything after the device gate.  Returns the last line as a dict;
    never raises.  Two hooks serve ``benchmark/tests`` and
    ``benchmark/control.py`` and nothing else: ``sabotage`` is a callable
    given the built system before the window, to break the timed path
    underneath; ``control`` names a precision in which the reference is put
    in the program's place, its numbers going under ``control`` in the line."""
    line = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}, "device": {}}
    compared: dict = {}
    scratch = system = None
    try:
        line["device"] = {**device_info(), "memory_peak_bytes": 0}
        spec = load_cell(manifest, root, workload)
        config, wl = spec["config"], spec["workload"]
        os.makedirs(spec["scratch_parent"], exist_ok=True)
        scratch = tempfile.mkdtemp(prefix="run-", dir=spec["scratch_parent"])
        from benchmark import check, trace as trace_mod
        from benchmark.peaks import peaks_for

        tracer = trace_mod.Tracer(os.path.join(scratch, "trace")) if trace else None
        cfg_file, wl_file = f"configs/{spec['cell']['config']}.json", f"workloads/{workload}.json"
        system_kind = doors.find("systems", config.get("system"), f"{cfg_file} `system`")
        traffic_kind = doors.find("traffic", wl.get("kind"), f"{wl_file} `kind`")
        check_kind = doors.find("checks", wl.get("check", {}).get("kind"), f"{wl_file} `check.kind`")
        system = system_kind.System(config, seed, scratch, chips=spec["cell"]["chips"])
        system.start()
        system.fill(wl)
        traffic = traffic_kind.Traffic(system, wl, seed, seconds, tracer)
        traffic.setup()
        if sabotage is not None:
            sabotage(system)
        from pathway_tpu.internals import device_counters

        counters_open = device_counters.snapshot()
        window = traffic.run_window()
        counters_close = device_counters.snapshot()
        setup_s = window["t_open"] - T0
        line["attempted"], line["failed"] = window["attempted"], window["failed"]
        log(f"set-up {setup_s:.1f} s; window closed")
        missing = traffic.drain()
        if missing:
            line["failed"] += missing
        if tracer is not None:
            tracer.wait(timeout=120)
        collected = check_kind.collect(system, traffic, wl)
        line["device"]["memory_peak_bytes"] = memory_peak()
        fault = system.watch.fault()
        system.stop()
        params = system.params
        system.free()

        # --- the output check, against the plain reference -----------------
        t_check = time.monotonic()
        ok, compared = check.verdict(check_kind.numbers(collected, params, config, wl, seed), wl["limits"])
        if control is not None:
            line["control"] = check_kind.numbers(collected, params, config, wl, seed, precision=control)
        log(f"output check took {time.monotonic() - t_check:.1f} s")
        if fault:
            log(f"engine fault: {fault}")
        line["correct"] = bool(ok and not fault and line["failed"] == 0 and bool(window["metrics"]))

        # --- the metrics ----------------------------------------------------
        if not trace:
            line["metrics"] = {"setup_s": {"value": setup_s, "unit": "s"}}
            units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
            for name, value in window["metrics"].items():
                line["metrics"][name] = {"value": value, "unit": units[name]}
        else:
            peaks = peaks_for(line["device"]["kind"]) if line["device"]["platform"] == "tpu" else {}
            readings = make_readings(spec, traffic, tracer, window, counters_open, counters_close, peaks)
            line["device"]["busy_s"] = readings["trace"]["busy_s"]
            line["device"]["window_s"] = readings["trace"]["window_s"]
            for decl in spec["per_layer"]:
                reader = doors.find("readers", decl.get("reader"), f"metrics/{decl['name']}.json `reader`")
                value = reader.read(decl, readings)
                if value is not None:
                    line["metrics"][decl["name"]] = {"value": float(value), "unit": decl["unit"]}
            line["breakdown"] = breakdown(readings, traffic)
        line["notes"] = {**window.get("notes", {}), "setup_s": setup_s}
    except BaseException as e:  # the run still ends in its line
        traceback.print_exc(file=sys.stderr)
        log(f"the run broke: {type(e).__name__}: {e}")
        line["error"] = f"{type(e).__name__}: {e}"
        line["correct"] = False
        line["failed"] = max(line["failed"], line["attempted"], 1)
        line["attempted"] = max(line["attempted"], line["failed"])
        if isinstance(e, KeyboardInterrupt):
            raise
    finally:
        try:
            if system is not None:
                system.stop()
        except Exception:
            traceback.print_exc(file=sys.stderr)
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    line["compared"] = compared
    return line


def make_readings(spec, traffic, tracer, window, counters_open, counters_close, peaks: dict) -> dict:
    from benchmark import trace as trace_mod

    reduced = {"window_s": 0.0, "busy_s": 0.0, "modules": {}, "ops": [], "gaps": [], "clock": "no trace"}
    slice_r = {"requests": 0, "latency_ms": [], "useful_tokens": [], "chunks": 0}
    path = tracer.xplane_path() if tracer is not None else None
    if tracer is not None and tracer.error:
        log(f"profiler: {tracer.error}")
    if path and tracer.t_started is not None and tracer.t_stopped is not None:
        slice_r = traffic.slice_readings(tracer.t_started, tracer.t_stopped)
        clip = slice_r.get("clip", (tracer.t_started, tracer.t_stopped))
        reduced = trace_mod.reduce_xplane(path, clip_mono=clip, mark_mono_ns=tracer.mark_mono_ns)
        reduced["clip"] = clip
        log(
            f"trace: {os.path.getsize(path)} bytes, window {reduced['window_s']:.3f} s, busy {reduced['busy_s']:.3f} s, "
            f"clock: {reduced['clock']}; modules: "
            + ", ".join(f"{n} x{m['count']} {m['total_s']:.3f}s" for n, m in sorted(reduced["modules"].items(), key=lambda kv: -kv[1]["total_s"])[:6])
        )
    return {
        "trace": reduced,
        "slice": slice_r,
        "window": window,
        "counters": {"open": counters_open, "close": counters_close},
        "config": spec["config"],
        "workload": spec["workload"],
        "peaks": peaks,
    }


def breakdown(readings: dict, traffic) -> dict:
    reduced = readings["trace"]
    gaps = []
    clip = reduced.get("clip")
    for start_s, seconds in reduced["gaps"]:
        label = traffic.gap_label(clip[0] - readings["window"]["t_open"] + start_s) if clip else "unlabelled"
        gaps.append([label, seconds])
    return {"device_ops": reduced["ops"][:10], "idle_gaps": gaps[:10]}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "pathway_tpu")):
        print(f"benchmark: the program (pathway_tpu/) is not beside BENCHMARK.json in {ROOT}", file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r}; BENCHMARK.json has {sorted(cells)}", file=sys.stderr)
        return 3
    import jax

    info = device_info()
    print(f"backend={jax.default_backend()} device={info}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu" or info["count"] < cells[args.workload]["chips"]:
        print(
            f"benchmark: needs {cells[args.workload]['chips']} TPU chip(s), JAX found {info}; refusing to fall back",
            file=sys.stderr,
        )
        return 2
    line = run_cell(manifest, ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    compared = line.pop("compared")
    print(json.dumps({**line, "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
