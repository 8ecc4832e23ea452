"""Profiler slices: taking one inside the window, and reducing its
``.xplane.pb`` to the few things the readers ask for.

Which planes are the device and how the programs are named is written in
PERF.md section 3 (looked at by hand on a v5e, PR 24): the plane
``/device:TPU:<n>`` carries a line ``XLA Modules`` (one event per execution of
a compiled program, named ``<module>(<fingerprint>)``) and a line ``XLA Ops``
(one event per HLO op); host threads are planes ``/host:CPU``.  The harness
writes one ``TraceAnnotation`` named ``bench_mark`` when the slice opens, at
a harness-clock instant it records, which ties the two clocks together.

The reduction is checked against a small trace recorded on the chip
(``benchmark/tests/data``).
"""

from __future__ import annotations

import glob
import os
import re
import threading
import time

MARK = "bench_mark"
_MODULE_LINE = "XLA Modules"
_OPS_LINE = "XLA Ops"


class Tracer:
    """Runs ``jax.profiler`` for one slice on a thread of its own, so that
    the load generator never waits for the profiler to start or to write."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.started = threading.Event()
        self._stop = threading.Event()
        self.done = threading.Event()
        self.mark_mono_ns: int | None = None
        self.t_started: float | None = None
        self.t_stopped: float | None = None
        self.error: str | None = None
        self._thread: threading.Thread | None = None

    def request_start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, name="bench_tracer", daemon=True)
            self._thread.start()

    def request_stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        import jax

        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                self.mark_mono_ns = time.monotonic_ns()
                with jax.profiler.TraceAnnotation(MARK):
                    time.sleep(0.001)
                self.t_started = time.monotonic()
                self.started.set()
                self._stop.wait(timeout=120)
                self.t_stopped = time.monotonic()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # a traced run still ends in its line
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self.started.set()
            self.done.set()

    def wait(self, timeout: float) -> None:
        if self._thread is not None:
            self.request_stop()
            self.done.wait(timeout)

    def xplane_path(self) -> str | None:
        found = sorted(glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def _union(intervals: list[tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _gaps(intervals: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    gaps, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if hi > end:
        gaps.append((end, hi))
    return gaps


def module_name(event_name: str) -> str:
    """``jit_run(1234567)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_kind(event_name: str) -> str:
    """``%convert_reduce_fusion.43 = (f32[...]) fusion(...)`` ->
    ``convert_reduce_fusion``: ops are summed by what they are, not one by one."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def reduce_xplane(path: str, clip_mono: tuple[float, float] | None = None, mark_mono_ns: int | None = None) -> dict:
    """Reduce one ``.xplane.pb``.

    Returns ``{"window_s", "busy_s", "devices", "modules": {name: {"count",
    "total_s", "busy_s"}}, "ops": [[name, seconds], ...], "gaps": [[start_s,
    seconds], ...], "clock": ...}``.  ``busy_s`` is the union of the device's
    op intervals, averaged over device planes; per module, ``total_s`` sums
    its executions and ``busy_s`` is the union of them.  With ``clip_mono``
    (harness monotonic seconds) and the mark, only what lies inside the clip
    counts, events cut at its edges; without, the span from the first to the
    last device event is the window.  Gap starts are seconds from the
    window's start.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    offset_ns = None  # profile clock minus harness monotonic clock
    if mark_mono_ns is not None:
        host_events = (
            ev for plane in data.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
        )
        mark = next((ev for ev in host_events if ev.name == MARK), None)
        if mark is not None:
            offset_ns = int(mark.start_ns) - mark_mono_ns
    per_plane = []
    for plane in device_planes:
        mods, ops = [], []
        for line in plane.lines:
            if line.name == _MODULE_LINE:
                mods = [(module_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
            elif line.name == _OPS_LINE:
                ops = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
        per_plane.append((mods, ops))
    every = [iv for mods, ops in per_plane for iv in (*ops, *mods)]
    if not every:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": len(device_planes), "modules": {}, "ops": [], "gaps": [], "clock": "no device events"}
    if clip_mono is not None and offset_ns is not None:
        lo = int(clip_mono[0] * 1e9) + offset_ns
        hi = int(clip_mono[1] * 1e9) + offset_ns
        clock = "clipped to the harness's slice by the mark"
    else:
        lo = min(a for _n, a, _b in every)
        hi = max(b for _n, _a, b in every)
        clock = "first to last device event (no mark found)" if clip_mono is not None else "first to last device event"

    def clipped(events):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]

    busy_ns, modules, op_time, gaps = 0, {}, {}, []
    for mods, ops in per_plane:
        mods, ops = clipped(mods), clipped(ops)
        base = ops or mods
        busy_ns += _union([(a, b) for _n, a, b in base])
        by_mod: dict[str, list[tuple[int, int]]] = {}
        for n, a, b in mods:
            by_mod.setdefault(n, []).append((a, b))
        for n, ivs in by_mod.items():
            m = modules.setdefault(n, {"count": 0, "total_s": 0.0, "busy_s": 0.0})
            m["count"] += len(ivs)
            m["total_s"] += sum(b - a for a, b in ivs) / 1e9
            m["busy_s"] += _union(ivs) / 1e9
        for n, a, b in ops:
            kind = op_kind(n)
            op_time[kind] = op_time.get(kind, 0) + (b - a)
        if not gaps:
            gaps = [(a - lo, b - a) for a, b in _gaps([(a, b) for _n, a, b in base], lo, hi)]
    n_dev = max(len(per_plane), 1)
    for m in modules.values():
        m["total_s"] /= n_dev
        m["busy_s"] /= n_dev
        m["count"] //= n_dev
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n_dev,
        "devices": len(device_planes),
        "modules": modules,
        "ops": [[n, t / 1e9] for n, t in top_ops],
        "gaps": [[a / 1e9, d / 1e9] for a, d in top_gaps],
        "clock": clock,
    }
