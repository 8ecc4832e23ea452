"""The reduction from ``.xplane.pb`` to numbers, against a small trace
recorded on a v5e (``data/small.xplane.pb``: five executions each of two
programs, 20 ms apart; ``record_fixture.py`` made it)."""

from __future__ import annotations

import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "small.xplane.pb")


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(DATA, "small.expected.json")) as f:
        return json.load(f)


def _device_events(line_name):
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(XPLANE).planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == line_name)
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def _union_by_sweep(intervals):
    """Busy time by another road: sweep the sorted edges, count the depth."""
    edges = sorted([(a, 1) for a, _b in intervals] + [(b, -1) for _a, b in intervals])
    busy = depth = 0
    last = None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_whole_trace(expected):
    r = trace.reduce_xplane(XPLANE)
    ops = _device_events("XLA Ops")
    mods = _device_events("XLA Modules")
    assert r["devices"] == 1 and r["clock"] == "first to last device event"
    assert r["busy_s"] == pytest.approx(_union_by_sweep([(a, b) for _n, a, b in ops]) / 1e9, rel=1e-9)
    both = ops + mods
    assert r["window_s"] == pytest.approx((max(b for _n, _a, b in both) - min(a for _n, a, _b in both)) / 1e9, rel=1e-9)
    assert {n: m["count"] for n, m in r["modules"].items()} == {"jit_run": 5, "jit__apply_cast": 5}
    for name in ("jit_run", "jit__apply_cast"):
        total = sum(b - a for n, a, b in mods if trace.module_name(n) == name) / 1e9
        assert r["modules"][name]["total_s"] == pytest.approx(total, rel=1e-9)
    # and the numbers as they were when the trace was looked at by hand
    assert r["busy_s"] == pytest.approx(expected["whole"]["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(expected["whole"]["window_s"], rel=1e-6)


def test_idle_gaps_are_the_pauses_between_calls():
    r = trace.reduce_xplane(XPLANE)
    long_gaps = [g for g in r["gaps"] if g[1] > 0.015]
    assert len(long_gaps) == 4  # five calls, 20 ms apart
    assert sum(g[1] for g in r["gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    assert r["ops"] and all(" = " not in name and len(name) < 64 for name, _s in r["ops"])


def test_clip_by_the_mark(expected):
    r = trace.reduce_xplane(XPLANE, clip_mono=tuple(expected["clip_mono"]), mark_mono_ns=expected["mark_mono_ns"])
    assert r["clock"].startswith("clipped")
    assert r["window_s"] == pytest.approx(expected["clip_mono"][1] - expected["clip_mono"][0], abs=1e-6)
    assert r["busy_s"] == pytest.approx(expected["clipped"]["busy_s"], rel=1e-6)
    assert r["busy_s"] < trace.reduce_xplane(XPLANE)["busy_s"]  # the first call began before the clip
    # without the mark the clip cannot be placed: the whole span is the window
    r2 = trace.reduce_xplane(XPLANE, clip_mono=tuple(expected["clip_mono"]))
    assert "no mark" in r2["clock"] and r2["busy_s"] == pytest.approx(expected["whole"]["busy_s"], rel=1e-6)


def test_interval_arithmetic():
    assert trace._union([(0, 10), (5, 12), (20, 30), (22, 25)]) == 22
    assert trace._gaps([(5, 12), (0, 10), (20, 30)], 0, 40) == [(12, 20), (30, 40)]
    assert trace.module_name("jit_run(7588361322511451609)") == "jit_run"
    assert trace.op_kind("%convert_reduce_fusion.43 = (f32[128,512]{1,0}) fusion(...)") == "convert_reduce_fusion"
