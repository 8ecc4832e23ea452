"""The declarations of PR 39 (``epoch_unfed_ms.*``, ``chip_unfed_pct.*``,
``stall_ms_per_s.*``) read by ``counter_ratio`` from two snapshots of the
program's one door, as a traced run takes them at the window's ends."""

from __future__ import annotations

import json
import os
import time

import pytest

from benchmark.readers import counter_ratio
from pathway_tpu.internals import device_counters, tracing

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = [
    "epoch_unfed_ms.ingest", "epoch_unfed_ms.retrieve", "epoch_unfed_ms.answer",
    "chip_unfed_pct.retrieve", "chip_unfed_pct.answer",
    "stall_ms_per_s.ingest", "stall_ms_per_s.retrieve", "stall_ms_per_s.answer",
]


def _decl(name: str) -> dict:
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.fixture
def window():
    tracing.configure(PATHWAY_TRACE="1")
    tracing.reset()
    with tracing.span("epoch_process"):  # an epoch before the window: its stage total exists at the opening
        pass
    opened = device_counters.snapshot()
    for host_ms in (4, 6):  # two epochs: host work with the chip idle, then a device wait
        with tracing.span("epoch_process"):
            time.sleep(host_ms / 1e3)
            ticket = tracing.chip.ticket()
            time.sleep(0.003)
            tracing.chip.collected(ticket)
    clock = iter(range(0, 10**12, 10**6))
    wd = tracing.StallWatchdog(clock=lambda: next(clock), cpu=lambda: 0, steal=lambda: 0, majflt=lambda: 0)
    wd.tick(wd.last[0] + tracing.STALL_TICK_NS + 150_000_000)  # one stall of 150 ms, into the process's totals
    closed = device_counters.snapshot()
    yield {"counters": {"open": opened, "close": closed}, "window": {}}
    tracing.configure(PATHWAY_TRACE=None)
    tracing.reset()


@pytest.mark.parametrize("name", NAMES)
def test_each_declaration_reads_its_counters_over_the_window(name, window):
    o, c = window["counters"]["open"], window["counters"]["close"]
    moved = {k: c[k] - o.get(k, 0) for k in c}
    want = {
        "epoch_unfed_ms": 1e-6 * moved["span_idle_ns.epoch_process"] / moved["span_count.epoch_process"],
        "chip_unfed_pct": 100.0 * moved["chip_idle_ns"] / moved["chip_watch_ns"],
        "stall_ms_per_s": 1e3 * moved["stall_ns"] / moved["chip_watch_ns"],
    }[name.split(".")[0]]
    decl = _decl(name)
    assert decl["reader"] == "counter_ratio" and decl["source"] == "program_counter"
    assert counter_ratio.read(decl, window) == pytest.approx(want)
    assert moved["span_count.epoch_process"] == 2 and moved["stall_ns"] == 150_000_000
    if name.startswith("epoch_unfed_ms"):
        assert 5.0 <= want < 20.0  # 4 and 6 ms of host work an epoch (sleeps overshoot under load), the waits busy


def test_the_declarations_are_in_the_manifest_with_their_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"]: w["traffic"] for w in manifest["workloads"]}
    for name in NAMES:
        traffic = name.split(".")[1]
        assert entries[name]["workloads"] == [c for c, t in cells.items() if t == traffic], name
