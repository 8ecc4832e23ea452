"""Observability surfaces: /metrics + /status HTTP endpoints, operator
probes, connector stats, attach_prober callbacks, and license
introspection (reference monitoring/telemetry subsystem roles:
``src/engine/telemetry.rs``, ``prober`` machinery in graph.rs:988).
"""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.request

import pytest

import pathway_tpu as pw
from tests.utils import T, run_to_rows


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_monitoring_http_metrics_and_status():
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    t = T(
        """
    a
    1
    2
    """
    )
    out = t.select(b=t.a * 2)
    out._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    port = _free_port()
    import pathway_tpu.internals.config as cfg

    try:
        start_http_server(sched, port=port)
        sched.run()
        # /metrics: prometheus text with per-operator counters
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "pathway" in body and "rows" in body
        # /status: json health document
        status = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status", timeout=5
            ).read()
        )
        assert isinstance(status, dict) and status
    finally:
        server = getattr(sched, "_monitoring_server", None)
        if server is not None:
            server.shutdown()
            server.server_close()


def test_operator_probes_record_rows_and_latency():
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    t = T(
        """
    a
    1
    2
    3
    """
    )
    out = t.select(b=t.a + 1).filter(pw.this.b > 2)
    out._capture_node()
    sched = Scheduler(G.engine_graph)
    ctx = sched.run()
    probes = sched.snapshot_operator_probes(ctx)
    assert probes, "operators must register probes"
    total_rows = sum(p.get("rows_out", 0) for p in probes.values())
    assert total_rows > 0
    assert all(p.get("ms_total", 0) >= 0 for p in probes.values())


def test_attach_prober_fires_per_epoch():
    events = []
    pw.G.clear()
    t = T(
        """
    a
    1
    """
    )
    t.select(b=t.a)._capture_node()
    pw.attach_prober(lambda stats: events.append(stats))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert events
    first = events[0]
    assert "time" in first and "worker" in first and "operators" in first


def test_connector_stats_track_rows(tmp_path):
    p = tmp_path / "in.jsonl"
    p.write_text('{"a": 1}\n{"a": 2}\n{"a": 3}\n')

    class S(pw.Schema):
        a: int

    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    t = pw.io.jsonlines.read(str(p), schema=S, mode="static")
    t._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    sched.run()
    stats = sched.snapshot_connector_stats()
    assert stats
    name, s = next(iter(stats.items()))
    assert s["rows"] == 3
    assert s["closed"] is True


def test_telemetry_gauges_after_run():
    from pathway_tpu.internals.telemetry import get_telemetry

    pw.G.clear()
    t = T(
        """
    a
    1
    """
    )
    t.select(b=t.a)._capture_node()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    tel = get_telemetry()
    assert "run.epoch" in tel.gauges
    assert tel.gauges["run.errors"] == 0
    assert any(s["name"] == "graph_runner.run" for s in tel.spans)


def test_license_free_tier_reports():
    from pathway_tpu.internals.license import get_license

    from pathway_tpu.internals.license import LicenseError

    lic = get_license()
    # free tier: a worker cap exists; entitlement checks answer cleanly
    assert lic.worker_cap() is None or lic.worker_cap() >= 1
    if "scale" not in lic.entitlements:
        with pytest.raises(LicenseError, match="entitlement"):
            lic.check_entitlements("scale")


def test_global_graph_clear_resets_state():
    pw.G.clear()
    T(
        """
    a
    1
    """
    )
    from pathway_tpu.internals.parse_graph import G

    assert len(G.engine_graph.nodes) > 0
    pw.G.clear()
    assert len(G.engine_graph.nodes) == 0


def test_metrics_stage_latency_count_sum_companions():
    """The quantile gauges gained _count/_sum companion counters so
    rate(sum)/rate(count) yields true windowed means (ISSUE 14)."""
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.monitoring_server import _metrics_text
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    t = T(
        """
    a
    1
    2
    """
    )
    out = t.select(b=t.a * 2)
    out._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    sched.run()
    # known samples: 2ms + 4ms into the process stage
    sched.latency.record("process", 2_000_000)
    sched.latency.record("process", 4_000_000)
    body = _metrics_text(sched)
    assert "# TYPE pathway_tpu_stage_latency_ms_count counter" in body
    assert "# TYPE pathway_tpu_stage_latency_ms_sum counter" in body
    import re

    counts = {
        m.group(1): int(m.group(2))
        for m in re.finditer(
            r'pathway_tpu_stage_latency_ms_count\{stage="([^"]+)"\} (\d+)',
            body,
        )
    }
    sums = {
        m.group(1): float(m.group(2))
        for m in re.finditer(
            r'pathway_tpu_stage_latency_ms_sum\{stage="([^"]+)"\} ([\d.]+)',
            body,
        )
    }
    assert set(counts) == set(sums)
    assert counts["process"] == 2
    assert sums["process"] == pytest.approx(6.0, rel=0.01)


def test_metrics_serving_latency_companions_carry_tenant_class():
    from pathway_tpu import serving
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.monitoring_server import _metrics_text
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    probe = serving.serving_probe()
    probe.record("serve_e2e", "interactive", 5_000_000)
    probe.record("serve_e2e", "interactive", 7_000_000)
    t = T(
        """
    a
    1
    """
    )
    t.select(b=t.a)._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    body = _metrics_text(sched)
    assert (
        'pathway_tpu_stage_latency_ms_count{stage="serve_e2e",'
        'tenant_class="interactive"}' in body
    )
    import re

    m = re.search(
        r'pathway_tpu_stage_latency_ms_sum\{stage="serve_e2e",'
        r'tenant_class="interactive"\} ([\d.]+)',
        body,
    )
    assert m is not None and float(m.group(1)) >= 12.0  # 5ms + 7ms


def test_debug_stacks_and_trace_endpoints():
    """/debug/stacks dumps every thread; /debug/trace?seconds=N returns
    Chrome-trace JSON windowed to the last N seconds (ISSUE 14)."""
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals import tracing
    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    tracing.configure(PATHWAY_TRACE="1", PATHWAY_TRACE_SAMPLE="1.0")
    t = T(
        """
    a
    1
    """
    )
    t.select(b=t.a)._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    port = _free_port()
    try:
        start_http_server(sched, port=port)
        ctx = tracing.new_trace()
        now = tracing.now_ns()
        tracing.record_span("debug_probe", now - 1_000_000, now, ctx=ctx)
        stacks = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/stacks", timeout=5
        ).read().decode()
        assert "--- Thread" in stacks
        assert "pw_monitoring" in stacks  # the server's own thread shows up
        doc = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/trace?seconds=30", timeout=5
            ).read()
        )
        names = [e["name"] for e in doc["traceEvents"]]
        assert "debug_probe" in names
        # a window that excludes the span returns without it
        doc0 = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/trace?seconds=0.0000001",
                timeout=5,
            ).read()
        )
        assert "debug_probe" not in [e["name"] for e in doc0["traceEvents"]]
    finally:
        server = getattr(sched, "_monitoring_server", None)
        if server is not None:
            server.shutdown()
            server.server_close()


def test_sigusr2_dumps_stacks_and_flushes_flight_recorder(tmp_path, capfd):
    import os
    import signal

    from pathway_tpu.internals import tracing

    if not tracing.install_sigusr2():
        pytest.skip("SIGUSR2 handler not installable here")
    tracing.configure(
        PATHWAY_TRACE="1",
        PATHWAY_TRACE_SAMPLE="1.0",
        PATHWAY_TRACE_DIR=str(tmp_path),
    )
    try:
        ctx = tracing.new_trace()
        now = tracing.now_ns()
        tracing.record_span("pre_kill", now - 1000, now, ctx=ctx)
        os.kill(os.getpid(), signal.SIGUSR2)
        time.sleep(0.1)  # handler runs on the main thread at a bytecode edge
        err = capfd.readouterr().err
        assert "--- Thread" in err
        dumps = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert any("sigusr2" in f for f in dumps)
    finally:
        tracing.configure(PATHWAY_TRACE_DIR=None)


# ------------------------------------ the chip account and the stall watchdog

_ALWAYS = ("chip_idle_ns", "chip_watch_ns", "stall_count", "stall_ns", "stall_cpu_ns", "stall_steal_ns")


@pytest.fixture
def recorder():
    from pathway_tpu.internals import tracing

    tracing.configure(PATHWAY_TRACE="1", PATHWAY_TRACE_SAMPLE="1.0")
    tracing.reset()
    yield tracing
    tracing.configure(PATHWAY_TRACE=None, PATHWAY_TRACE_SAMPLE=None)
    tracing.reset()


def test_snapshot_and_metrics_carry_the_chip_account_and_the_stalls(recorder):
    """The one door carries the account, the watchdog's totals and each
    stage's idle time from the process's start; /metrics the same."""
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals import device_counters
    from pathway_tpu.internals.monitoring_server import _metrics_text
    from pathway_tpu.internals.parse_graph import G

    first = device_counters.snapshot()
    assert all(k in first for k in _ALWAYS)  # before any stage or stall
    with recorder.span("host_work"):
        time.sleep(0.01)  # no ticket outstanding: the chip waits on this stage
    ticket = recorder.chip.ticket()
    with recorder.span("device_wait"):
        time.sleep(0.01)
        recorder.chip.collected(ticket)
    snap = device_counters.snapshot()
    assert snap["chip_watch_ns"] > first["chip_watch_ns"] and snap["chip_idle_ns"] > first["chip_idle_ns"]
    assert snap["chip_idle_ns"] <= snap["chip_watch_ns"]
    assert snap["span_idle_ns.host_work"] == snap["span_ns.host_work"] >= 10_000_000
    # busy until the collect, idle only for the exit's few microseconds after it
    assert snap["span_idle_ns.device_wait"] < snap["span_ns.device_wait"] / 100
    pw.G.clear()
    pw.debug.table_from_markdown("a\n1").select(b=pw.this.a)._capture_node()
    body = _metrics_text(Scheduler(G.engine_graph, autocommit_ms=20))
    pw.G.clear()
    assert 'pathway_tpu_span_idle_ns_total{stage="host_work"} ' in body
    for name in _ALWAYS:
        assert f"pathway_tpu_{name}_total " in body, name


def test_the_account_and_its_counters_stay_zero_under_trace_zero(recorder):
    from pathway_tpu.internals import device_counters

    recorder.configure(PATHWAY_TRACE="0")
    recorder.reset()
    ticket = recorder.chip.ticket()
    with recorder.span("off"):
        recorder.chip.collected(ticket)
    snap = device_counters.snapshot()
    assert ticket == 0 and recorder.chip.enq == 0
    assert {k: snap[k] for k in _ALWAYS} == dict.fromkeys(_ALWAYS, 0)
    assert not [k for k in snap if k.startswith("span_")]


def test_the_encoder_and_the_slab_leave_no_ticket_outstanding(recorder):
    import numpy as np

    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.tokenizer import HashTokenizer
    from pathway_tpu.parallel.executor import JittedEncoder
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    chip = recorder.chip
    cfg = EncoderConfig(hidden=32, layers=1, heads=2, mlp_dim=64, vocab_size=512, max_len=64)
    enc = JittedEncoder(cfg, tokenizer=HashTokenizer(512), max_batch=4)
    texts = ["w%d " % i * (1 + i % 5) for i in range(11)]  # three batches, pipelined
    taken = chip.enq
    vecs = enc.encode(texts)
    assert chip.enq - taken >= 3 and chip.outstanding() == 0
    idx = ShardedKnnIndex(32, capacity=64)
    idx.add_batch([f"k{i}" for i in range(len(texts))], vecs)  # a scatter takes no ticket
    taken = chip.enq
    hits = idx.search(vecs[:2], k=3)
    assert [len(h) for h in hits] == [3, 3]
    assert chip.enq - taken == 1 and chip.outstanding() == 0


def test_a_generation_leaves_no_ticket_outstanding(recorder):
    """A ticket for each prefill chunk and each decode step, all collected
    by the two blocks of ``generate``."""
    import numpy as np

    from pathway_tpu.parallel import JittedDecoder
    from tests import hybrid_toy

    executor = JittedDecoder(
        hybrid_toy.config_of(hybrid_toy.GROUP), params=hybrid_toy.float32_params(hybrid_toy.GROUP),
        slots=2, positions=hybrid_toy.POSITIONS, chunk_buckets=(8, 16),
    )
    chip = recorder.chip
    taken = chip.enq
    out = executor.generate(np.arange(1000, 1029, dtype=np.int32), 4)  # chunks of 16 and 16, 3 steps
    assert out["ids"].shape == (4,)
    assert chip.enq - taken == 2 + 3 and chip.outstanding() == 0
