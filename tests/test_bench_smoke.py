"""``bench.py --smoke`` is the benchmark driver's own CI check: a
seconds-long run over a tiny corpus that exercises the host-plane
sections (including the multi-process exchange probe) end to end and
must emit the driver contract — the LAST stdout line is one JSON object.
Keeps the committed BENCH numbers honest: if the driver rots, this fails
in tier-1 instead of at artifact-refresh time.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_emits_wellformed_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        env=env,
        capture_output=True,
        timeout=240,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
    assert lines, "no stdout from bench.py --smoke"
    doc = json.loads(lines[-1])  # driver contract: last line is the JSON

    assert doc["smoke"] is True
    assert doc["metric"] == "smoke_wordcount_rows_per_sec"
    assert isinstance(doc["value"], (int, float)) and doc["value"] > 0
    extra = doc["extra"]
    # the pipelined-exchange probe ran: both cluster sizes and the
    # overhead/efficiency keys the README rows trace back to
    for key in (
        "wordcount_rows_per_sec",
        "wordcount_1proc_rows_per_sec",
        "wordcount_multiprocess_rows_per_sec",
        "wordcount_exchange_overhead_pct",
        "wordcount_cpu_normalized_efficiency_2proc",
        "select_rows_per_sec",
        "strdt_rows_per_sec",
    ):
        assert isinstance(extra[key], (int, float)), key
    stats = extra["wordcount_exchange_stats"]
    assert stats["transmissions"] > 0
    assert stats["status_rounds"] > 0
    # the columnar differential ran and its gates held (ISSUE 19: the
    # columnar kernels must beat the row path and the _K_FRAME wire must
    # engage, ship fewer bytes, and burn less codec CPU than the row
    # wire; an assert inside bench_columnar surfaces here as
    # columnar_error)
    assert "columnar_error" not in extra, extra.get("columnar_error")
    assert extra["columnar_rows_per_sec"] >= extra["columnar_row_path_rows_per_sec"]
    assert extra["columnar_speedup_single_core"] >= 1.0
    # the streaming-latency probe ran and its dispersion gate held: a
    # p99/p50 blowout (raised inside bench.py) would surface here as a
    # streaming_latency_error key instead of the smoke summary
    assert "streaming_latency_error" not in extra, extra.get(
        "streaming_latency_error"
    )
    probe = extra["streaming_latency_smoke"]
    assert probe["p50_ms"] > 0
    assert probe["p99_ms"] >= probe["p50_ms"]
    assert probe["dispersion_p99_over_p50"] <= 25.0
    # per-stage breakdown present for the probed rate, with the wakeup
    # pipeline's stages all recording
    (rate_entry,) = extra["streaming_latency_vs_rate"].values()
    stages = rate_entry["stages"]
    for stage in ("ingest", "cut", "process", "sink", "e2e"):
        assert stages[stage]["count"] > 0, stage
        assert stages[stage]["p50_ms"] <= stages[stage]["p99_ms"]
    # the capacity cross-validation ran and held (ISSUE 15: the static
    # estimator's prediction must land within 3x of the sampled operator
    # state on both graphs; a breach raises inside bench.py and would
    # surface here as capacity_error)
    assert "capacity_error" not in extra, extra.get("capacity_error")
    for graph in ("wordcount", "index_churn"):
        ratio = extra[f"capacity_{graph}_ratio"]
        assert 1.0 / 3.0 <= ratio <= 3.0, (graph, ratio)
        assert extra[f"capacity_{graph}_measured_bytes"] > 0, graph
    # the device cross-validation ran and its gates held (ISSUE 20: a
    # warmed serving loop records ZERO steady-state compiles, the
    # shape-unstable control proves the counter is live, and the static
    # sweep predicts no recompile sites; any breach raises inside
    # bench_device and would surface here as device_error)
    assert "device_error" not in extra, extra.get("device_error")
    assert extra["device_steady_state_compiles"] == 0
    assert extra["device_unbucketed_compiles"] > 0
    assert extra["device_predicted_recompile_sites"] == 0
    assert extra["device_warmup_compiles"] < extra["device_unbucketed_compiles"]
    # the tracing-overhead gate ran and held (ISSUE 14: the always-on
    # flight recorder must cost <=2% on both workloads; a gate trip
    # raises inside bench.py and surfaces here as tracing_error)
    assert "tracing_error" not in extra, extra.get("tracing_error")
    assert extra["tracing_overhead_wordcount_pct"] <= 2.0
    assert extra["tracing_overhead_serving_pct"] <= 2.0
    # ...and the attribution block made it into the artifact: serving
    # requests attribute real time to embed / search / generate work (host
    # clock: `host_compute`; the category was called `device` before PR 25)
    assert extra["tracing_serving_attribution"].get("host_compute", 0) > 0
    assert "device" not in extra["tracing_serving_attribution"]
