"""Unit tests for the span recorder, flight recorder, and critical-path
attribution (pathway_tpu/internals/tracing.py + analysis/tracecrit.py)."""

import json
import os
import threading
import time

import pytest

from pathway_tpu.analysis import tracecrit
from pathway_tpu.internals import tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.configure(
        PATHWAY_TRACE="1",
        PATHWAY_TRACE_SAMPLE="1.0",
        PATHWAY_TRACE_TAIL_MS=None,
        PATHWAY_TRACE_RING=None,
        PATHWAY_TRACE_DIR=None,
    )
    tracing.reset()
    yield
    tracing.configure(
        PATHWAY_TRACE=None,
        PATHWAY_TRACE_SAMPLE=None,
        PATHWAY_TRACE_TAIL_MS=None,
        PATHWAY_TRACE_RING=None,
        PATHWAY_TRACE_DIR=None,
    )
    tracing.reset()


def _events(**kw):
    kw.setdefault("all_spans", True)
    return tracing.chrome_events(**kw)


# ------------------------------------------------------------- record path


def test_record_span_lands_in_ring_with_context_identity():
    ctx = tracing.new_trace()
    t0 = tracing.now_ns()
    sid = tracing.record_span("work", t0, t0 + 1000, ctx=ctx, args={"k": 3})
    assert sid != 0
    (ev,) = [e for e in _events() if e["name"] == "work"]
    assert ev["ph"] == "X"
    assert ev["args"]["trace_id"] == ctx.trace_id
    assert ev["args"]["parent"] == ctx.span_id
    assert ev["args"]["span_id"] == sid
    assert ev["args"]["k"] == 3
    assert ev["dur"] == pytest.approx(1.0)  # µs


def test_record_span_disabled_returns_zero_and_records_nothing():
    tracing.configure(PATHWAY_TRACE="0")
    ctx = tracing.TraceContext(1, 1)
    assert tracing.record_span("off", 0, 1, ctx=ctx) == 0
    assert _events() == []


def test_record_spans_batch_shares_parent_and_orders_ids():
    ctx = tracing.new_trace()
    t = tracing.now_ns()
    tracing.record_spans(
        ctx,
        [("a", t, t + 10, None), ("b", t + 10, t + 20, None),
         ("c", t + 20, t + 30, {"n": 1})],
    )
    evs = {e["name"]: e for e in _events() if e["name"] in "abc"}
    assert set(evs) == {"a", "b", "c"}
    for ev in evs.values():
        assert ev["args"]["trace_id"] == ctx.trace_id
        assert ev["args"]["parent"] == ctx.span_id
    ids = [evs[n]["args"]["span_id"] for n in "abc"]
    assert ids == sorted(ids) and len(set(ids)) == 3
    assert evs["c"]["args"]["n"] == 1


def test_span_cm_nests_and_parents_onto_enclosing_span():
    ctx = tracing.new_trace()
    with tracing.use(ctx):
        with tracing.span("outer") as outer:
            with tracing.span("inner"):
                pass
    by_name = {e["name"]: e for e in _events()}
    assert by_name["outer"]["args"]["parent"] == ctx.span_id
    assert by_name["inner"]["args"]["parent"] == outer.span_id
    assert by_name["inner"]["args"]["trace_id"] == ctx.trace_id


def test_span_cm_contextless_records_unsampled_zero_trace():
    with tracing.span("orphan"):
        pass
    (ev,) = [e for e in _events() if e["name"] == "orphan"]
    assert ev["args"]["trace_id"] == 0
    # context-free spans are flight-recorder noise floor: exported even
    # without all_spans
    assert [e["name"] for e in tracing.chrome_events()] == ["orphan"]


def test_span_cm_toggle_on_mid_block_records_nothing():
    tracing.configure(PATHWAY_TRACE="0")
    cm = tracing.span("flip", ctx=tracing.TraceContext(9, 9))
    cm.__enter__()
    tracing.configure(PATHWAY_TRACE="1")
    cm.__exit__(None, None, None)
    assert _events() == []


def test_set_ambient_swaps_and_restores():
    ctx = tracing.new_trace()
    assert tracing.current() is None
    prev = tracing.set_ambient(ctx)
    assert prev is None and tracing.current() is ctx
    assert tracing.set_ambient(prev) is ctx
    assert tracing.current() is None


def test_ring_wraps_keeping_most_recent_spans():
    tracing.configure(PATHWAY_TRACE_RING="64")
    tracing.reset()
    ctx = tracing.new_trace()
    for i in range(200):
        tracing.record_span(f"s{i}", i, i + 1, ctx=ctx)
    names = [e["name"] for e in _events()]
    assert len(names) == 64
    assert names[-1] == "s199" and "s0" not in names


def test_span_ids_unique_across_threads():
    ctx = tracing.new_trace()
    done = []

    def work(tag):
        for i in range(50):
            tracing.record_span(f"{tag}", i, i + 1, ctx=ctx)
        done.append(tag)

    ts = [threading.Thread(target=work, args=(f"t{j}",)) for j in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(done) == 4
    ids = [e["args"]["span_id"] for e in _events() if e["name"].startswith("t")]
    assert len(ids) == 200 and len(set(ids)) == 200


# -------------------------------------------------- sampling + tail keep


def test_head_sampling_governs_export_not_recording():
    tracing.configure(PATHWAY_TRACE_SAMPLE="0.0")
    ctx = tracing.new_trace()
    assert ctx.sampled is False
    tracing.record_span("hidden", 0, 1000, ctx=ctx)
    # not exported by default...
    assert [e for e in tracing.chrome_events() if e["name"] == "hidden"] == []
    # ...but the flight recorder still holds it
    assert [e for e in _events() if e["name"] == "hidden"]


def test_tail_keep_resurrects_slow_unsampled_request():
    tracing.configure(PATHWAY_TRACE_SAMPLE="0.0", PATHWAY_TRACE_TAIL_MS="1")
    ctx = tracing.new_trace()
    tracing.record_span("slow_req", ctx.t0_ns, ctx.t0_ns + 5_000_000, ctx=ctx)
    tracing.finish_request(ctx, ctx.t0_ns + 5_000_000)  # 5ms > 1ms threshold
    assert [e for e in tracing.chrome_events() if e["name"] == "slow_req"]


def test_fast_unsampled_request_stays_hidden():
    tracing.configure(PATHWAY_TRACE_SAMPLE="0.0", PATHWAY_TRACE_TAIL_MS="1")
    ctx = tracing.new_trace()
    tracing.record_span("fast_req", ctx.t0_ns, ctx.t0_ns + 10_000, ctx=ctx)
    tracing.finish_request(ctx, ctx.t0_ns + 10_000)  # 10µs < 1ms threshold
    assert [e for e in tracing.chrome_events() if e["name"] == "fast_req"] == []


# ------------------------------------------------------- context on wire


def test_trace_context_wire_roundtrip():
    ctx = tracing.TraceContext(123, 456, sampled=False)
    back = tracing.TraceContext.from_wire(ctx.to_wire())
    assert (back.trace_id, back.span_id, back.sampled) == (123, 456, False)
    assert tracing.TraceContext.from_wire("garbage") is None
    assert tracing.TraceContext.from_wire(None) is None


# ----------------------------------------------------- dump + merge paths


def test_dump_and_merge_trace_dir_stitch_ranks(tmp_path):
    spool = str(tmp_path)
    tracing.configure(PATHWAY_TRACE_DIR=spool)
    ctx = tracing.new_trace()
    tracing.set_rank(0)
    tracing.record_span("r0_work", 0, 1000, ctx=ctx)
    assert tracing.flush("test")
    # same machine-wide ids, different "process": re-stamp the rank the
    # way a supervised worker would and flush again
    tracing.reset()
    tracing.configure(PATHWAY_TRACE_DIR=spool)
    tracing.set_rank(1)
    tracing.record_span("r1_work", 2000, 3000, ctx=ctx)
    assert tracing.flush("test")
    merged = tracing.merge_trace_dir(spool)
    assert merged and os.path.exists(merged)
    with open(merged) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1}
    assert {e["name"] for e in evs} == {"r0_work", "r1_work"}
    tracing.set_rank(0)


def test_merge_trace_dir_empty_and_missing(tmp_path):
    assert tracing.merge_trace_dir(str(tmp_path)) is None
    assert tracing.merge_trace_dir(str(tmp_path / "nope")) is None


def test_flush_without_spool_is_noop():
    assert tracing.flush("test") is None


def test_dump_stacks_names_this_thread():
    text = tracing.dump_stacks()
    assert "--- Thread" in text
    assert "test_dump_stacks_names_this_thread" in text


# -------------------------------------------------------------- tracecrit


def _synthetic_trace(trace_id=7, base=1000.0):
    """root(10ms) -> [queue(4ms), work(5ms) -> inner_search(3ms)]"""

    def ev(name, sid, parent, ts, dur):
        return {
            "ph": "X", "name": name, "pid": 0, "tid": "t",
            "ts": ts, "dur": dur,
            "args": {"trace_id": trace_id, "span_id": sid, "parent": parent},
        }

    return [
        ev("serve_e2e", 70, trace_id, base, 10_000.0),
        ev("serve_sched_wait", 71, 70, base, 4_000.0),
        ev("generate", 72, 70, base + 4_000.0, 5_000.0),
        ev("search", 73, 72, base + 4_500.0, 3_000.0),
    ]


def test_attribute_exclusive_times_partition_the_root():
    info = tracecrit.attribute(_synthetic_trace())
    by = info["by_stage_ms"]
    assert by["serve_sched_wait"] == pytest.approx(4.0)
    assert by["generate"] == pytest.approx(2.0)  # 5ms minus 3ms child
    assert by["search"] == pytest.approx(3.0)
    assert by["serve_e2e"] == pytest.approx(1.0)  # 10 - (4 + 5) covered
    assert sum(by.values()) == pytest.approx(info["wall_ms"])
    cats = info["by_category_ms"]
    assert cats["queue_wait"] == pytest.approx(4.0)
    assert cats["host_compute"] == pytest.approx(5.0)


def test_critical_path_descends_into_biggest_child():
    path = tracecrit.critical_path(_synthetic_trace())
    assert [p["stage"] for p in path] == ["serve_e2e", "generate", "search"]
    assert path[0]["ms"] == pytest.approx(10.0)


def test_connected_traces_flags_orphaned_parent():
    good = _synthetic_trace(trace_id=7)
    bad = _synthetic_trace(trace_id=8)
    bad[3]["args"]["parent"] = 99999  # points at a span nobody recorded
    conn = tracecrit.connected_traces(good + bad)
    assert conn[7] is True and conn[8] is False


def test_report_rolls_up_quantiles_and_critical_path():
    events = []
    for i in range(10):
        events += _synthetic_trace(trace_id=100 + i, base=i * 100_000.0)
    rep = tracecrit.report(events)
    assert rep["requests"] == 10
    assert rep["p50"]["wall_ms"] == pytest.approx(10.0)
    assert rep["p99"]["wall_ms"] == pytest.approx(10.0)
    assert rep["mean_by_category_ms"]["host_compute"] == pytest.approx(5.0)
    assert [s["stage"] for s in rep["slowest"]["critical_path"]][0] == "serve_e2e"
    assert tracecrit.report([]) == {"requests": 0}


def test_report_over_real_recorded_spans():
    """End-to-end: record via the real API, export, attribute."""
    ctx = tracing.new_trace()
    with tracing.use(ctx):
        with tracing.span("serve_e2e"):
            with tracing.span("serve_sched_wait"):
                time.sleep(0.002)
            with tracing.span("generate"):
                time.sleep(0.003)
    tracing.finish_request(ctx)
    rep = tracecrit.report(_events())
    assert rep["requests"] == 1
    p50 = rep["p50"]["by_category_ms"]
    assert p50["queue_wait"] >= 1.0
    assert p50["host_compute"] >= 2.0
    conn = tracecrit.connected_traces(_events())
    assert conn[ctx.trace_id] is True


def test_served_requests_leave_connected_traces_of_host_stages():
    """What a request through the serving co-scheduler records: its
    stages under one trace with ``serve_e2e`` at the root, which the
    attribution files under ``host_compute`` (embed, search, generate, on
    the host's clock) and ``queue_wait``; no category is called
    ``device``."""
    from pathway_tpu.serving import HashingEmbedder, StageCoScheduler
    from pathway_tpu.stdlib.indexing.hnsw import HnswIndex
    from pathway_tpu.stdlib.indexing.segments import SegmentedIndex

    emb = HashingEmbedder(dim=32)
    seg = SegmentedIndex(HnswIndex(32, metric="cos"), delta_cap=64, auto_merge=False)
    seg.add([(f"doc{i}", emb(f"slab bucket probe lane {i}")) for i in range(12)])
    co = StageCoScheduler(embedder=emb, index=seg, k=4, lookahead=True)
    try:
        for i in range(3):
            co.submit(f"bucket probe {i}").result(timeout=10)
    finally:
        co.close()
        seg.close()
    events = _events()
    stages = {e["name"] for e in events}
    assert {"serve_embed", "generate", "serve_e2e"} <= stages, stages
    rep = tracecrit.report(events)
    assert rep["requests"] == 3
    assert all(tracecrit.connected_traces(events).values())
    assert rep["slowest"]["critical_path"][0]["stage"] == "serve_e2e"
    categories = set(rep["mean_by_category_ms"])
    assert "host_compute" in categories and "device" not in categories


@pytest.mark.parametrize(
    "stage,category",
    [
        ("epoch_process", "host_compute"),
        ("dispatch_segments", "host_compute"),
        ("collect_segments", "host_compute"),
        ("encoder_tokenize", "host_compute"),
        ("encoder_dispatch", "host_compute"),
        ("index_add", "host_compute"),
        ("index_keyset_rebuild", "host_compute"),
        ("slab_assign_slots", "host_compute"),
        ("slab_scatter", "host_compute"),
        ("connector_read", "host_compute"),
        ("rest_ingress", "host_compute"),
        ("encoder_readback", "device_wait"),
        ("search_readback", "device_wait"),
        ("epoch_cut_wait", "queue_wait"),
        ("rest_respond", "queue_wait"),
    ],
)
def test_measured_path_stages_have_a_category(stage, category):
    """The stages of the REST -> epoch -> encoder -> slab path (PERF.md
    section 3): host work is ``host_compute``, only the two readbacks are
    the host waiting for the device, and nothing is called ``device``."""
    assert tracecrit.categorize(stage) == category
    assert category in tracecrit.CATEGORIES and "device" not in tracecrit.CATEGORIES


# ------------------------------------------------------------ stage totals


def _record_by(path: str, stage: str, ctx, t0: int, dur: int) -> None:
    if path == "record_span":
        tracing.record_span(stage, t0, t0 + dur, ctx=ctx)
    elif path == "record_spans":
        tracing.record_spans(ctx, [(stage, t0, t0 + dur, None)])
    else:
        with tracing.use(ctx), tracing.span(stage):
            pass


@pytest.mark.parametrize("path", ["record_span", "record_spans", "span_cm"])
def test_stage_totals_count_every_record_path_and_never_fall(path):
    tracing.configure(PATHWAY_TRACE_RING="64")
    tracing.reset()
    ctx = tracing.new_trace()
    seen = (0, 0)
    for i in range(200):  # three times round the ring: totals do not wrap
        _record_by(path, "st", ctx, 1000 * i, 7)
        now = tracing.stage_totals()["st"]
        assert now[0] == i + 1 and now[1] >= seen[1]
        seen = now
    if path != "span_cm":
        assert seen == (200, 1400)
    assert len([e for e in _events() if e["name"] == "st"]) == 64


def test_stage_totals_sum_over_threads():
    ctx = tracing.new_trace()

    def work():
        for i in range(50):
            tracing.record_span("shared", i, i + 3, ctx=ctx)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    tracing.record_span("shared", 0, 5, ctx=ctx)  # and this thread's own ring
    assert tracing.stage_totals()["shared"] == (201, 4 * 50 * 3 + 5)


def test_stage_totals_read_zero_with_tracing_off():
    tracing.configure(PATHWAY_TRACE="0")
    ctx = tracing.TraceContext(1, 1)
    for path in ("record_span", "record_spans", "span_cm"):
        _record_by(path, "off", ctx, 0, 9)
    assert tracing.stage_totals() == {}
    from pathway_tpu.internals import device_counters

    assert not [k for k in device_counters.snapshot() if k.startswith("span_")]


def test_snapshot_and_metrics_carry_the_stage_totals():
    """The one door to the benchmark (flat ``span_ns.<stage>`` keys) and
    the same totals on /metrics under a ``stage`` label."""
    from pathway_tpu.internals import device_counters
    from pathway_tpu.internals.monitoring_server import _metrics_text

    tracing.record_span("door", 100, 350)
    tracing.record_span("door", 400, 450)
    snap = device_counters.snapshot()
    assert snap["span_ns.door"] == 300 and snap["span_count.door"] == 2

    import pathway_tpu as pw
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    pw.debug.table_from_markdown("a\n1").select(b=pw.this.a)._capture_node()
    body = _metrics_text(Scheduler(G.engine_graph, autocommit_ms=20))
    pw.G.clear()
    assert 'pathway_tpu_span_ns_total{stage="door"} 300' in body
    assert 'pathway_tpu_span_count_total{stage="door"} 2' in body
    assert body.count("# TYPE pathway_tpu_span_ns_total counter") == 1
    for name in ("epochs", "rest_requests", "encoder_tokens_padded", "encoder_segments", "search_queries",
                 "scatter_rows", "jit_compiles", "h2d_bytes", "d2h_bytes",
                 "h2d_transfers", "d2h_transfers"):
        assert f"pathway_tpu_{name}_total " in body, name


def test_span_cm_enters_a_profiler_annotation_of_its_name(monkeypatch):
    """While a jax.profiler session runs the stages lie in the profile's
    host plane: every ``span`` block enters the annotation class the
    recorder found (jax's ``TraceAnnotation``; a stand-in here)."""
    seen = []

    class _Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "_trace_annotation", _Annotation)
    with tracing.span("outer"):
        with tracing.span("inner"):
            pass
    assert seen == [("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]
    tracing.configure(PATHWAY_TRACE="0")
    with tracing.span("off"):
        pass
    assert len(seen) == 4


def test_recorder_and_counters_import_without_jax():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from pathway_tpu.internals import tracing, device_counters\n"
        "with tracing.span('s'):\n"
        "    pass\n"
        "device_counters.bump(epochs=1)\n"
        "assert device_counters.snapshot()['span_count.s'] == 1\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr


def test_jax_annotation_is_found_once_jax_is_loaded():
    pytest.importorskip("jax")
    import jax

    tracing._trace_annotation = None
    with tracing.span("find"):
        pass
    assert tracing._trace_annotation is jax.profiler.TraceAnnotation


# ------------------------------------------------------------ the one clock


def test_native_and_python_monotonic_clocks_are_one_clock():
    """``LatencyProbe.now_ns`` (native ``steady_clock``) stamps the
    scheduler's ``origin_ns`` / ``cut_ns``, which ``epoch_cut_wait`` puts
    beside ``time.monotonic_ns`` spans: both must be CLOCK_MONOTONIC."""
    from pathway_tpu.internals import native as native_mod
    from pathway_tpu.internals.monitoring import LatencyProbe

    native = native_mod.load()
    if native is None or not hasattr(native, "monotonic_ns"):
        pytest.skip("no native extension here")
    assert LatencyProbe().now_ns is native.monotonic_ns
    for _ in range(100):
        a = time.monotonic_ns()
        b = native.monotonic_ns()
        c = tracing.now_ns()
        assert a <= b <= c and c - a < 1_000_000


# ----------------------------------------- the REST -> epoch -> REST path


def _rest_roundtrip(handler_sleep_s: float = 0.0):
    """One request through ``rest_connector`` on loopback; returns the
    span events the recorder exports by default and all it holds."""
    import socket
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals import device_counters
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    class QuerySchema(pw.Schema):
        query: str

    def answer(q: str) -> str:
        time.sleep(handler_sleep_s)
        return q.upper()

    queries, response_writer = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=QuerySchema, delete_completed_queries=False
    )
    response_writer(queries.select(result=pw.apply(answer, pw.this.query)))
    sched = Scheduler(G.engine_graph, autocommit_ms=10)
    run_t = threading.Thread(target=sched.run, daemon=True)
    run_t.start()
    before = device_counters.snapshot()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/",
        data=json.dumps({"query": "hello"}).encode(),
        headers={"Content-Type": "application/json"},
    )
    body = None
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                body = json.loads(resp.read())
            break
        except (ConnectionError, urllib.error.URLError):
            time.sleep(0.2)  # server still coming up
    after = device_counters.snapshot()
    sched.stop()
    run_t.join(timeout=5)
    pw.G.clear()
    assert body == "HELLO"
    moved = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    return tracing.chrome_events(), _events(), moved


def test_rest_request_spans_share_a_trace_with_the_epoch_between_them():
    _default, events, moved = _rest_roundtrip()
    (ingress,) = [e for e in events if e["name"] == "rest_ingress"]
    (respond,) = [e for e in events if e["name"] == "rest_respond"]
    assert ingress["args"]["trace_id"] == respond["args"]["trace_id"] != 0
    epochs = [
        e for e in events
        if e["name"] == "epoch_process" and e["args"].get("epoch") == respond["args"]["epoch"]
    ]
    (epoch,) = epochs  # the request's rest_respond names the epoch that answered it
    assert epoch["args"]["rows"] >= 1 and epoch["args"]["requests"] == 1
    assert ingress["ts"] + ingress["dur"] <= epoch["ts"] + epoch["dur"]
    assert epoch["ts"] <= respond["ts"] <= epoch["ts"] + epoch["dur"] <= respond["ts"] + respond["dur"]
    # the cut wait lies under the epoch's trace, before its processing
    waits = [e for e in events if e["name"] == "epoch_cut_wait"
             and e["args"]["trace_id"] == epoch["args"]["trace_id"]]
    assert waits and waits[0]["ts"] + waits[0]["dur"] <= epoch["ts"] + 1.0
    assert moved["rest_requests"] == 1 and moved["rest_responses"] == 1
    assert moved["epochs"] >= 1 and moved["epoch_rows"] >= 1
    for stage in ("rest_ingress", "rest_respond", "epoch_cut_wait", "epoch_process"):
        assert moved[f"span_count.{stage}"] >= 1 and moved[f"span_ns.{stage}"] > 0


def test_rest_request_slower_than_the_tail_threshold_is_kept():
    tracing.configure(PATHWAY_TRACE_SAMPLE="0.0", PATHWAY_TRACE_TAIL_MS="20")
    default, events, _moved = _rest_roundtrip(handler_sleep_s=0.05)
    (ingress,) = [e for e in events if e["name"] == "rest_ingress"]
    kept = {e["name"] for e in default if e["args"]["trace_id"] == ingress["args"]["trace_id"]}
    assert kept == {"rest_ingress", "rest_respond"}


def test_fast_unsampled_rest_request_is_not_exported():
    tracing.configure(PATHWAY_TRACE_SAMPLE="0.0", PATHWAY_TRACE_TAIL_MS="20000")
    default, events, _moved = _rest_roundtrip()
    (ingress,) = [e for e in events if e["name"] == "rest_ingress"]
    assert not [e for e in default if e["args"]["trace_id"] == ingress["args"]["trace_id"]]


def test_a_collector_sweep_is_a_span():
    """The run loop's collector sweeps hold the GIL against every thread;
    each is a ``gc_sweep`` span, so a stall one causes lies in the flight
    recorder beside the requests that waited for it."""
    from pathway_tpu.internals.run import _ManagedGc

    with _ManagedGc() as mgc:
        assert not mgc.maybe_sweep()  # not due yet
        for n in range(1, 9):
            mgc._next_due = 0.0
            assert mgc.maybe_sweep()
    sweeps = [e["args"]["generation"] for e in _events() if e["name"] == "gc_sweep"]
    assert sweeps == [1, 1, 1, 1, 1, 1, 1, 2]  # every eighth is a full collection
    assert tracing.stage_totals()["gc_sweep"][0] == 8


# ------------------------------------------------------ the chip account


class _Clock:
    """A clock the test sets by hand (ns)."""

    def __init__(self, t: int = 1_000):
        self.t = t

    def __call__(self) -> int:
        return self.t


@pytest.fixture
def clock():
    """The account and the span clock on one hand-set clock, the account
    restarted idle at t = 1,000."""
    c = _Clock()
    real = tracing.chip.clock
    tracing.chip.clock = c
    tracing._monotonic_ns = c
    tracing.chip.restart()
    yield c
    tracing.chip.clock = real
    tracing._monotonic_ns = time.monotonic_ns
    tracing.chip.restart()


def _at(clock, t, fn, *args):
    clock.t = t
    return fn(*args)


def test_the_account_is_idle_with_no_ticket_outstanding(clock):
    chip = tracing.chip
    t1 = _at(clock, 1_100, tracing.chip.ticket)
    t2 = _at(clock, 1_150, tracing.chip.ticket)  # already busy: no transition
    _at(clock, 1_200, tracing.chip.collected, t1)  # t2 still outstanding
    assert chip.outstanding() == 1 and chip.idle_at(1_200) == 100
    _at(clock, 1_300, tracing.chip.collected, t2)
    assert chip.outstanding() == 0
    assert [chip.idle_at(t) for t in (1_000, 1_050, 1_100, 1_250, 1_300, 1_400)] == [0, 50, 100, 100, 100, 200]
    assert chip.idle_intervals(since_ns=None) == [(1_000, 1_100), (1_300, 1_300)]


def test_tickets_from_two_threads_interleave_in_enqueue_order(clock):
    """Two threads enqueue and wait in turn; collecting a later ticket says
    the earlier ones are done, and collecting an earlier one after it
    changes nothing."""
    import queue

    inboxes = {"a": queue.Queue(), "b": queue.Queue()}
    done: queue.Queue = queue.Queue()
    tickets: dict[str, int] = {}

    def worker(name):
        while True:
            op = inboxes[name].get()
            if op is None:
                return
            kind, key = op
            if kind == "take":
                tickets[key] = tracing.chip.ticket()
            else:
                tracing.chip.collected(tickets[key])
            done.put(op)

    threads = [threading.Thread(target=worker, args=(n,)) for n in inboxes]
    for t in threads:
        t.start()
    # (instant, thread, op, ticket name): idle 1,000-1,010, 1,040-1,050, 1,080-1,090, 1,110-
    plan = [
        (1_010, "a", "take", 1), (1_020, "b", "take", 2), (1_030, "a", "collect", 1), (1_040, "b", "collect", 2),
        (1_050, "b", "take", 3), (1_060, "a", "take", 4), (1_070, "b", "collect", 3), (1_080, "a", "collect", 4),
        (1_090, "a", "take", 5), (1_100, "b", "take", 6), (1_110, "b", "collect", 6), (1_120, "a", "collect", 5),
    ]
    try:
        for t, name, kind, key in plan:
            clock.t = t
            inboxes[name].put((kind, key))
            assert done.get(timeout=30) == (kind, key)
    finally:
        for q in inboxes.values():
            q.put(None)
        for t in threads:
            t.join(timeout=30)
    assert tickets == {1: 1, 2: 2, 3: 3, 4: 4, 5: 5, 6: 6}
    assert tracing.chip.outstanding() == 0
    clock.t = 1_130
    assert tracing.chip.idle_at(1_130) == 10 + 10 + 10 + 20
    assert tracing.chip.idle_intervals() == [(1_000, 1_010), (1_040, 1_050), (1_080, 1_090), (1_110, 1_130)]


def test_a_span_adds_the_idle_time_between_its_ends_to_its_stage(clock):
    clock.t = 1_100
    with tracing.span("host_stage"):
        ticket = _at(clock, 1_120, tracing.chip.ticket)
        with tracing.span("wait"):
            _at(clock, 1_150, tracing.chip.collected, ticket)
        clock.t = 1_170
    # the outer stage: idle 1,100-1,120 and 1,150-1,170; the wait: 1,120-1,150 busy
    assert tracing.stage_totals()["host_stage"] == (1, 70)
    assert tracing.stage_idle() == {"host_stage": 40, "wait": 0}
    clock.t = 1_200
    with tracing.span("idle_all_through"):
        clock.t = 1_260
    assert tracing.stage_idle()["idle_all_through"] == 60


def test_a_span_recorded_after_the_fact_looks_its_ends_up_in_the_ring(clock):
    """``record_span`` with a past ``t0`` (as ``epoch_cut_wait``,
    ``groupby_emit`` and ``dispatch_segments`` are recorded) gets the idle
    time between its ends from the transition ring."""
    for busy_from, busy_to in ((1_100, 1_200), (1_300, 1_350), (1_400, 1_500)):
        ticket = _at(clock, busy_from, tracing.chip.ticket)
        _at(clock, busy_to, tracing.chip.collected, ticket)
    clock.t = 1_600
    tracing.record_span("past", 1_150, 1_450)  # idle 1,200-1,300 and 1,350-1,400
    tracing.record_span("past", 1_520, 1_580)  # after the last transition: all idle
    tracing.record_span("before_the_account", 10, 20)  # nothing known before its start
    tracing.record_spans(tracing.new_trace(), [("batch", 1_050, 1_320, None), ("batch", 1_210, 1_290, None)])
    idle = tracing.stage_idle()
    assert idle["past"] == 100 + 50 + 60
    assert idle["before_the_account"] == 0
    assert idle["batch"] == (50 + 100) + 80


def test_the_ring_forgets_its_oldest_transitions(clock, monkeypatch):
    monkeypatch.setattr(tracing, "_CHIP_RING", 8)
    tracing.chip.restart()
    for i in range(10):  # 20 transitions: the ring keeps the last 8
        ticket = _at(clock, 2_000 + 100 * i, tracing.chip.ticket)
        _at(clock, 2_050 + 100 * i, tracing.chip.collected, ticket)
    chip = tracing.chip
    assert chip.idle_at(2_975) == chip.idle_at(2_950) + 25
    assert chip.idle_at(2_900) - chip.idle_at(2_650) == 3 * 50
    oldest = chip.idle_at(2_600)  # the oldest kept transition (busy from 2,600)
    assert chip.idle_at(1_500) == oldest
    tracing.chip.restart()


def test_the_account_and_the_watchdog_are_off_under_trace_zero(clock):
    tracing.configure(PATHWAY_TRACE="0")
    assert _at(clock, 1_100, tracing.chip.ticket) == 0
    assert tracing.chip.outstanding() == 0 and tracing.chip.enq == 0
    wd = tracing.StallWatchdog(clock=_Clock(0), cpu=_Clock(0), steal=_Clock(0), majflt=_Clock(0), totals=dict.fromkeys(tracing._stalls, 0))
    assert wd.tick(5_000_000_000) == 0 and not any(wd.totals.values())


# ------------------------------------------------------ the stall watchdog


def test_the_watchdog_records_a_late_wake_with_what_it_can_see():
    now, cpu, steal, flt = _Clock(0), _Clock(0), _Clock(0), _Clock(0)
    totals = dict.fromkeys(tracing._stalls, 0)
    wd = tracing.StallWatchdog(clock=now, cpu=cpu, steal=steal, majflt=flt, totals=totals)
    held, release = threading.Event(), threading.Event()

    def holder():
        with tracing.span("epoch_process"):
            with tracing.span("encoder_tokenize"):
                held.set()
                release.wait(30)

    t = threading.Thread(target=holder, name="engine-worker")
    t.start()
    try:
        assert held.wait(30)
        # on time: due at 20 ms, woken at 25 (5 ms of scheduling is no stall), and 90 ms late is none either
        now.t, cpu.t = 25_000_000, 3_000_000
        assert wd.tick(now.t) == 0
        now.t = 135_000_000
        assert wd.tick(now.t) == 0
        # due at 155 ms, woken at 2.155 s: 2 s late
        now.t, cpu.t, steal.t, flt.t = 2_155_000_000, 43_000_000, 1_950_000_000, 7
        assert wd.tick(now.t) == 2_000_000_000
        now.t += 21_000_000
        assert wd.tick(now.t) == 0
    finally:
        release.set()
        t.join(timeout=30)
    here = threading.current_thread().name  # not the process's own watchdog, which may meet a real stall meanwhile
    (ev,) = [e for e in _events() if e["name"] == "process_stall" and e["tid"] == here]
    assert ev["ts"] == 155_000 and ev["dur"] == 2_000_000  # µs
    assert ev["args"]["cpu_ms"] == 40.0 and ev["args"]["steal_ms"] == 1950.0 and ev["args"]["majflt"] == 7
    assert ev["args"]["open"] == {"engine-worker": "encoder_tokenize"}
    assert totals == {"stall_count": 1, "stall_ns": 2_000_000_000, "stall_cpu_ns": 40_000_000, "stall_steal_ns": 1_950_000_000}
    assert ev in tracing.chrome_events()  # context-free: exported by default


def test_one_watchdog_thread_runs_with_the_recorder():
    with tracing.span("starts_it"):
        pass
    names = [t.name for t in threading.enumerate()]
    assert names.count("pathway-stall-watchdog") == 1
    assert tracing._watchdog is not None


def test_a_stopped_process_leaves_one_stall_with_no_cpu(tmp_path):
    """SIGSTOP from outside for 0.6 s: the process's own watchdog records
    one ``process_stall`` of about that length, with little CPU time in it."""
    import subprocess
    import sys

    code = (
        "import json, os, signal, subprocess, sys, time\n"
        "from pathway_tpu.internals import tracing\n"
        "with tracing.span('s'):\n"
        "    pass\n"
        "time.sleep(0.2)\n"
        "subprocess.Popen(['sh', '-c', f'kill -STOP {os.getpid()}; sleep 0.6; kill -CONT {os.getpid()}'])\n"
        "time.sleep(1.5)\n"
        "print(json.dumps([e for e in tracing.chrome_events() if e['name'] == 'process_stall']))\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    stalls = json.loads(proc.stdout.strip().splitlines()[-1])
    longest = max(stalls, key=lambda e: e["dur"])
    assert 0.45e6 <= longest["dur"] <= 1.2e6, stalls
    assert longest["args"]["cpu_ms"] < 100, stalls


# ------------------------------------------------ the chip on a dumped trace


def test_a_dumped_trace_shows_the_chip_track_beside_the_stages(clock, tmp_path):
    assert not [e for e in _events() if e["tid"] == "chip"]  # no device work yet: no track
    ticket = _at(clock, 1_500, tracing.chip.ticket)
    clock.t = 1_600
    with tracing.span("encoder_readback"):
        clock.t = 2_500
        tracing.chip.collected(ticket)
    clock.t = 4_000
    path = tracing.dump(str(tmp_path / "t.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    chip = [(e["name"], e["ts"], e["dur"]) for e in events if e["tid"] == "chip"]
    assert chip == [("chip_idle", 1.0, 0.5), ("chip_idle", 2.5, 1.5)]  # µs
    assert {e["pid"] for e in events} == {tracing.current_rank()}
    assert [e["name"] for e in events if e["tid"] == threading.current_thread().name] == ["encoder_readback"]
