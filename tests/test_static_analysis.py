"""Tier-1 tests for the pre-flight static analyzer
(``pathway_tpu/analysis/``): every diagnostic code has a trigger graph
and a near-miss, plus the strict-mode abort-before-connectors gate."""

from __future__ import annotations

import pathlib
import threading

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import (
    SEV_ERROR,
    SEV_WARNING,
    AnalysisError,
    analyze,
)
from pathway_tpu.analysis import memory as mem
from pathway_tpu.engine import graph as eg
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals.parse_graph import G

REPO = pathlib.Path(__file__).resolve().parent.parent


def codes(diags):
    return [d.code for d in diags]


def _static_table():
    class S(pw.Schema):
        word: str
        n: int

    return pw.debug.table_from_rows(S, [("a", 1), ("b", 2)])


class _Subject(pw.io.python.ConnectorSubject):
    """Never-started source: graphs here are analyzed, not run."""

    def run(self) -> None:  # pragma: no cover - not executed
        pass


def _streaming_table():
    class S(pw.Schema):
        word: str
        n: int

    return pw.io.python.read(_Subject(), schema=S)


# ---------------------------------------------------------------- T001


def test_t001_join_key_type_mismatch():
    class L(pw.Schema):
        k: int
        v: int

    class R(pw.Schema):
        k: str
        w: int

    left = pw.debug.table_from_rows(L, [(1, 10)])
    right = pw.debug.table_from_rows(R, [("1", 20)])
    left.join(right, left.k == right.k).select(pw.this.v, pw.this.w)
    diags = analyze()
    t001 = [d for d in diags if d.code == "PW-T001"]
    assert t001 and t001[0].severity == SEV_ERROR


def test_t001_join_key_match_clean():
    class L(pw.Schema):
        k: int
        v: int

    class R(pw.Schema):
        k: int
        w: int

    left = pw.debug.table_from_rows(L, [(1, 10)])
    right = pw.debug.table_from_rows(R, [(1, 20)])
    left.join(right, left.k == right.k).select(pw.this.v, pw.this.w)
    assert "PW-T001" not in codes(analyze())


def test_t001_declare_type_contradiction():
    t = _static_table()
    t.select(s=pw.declare_type(str, pw.this.n + 1))
    diags = analyze()
    t001 = [d for d in diags if d.code == "PW-T001"]
    assert t001 and t001[0].severity == SEV_ERROR


def test_t001_declare_type_widening_clean():
    t = _static_table()
    # int -> float widening is a legal declaration
    t.select(f=pw.declare_type(float, pw.this.n + 1))
    assert "PW-T001" not in codes(analyze())


# ---------------------------------------------------------------- P001


def test_p001_call_py_on_streaming_column():
    t = _streaming_table()
    t.select(u=pw.apply(str.upper, t.word))
    diags = analyze()
    p001 = [d for d in diags if d.code == "PW-P001"]
    assert p001 and p001[0].severity == SEV_WARNING


def test_p001_static_call_py_clean():
    t = _static_table()
    t.select(u=pw.apply(str.upper, t.word))
    assert "PW-P001" not in codes(analyze())


def test_p001_vectorized_streaming_clean():
    t = _streaming_table()
    t.select(m=t.n + 1)  # lowers to pure VM bytecode, no CALL_PY
    assert "PW-P001" not in codes(analyze())


# ---------------------------------------------------------------- S001


def test_s001_unwindowed_groupby_over_stream():
    t = _streaming_table()
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    diags = analyze()
    s001 = [d for d in diags if d.code == "PW-S001"]
    assert s001 and s001[0].severity == SEV_WARNING


def test_s001_static_groupby_clean():
    t = _static_table()
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    assert "PW-S001" not in codes(analyze())


def _streaming_events():
    class S(pw.Schema):
        k: str
        t: int
        v: int

    return pw.io.python.read(_Subject(), schema=S)


def test_s001_interval_join_bounds_downstream_state():
    """A finite-interval temporal join is watermark-evicted: stateful
    consumers downstream of it must not be reported as unbounded."""
    from pathway_tpu.stdlib import temporal

    a = _streaming_events()
    b = _streaming_events()
    j = temporal.interval_join(
        a, b, a.t, b.t, temporal.interval(-1, 1), pw.left.k == pw.right.k
    ).select(k=pw.left.k, v=pw.left.v)
    j.groupby(j.k).reduce(j.k, c=pw.reducers.count())
    assert "PW-S001" not in codes(analyze())


def test_s001_asof_join_bounds_downstream_state():
    from pathway_tpu.stdlib import temporal

    a = _streaming_events()
    b = _streaming_events()
    j = temporal.asof_join(
        a, b, a.t, b.t, pw.left.k == pw.right.k
    ).select(k=pw.left.k, v=pw.left.v)
    j.groupby(j.k).reduce(j.k, c=pw.reducers.count())
    assert "PW-S001" not in codes(analyze())


def test_s001_asof_now_join_bounds_downstream_state():
    from pathway_tpu.stdlib import temporal

    a = _streaming_events()
    b = _streaming_events()
    j = temporal.asof_now_join(a, b, pw.left.k == pw.right.k).select(
        k=pw.left.k, v=pw.left.v
    )
    j.groupby(j.k).reduce(j.k, c=pw.reducers.count())
    assert "PW-S001" not in codes(analyze())


def test_s001_plain_join_still_fires_downstream():
    """Positive control for the temporal near-misses: the same shape with
    an unwindowed join keeps the diagnostic."""
    a = _streaming_events()
    b = _streaming_events()
    a.join(b, a.k == b.k).select(k=pw.left.k, v=pw.right.v)
    diags = analyze()
    assert "PW-S001" in codes(diags)


# ---------------------------------------------------------------- S002


def test_s002_deduplicate_over_retracting_input():
    t = _streaming_table()
    agg = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    agg.deduplicate(value=agg.c, acceptor=lambda new, old: new > old)
    diags = analyze()
    s002 = [d for d in diags if d.code == "PW-S002"]
    assert s002 and s002[0].severity == SEV_ERROR


def test_s002_deduplicate_over_append_only_clean():
    t = _static_table()
    t.deduplicate(value=t.n, acceptor=lambda new, old: new > old)
    assert "PW-S002" not in codes(analyze())


# ---------------------------------------------------------------- D001


def test_d001_dead_column():
    t = _static_table()
    sel = t.select(t.word, dead=t.n + 1)
    sel.select(t2=pw.this.word)._capture_node()
    diags = analyze()
    d001 = [d for d in diags if d.code == "PW-D001"]
    assert d001 and d001[0].severity == SEV_WARNING
    assert "dead" in d001[0].message


def test_d001_used_column_clean():
    t = _static_table()
    sel = t.select(t.word, kept=t.n + 1)
    sel.select(t2=pw.this.word, k=pw.this.kept)._capture_node()
    assert "PW-D001" not in codes(analyze())


# ---------------------------------------------------------------- N001


def test_n001_optional_into_declared_non_optional_sink():
    t = _static_table()
    opt = pw.if_else(t.n > 1, t.n, None)  # Optional[int]
    t.select(v=pw.declare_type(int, opt))._capture_node()
    diags = analyze()
    n001 = [d for d in diags if d.code == "PW-N001"]
    assert n001 and n001[0].severity == SEV_WARNING


def test_n001_unwrap_clean():
    t = _static_table()
    opt = pw.if_else(t.n > 1, t.n, None)
    t.select(v=pw.unwrap(opt))._capture_node()
    assert "PW-N001" not in codes(analyze())


# ------------------------------------------------------------ surfaces


def test_analyze_returns_sorted_diagnostics():
    t = _streaming_table()
    agg = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    agg.deduplicate(value=agg.c, acceptor=lambda new, old: new > old)
    diags = analyze()
    sevs = [d.severity for d in diags]
    assert sevs == sorted(sevs, key=(SEV_ERROR, SEV_WARNING, "info").index)
    assert all(d.format() for d in diags)


def test_strict_mode_aborts_before_connector_starts():
    started = threading.Event()

    class Tracking(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            started.set()

    class S(pw.Schema):
        word: str

    t = pw.io.python.read(Tracking(), schema=S)
    # an error-severity finding: dedup over a retracting input
    agg = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    agg.deduplicate(value=agg.c, acceptor=lambda new, old: new > old)
    with pytest.raises(AnalysisError) as ei:
        pw.run(strict=True)
    assert any(d.code == "PW-S002" for d in ei.value.diagnostics)
    assert not started.is_set(), "connector thread ran despite strict abort"


def test_strict_env_var(monkeypatch):
    monkeypatch.setenv("PATHWAY_STRICT", "1")

    t = _streaming_table()
    agg = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    agg.deduplicate(value=agg.c, acceptor=lambda new, old: new > old)
    with pytest.raises(AnalysisError):
        pw.run()


def test_non_strict_run_tolerates_warnings():
    t = _static_table()
    t.select(t.word, t.n)._capture_node()
    ctx = pw.run(strict=True)  # clean graph: strict run proceeds
    assert ctx is not None


def test_package_exports():
    assert pw.analyze is analyze
    assert pw.Diagnostic is not None
    assert pw.AnalysisError is AnalysisError
    assert pw.estimate_memory is mem.estimate_memory
    assert pw.MemoryReport is mem.MemoryReport
    assert pw.EstimateParams is mem.EstimateParams


# ------------------------------------------------- distribution helpers


def _files_table(tmp_path):
    """Byte-range-partitioned, non-order-preserving source (PR 9 split)."""
    d = tmp_path / "data"
    d.mkdir(exist_ok=True)
    (d / "part.jsonl").write_text(
        '{"word": "a", "n": 1}\n{"word": "b", "n": 2}\n'
    )

    class S(pw.Schema):
        word: str
        n: int

    return pw.io.jsonlines.read(str(d), schema=S, mode="static")


def _input_node():
    return next(
        n for n in G.engine_graph.nodes if isinstance(n, eg.InputNode)
    )


# ---------------------------------------------------------------- X001


def test_x001_dedup_over_byte_range_files(tmp_path):
    t = _files_table(tmp_path)
    t.deduplicate(value=t.n, acceptor=lambda new, old: new > old)
    diags = analyze()
    x001 = [d for d in diags if d.code == "PW-X001"]
    assert x001 and x001[0].severity == SEV_ERROR
    assert "order" in x001[0].message


def test_x001_index_upsert_over_byte_range_files(tmp_path):
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    docs = _files_table(tmp_path)
    docs = docs.select(
        word=pw.this.word,
        vec=pw.apply(lambda n: (float(n), 0.0), pw.this.n),
    )
    index = BruteForceKnnFactory(dimensions=2, reserved_space=8).build_data_index(
        docs.vec, docs
    )

    class QueryS(pw.Schema):
        qx: float
        qy: float

    queries = pw.io.python.read(_Subject(), schema=QueryS)
    queries = queries.select(
        qvec=pw.apply(lambda x, y: (float(x), float(y)), pw.this.qx, pw.this.qy)
    )
    # the index node only materializes once a query consumes it
    index.query_as_of_now(queries.qvec, number_of_matches=1)
    assert "PW-X001" in codes(analyze())


def test_x001_python_fed_index_upsert_clean():
    """The ISSUE near-miss: a ``pw.io.python``-fed upsert stream is a
    single reader, so the keyed index upsert must NOT fire PW-X001."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    class DocS(pw.Schema):
        doc_id: str = pw.column_definition(primary_key=True)
        vx: float
        vy: float

    docs = pw.io.python.read(_Subject(), schema=DocS)
    docs = docs.select(
        doc_id=pw.this.doc_id,
        vec=pw.apply(lambda x, y: (float(x), float(y)), pw.this.vx, pw.this.vy),
    )
    BruteForceKnnFactory(dimensions=2, reserved_space=8).build_data_index(
        docs.vec, docs
    )
    assert "PW-X001" not in codes(analyze())


def test_x001_python_fed_dedup_clean():
    t = _streaming_table()
    t.deduplicate(value=t.n, acceptor=lambda new, old: new > old)
    assert "PW-X001" not in codes(analyze())


def test_x001_unordered_partitioned_upsert_source():
    """The source itself is the order-sensitive consumer when it dedups
    an upsert session across an unordered split."""
    _streaming_table()
    _input_node().meta["source"].update(
        {"upsert": True, "partitioning": "round-robin", "order_preserving": False}
    )
    diags = analyze()
    x001 = [d for d in diags if d.code == "PW-X001"]
    assert x001 and x001[0].severity == SEV_ERROR
    assert "upsert" in x001[0].message


# ---------------------------------------------------------------- X002


def test_x002_non_copartitioned_groupby(tmp_path):
    t = _files_table(tmp_path)
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    diags = analyze()
    x002 = [d for d in diags if d.code == "PW-X002"]
    assert x002 and x002[0].severity == SEV_WARNING
    assert "exchange" in x002[0].message
    # volume estimate comes from the source's build-time dtype annotation
    assert "bytes/row" in x002[0].message


def test_x002_copartitioned_regroup_clean(tmp_path):
    """A second groupby on the first one's key is already co-partitioned:
    only the first (source-fed) groupby warns."""
    t = _files_table(tmp_path)
    agg = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    agg.groupby(agg.word).reduce(agg.word, m=pw.reducers.max(agg.c))
    diags = analyze()
    x002 = [d for d in diags if d.code == "PW-X002"]
    assert len(x002) == 1


def test_x002_local_source_clean():
    t = _streaming_table()
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    assert "PW-X002" not in codes(analyze())


# ---------------------------------------------------------------- X003


def test_x003_order_dependent_reducer_to_sink(tmp_path):
    t = _files_table(tmp_path)
    agg = t.groupby(t.word).reduce(t.word, last=pw.reducers.latest(t.n))
    agg._capture_node()
    diags = analyze()
    x003 = [d for d in diags if d.code == "PW-X003"]
    assert x003 and x003[0].severity == SEV_ERROR
    assert "latest" in x003[0].message


def test_x003_commutative_reducer_clean(tmp_path):
    t = _files_table(tmp_path)
    agg = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    agg._capture_node()
    assert "PW-X003" not in codes(analyze())


def test_x003_ordered_source_clean():
    t = _streaming_table()
    agg = t.groupby(t.word).reduce(t.word, last=pw.reducers.latest(t.n))
    agg._capture_node()
    assert "PW-X003" not in codes(analyze())


# ---------------------------------------------------------------- R001


def test_r001_external_state_without_hooks():
    t = _streaming_table()
    node = eg.Node(G.engine_graph, [t._node], "external_sink")
    node.adapter = object()
    diags = analyze()
    r001 = [d for d in diags if d.code == "PW-R001"]
    assert r001 and r001[0].severity == SEV_ERROR
    assert "checkpoint" in r001[0].message


class _StatefulAdapter:
    def state_dict(self):
        return {}

    def load_state_dict(self, state):
        pass


class _HookedNode(eg.Node):
    def snapshot_state(self, ctx):
        return {}

    def on_restore(self, ctx):
        pass


def test_r001_hooked_external_state_clean():
    t = _streaming_table()
    node = _HookedNode(G.engine_graph, [t._node], "hooked_sink")
    node.adapter = _StatefulAdapter()
    assert "PW-R001" not in codes(analyze())


def test_r001_unserializable_adapter_flagged():
    """Hooks overridden but the adapter cannot round-trip its state:
    snapshot_state has nothing to fold in, still a coverage hole."""
    t = _streaming_table()
    node = _HookedNode(G.engine_graph, [t._node], "hooked_sink")
    node.adapter = object()
    diags = analyze()
    r001 = [d for d in diags if d.code == "PW-R001"]
    assert r001 and "state_dict" in r001[0].message


def test_r001_static_path_clean():
    """Out-of-band state on a static (bounded, replayable-from-source)
    path is not a recovery hazard."""
    t = _static_table()
    node = eg.Node(G.engine_graph, [t._node], "static_sink")
    node.adapter = object()
    assert "PW-R001" not in codes(analyze())


# ---------------------------------------------------------------- R002


def test_r002_single_owner_index_without_standby():
    """Availability hole: checkpoint-covered (hooks + stateful adapter,
    so no PW-R001) but the only copy of serving state lives on one rank
    with no snapshot-backed standby."""
    t = _streaming_table()
    node = _HookedNode(G.engine_graph, [t._node], "index_sink")
    node.adapter = _StatefulAdapter()
    diags = analyze()
    r002 = [d for d in diags if d.code == "PW-R002"]
    assert r002 and r002[0].severity == SEV_WARNING
    assert "standby" in r002[0].message


def test_r002_standby_annotation_clean():
    """Near-miss: the same single-owner node with a declared
    snapshot-backed standby (meta['failover']['standby']) is covered."""
    t = _streaming_table()
    node = _HookedNode(G.engine_graph, [t._node], "index_sink")
    node.adapter = _StatefulAdapter()
    node.meta["failover"] = {"standby": True}
    assert "PW-R002" not in codes(analyze())


def test_r002_static_path_clean():
    """A bounded static pipeline has no availability window to cover."""
    t = _static_table()
    node = _HookedNode(G.engine_graph, [t._node], "index_sink")
    node.adapter = _StatefulAdapter()
    assert "PW-R002" not in codes(analyze())


def test_r002_sharded_serving_graph_clean_single_owner_flagged():
    """The composed serving graph: RagServingApp(shards=2) stamps the
    standby annotation (near-miss), the default single-owner app does
    not (trigger)."""
    from pathway_tpu.serving import RagServingApp

    app = RagServingApp(shards=2)
    try:
        app.build()
        assert "PW-R002" not in codes(analyze())
    finally:
        app.close()

    G.clear()
    app2 = RagServingApp()
    try:
        app2.build()
        diags = analyze()
        r002 = [d for d in diags if d.code == "PW-R002"]
        assert r002 and r002[0].severity == SEV_WARNING
    finally:
        app2.close()


# ------------------------------------- M001 / M002 / M003 (memory pass)


def _keyed_streaming_events():
    """Upsert-keyed stream: live cardinality is O(keys), not O(stream)."""

    class S(pw.Schema):
        k: str = pw.column_definition(primary_key=True)
        t: int
        v: int

    return pw.io.python.read(_Subject(), schema=S)


def _stream_join(sink: bool):
    a = _streaming_events()
    b = _streaming_events()
    j = a.join(b, a.k == b.k).select(k=pw.left.k, v=pw.right.v)
    if sink:
        j._capture_node()


def test_m001_stream_linear_state_reaching_sink():
    _stream_join(sink=True)
    diags = analyze()
    m1 = [d for d in diags if d.code == "PW-M001"]
    assert m1 and all(d.severity == SEV_ERROR for d in m1)
    assert m1[0].details["growth"] == mem.G_STREAM
    assert m1[0].details["estimated_bytes"] > 0


def test_m001_needs_sink_but_m003_still_warns():
    """Same join, nothing captured: not an M001 error (no sink pays the
    cost at read time), but snapshot bytes still grow -> M003."""
    _stream_join(sink=False)
    diags = analyze()
    assert "PW-M001" not in codes(diags)
    m3 = [d for d in diags if d.code == "PW-M003"]
    assert m3 and all(d.severity == SEV_WARNING for d in m3)
    assert m3[0].details["growth"] == mem.G_STREAM


def test_m001_m003_upsert_keyed_join_clean():
    """The fix the M001 message recommends: key the sources and the same
    join shape retains O(keys), even with a sink attached."""
    a = _keyed_streaming_events()
    b = _keyed_streaming_events()
    a.join(b, a.k == b.k).select(
        k=pw.left.k, v=pw.right.v
    )._capture_node()
    got = codes(analyze())
    assert "PW-M001" not in got
    assert "PW-M003" not in got


def test_m003_bounded_temporal_join_clean():
    from pathway_tpu.stdlib import temporal

    a = _streaming_events()
    b = _streaming_events()
    temporal.interval_join(
        a, b, a.t, b.t, temporal.interval(-1, 1), pw.left.k == pw.right.k
    ).select(k=pw.left.k, v=pw.left.v)
    got = codes(analyze())
    assert "PW-M003" not in got
    assert "PW-M001" not in got


def test_m002_budget_breach_carries_breakdown(monkeypatch):
    monkeypatch.setenv("PATHWAY_MEMORY_BUDGET", "64K")
    t = _streaming_table()
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    diags = analyze()
    m2 = [d for d in diags if d.code == "PW-M002"]
    assert m2 and m2[0].severity == SEV_WARNING
    det = m2[0].details
    assert det["budget_bytes"] == 64 * 1024
    assert det["estimated_bytes"] > det["budget_bytes"]
    sizes = [b for _label, b in det["breakdown"]]
    assert sizes and sizes == sorted(sizes, reverse=True)


def test_m002_ample_budget_clean(monkeypatch):
    monkeypatch.setenv("PATHWAY_MEMORY_BUDGET", "1TiB")
    t = _streaming_table()
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    assert "PW-M002" not in codes(analyze())


# ------------------------------------------------ estimator unit tests


def test_growth_lattice_total_order():
    order = (mem.G_CONSTANT, mem.G_BOUNDED, mem.G_KEYS, mem.G_STREAM)
    for i, lo in enumerate(order):
        for hi in order[i:]:
            assert mem.growth_join(lo, hi) == hi
            assert mem.growth_meet(lo, hi) == lo
    assert mem.growth_join() == mem.G_CONSTANT
    assert mem.growth_meet() == mem.G_STREAM


def test_dtype_width_from_annotations():
    assert mem.dtype_width(dt.INT) == 8
    assert mem.dtype_width(dt.DATE_TIME_UTC) == 8
    assert mem.dtype_width(dt.STR, str_bytes=40) == 40
    assert mem.dtype_width(dt.JSON, str_bytes=10) == 40  # nested payload
    assert mem.dtype_width(dt.ANY) == 24  # unannotated boxed object
    assert mem.dtype_width(dt.Optional(dt.INT)) == 8  # optionality is free


def test_parse_budget_suffixes():
    assert mem.parse_budget(None) is None
    assert mem.parse_budget("") is None
    assert mem.parse_budget("4096") == 4096
    assert mem.parse_budget("64K") == 64 * 1024
    assert mem.parse_budget("64KB") == 64 * 1024
    assert mem.parse_budget("4GiB") == 4 * (1 << 30)
    assert mem.parse_budget("1.5M") == int(1.5 * (1 << 20))
    assert mem.parse_budget("2T") == 2 * (1 << 40)
    assert mem.parse_budget("lots") is None


def test_estimate_params_env_and_overrides(monkeypatch):
    monkeypatch.setenv("PATHWAY_MEMORY_ROWS", "123")
    monkeypatch.setenv("PATHWAY_MEMORY_KEYS", "7")
    monkeypatch.setenv("PATHWAY_MEMORY_STR_BYTES", "not-a-number")
    p = mem.EstimateParams.from_env(workers=3)
    assert p.rows == 123
    assert p.distinct_keys == 7
    assert p.str_bytes == mem.EstimateParams.str_bytes  # bad env -> default
    assert p.workers == 3  # explicit override beats env
    assert p.cardinality(mem.G_STREAM) == 123
    assert p.cardinality(mem.G_KEYS) == 7
    assert p.cardinality(mem.G_BOUNDED) == p.window_rows
    assert p.cardinality(mem.G_CONSTANT) == 0


def test_split_bytes_placement_lattice():
    assert mem._split_bytes(("single",), 100, 4) == 100
    assert mem._split_bytes(("repl",), 100, 4) == 100  # every rank holds it
    assert mem._split_bytes(("key", "word"), 100, 4) == 25
    assert mem._split_bytes(("key", "word"), 101, 4) == 26  # ceil, not floor
    assert mem._split_bytes(("key", "word"), 100, 1) == 100


def test_window_bounds_join_retention_not_stream_length():
    from pathway_tpu.stdlib import temporal

    a = _streaming_events()
    b = _streaming_events()
    temporal.interval_join(
        a, b, a.t, b.t, temporal.interval(-1, 1), pw.left.k == pw.right.k
    ).select(k=pw.left.k)
    small = pw.estimate_memory(optimize=0, window_rows=16)
    big = pw.estimate_memory(optimize=0, window_rows=4096)
    j_small = next(o for o in small.operators if o.kind == "IntervalJoinNode")
    j_big = next(o for o in big.operators if o.kind == "IntervalJoinNode")
    assert j_small.growth == mem.G_BOUNDED
    assert j_small.total_bytes < j_big.total_bytes
    # a 100x longer stream must not move a window-bounded buffer
    longer = pw.estimate_memory(optimize=0, window_rows=16, rows=100_000_000)
    j_longer = next(
        o for o in longer.operators if o.kind == "IntervalJoinNode"
    )
    assert j_longer.total_bytes == j_small.total_bytes


def test_per_worker_split_with_partitioned_source(tmp_path):
    t = _files_table(tmp_path)
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    one = pw.estimate_memory(optimize=0, workers=1)
    four = pw.estimate_memory(optimize=0, workers=4)
    assert four.workers == 4
    assert 0 < four.max_worker_bytes < one.max_worker_bytes
    assert four.total_bytes == one.total_bytes  # split, not shrunk


def test_memory_report_surfaces():
    t = _streaming_table()
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
    rep = pw.estimate_memory()
    assert rep.total_bytes > 0
    assert rep.by_id()  # node-keyed view
    txt = rep.format()
    assert "TOTAL" in txt and "groupby" in txt


# --------------------------------- golden: plan-aware estimates (sat 3)


def test_golden_dead_column_elided_from_optimized_estimate():
    """The estimate must price the graph that RUNS: a join side's dead
    column is nulled by the plan rewriter, so the optimize=2 report is
    strictly cheaper than the raw optimize=0 one."""
    a = _streaming_events()
    sel = a.select(a.k, dead=a.k)  # str-width ballast, never used
    b = _streaming_events()
    sel.join(b, sel.k == b.k).select(
        k=pw.left.k, v=pw.right.v
    )._capture_node()
    r0 = pw.estimate_memory(optimize=0)
    r2 = pw.estimate_memory(optimize=2)
    assert r0.level == 0 and r2.level == 2
    j0 = next(o for o in r0.operators if o.kind == "JoinNode")
    j2 = next(o for o in r2.operators if o.kind == "JoinNode")
    assert j2.total_bytes < j0.total_bytes
    assert r2.total_bytes < r0.total_bytes


# ----------------------- predicted vs measured (runtime cross-check)


def _run_wordcount_scenario(monkeypatch) -> None:
    n_rows, n_keys = 600, 40
    monkeypatch.setenv("PATHWAY_MEMORY_ROWS", str(n_rows))
    monkeypatch.setenv("PATHWAY_MEMORY_KEYS", str(n_keys))
    monkeypatch.setenv("PATHWAY_MEMORY_STR_BYTES", "8")

    class Feed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for i in range(n_rows):
                self.next(word=f"w{i % n_keys}", n=i)
            self.commit()

    class S(pw.Schema):
        word: str
        n: int

    t = pw.io.python.read(Feed(), schema=S)
    t.groupby(t.word).reduce(t.word, c=pw.reducers.count())._capture_node()
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)


def _run_index_churn_scenario(monkeypatch) -> None:
    """Keyed upserts through an external KNN index, every second key
    upserted again, and as many queries as re-upserts (the scenario's
    ``keys`` is one cardinality for every upsert source)."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    n_docs = 400
    churn = n_q = n_docs // 2
    monkeypatch.setenv("PATHWAY_MEMORY_ROWS", str(n_docs + churn + n_q))
    monkeypatch.setenv("PATHWAY_MEMORY_KEYS", str(n_docs))
    monkeypatch.setenv("PATHWAY_MEMORY_STR_BYTES", "8")
    monkeypatch.setenv("PATHWAY_MEMORY_ARRAY_BYTES", "160")

    class Doc(pw.Schema):
        doc_id: str = pw.column_definition(primary_key=True)
        vx: float
        vy: float
        vz: float
        vw: float

    class Query(pw.Schema):
        qid: str = pw.column_definition(primary_key=True)
        qx: float
        qy: float
        qz: float
        qw: float

    class DocFeed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for i in range(n_docs + churn):
                key = i if i < n_docs else (i - n_docs) * 2
                self.next(doc_id=f"doc{key}", vx=float(i), vy=1.0, vz=float(i % 7), vw=2.0)
                if i % 128 == 127:
                    self.commit()
            self.commit()

    class QueryFeed(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            for i in range(n_q):
                self.next(qid=f"q{i}", qx=1.0, qy=float(i), qz=0.0, qw=0.0)
            self.commit()

    def vec(a, b, c, e):
        return (float(a), float(b), float(c), float(e))

    docs = pw.io.python.read(DocFeed("docs"), schema=Doc, name="docs")
    docs = docs.select(
        doc_id=pw.this.doc_id,
        vec=pw.apply(vec, pw.this.vx, pw.this.vy, pw.this.vz, pw.this.vw),
    )
    queries = pw.io.python.read(QueryFeed("queries"), schema=Query, name="queries")
    queries = queries.select(
        qid=pw.this.qid,
        qvec=pw.apply(vec, pw.this.qx, pw.this.qy, pw.this.qz, pw.this.qw),
    )
    index = BruteForceKnnFactory(
        dimensions=4, reserved_space=n_docs + n_q
    ).build_data_index(docs.vec, docs)
    hits = index.query_as_of_now(queries.qvec, number_of_matches=2)
    answered: list = []
    pw.io.subscribe(
        hits, on_change=lambda key, row, time, is_addition: answered.append(key)
    )
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert answered, "index-churn queries produced no results"


@pytest.mark.parametrize(
    "scenario",
    [_run_wordcount_scenario, _run_index_churn_scenario],
    ids=["wordcount", "index_churn"],
)
def test_predicted_vs_measured_operator_state(monkeypatch, scenario):
    """Capacity cross-validation: run a real graph with its scenario in
    ``PATHWAY_MEMORY_*``, then join the static estimate against the
    scheduler's sampled ``approx_state_bytes`` via ``memory_stats``, over
    the operators that have both.  The estimator is a provisioning
    tool: a miss beyond 3x either way means its constants or growth
    classes no longer describe the engine."""
    scenario(monkeypatch)

    from pathway_tpu.internals.monitoring import memory_stats

    sched = G.active_scheduler
    assert sched is not None
    stats = memory_stats(sched)
    joined = {
        label: v
        for label, v in stats.items()
        if v["estimated"] > 0 and v["measured"] > 0
    }
    assert joined, stats  # estimate and probe agree on operator labels
    predicted = sum(v["estimated"] for v in joined.values())
    measured = sum(v["measured"] for v in joined.values())
    assert 1 / 3 <= predicted / measured <= 3.0, stats


# ---------------------------------------------- registry + docs (sat 1)


def test_registry_is_single_source_of_truth():
    from pathway_tpu.analysis.diagnostics import CODE_INFO, CODES, render_code_table

    table = render_code_table()
    for code, (sev, desc) in CODE_INFO.items():
        assert CODES[code] == sev
        assert code in table and sev in table
        assert desc  # every code carries a human description
    for code in ("PW-X001", "PW-X002", "PW-X003", "PW-R001"):
        assert code in CODE_INFO

    import pathway_tpu.analysis.diagnostics as diag_mod

    for code in CODE_INFO:
        assert code in (diag_mod.__doc__ or ""), code


def test_readme_documents_every_code():
    readme = (REPO / "README.md").read_text()
    from pathway_tpu.analysis.diagnostics import CODE_INFO

    for code in CODE_INFO:
        assert f"`{code}`" in readme, f"{code} missing from README table"


# ----------------------------------------------- acceptance graphs


def test_wordcount_graph_zero_errors(tmp_path):
    t = _files_table(tmp_path)
    counts = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
    counts._capture_node()
    diags = analyze()
    assert not [d for d in diags if d.severity == SEV_ERROR], diags


def test_index_churn_graph_zero_errors():
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    class DocS(pw.Schema):
        doc_id: str = pw.column_definition(primary_key=True)
        vx: float
        vy: float

    class QueryS(pw.Schema):
        qid: str = pw.column_definition(primary_key=True)
        qx: float
        qy: float

    docs = pw.io.python.read(_Subject(), schema=DocS)
    docs = docs.select(
        doc_id=pw.this.doc_id,
        vec=pw.apply(lambda x, y: (float(x), float(y)), pw.this.vx, pw.this.vy),
    )
    queries = pw.io.python.read(_Subject(), schema=QueryS)
    queries = queries.select(
        qid=pw.this.qid,
        qvec=pw.apply(lambda x, y: (float(x), float(y)), pw.this.qx, pw.this.qy),
    )
    index = BruteForceKnnFactory(dimensions=2, reserved_space=8).build_data_index(
        docs.vec, docs
    )
    index.query_as_of_now(queries.qvec, number_of_matches=2)._capture_node()
    diags = analyze()
    assert not [d for d in diags if d.severity == SEV_ERROR], diags


def test_rag_serving_graph_zero_errors():
    from pathway_tpu.serving import RagServingApp, TenantPolicy

    app = RagServingApp(
        {"t": TenantPolicy("interactive", rate_per_s=10.0, burst=4, queue_cap=8)},
        embed_dim=8,
        delta_cap=8,
        auto_merge=False,
    )
    app.build()
    try:
        diags = analyze()
        assert not [d for d in diags if d.severity == SEV_ERROR], diags
        # satellite 2: serving nodes carry build-time stage annotations
        stages = {
            n.meta["serving"]["stage"]
            for n in G.engine_graph.nodes
            if "serving" in n.meta
        }
        assert {"ingest", "chunk", "index-upsert"} <= stages
    finally:
        app.close()


def test_strict_mode_surfaces_distribution_errors(tmp_path):
    t = _files_table(tmp_path)
    t.deduplicate(value=t.n, acceptor=lambda new, old: new > old)
    with pytest.raises(AnalysisError) as ei:
        pw.run(strict=True)
    assert any(d.code == "PW-X001" for d in ei.value.diagnostics)
    from pathway_tpu.analysis import count_by_severity

    counts = count_by_severity(ei.value.diagnostics)
    assert counts.get("error", 0) >= 1  # the /status + metrics payload


# ------------------------------------------- PW-J device safety (ISSUE 20)


def _dscan(src, filename="pathway_tpu/parallel/mod.py"):
    from pathway_tpu.analysis.device import scan_source

    return scan_source(src, filename)


_JIT_PRELUDE = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "\n"
    "_score = jax.jit(lambda q, c: q @ c.T)\n"
    "\n"
)


def test_j001_unpadded_param_into_jit():
    src = _JIT_PRELUDE + (
        "def search(queries, corpus):\n"
        "    return _score(jnp.asarray(queries), corpus)\n"
    )
    diags = _dscan(src)
    assert codes(diags) == ["PW-J001"]
    assert diags[0].severity == SEV_ERROR
    assert diags[0].details["pattern"] == "unpadded_param"


def test_j001_bucketed_padding_clean():
    src = _JIT_PRELUDE + (
        "def search(queries, corpus):\n"
        "    queries = pad_rows(queries, bucket_size(len(queries)))\n"
        "    return _score(jnp.asarray(queries), corpus)\n"
    )
    assert _dscan(src) == []


def test_j001_ceil_div_multiple_padding():
    # multiple-of-block padding still compiles one program per distinct
    # block count — the recompile storm the IVF fix removed
    src = _JIT_PRELUDE + (
        "def search(queries, corpus):\n"
        "    n = queries.shape[0]\n"
        "    pad = ((n + 8 - 1) // 8) * 8\n"
        "    queries = pad_rows(queries, pad)\n"
        "    return _score(jnp.asarray(queries), corpus)\n"
    )
    diags = _dscan(src)
    assert codes(diags) == ["PW-J001"]
    assert diags[0].details["pattern"] == "ceil_div_multiple"


def test_j001_ceil_div_over_bucketed_blocks_clean():
    # the fixed IVF shape: block COUNT rounded to a power of two
    src = _JIT_PRELUDE + (
        "def search(queries, corpus, qb):\n"
        "    n = queries.shape[0]\n"
        "    pad = qb * bucket_size(-(-n // qb), min_bucket=1)\n"
        "    queries = pad_rows(queries, pad)\n"
        "    return _score(jnp.asarray(queries), corpus)\n"
    )
    assert _dscan(src) == []


def test_j001_cold_path_clean():
    # train/init/restore paths compile once by design
    src = _JIT_PRELUDE + (
        "def train_step(batch, corpus):\n"
        "    return _score(jnp.asarray(batch), corpus)\n"
    )
    assert _dscan(src) == []


def test_j001_waiver_comment_suppresses():
    src = _JIT_PRELUDE + (
        "def search(queries, corpus):\n"
        "    return _score(jnp.asarray(queries), corpus)"
        "  # pw-j001: fixed upstream batch size\n"
    )
    assert _dscan(src) == []


def test_j002_transfer_in_hot_loop():
    src = (
        "import jax\n"
        "def serve(batches):\n"
        "    out = []\n"
        "    for b in batches:\n"
        "        out.append(jax.device_put(b))\n"
        "    return out\n"
    )
    diags = _dscan(src)
    assert codes(diags) == ["PW-J002"]
    assert diags[0].severity == SEV_WARNING


def test_j002_pipelined_readback_clean():
    # copy_to_host_async is the cure, not the disease
    src = (
        "import jax\n"
        "def serve(outs):\n"
        "    for o in outs:\n"
        "        o.copy_to_host_async()\n"
        "    return jax.device_get(outs)\n"
    )
    assert _dscan(src) == []


def test_j002_comprehension_not_a_loop():
    # a device_put list comprehension is one batched staging step, not a
    # per-iteration stall (executor._dispatch idiom)
    src = (
        "import jax\n"
        "def dispatch(args, shardings):\n"
        "    return [jax.device_put(a, s) for a, s in zip(args, shardings)]\n"
    )
    assert _dscan(src) == []


def test_j003_inplace_without_donation():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def scatter(buf, idx, vals):\n"
        "    return buf.at[idx].set(vals)\n"
    )
    diags = _dscan(src)
    assert codes(diags) == ["PW-J003"]
    assert diags[0].severity == SEV_WARNING


def test_j003_donated_scatter_clean():
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, donate_argnums=(0,))\n"
        "def scatter(buf, idx, vals):\n"
        "    return buf.at[idx].set(vals)\n"
    )
    assert _dscan(src) == []


def test_j003_safe_twin_of_donated_scatter_clean():
    # sharded_knn's deliberate non-donating *_safe twin for
    # concurrent-dispatch windows
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, donate_argnums=(0,))\n"
        "def scatter(buf, idx, vals):\n"
        "    return buf.at[idx].set(vals)\n"
        "@jax.jit\n"
        "def scatter_safe(buf, idx, vals):\n"
        "    return buf.at[idx].set(vals)\n"
    )
    assert _dscan(src) == []


def test_j004_collective_under_rank_branch():
    src = (
        "import jax\n"
        "def exchange(x, rank):\n"
        "    if rank == 0:\n"
        "        return jax.lax.psum(x, 'i')\n"
        "    return x\n"
    )
    diags = _dscan(src)
    assert codes(diags) == ["PW-J004"]
    assert diags[0].severity == SEV_ERROR


def test_j004_fires_even_on_cold_paths():
    # a deadlock at init hangs the mesh too — coldness is no excuse
    src = (
        "import jax\n"
        "def init_mesh(x, rank):\n"
        "    if rank == 0:\n"
        "        return jax.lax.psum(x, 'i')\n"
        "    return x\n"
    )
    assert codes(_dscan(src)) == ["PW-J004"]


def test_j004_static_config_branch_clean():
    # every process computes the same truth value — not divergent
    src = (
        "import jax\n"
        "class Index:\n"
        "    def exchange(self, x):\n"
        "        if self.mesh is not None:\n"
        "            return jax.lax.psum(x, 'i')\n"
        "        return x\n"
    )
    assert _dscan(src) == []


def test_j005_blocking_sync_under_lock():
    src = (
        "import jax\n"
        "class Index:\n"
        "    def swap(self, new):\n"
        "        with self._lock:\n"
        "            self._buf = new\n"
        "            self._buf.block_until_ready()\n"
    )
    diags = _dscan(src)
    assert codes(diags) == ["PW-J005"]
    assert diags[0].severity == SEV_WARNING


def test_j005_sync_outside_lock_clean():
    src = (
        "import jax\n"
        "class Index:\n"
        "    def swap(self, new):\n"
        "        new.block_until_ready()\n"
        "        with self._lock:\n"
        "            self._buf = new\n"
    )
    assert _dscan(src) == []


def test_j005_serving_lane_readback():
    src = (
        "import jax\n"
        "def answer_lane(out):\n"
        "    return out.item()\n"
    )
    diags = _dscan(src, filename="pathway_tpu/serving/lanes.py")
    assert codes(diags) == ["PW-J005"]
    # same function outside the serving tree: nothing to serialize
    assert _dscan(src, filename="pathway_tpu/parallel/lanes.py") == []


def test_jitted_body_is_exempt_from_hot_checks():
    # inside a traced body coercions are free: they fold into the program
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def kernel(xs):\n"
        "    acc = jnp.asarray(0.0)\n"
        "    for x in xs:\n"
        "        acc = acc + jnp.asarray(x)\n"
        "    return acc\n"
    )
    assert _dscan(src) == []


def test_device_surface_scans_clean():
    """Acceptance: the committed device modules carry zero PW-J errors
    and zero predicted recompile sites — the static half of the
    zero-recompile invariant ``tests/test_device_runtime.py`` holds live."""
    from pathway_tpu.analysis.device import device_module_files, scan_paths

    report = scan_paths(device_module_files())
    assert len(report.files) >= 10
    assert report.errors == 0, report.diagnostics
    assert report.predicted_recompile_sites == 0


def test_device_profile_shape():
    from pathway_tpu.analysis.device import device_profile

    prof = device_profile(refresh=True)
    assert set(prof) >= {
        "files_scanned",
        "findings",
        "errors",
        "by_code",
        "predicted_recompile_sites",
    }
    assert prof["errors"] == 0


def test_j_codes_registered():
    from pathway_tpu.analysis.diagnostics import CODE_INFO, SEV_ERROR, SEV_WARNING

    assert CODE_INFO["PW-J001"][0] == SEV_ERROR
    assert CODE_INFO["PW-J002"][0] == SEV_WARNING
    assert CODE_INFO["PW-J003"][0] == SEV_WARNING
    assert CODE_INFO["PW-J004"][0] == SEV_ERROR
    assert CODE_INFO["PW-J005"][0] == SEV_WARNING


def test_device_pass_runs_in_analyze_for_serving_graphs():
    """check_device is wired into ALL_PASSES: a graph whose node carries
    a serving stage annotation sweeps the whole device surface."""
    from pathway_tpu.analysis.passes import ALL_PASSES
    from pathway_tpu.analysis.device import check_device

    assert check_device in ALL_PASSES
    t = _static_table()
    t.select(w=pw.this.word)._capture_node()
    for n in G.engine_graph.nodes:
        n.meta["serving"] = {"stage": "ingest"}
        break
    diags = analyze()
    assert not [d for d in diags if d.code.startswith("PW-J")], diags


def _indexed_docs_graph():
    """Python-fed docs feeding a KNN index (the device-resident state
    the per-chip budget prices)."""
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    class DocS(pw.Schema):
        doc_id: str = pw.column_definition(primary_key=True)
        vx: float
        vy: float

    docs = pw.io.python.read(_Subject(), schema=DocS)
    docs = docs.select(
        doc_id=pw.this.doc_id,
        vec=pw.apply(lambda x, y: (float(x), float(y)), pw.this.vx, pw.this.vy),
    )
    index = BruteForceKnnFactory(
        dimensions=2, reserved_space=4096
    ).build_data_index(docs.vec, docs)
    index.query_as_of_now(docs.vec, number_of_matches=2)


def test_device_budget_per_chip(monkeypatch):
    """PATHWAY_DEVICE_BUDGET_BYTES: the device-resident share of the
    estimate must fit per chip; PW-M002 carries the device scope."""
    monkeypatch.setenv("PATHWAY_DEVICE_BUDGET_BYTES", "1")
    monkeypatch.setenv("PATHWAY_DEVICE_CHIPS", "2")
    _indexed_docs_graph()
    diags = analyze()
    dev = [
        d
        for d in diags
        if d.code == "PW-M002"
        and d.details.get("scope") == "device-per-chip"
    ]
    assert dev, codes(diags)
    det = dev[0].details
    assert det["chips"] == 2
    assert det["estimated_bytes"] > det["budget_bytes"]
    assert det["breakdown"]


def test_device_budget_ample_clean(monkeypatch):
    monkeypatch.setenv("PATHWAY_DEVICE_BUDGET_BYTES", "1TiB")
    _indexed_docs_graph()
    assert not [
        d
        for d in analyze()
        if d.code == "PW-M002"
        and d.details.get("scope") == "device-per-chip"
    ]
