"""iterate, graphs, ml, sql, yaml, universes, utils, monitoring."""

import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from tests.utils import T, run_to_rows


# ---------------------------------------------------------------------------
# iterate


def test_iterate_fixed_point():
    t = T(
        """
    x
    5
    16
    """
    )

    def body(x):
        return x.select(
            x=pw.apply(
                lambda v: 1 if v == 1 else (v // 2 if v % 2 == 0 else 3 * v + 1),
                pw.this.x,
            )
        )

    res = pw.iterate(body, x=t)
    assert run_to_rows(res) == [(1,), (1,)]


def test_iterate_with_limit():
    t = T(
        """
    x
    0
    """
    )

    def body(x):
        return x.select(x=pw.this.x + 1)  # never converges

    res = pw.iterate(body, iteration_limit=5, x=t)
    assert run_to_rows(res) == [(5,)]


# ---------------------------------------------------------------------------
# graphs


def _edges():
    # a -1- b -1- c;  a -5- c
    v = T(
        """
    name | dist0
    a    | 0
    b    | __none__
    c    | __none__
    """
    ).select(
        name=pw.this.name,
        dist=pw.apply(lambda d: 0.0 if str(d) == "0" else None, pw.this.dist0),
    )
    vertices = v.with_id_from(pw.this.name)
    e = T(
        """
    u | v | dist
    a | b | 1
    b | c | 1
    a | c | 5
    """
    )
    edges = e.select(
        u=vertices.pointer_from(e.u),
        v=vertices.pointer_from(e.v),
        dist=pw.this.dist,
    )
    return vertices, edges


def test_bellman_ford():
    from pathway_tpu.stdlib.graphs import bellman_ford

    vertices, edges = _edges()
    res = bellman_ford(vertices, edges)
    dists = sorted(r[0] for r in run_to_rows(res))
    assert dists == [0.0, 1.0, 2.0]


def test_pagerank():
    from pathway_tpu.stdlib.graphs import pagerank

    e = T(
        """
    un | vn
    a  | b
    b  | c
    c  | a
    """
    )
    edges = e.select(u=pw.this.un, v=pw.this.vn)
    ranks = run_to_rows(pagerank(edges, steps=10))
    vals = [r[1] for r in ranks]
    assert len(vals) == 3
    assert all(abs(v - 1.0) < 0.1 for v in vals)  # symmetric cycle -> equal


def test_louvain_two_cliques():
    from pathway_tpu.stdlib.graphs import WeightedGraph, louvain_level

    e = T(
        """
    u | v | weight
    a | b | 1
    b | c | 1
    a | c | 1
    x | y | 1
    y | z | 1
    x | z | 1
    a | x | 0.1
    """
    )
    comms = run_to_rows(louvain_level(WeightedGraph(e)))
    by_node = {r[0]: r[1] for r in comms}
    assert by_node["a"] == by_node["b"] == by_node["c"]
    assert by_node["x"] == by_node["y"] == by_node["z"]
    assert by_node["a"] != by_node["x"]


# ---------------------------------------------------------------------------
# ml


def test_knn_index_legacy():
    from pathway_tpu.stdlib.ml import KNNIndex

    data = T(
        """
    label | x  | y
    l1    | 1  | 0
    l2    | 0  | 1
    """
    ).select(
        label=pw.this.label,
        vec=pw.apply(lambda a, b: (float(a), float(b)), pw.this.x, pw.this.y),
    )
    index = KNNIndex(data.vec, data, n_dimensions=2)
    queries = T(
        """
    qx | qy
    1  | 0
    """
    ).select(vec=pw.apply(lambda a, b: (float(a), float(b)), pw.this.qx, pw.this.qy))
    res = index.get_nearest_items(queries.vec, k=1)
    rows = run_to_rows(res)
    labels = [r for r in rows[0] if isinstance(r, tuple)][0]
    assert labels == ("l1",)


def test_knn_classifier():
    from pathway_tpu.stdlib.ml.classifiers import knn_lsh_classify, knn_lsh_train

    data = T(
        """
    label | x | y
    A     | 1 | 0
    A     | 1 | 1
    B     | 0 | 1
    """
    ).select(
        label=pw.this.label,
        data=pw.apply(lambda a, b: (float(a), float(b)), pw.this.x, pw.this.y),
    )
    index = knn_lsh_train(data, d=2)
    queries = T(
        """
    x | y
    1 | 0
    """
    ).select(data=pw.apply(lambda a, b: (float(a), float(b)), pw.this.x, pw.this.y))
    res = knn_lsh_classify(index, queries.data, k=3)
    assert run_to_rows(res) == [("A",)]


def test_hmm_reducer():
    from pathway_tpu.stdlib.ml.hmm import create_hmm_reducer

    hmm = create_hmm_reducer(
        graph={"sunny": {"sunny": 0.9, "rainy": 0.1}, "rainy": {"rainy": 0.9, "sunny": 0.1}},
    )
    t = T(
        """
    k | t | obs
    a | 1 | sunny
    a | 2 | sunny
    a | 3 | rainy
    a | 4 | rainy
    """
    )
    res = t.groupby(t.k).reduce(state=hmm(pw.make_tuple(t.t, t.obs)))
    assert run_to_rows(res) == [("rainy",)]


def test_fuzzy_match():
    from pathway_tpu.stdlib.ml.smart_table_ops import fuzzy_match_tables

    left = T(
        """
    ln | name
    1  | john smith
    2  | acme corp ltd
    """
    )
    right = T(
        """
    rn | title
    a  | smith john
    b  | acme corporation
    """
    )
    res = fuzzy_match_tables(left, right, left_column=left.name, right_column=right.title)
    rows = run_to_rows(res)
    assert len(rows) == 2
    weights = sorted(r[2] for r in rows)
    assert weights[0] > 0.2


# ---------------------------------------------------------------------------
# sql


def test_sql_select_where():
    t = T(
        """
    a | b
    1 | 10
    2 | 20
    3 | 30
    """
    )
    res = pw.sql("SELECT a, b FROM tab WHERE b > 15", tab=t)
    assert sorted(run_to_rows(res)) == [(2, 20), (3, 30)]


def test_sql_group_by():
    t = T(
        """
    owner | pets
    alice | 1
    bob   | 2
    alice | 3
    """
    )
    res = pw.sql(
        "SELECT owner, SUM(pets) AS total, COUNT(*) AS n FROM t GROUP BY owner",
        t=t,
    )
    assert sorted(run_to_rows(res)) == [("alice", 4, 2), ("bob", 2, 1)]


def test_sql_having_restated_aggregate():
    t = T(
        """
    owner | pets
    alice | 1
    bob   | 2
    alice | 3
    """
    )
    res = pw.sql(
        "SELECT owner, SUM(pets) AS total FROM t GROUP BY owner HAVING SUM(pets) > 2",
        t=t,
    )
    assert run_to_rows(res) == [("alice", 4)]


def test_sql_distinct_union_subquery():
    t = T(
        """
    a | b
    1 | x
    1 | x
    2 | y
    """
    )
    res = pw.sql("SELECT DISTINCT a, b FROM t", t=t)
    assert sorted(run_to_rows(res)) == [(1, "x"), (2, "y")]

    u = pw.sql(
        "SELECT a FROM t WHERE b = 'x' UNION SELECT a FROM t WHERE a = 2",
        t=t,
    )
    assert sorted(run_to_rows(u)) == [(1,), (2,)]

    ua = pw.sql(
        "SELECT a FROM t WHERE a = 2 UNION ALL SELECT a FROM t WHERE a = 2",
        t=t,
    )
    assert sorted(run_to_rows(ua)) == [(2,), (2,)]

    sub = pw.sql(
        "SELECT big.a AS a FROM (SELECT a FROM t WHERE a > 1) AS big",
        t=t,
    )
    assert sorted(run_to_rows(sub)) == [(2,)]


def test_sql_cte_case_in_between_like_null():
    t = T(
        """
    name  | score
    ann   | 10
    bob   | 25
    carol | 40
    """
    )
    res = pw.sql(
        """
        WITH ranked AS (
            SELECT name,
                   CASE WHEN score >= 30 THEN 'high'
                        WHEN score BETWEEN 15 AND 30 THEN 'mid'
                        ELSE 'low' END AS tier
            FROM t
        )
        SELECT name, tier FROM ranked WHERE tier IN ('high', 'mid')
        """,
        t=t,
    )
    assert sorted(run_to_rows(res)) == [("bob", "mid"), ("carol", "high")]

    like = pw.sql("SELECT name FROM t WHERE name LIKE 'c%l'", t=t)
    assert run_to_rows(like) == [("carol",)]

    notlike = pw.sql("SELECT name FROM t WHERE name NOT LIKE '%o%'", t=t)
    assert run_to_rows(notlike) == [("ann",)]

    # IS NULL over an optional column
    t2 = T(
        """
    v | w
    1 |
    2 | x
    """
    )
    isnull = pw.sql("SELECT v FROM t2 WHERE w IS NULL", t2=t2)
    assert run_to_rows(isnull) == [(1,)]
    notnull = pw.sql("SELECT v FROM t2 WHERE w IS NOT NULL", t2=t2)
    assert run_to_rows(notnull) == [(2,)]
    # three-valued logic: NULL NOT LIKE / NOT IN excludes the NULL row
    nl = pw.sql("SELECT v FROM t2 WHERE w NOT LIKE 'z%'", t2=t2)
    assert run_to_rows(nl) == [(2,)]
    ni = pw.sql("SELECT v FROM t2 WHERE w NOT IN ('zzz')", t2=t2)
    assert run_to_rows(ni) == [(2,)]


def test_yaml_forward_reference():
    cfg = pw.load_yaml(
        """
pipeline:
  size: $dim
dim: 7
"""
    )
    assert cfg["pipeline"]["size"] == 7


def test_groupby_majority():
    from pathway_tpu.stdlib.utils.col import groupby_reduce_majority

    t = T(
        """
    g | v
    a | x
    a | x
    a | y
    b | z
    """
    )
    res = run_to_rows(groupby_reduce_majority(t.g, t.v))
    assert sorted(res) == [("a", "x"), ("b", "z")]


def test_sql_join():
    a = T(
        """
    k | va
    1 | x
    2 | y
    """
    )
    b = T(
        """
    k2 | vb
    1  | p
    2  | q
    """
    )
    res = pw.sql("SELECT va, vb FROM a JOIN b ON a.k = b.k2", a=a, b=b)
    assert sorted(run_to_rows(res)) == [("x", "p"), ("y", "q")]


# ---------------------------------------------------------------------------
# yaml loader


def test_load_yaml_vars_and_tags():
    cfg = pw.load_yaml(
        """
dim: 4
splitter: !pw.xpacks.llm.splitters.TokenCountSplitter
  min_tokens: 2
  max_tokens: $dim
"""
    )
    from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter

    assert cfg["dim"] == 4
    assert isinstance(cfg["splitter"], TokenCountSplitter)
    assert cfg["splitter"].max_tokens == 4


# ---------------------------------------------------------------------------
# universes


def test_universe_promises():
    import pathway_tpu.universes as U

    t1 = T(
        """
    a
    1
    2
    """
    )
    t2 = t1.filter(pw.this.a > 1)
    t3 = U.promise_is_subset_of(t2, t1)
    # cross-table select now allowed
    combined = t1.select(a=pw.this.a, b=t3.a)
    rows = run_to_rows(combined)
    assert (2, 2) in rows


# ---------------------------------------------------------------------------
# AsyncTransformer


def test_async_transformer():
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.parse_graph import G

    class OutSchema(pw.Schema):
        ret: int

    class Doubler(pw.AsyncTransformer):
        output_schema = OutSchema

        async def invoke(self, value: int) -> dict:
            if value == 13:
                raise ValueError("unlucky")
            return {"ret": value * 2}

    class InSubject(pw.io.python.ConnectorSubject):
        def run(self):
            for v in (1, 13, 4):
                self.next(value=v)
                self.commit()
                time.sleep(0.05)

    class InSchema(pw.Schema):
        value: int

    inputs = pw.io.python.read(InSubject(), schema=InSchema)
    transformer = Doubler(inputs)
    got: list = []
    pw.io.subscribe(
        transformer.successful,
        on_change=lambda key, row, time, is_addition: got.append(row["ret"])
        if is_addition
        else None,
    )
    failed: list = []
    pw.io.subscribe(
        transformer.failed,
        on_change=lambda key, row, time, is_addition: failed.append(1),
    )
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    th = threading.Thread(target=sched.run)
    th.start()
    th.join(timeout=15)
    alive = th.is_alive()
    sched.stop()
    assert not alive
    assert sorted(got) == [2, 8]
    assert len(failed) == 1


# ---------------------------------------------------------------------------
# utils.col


def test_unpack_col():
    from pathway_tpu.stdlib.utils import unpack_col

    t = T(
        """
    n
    1
    """
    ).select(packed=pw.apply(lambda n: (n, n * 10), pw.this.n))
    res = unpack_col(t.packed, "a", "b")
    assert run_to_rows(res) == [(1, 10)]


def test_pandas_transformer():
    from pathway_tpu.stdlib.utils import pandas_transformer

    class Out(pw.Schema):
        s: int

    @pandas_transformer(output_schema=Out)
    def double_sum(df):
        import pandas as pd

        return pd.DataFrame({"s": [int(df["x"].sum()) * 2]})

    t = T(
        """
    x
    1
    2
    """
    )
    assert run_to_rows(double_sum(t)) == [(6,)]


# ---------------------------------------------------------------------------
# monitoring HTTP server


def test_monitoring_http_server():
    import json
    import socket
    import urllib.request

    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.internals.parse_graph import G

    t = T(
        """
    a
    1
    """
    )
    t.select(b=pw.this.a)
    sched = Scheduler(G.engine_graph)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    start_http_server(sched, port=port)
    time.sleep(0.3)
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/status", timeout=5) as r:
        status = json.loads(r.read())
    assert status["operators"] >= 2
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
        metrics = r.read().decode()
    assert "pathway_tpu_operator_count" in metrics
    sched._monitoring_server.shutdown()


def test_operator_probes_and_connector_counters():
    """Per-operator latency/row probes + per-connector counters feed
    ProberStats and the /metrics endpoint (reference attach_prober
    graph.rs:988-995, connectors/monitoring.rs)."""
    import json
    import urllib.request

    import pathway_tpu as pw
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.monitoring import collect_stats
    from pathway_tpu.internals.monitoring_server import _metrics_text
    from pathway_tpu.internals.parse_graph import G

    class Src(pw.io.python.ConnectorSubject):
        def run(self):
            for i in range(10):
                self.next(a=i)
            self.commit()

    class S(pw.Schema):
        a: int

    t = pw.io.python.read(Src(), schema=S)
    c = t.groupby(t.a).reduce(t.a, n=pw.reducers.count())
    cap = c._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=10)
    G.active_scheduler = sched
    sched.run()

    stats = collect_stats(sched)
    assert stats.input_rows == 10
    (cstats,) = stats.connectors.values()
    assert cstats["rows"] == 10 and cstats["commits"] >= 1 and cstats["closed"]
    probes = stats.operator_probes
    gb = next(p for p in probes.values() if p["name"].startswith("groupby"))
    assert gb["rows_in"] == 10 and gb["total_ms"] >= 0.0 and gb["epochs"] >= 1

    text = _metrics_text(sched)
    assert "pathway_tpu_connector_rows_total" in text
    assert 'pathway_tpu_operator_latency_ms_total{operator="groupby' in text


def test_viz_live_plot_svg():
    t = T(
        """
    x | y  | z
    1 | 10 | a
    2 | 40 | b
    3 | 25 | c
    """
    )
    view = pw.viz.plot(t, sorting_col="x")
    pw.run(monitoring_level=pw.internals.run.MonitoringLevel.NONE)
    svg = view.to_svg()
    assert svg.startswith("<svg") and "polyline" in svg
    assert ">y<" in svg  # numeric series labelled
    html = view._repr_html_()
    assert html == svg


def test_debug_parquet_roundtrip(tmp_path):
    import pandas as pd

    src = tmp_path / "t.parquet"
    pd.DataFrame({"a": [1, 2], "b": ["x", "y"]}).to_parquet(src)
    t = pw.debug.table_from_parquet(str(src))
    out = tmp_path / "o.parquet"
    pw.debug.table_to_parquet(t.select(t.a, t.b), str(out))
    back = pd.read_parquet(out)
    assert back.to_dict("records") == [
        {"a": 1, "b": "x"},
        {"a": 2, "b": "y"},
    ]


def test_sql_intersect_except():
    """INTERSECT/EXCEPT vs Table-op ground truth."""
    a = T(
        """
    x | y
    1 | p
    2 | q
    2 | q
    3 | r
    """
    )
    b = T(
        """
    x | y
    2 | q
    3 | r
    4 | s
    """
    )
    inter = pw.sql("SELECT x, y FROM a INTERSECT SELECT x, y FROM b", a=a, b=b)
    assert sorted(run_to_rows(inter)) == [(2, "q"), (3, "r")]

    exc = pw.sql("SELECT x, y FROM a EXCEPT SELECT x, y FROM b", a=a, b=b)
    assert sorted(run_to_rows(exc)) == [(1, "p")]

    # EXCEPT dedups its result (set semantics): the duplicate (2,q) row
    # vanishes entirely, (1,p) appears once
    exc2 = pw.sql(
        "SELECT x, y FROM a EXCEPT SELECT x, y FROM b WHERE x = 3", a=a, b=b
    )
    assert sorted(run_to_rows(exc2)) == [(1, "p"), (2, "q")]

    # INTERSECT binds tighter than UNION (SQL precedence):
    # a UNION (b INTERSECT b-where-x=4) == a-distinct + (4,s)
    mix = pw.sql(
        "SELECT x FROM a UNION SELECT x FROM b INTERSECT "
        "SELECT x FROM b WHERE x = 4",
        a=a,
        b=b,
    )
    assert sorted(run_to_rows(mix)) == [(1,), (2,), (3,), (4,)]


def test_sql_in_subquery():
    orders = T(
        """
    cust | amount
    ann  | 10
    bob  | 25
    carol| 40
    dave | 5
    """
    )
    vips = T(
        """
    name
    bob
    carol
    """
    )
    semi = pw.sql(
        "SELECT cust, amount FROM o WHERE cust IN (SELECT name FROM v)",
        o=orders,
        v=vips,
    )
    assert sorted(run_to_rows(semi)) == [("bob", 25), ("carol", 40)]

    anti = pw.sql(
        "SELECT cust, amount FROM o WHERE cust NOT IN (SELECT name FROM v)",
        o=orders,
        v=vips,
    )
    assert sorted(run_to_rows(anti)) == [("ann", 10), ("dave", 5)]

    # combined with an ordinary conjunct
    both = pw.sql(
        "SELECT cust FROM o WHERE amount > 7 AND cust IN (SELECT name FROM v)",
        o=orders,
        v=vips,
    )
    assert sorted(run_to_rows(both)) == [("bob",), ("carol",)]

    # subquery with its own WHERE
    sub_where = pw.sql(
        "SELECT cust FROM o WHERE cust IN "
        "(SELECT name FROM v WHERE name = 'bob')",
        o=orders,
        v=vips,
    )
    assert run_to_rows(sub_where) == [("bob",)]

    # ground truth via table ops: semi-join equivalence
    vd = vips.groupby(vips.name).reduce(vips.name)
    gt = orders.join(vd, orders.cust == vd.name).select(
        pw.left.cust, pw.left.amount
    )
    assert sorted(run_to_rows(semi)) == sorted(run_to_rows(gt))


def test_load_yaml_private_keys_and_escape():
    """Reference app-template key conventions: a leading $ marks a
    private variable (referenced as $name, dropped from the result);
    $$name escapes to the literal key $name, which a $$name value
    reference resolves to."""
    cfg = pw.load_yaml(
        """
$hidden: 41
visible: $hidden
$$literal: 7
also: $$literal
"""
    )
    assert cfg == {"visible": 41, "$literal": 7, "also": 7}
    # private/public collision raises instead of silently shadowing
    import pytest as _pytest

    with _pytest.raises(KeyError, match="same variable name"):
        pw.load_yaml("$x: 1\nx: 2")
    # non-string keys pass through untouched
    assert pw.load_yaml("1: a\nb: 2") == {1: "a", "b": 2}
