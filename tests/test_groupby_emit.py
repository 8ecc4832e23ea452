"""``GroupByNode``'s emission: a dirty group's change is decided from its two
rows (``engine/stream.same_row``) and never differs from what ``consolidate``
over the naive retract-and-insert list gives; where the two rows cannot tell,
that pair alone goes through ``consolidate``; and telling costs the batch,
not the group's size.
"""

from __future__ import annotations

import dataclasses
import os
import random
from collections import Counter

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import graph as eg
from pathway_tpu.engine import reducers as red
from pathway_tpu.engine import stream
from pathway_tpu.engine.stream import Update, consolidate, hashable_row, same_row
from pathway_tpu.internals import api, device_counters, tracing
from pathway_tpu.internals.keys import Pointer

NAN = float("nan")
OTHER_NAN = float("inf") - float("inf")
DOC = {"path": "/a.txt", "owner": "a"}

#: what a cell of a drawn stream may hold, by name
CELLS = {
    "scalar": lambda rng: rng.randrange(4),
    "dict": lambda rng: {"path": f"/{rng.randrange(3)}.txt", "n": rng.randrange(2)},
    "tuple_of_dict": lambda rng: tuple(
        {"k": rng.randrange(2)} for _ in range(rng.randrange(3))
    ),
    "none": lambda rng: rng.choice([None, 0, "x"]),
    "nan": lambda rng: rng.choice([NAN, OTHER_NAN, float("nan"), 1.5]),
    "one": lambda rng: rng.choice(
        [1, 1.0, True, {"v": 1}, {"v": 1.0}, {"v": True}, {1: "x"}, {"1": "x"}]
    ),
    "error": lambda rng: rng.choice([api.ERROR, 1, 2, {"v": 1}]),
    "ndarray": lambda rng: np.array(
        [rng.randrange(2), rng.randrange(2)], dtype=rng.choice(["int64", "float64"])
    ),
}

#: cells that max() can order, for the reducers the native partials take
NATIVE_CELLS = {
    "scalar": CELLS["scalar"],
    "none": lambda rng: rng.choice([None, 0, 3]),
    "nan": CELLS["nan"],
    "error": lambda rng: rng.choice([api.ERROR, 1, 2]),
    "one": lambda rng: rng.choice([1, 1.0, True, 2]),
}


def _canon(batch) -> Counter:
    return Counter((u.key, hashable_row(u.values), u.diff) for u in batch)


def _node(reducers, *, one_group, include_group_values=True, fast_spec=None):
    g = eg.EngineGraph()
    inp = eg.InputNode(g, 2)
    group_fn = (lambda k, v: ()) if one_group else (lambda k, v: (v[0],))
    args = [
        (r, (lambda k, v: ()) if r.n_args == 0 else (lambda k, v: (v[1],)))
        for r in reducers
    ]
    return eg.GroupByNode(
        g, inp, group_fn, args, include_group_values=include_group_values,
        fast_spec=fast_spec,
    )


def _epochs(rng, draw, n_epochs, one_group):
    """Batches of a consistent stream: insertions, retractions of live rows
    and in-place modifications (a retraction and an insertion under one key,
    possibly into another group)."""
    live: dict[int, tuple] = {}
    next_key = 0
    for _ in range(n_epochs):
        batch = []
        for _ in range(rng.randrange(1, 7)):
            op = rng.random()
            if op < 0.5 or not live:
                values = (0 if one_group else rng.randrange(4), draw(rng))
                live[next_key] = values
                batch.append(Update(Pointer(next_key), values, 1))
                next_key += 1
                continue
            key = rng.choice(sorted(live))
            batch.append(Update(Pointer(key), live.pop(key), -1))
            if op < 0.8:
                values = (0 if one_group else rng.randrange(4), draw(rng))
                live[key] = values
                batch.append(Update(Pointer(key), values, 1))
        yield batch


def _process_against_naive(node, ctx, time, batch):
    """One epoch through the node, and beside it ``consolidate`` over the
    retraction of every group's row as it stood and the insertion of its row
    as it stands (a group the epoch did not touch keeps its row object)."""
    st = ctx.state(node)
    before = {gh: (g, g["last_out"]) for gh, g in st["groups"].items()}
    out = node.process(ctx, time, [list(batch)])
    naive = []
    for gh in {**before, **st["groups"]}:
        g, old = before.get(gh, (None, None))
        g = st["groups"].get(gh, g)
        new = g["last_out"] if gh in st["groups"] else None
        if old is new:
            continue
        if old is not None:
            naive.append(Update(g["okey"], old, -1))
        if new is not None:
            naive.append(Update(g["okey"], new, 1))
    return out, consolidate(naive)


@pytest.mark.parametrize("include_group_values", [True, False])
@pytest.mark.parametrize("one_group", [True, False], ids=["one_group", "many_groups"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_python_loop_emits_what_consolidate_over_the_naive_list_gives(
    cell, one_group, include_group_values
):
    for seed in range(6):
        rng = random.Random(f"{cell}-{one_group}-{seed}")
        node = _node(
            [red.CountReducer(), red.TupleReducer(), red.AnyReducer(),
             red.UniqueReducer(), red.LatestReducer()],
            one_group=one_group, include_group_values=include_group_values,
        )
        ctx = eg.RunContext()
        emitted = 0
        for t, batch in enumerate(_epochs(rng, CELLS[cell], 30, one_group)):
            out, expected = _process_against_naive(node, ctx, 2 * t, batch)
            assert _canon(out) == _canon(expected), (seed, t)
            emitted += len(out)
        assert emitted


@pytest.mark.parametrize("include_group_values", [True, False])
@pytest.mark.parametrize("one_group", [True, False], ids=["one_group", "many_groups"])
@pytest.mark.parametrize("cell", sorted(NATIVE_CELLS))
def test_the_native_partials_emit_what_consolidate_over_the_naive_list_gives(
    cell, one_group, include_group_values
):
    reducers = [red.CountReducer(), red.MaxReducer(), red.UniqueReducer()]
    if cell == "scalar":
        reducers.append(red.SumReducer())
    spec = tuple((r.native_code, () if r.n_args == 0 else (1,)) for r in reducers)
    for seed in range(6):
        rng = random.Random(f"native-{cell}-{one_group}-{seed}")
        node = _node(
            reducers, one_group=one_group, include_group_values=include_group_values,
            fast_spec=(() if one_group else (0,), spec),
        )
        ctx = eg.RunContext()
        for t, batch in enumerate(_epochs(rng, NATIVE_CELLS[cell], 30, one_group)):
            out, expected = _process_against_naive(node, ctx, 2 * t, batch)
            assert _canon(out) == _canon(expected), (seed, t)


@pytest.mark.parametrize("two_pairs", [False, True], ids=["one_pair", "two_pairs"])
def test_groups_under_one_caller_given_key_still_cancel_across_groups(two_pairs):
    """Rows without their group's values under an ``output_key_fn`` that maps
    every group to one key: group 0 loses the row that group 1 gains (and,
    with two pairs, gains the one that group 1 loses), and only
    ``consolidate`` over the whole list sees that nothing changed."""
    g = eg.EngineGraph()
    node = eg.GroupByNode(
        g, eg.InputNode(g, 2), lambda k, v: (v[0],),
        [(red.TupleReducer(), lambda k, v: (v[1],))],
        output_key_fn=lambda gvals: Pointer(7), include_group_values=False,
    )
    ctx = eg.RunContext()
    first = [Update(Pointer(1), (0, DOC), 1)]
    moved = [Update(Pointer(1), (0, DOC), -1), Update(Pointer(1), (1, DOC), 1)]
    if two_pairs:
        first.append(Update(Pointer(2), (1, {"x": 1}), 1))
        moved += [Update(Pointer(2), (1, {"x": 1}), -1), Update(Pointer(2), (0, {"x": 1}), 1)]
    assert [u.diff for u in node.process(ctx, 0, [first])] == [1] * len(first)
    assert node.process(ctx, 2, [moved]) == []


NDARRAY = np.array([1, 2])
PAIRS = [
    # (old cell, new cell, decided without consolidate)
    (1, 1.0, True), (1, True, True), (0, False, True), (1, 2, True),
    ("a", "a", True), (None, None, True), (None, 0, True),
    (NAN, NAN, True), (NAN, OTHER_NAN, True), ((NAN,), (NAN,), True),
    ((NAN, DOC), (NAN, DOC), True), ((NAN, DOC), (OTHER_NAN, DOC), True),
    (api.ERROR, api.ERROR, True), (api.ERROR, None, True),
    ({"a": 1}, {"a": 1}, True), ({"a": 1}, {"a": 1.0}, True),
    ({"a": 1}, {"a": True}, True), ({1: "x"}, {"1": "x"}, True),
    ({"a": (1, 2)}, {"a": [1, 2]}, True), ({"a": NAN}, {"a": OTHER_NAN}, True),
    ({"a": 1, "b": 2}, {"b": 2, "a": 1}, True),
    ({"a": api.ERROR}, {"a": str(api.ERROR)}, True),
    ([1], [1.0], True), ([1], [1, 2], True), ([{"a": 1}], [{"a": 1.0}], True),
    (({"a": 1},), ({"a": 1},), True), (({"a": 1},), ({"a": 1}, {"a": 1}), True),
    ((DOC, {"a": 1}), (DOC, {"a": 2}), True),
    (pw.Json({"a": 1}), pw.Json({"a": 1.0}), True),
    (pw.Json({"a": 1}), pw.Json({"a": 1}), True),
    ((pw.Json({"a": 1}), DOC), (pw.Json({"a": 1.0}), DOC), True),
    (Pointer(5), Pointer(5), True), (Pointer(5), 5, True),
    # only consolidate can say
    (NDARRAY, NDARRAY.copy(), False), (NDARRAY, NDARRAY.astype("float64"), False),
    (np.array([1]), 1, False), ((DOC, NDARRAY), (DOC, NDARRAY.copy()), False),
    ((1, 2), [1, 2], False), (("__list__", (1, 2)), [1, 2], False),
    ({"a": 1}, 1, False), ({1, 2}, {1, 2}, False),
]


@pytest.mark.parametrize("old, new, decided", PAIRS, ids=[repr(p[:2])[:60] for p in PAIRS])
def test_same_row_is_consolidates_equality_where_pythons_is_not(old, new, decided):
    key = Pointer(1)
    try:
        cancels = consolidate([Update(key, (7, old), -1), Update(key, (7, new), 1)]) == []
    except TypeError:
        cancels = None  # a set: its tagged form does not hash either
    same = same_row((7, old), (7, new))
    assert (same is not None) == decided
    if decided:
        assert same is cancels
    # and a row is always its own equal, whatever it holds
    assert same_row((7, old), (7, old)) is True


def _count_hashable_visits(monkeypatch):
    visits = [0]
    real_hashable, real_row = stream.hashable, stream.hashable_row

    def hashable(value):
        visits[0] += 1
        return real_hashable(value)

    def counted_row(values):
        visits[0] += 1
        return real_row(values)

    monkeypatch.setattr(stream, "hashable", hashable)
    monkeypatch.setattr(stream, "hashable_row", counted_row)
    return visits


@pytest.mark.parametrize("change", ["append", "modify_one", "remove_and_append"])
def test_an_epoch_costs_the_batch_not_the_group(monkeypatch, change):
    """Cells ``hashable`` / ``hashable_row`` visit in an epoch that changes 64
    documents of a ``reducers.tuple`` of dicts: no more beside 20,000
    documents than beside 200 (a count; no time is taken)."""
    visits = _count_hashable_visits(monkeypatch)

    def epoch_visits(n_docs):
        node = _node([red.TupleReducer()], one_group=True)
        ctx = eg.RunContext()
        docs = [Update(Pointer(i), (0, {"path": f"/{i}.txt"}), 1) for i in range(n_docs)]
        node.process(ctx, 0, [docs])
        extra = [
            Update(Pointer(n_docs + i), (0, {"path": f"/new{i}.txt"}), 1) for i in range(64)
        ]
        if change == "append":
            batch = extra
        elif change == "modify_one":
            batch = [docs[-1]._replace(diff=-1), extra[0]._replace(key=docs[-1].key)]
        else:
            batch = [docs[-1]._replace(diff=-1)] + extra
        # TupleReducer.update has its own binding of hashable for the walk a
        # retraction makes; what is counted here is the emission's
        visits[0] = 0
        out = node.process(ctx, 2, [batch])
        assert sorted(u.diff for u in out) == [-1, 1]
        return visits[0]

    small, large = epoch_visits(200), epoch_visits(20_000)
    assert large <= small
    assert small <= 4


def test_an_ndarray_valued_group_takes_the_fallback_and_the_counter_says_so():
    node = _node([red.SumReducer()], one_group=False)
    ctx = eg.RunContext()
    first = [
        Update(Pointer(1), (0, np.array([1.0, 2.0])), 1),
        Update(Pointer(2), (1, 5), 1),
    ]
    assert len(node.process(ctx, 0, [first])) == 2
    before = device_counters.snapshot()
    # group 0's sum changes, group 1's (a scalar) too: one fallback, two groups
    out = node.process(ctx, 2, [[
        Update(Pointer(3), (0, np.array([1.0, 0.0])), 1), Update(Pointer(4), (1, 1), 1),
    ]])
    assert sorted(u.diff for u in out) == [-1, -1, 1, 1]
    # a row of zeros leaves group 0's bytes as they were: consolidate cancels
    assert node.process(ctx, 4, [[Update(Pointer(5), (0, np.zeros(2)), 1)]]) == []
    after = device_counters.snapshot()
    assert after["groupby_groups_emitted"] - before["groupby_groups_emitted"] == 3
    assert after["groupby_groups_consolidated"] - before["groupby_groups_consolidated"] == 2


UNCHANGED = {
    "max_below": ([red.MaxReducer()], [(0, 9), (0, 3)], [Update(Pointer(9), (0, 5), 1)]),
    "count_swap": (
        [red.CountReducer()], [(0, 1), (0, 2)],
        [Update(Pointer(0), (0, 1), -1), Update(Pointer(9), (0, 7), 1)],
    ),
    "tuple_of_dicts_row_replaced_by_its_equal": (
        [red.TupleReducer()], [(0, {"a": 1}), (0, {"a": 2})],
        [Update(Pointer(1), (0, {"a": 2}), -1), Update(Pointer(1), (0, {"a": 2}), 1)],
    ),
    "sum_of_one_and_one_point_zero": (
        [red.SumReducer()], [(0, 1), (0, 2)],
        [Update(Pointer(0), (0, 1), -1), Update(Pointer(9), (0, 1.0), 1)],
    ),
    "any_of_a_dict": (
        [red.AnyReducer()], [(0, {"a": 1})], [Update(Pointer(9), (0, {"b": 1}), 1)],
    ),
}


@pytest.mark.parametrize("case", sorted(UNCHANGED))
def test_an_unchanged_aggregate_emits_nothing(case):
    reducers, rows, batch = UNCHANGED[case]
    node = _node(reducers, one_group=False)
    ctx = eg.RunContext()
    first = node.process(ctx, 0, [[Update(Pointer(i), r, 1) for i, r in enumerate(rows)]])
    assert [u.diff for u in first] == [1]
    assert node.process(ctx, 2, [batch]) == []


def test_a_group_that_empties_retracts_and_one_that_returns_inserts():
    node = _node([red.TupleReducer()], one_group=False)
    ctx = eg.RunContext()
    row = Update(Pointer(1), (0, DOC), 1)
    (ins,) = node.process(ctx, 0, [[row]])
    (ret,) = node.process(ctx, 2, [[row._replace(diff=-1)]])
    assert (ins.diff, ret.diff, ret.values) == (1, -1, ins.values)
    assert ctx.state(node)["groups"] == {}
    (again,) = node.process(ctx, 4, [[row]])
    assert (again.key, again.diff) == (ins.key, 1)


# --------------------------------------------------------------- /v1/inputs

EVENTS = [
    # (time, data, metadata, diff): added, modified in place, deleted
    (2, b"apples grow on trees", {"path": "/a/fruit.txt", "owner": "a", "modified_at": 5}, 1),
    (2, b"the tpu multiplies", {"path": "/b/tpu.md", "owner": "b", "modified_at": 9}, 1),
    (4, b"pears too", {"path": "/a/pear.txt", "owner": "a", "modified_at": 6}, 1),
    (6, b"apples grow on trees", {"path": "/a/fruit.txt", "owner": "a", "modified_at": 5}, -1),
    (6, b"apples grow on trees", {"path": "/a/fruit.txt", "owner": "b", "modified_at": 12}, 1),
    (8, b"the tpu multiplies", {"path": "/b/tpu.md", "owner": "b", "modified_at": 9}, -1),
    (10, b"plums", {"path": "/c/plum.txt", "owner": "a", "modified_at": 1}, 1),
    (12, b"pears too", {"path": "/a/pear.txt", "owner": "a", "modified_at": 6}, -1),
    (12, b"plums", {"path": "/c/plum.txt", "owner": "a", "modified_at": 1}, -1),
    (12, b"plums", {"path": "/c/plum.txt", "owner": "a", "modified_at": 2}, 1),
]

EVENTS_AS_ROWS = [(data, meta, t, diff) for t, data, meta, diff in EVENTS]


def _plain_lists(events):
    """The documents' metadata after each time's events, as a plain list in
    the order the reducer keeps: insertion, a retraction taking the latest
    equal entry."""
    docs: list[dict] = []
    states = []
    for t in sorted({e[0] for e in events}):
        for _t, _data, meta, diff in (e for e in events if e[0] == t):
            if diff > 0:
                docs.append(dict(meta))
            else:
                at = max(i for i, m in enumerate(docs) if m == meta)
                del docs[at]
        states.append(list(docs))
    return states


@pytest.fixture(scope="module")
def embedder():
    import jax.numpy as jnp

    from pathway_tpu.models import MINILM_L6
    from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder

    tiny = dataclasses.replace(
        MINILM_L6, layers=2, hidden=64, heads=4, mlp_dim=128, dtype=jnp.float32
    )
    return TPUEncoderEmbedder(config=tiny)


FILTERS = {
    "all": (None, None, lambda m: True),
    "glob": (None, "*.txt", lambda m: m["path"].endswith(".txt")),
    "owner": ("owner == 'a'", None, lambda m: m["owner"] == "a"),
    "both": ("modified_at > `4`", "/a/*", lambda m: m["modified_at"] > 4 and m["path"].startswith("/a/")),
    "malformed_fails_closed": ("owner ==", None, lambda m: False),
}


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_inputs_query_answers_what_a_plain_list_of_the_same_events_holds(embedder, name):
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from tests.utils import stream_rows

    metadata_filter, glob, keep = FILTERS[name]
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=dict), EVENTS_AS_ROWS, is_stream=True
    )
    store = DocumentStore(
        docs, retriever_factory=BruteForceKnnFactory(embedder=embedder, reserved_space=64)
    )
    queries = pw.debug.table_from_rows(
        pw.schema_from_types(metadata_filter=str, filepath_globpattern=str),
        [(metadata_filter, glob)],
    )
    updates = stream_rows(store.inputs_query(queries))
    # the query's answer after every epoch that changed it
    answers, held = [], None
    for time in sorted({time for _k, _v, time, _d in updates}):
        for _key, values, at, diff in updates:
            if at == time and diff > 0:
                held = values[0]
        answers.append(held)
    expected = [[m for m in docs if keep(m)] for docs in _plain_lists(EVENTS)]
    assert answers[-1] == expected[-1]
    # epochs may carry several times' events, never reorder them
    it = iter(expected)
    assert all(any(a == e for e in it) for a in answers if a), (answers, expected)


# ------------------------------------------------------------ instruments


@pytest.mark.parametrize("trace", ["1", "0"])
def test_the_span_and_both_counters_are_in_the_snapshot(trace):
    saved = os.environ.get("PATHWAY_TRACE")
    try:
        tracing.configure(PATHWAY_TRACE=trace)
        tracing.reset()
        before = device_counters.snapshot()
        node = _node([red.TupleReducer()], one_group=False)
        ctx = eg.RunContext()
        node.process(ctx, 0, [[Update(Pointer(1), (0, DOC), 1), Update(Pointer(2), (1, NDARRAY), 1)]])
        node.process(ctx, 2, [[]])  # no dirty group: no span, nothing counted
        node.process(ctx, 4, [[Update(Pointer(3), (0, DOC), 1)]])
        after = device_counters.snapshot()
        assert after["groupby_groups_emitted"] - before["groupby_groups_emitted"] == 3
        assert after["groupby_groups_consolidated"] == before["groupby_groups_consolidated"]
        if trace == "1":
            assert after["span_count.groupby_emit"] == 2
            assert after["span_ns.groupby_emit"] > 0
            spans = [e for e in tracing.chrome_events(all_spans=True) if e["name"] == "groupby_emit"]
            assert [(e["args"]["groups"], e["args"]["consolidated"]) for e in spans] == [(2, 0), (1, 0)]
            assert spans[0]["args"]["node"] == f"groupby#{node.id}"
        else:
            assert after.get("span_count.groupby_emit", 0) == 0
            assert after.get("span_ns.groupby_emit", 0) == 0
    finally:
        tracing.configure(PATHWAY_TRACE=saved)
        tracing.reset()
