"""Regression tests for code-review findings (round 1)."""

import numpy as np
import pytest

import pathway_tpu as pw
from tests.utils import T, _rows_of, assert_table_equality_wo_index


def test_str_methods_with_default_args():
    t = T(
        """
        s
        '  hi  '
        """
    )
    res = t.select(
        stripped=t.s.str.strip(),
        split=t.s.str.split(),
        found=t.s.str.find("h"),
    )
    assert list(_rows_of(res).values()) == [("hi", ("hi",), 2)]


def test_filter_numpy_bool():
    t = T(
        """
        a
        1
        5
        """
    )
    r = t.select(b=pw.apply(lambda x: np.int64(x), t.a))
    res = r.filter(r.b > 2)
    assert len(_rows_of(res)) == 1


def test_join_left_id_duplicate_matches_raises():
    t1 = T(
        """
        a | k
        1 | x
        """
    )
    t2 = T(
        """
        b | k
        100 | x
        200 | x
        """
    )
    # per-node containment: the id-collision error is routed
    # to the error log and the run survives instead of aborting
    rows = _rows_of(t1.join(t2, t1.k == t2.k, id=pw.left.id).select(c=t2.b))
    assert rows == {}
    ctx = pw.G.last_run_ctx
    assert any("join" in e and "right matches" in e for e in ctx.error_log)


def test_duplicate_column_reference_in_expr():
    target = T(
        """
        id | v
        1  | 5
        """
    )
    req = T(
        """
        x
        1
        """
    ).select(p=target.pointer_from(pw.this.x))
    res = target.ix_ref(req.p, req.p, context=req)
    # hash of (ptr, ptr) won't match target keys -> Error rows, but no crash
    assert len(_rows_of(res)) <= 1


def test_having_filters():
    t = T(
        """
        id | a
        1  | 1
        2  | 2
        3  | 3
        """
    )
    ptrs = T(
        """
        x
        1
        3
        """
    ).select(p=t.pointer_from(pw.this.x))
    assert sorted(_rows_of(t.having(ptrs.p)).values()) == [(1,), (3,)]


def test_ambiguous_join_column_raises():
    t1 = T(
        """
        v | k
        1 | x
        """
    )
    t2 = T(
        """
        v | k
        2 | x
        """
    )
    with pytest.raises(Exception):
        t1.join(t2, t1.k == t2.k).select(out=pw.this.v)


def test_sort_prev_next():
    t = T(
        """
        a
        30
        10
        20
        """
    )
    s = t.sort(key=pw.this.a)
    rows = _rows_of(s)
    pairs = list(rows.values())
    n_first = sum(1 for p in pairs if p[0] is None)
    n_last = sum(1 for p in pairs if p[1] is None)
    assert n_first == 1 and n_last == 1 and len(pairs) == 3


def test_diff():
    t = T(
        """
        ts | v
        1  | 10
        2  | 13
        3  | 17
        """
    )
    d = t.diff(pw.this.ts, pw.this.v)
    assert sorted(_rows_of(d).values()) == [(1, 10, None), (2, 13, 3), (3, 17, 4)]


def test_interpolate():
    from pathway_tpu.stdlib.statistical import interpolate

    t = T(
        """
        ts | v
        1  | 1.0
        2  | None
        3  | 3.0
        """
    )
    res = interpolate(t, pw.this.ts, pw.this.v)
    assert sorted(_rows_of(res).values()) == [(1, 1.0), (2, 2.0), (3, 3.0)]


def test_select_across_same_universe_tables_zip():
    t = T(
        """
        a
        1
        2
        """
    )
    doubled = t.select(b=t.a * 2)
    combined = t.select(t.a, doubled.b)
    assert_table_equality_wo_index(
        combined,
        T(
            """
            a | b
            1 | 2
            2 | 4
            """
        ),
    )
