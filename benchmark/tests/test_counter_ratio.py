"""Reader kind ``counter_ratio`` over hand-made readings, and the
declarations that use it."""

from __future__ import annotations

import glob
import json
import os

import pytest

from benchmark.readers import counter_ratio

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPEN = {"span_ns.a": 1_000_000, "span_count.a": 2, "span_ns.b": 500_000, "tokens": 100, "padded": 400, "still": 7}
CLOSE = {"span_ns.a": 9_000_000, "span_count.a": 6, "span_ns.b": 2_500_000, "tokens": 160, "padded": 1000, "still": 7, "late": 12}


def _readings(window=None):
    return {"counters": {"open": dict(OPEN), "close": dict(CLOSE)}, "window": window or {}}


@pytest.mark.parametrize(
    "decl,window,expected",
    [
        # plain ratio: 8 ms of spans over 4 spans
        ({"numerator": ["span_ns.a"], "denominator": ["span_count.a"], "scale": 1e-6}, None, 2.0),
        # no scale given: the bare quotient
        ({"numerator": ["tokens"], "denominator": ["padded"]}, None, 0.1),
        ({"numerator": ["tokens"], "denominator": ["padded"], "scale": 100.0}, None, 10.0),
        # minus: (8 ms - 2 ms) over 4 spans
        ({"numerator": ["span_ns.a"], "minus": ["span_ns.b"], "denominator": ["span_count.a"], "scale": 1e-6}, None, 1.5),
        # several keys are summed on either side
        ({"numerator": ["span_ns.a", "span_ns.b"], "denominator": ["span_count.a", "tokens"], "scale": 1e-6}, None, 10.0 / 64),
        # per: over a count the traffic kind kept
        ({"numerator": ["tokens"], "per": "window_chunks"}, {"window_chunks": 30}, 2.0),
        # a stage no span had fed before the window opened counts from zero
        ({"numerator": ["late"], "denominator": ["span_count.a"]}, None, 3.0),
        # nothing to read, nothing returned
        ({"numerator": ["absent"], "denominator": ["span_count.a"]}, None, None),
        ({"numerator": ["tokens"], "denominator": ["absent"]}, None, None),
        ({"numerator": ["tokens"], "minus": ["absent"], "denominator": ["padded"]}, None, None),
        ({"numerator": ["tokens"], "denominator": ["still"]}, None, None),  # did not move
        ({"numerator": ["tokens"], "per": "window_chunks"}, {"window_chunks": 0}, None),
        ({"numerator": ["tokens"], "per": "window_chunks"}, {}, None),
    ],
)
def test_counter_ratio(decl, window, expected):
    got = counter_ratio.read(decl, _readings(window))
    assert got is None if expected is None else got == pytest.approx(expected)


def test_the_parent_has_none_of_the_keys_and_reads_nothing():
    """On the commit before PR 25 ``snapshot()`` holds five byte and compile
    counters: every declaration of this reader has to read nothing there,
    and raise nothing."""
    old = {"jit_compiles": 3, "h2d_bytes": 10, "h2d_transfers": 1, "d2h_bytes": 20, "d2h_transfers": 2, "listener_installed": 1}
    r = {"counters": {"open": dict(old), "close": {k: v + 5 for k, v in old.items()}}, "window": {"window_chunks": 9}}
    decls = _declarations()
    # PR 25's nine and PR 26's one; a later PR's declaration is held to the same
    assert {d["name"] for d in decls} >= {
        "rest_ingress_ms", "epoch_cut_wait_ms", "epoch_process_ms", "search_wait_ms", "rest_respond_ms",
        "queries_per_search", "useful_token_pct", "epoch_host_ms", "index_add_ms", "chunks_per_encoder_row",
    }
    for decl in decls:
        assert counter_ratio.read(decl, r) is None, decl["name"]


def _declarations():
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json"))):
        with open(path) as f:
            decl = json.load(f)
        if decl["reader"] == "counter_ratio":
            out.append(decl)
    return out


def test_each_declaration_reads_a_number_from_a_program_that_has_its_keys():
    keys = {k for d in _declarations() for part in ("numerator", "minus", "denominator") for k in d.get(part, [])}
    opened = {k: 1000 for k in keys}
    closed = {k: 1000 + 10_000_000 * (1 + i) for i, k in enumerate(sorted(keys))}
    for decl in _declarations():
        value = counter_ratio.read(decl, {"counters": {"open": opened, "close": closed}, "window": {}})
        assert value is not None and value == value and abs(value) != float("inf"), decl["name"]
        assert decl["source"] == "program_counter" and ("denominator" in decl) != ("per" in decl)
