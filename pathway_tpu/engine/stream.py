"""Update-stream primitives.

The engine models every table as a stream of keyed row updates
``(key, values, diff)`` grouped into *epochs* (logical timestamps).  This is
the capability of the reference's differential collections
(``src/engine/dataflow.rs``) re-expressed for an epoch-synchronous scheduler:
within one epoch all operators see a consistent atomic batch; retractions are
``diff=-1`` updates.

Timestamps are even integers advancing by 2, matching the reference's
convention of reserving odd times for internal interleaving
(``src/connectors/mod.rs:199,538,552``).
"""

from __future__ import annotations

import datetime
import json
from typing import Any, Iterable, NamedTuple

import numpy as np

from pathway_tpu.internals import native as _native
from pathway_tpu.internals.keys import Pointer


class Update(NamedTuple):
    key: Pointer
    values: tuple
    diff: int


Batch = list[Update]

TIME_STEP = 2


def hashable(value: Any) -> Any:
    """Map an arbitrary cell value to something hashable (for multiset
    counters inside reducers)."""
    if isinstance(value, np.ndarray):
        return ("__ndarray__", value.shape, value.tobytes())
    if isinstance(value, dict):
        return ("__dict__", json.dumps(value, sort_keys=True, default=str))
    if isinstance(value, list):
        return ("__list__", tuple(hashable(v) for v in value))
    if isinstance(value, tuple):
        return tuple(hashable(v) for v in value)
    return value


def hashable_row(values: tuple) -> tuple:
    return tuple(hashable(v) for v in values)


def _same_cell(a: Any, b: Any) -> bool | None:
    """:func:`same_row` for one pair of cells of rows that hold an
    unhashable cell somewhere, by the kinds :func:`hashable` tells apart."""
    if a is b:
        return True
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return None
    for kind in (dict, list, tuple):
        if isinstance(a, kind):
            if not isinstance(b, kind):
                return None
            if kind is dict:
                # a dict's tagged form is its JSON text: {"a": 1} is not
                # {"a": 1.0}, and {1: "x"} is {"1": "x"}
                return hashable(a) == hashable(b)
            if len(a) != len(b):
                return False
            for x, y in zip(a, b):
                if x is not y:
                    same = _same_cell(x, y)
                    if not same:
                        return same
            return True
        if isinstance(b, kind):
            return None
    # the tagged form leaves both cells as they are, and the dict lookup
    # wants equal hashes before it asks ``==`` (pw.Json hashes its text)
    return hash(a) == hash(b) and bool(a == b)


def same_row(old: tuple, new: tuple) -> bool | None:
    """Whether :func:`consolidate` would cancel ``Update(k, old, -1)``
    against ``Update(k, new, 1)``, decided from the two rows alone: True,
    False, or None where only ``consolidate`` can say (an ndarray cell, a
    container beside a scalar, a cell that will not hash or compare).

    ``consolidate`` keys a row by itself where it hashes and by
    :func:`hashable_row` where it does not, and a dict lookup calls two keys
    equal when their hashes are and they are the same object or ``==``.  So
    cells that are one object are equal, a sequence is unequal to one of
    another length, and neither needs the walk over every cell of both rows
    that a tagged form costs: a ``reducers.tuple`` of a table's dicts that
    gained a row is told from its predecessor at its length."""
    if old is new:
        return True
    try:
        try:
            return hash(old) == hash(new) and bool(old == new)
        except TypeError:
            return _same_cell(old, new)
    except Exception:  # noqa: BLE001 - consolidate meets it too and reports it
        return None


def _py_consolidate(batch: Iterable[Update]) -> Batch:
    acc: dict[tuple, list] = {}
    for u in batch:
        k = (u.key, u.values)
        try:
            e = acc.get(k)
        except TypeError:
            k = (u.key, hashable_row(u.values))
            e = acc.get(k)
        if e is None:
            acc[k] = [u.key, u.values, u.diff]
        else:
            e[2] += u.diff
    return [Update(key, vals, d) for key, vals, d in acc.values() if d != 0]


def consolidate(batch: Iterable[Update]) -> Batch:
    """Merge updates with equal (key, row), dropping zero-diff entries.

    Fast path hashes the row tuple directly (scalar cells — the common
    case); rows holding unhashable cells (ndarray/dict/list) fall back to
    the type-tagged :func:`hashable_row` per update, so both spellings of
    an equal row land in the same bucket.

    Runs in C when the native extension is available
    (``native/pathway_native.cpp`` ``consolidate`` — the compaction loop
    the reference runs inside differential arrangements); unchanged
    single-occurrence updates are re-emitted by reference, so the common
    no-duplicate case allocates nothing.  The C path handles unhashable
    rows itself (via ``hashable_row``), so it needs no fallback."""
    native = _native.load()
    if native is not None:
        return native.consolidate(
            batch if isinstance(batch, list) else list(batch),
            Update,
            hashable_row,
        )
    return _py_consolidate(batch)


def per_key_changes(batch: Iterable[Update]) -> dict[Pointer, tuple[list, list]]:
    """Group a batch into per-key (removals, additions) lists."""
    native = _native.load()
    if native is not None:
        return native.per_key_changes(batch)
    out: dict[Pointer, tuple[list, list]] = {}
    for u in batch:
        rem, add = out.setdefault(u.key, ([], []))
        if u.diff < 0:
            rem.extend([u.values] * (-u.diff))
        else:
            add.extend([u.values] * u.diff)
    return out


def total_str(value: Any) -> str:
    if isinstance(value, datetime.datetime):
        return value.isoformat()
    return str(value)
