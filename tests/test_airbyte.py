"""Airbyte connector: protocol driver, incremental state machinery, and
full-refresh diffing — tested against a local fake connector speaking the
Airbyte protocol (no Docker needed; reference ``io/airbyte`` +
``third_party/airbyte_serverless``)."""

from __future__ import annotations

import json
import sys
import textwrap

import pytest

import pathway_tpu as pw
from pathway_tpu.io.airbyte import (
    AirbyteStateTracker,
    ExecutableAirbyteSource,
)
from tests.utils import run_to_rows

#: a minimal Airbyte-protocol source: `discover` emits a catalog for an
#: incremental "events" stream; `read` emits RECORDs for database rows
#: past the state cursor, then a STREAM-type STATE with the new cursor
_FAKE_CONNECTOR = textwrap.dedent(
    """
    import json, sys

    def emit(obj):
        print(json.dumps(obj), flush=True)

    args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
    cmd = sys.argv[1]
    args = {}
    rest = sys.argv[2:]
    for i in range(0, len(rest) - 1, 2):
        args[rest[i]] = rest[i + 1]

    config = json.load(open(args["--config"])) if "--config" in args else {}
    db_path = config["db"]

    if cmd == "discover":
        emit({
            "type": "CATALOG",
            "catalog": {
                "streams": [
                    {
                        "name": "events",
                        "json_schema": {},
                        "supported_sync_modes": ["full_refresh", "incremental"],
                    },
                    {
                        "name": "snapshots",
                        "json_schema": {},
                        "supported_sync_modes": ["full_refresh"],
                    },
                ]
            },
        })
        sys.exit(0)

    assert cmd == "read", cmd
    catalog = json.load(open(args["--catalog"]))
    stream = catalog["streams"][0]["stream"]["name"]
    sync_mode = catalog["streams"][0]["sync_mode"]
    cursor = 0
    if "--state" in args:
        state = json.load(open(args["--state"]))
        if state and state.get("type") == "GLOBAL":
            for s in state["global"]["stream_states"]:
                if s["stream_descriptor"]["name"] == stream:
                    cursor = s["stream_state"].get("cursor", 0)

    rows = json.load(open(db_path))
    emit({"type": "LOG", "log": {"level": "INFO", "message": "reading"}})
    out = [r for r in rows if sync_mode != "incremental" or r["id"] > cursor]
    for r in out:
        emit({
            "type": "RECORD",
            "record": {"stream": stream, "data": r, "emitted_at": 0},
        })
    if sync_mode == "incremental":
        new_cursor = max([r["id"] for r in rows], default=cursor)
        emit({
            "type": "STATE",
            "state": {
                "type": "STREAM",
                "stream": {
                    "stream_descriptor": {"name": stream},
                    "stream_state": {"cursor": new_cursor},
                },
            },
        })
    """
)


@pytest.fixture
def fake_connector(tmp_path):
    script = tmp_path / "fake_source.py"
    script.write_text(_FAKE_CONNECTOR)
    db = tmp_path / "db.json"
    db.write_text(json.dumps([{"id": 1, "v": "a"}, {"id": 2, "v": "b"}]))
    return [sys.executable, str(script)], db


def test_state_tracker_flavors():
    tr = AirbyteStateTracker()
    assert tr.envelope() is None
    tr.observe({"type": "LEGACY", "data": {"pos": 5}})
    assert tr.envelope() == {"type": "LEGACY", "data": {"pos": 5}}
    # STREAM states supersede the legacy blob in the envelope
    tr.observe(
        {
            "type": "STREAM",
            "stream": {
                "stream_descriptor": {"name": "events"},
                "stream_state": {"cursor": 7},
            },
        }
    )
    env = tr.envelope()
    assert env["type"] == "GLOBAL"
    assert env["global"]["stream_states"] == [
        {"stream_descriptor": {"name": "events"}, "stream_state": {"cursor": 7}}
    ]
    # GLOBAL folds stream states + shared state
    tr.observe(
        {
            "type": "GLOBAL",
            "global": {
                "stream_states": [
                    {
                        "stream_descriptor": {"name": "other"},
                        "stream_state": {"cursor": 1},
                    }
                ],
                "shared_state": {"cdc": "lsn9"},
            },
        }
    )
    env = tr.envelope()
    names = {s["stream_descriptor"]["name"] for s in env["global"]["stream_states"]}
    assert names == {"events", "other"}
    assert env["global"]["shared_state"] == {"cdc": "lsn9"}
    # round trip
    tr2 = AirbyteStateTracker()
    tr2.load(env)
    assert tr2.envelope() == env


def test_source_discover_and_sync_mode(fake_connector, tmp_path):
    cmd, db = fake_connector
    src = ExecutableAirbyteSource(
        cmd, config={"db": str(db)}, streams=["events"]
    )
    cat = src.discover()
    assert {s["name"] for s in cat["streams"]} == {"events", "snapshots"}
    assert src.sync_mode == "incremental"
    full = ExecutableAirbyteSource(
        cmd, config={"db": str(db)}, streams=["snapshots"]
    )
    assert full.sync_mode == "full_refresh"
    with pytest.raises(ValueError, match="not found"):
        ExecutableAirbyteSource(
            cmd, config={"db": str(db)}, streams=["nope"]
        ).configured_catalog


def test_airbyte_incremental_read_and_resume(fake_connector, tmp_path):
    cmd, db = fake_connector
    state_path = tmp_path / "state.json"
    t = pw.io.airbyte.read(
        {"source": {"config": {"db": str(db)}}},
        ["events"],
        command=cmd,
        mode="static",
        state_path=str(state_path),
    )
    rows = run_to_rows(t)
    assert sorted(r[0]["id"] for r in rows) == [1, 2]
    saved = json.loads(state_path.read_text())
    assert saved["type"] == "GLOBAL"
    assert saved["global"]["stream_states"][0]["stream_state"] == {"cursor": 2}

    # new rows arrive; a fresh pipeline resumes FROM THE SAVED STATE and
    # extracts only the increment
    db.write_text(
        json.dumps(
            [
                {"id": 1, "v": "a"},
                {"id": 2, "v": "b"},
                {"id": 3, "v": "c"},
            ]
        )
    )
    pw.G.clear()
    t2 = pw.io.airbyte.read(
        {"source": {"config": {"db": str(db)}}},
        ["events"],
        command=cmd,
        mode="static",
        state_path=str(state_path),
    )
    rows2 = run_to_rows(t2)
    assert [r[0]["id"] for r in rows2] == [3]
    assert json.loads(state_path.read_text())["global"]["stream_states"][0][
        "stream_state"
    ] == {"cursor": 3}


def test_airbyte_full_refresh_diffing(fake_connector, tmp_path):
    """full_refresh polls snapshot-diff: unchanged rows don't churn and
    disappeared rows retract."""
    from pathway_tpu.io.airbyte import _AirbyteSubject

    cmd, db = fake_connector
    src = ExecutableAirbyteSource(
        cmd, config={"db": str(db)}, streams=["snapshots"]
    )
    subject = _AirbyteSubject(src, mode="static", refresh_interval_ms=10)

    class Events:
        stopped = False

        def __init__(self):
            self.ops = []

        def add(self, key, row):
            self.ops.append(("add", row))

        def remove(self, key, row):
            self.ops.append(("remove", row))

        def commit(self):
            self.ops.append(("commit", None))

    import pathway_tpu.internals.schema as sch

    subject._schema = sch.schema_from_types(data=dict)
    subject._events = Events()
    subject.run()
    first = list(subject._events.ops)
    assert [op for op, _ in first] == ["add", "add", "commit"]

    # second poll, one row gone, one unchanged, one new
    db.write_text(json.dumps([{"id": 2, "v": "b"}, {"id": 9, "v": "z"}]))
    subject._events.ops.clear()
    subject.run()
    second = subject._events.ops
    kinds = [op for op, _ in second]
    assert kinds.count("add") == 1  # only the new row
    assert kinds.count("remove") == 1  # the disappeared row
    removed = [r for op, r in second if op == "remove"][0]
    assert removed[0]["id"] == 1


def test_airbyte_docker_config_stays_gated(tmp_path):
    from pathway_tpu.io._gated import MissingDependency

    with pytest.raises((MissingDependency, ImportError)):
        pw.io.airbyte.read(
            {"source": {"docker_image": "airbyte/source-faker:latest"}},
            ["users"],
            mode="static",
        )
