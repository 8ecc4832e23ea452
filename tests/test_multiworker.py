"""Multi-worker execution: sharded thread workers, TCP cluster processes,
partitioned readers, kill/restart recovery.

Mirrors the reference's scale-out contract: N-worker runs produce the same
output as 1-worker runs (reference thread-count CI matrix,
``tests/utils.py:37-50``; wordcount cluster harness
``integration_tests/wordcount/base.py:231-236``).
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.cluster import Cluster
from pathway_tpu.engine.scheduler import Scheduler
from pathway_tpu.internals.parse_graph import G

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_port_counter = [11000 + (os.getpid() % 500) * 16]


def next_port(n: int = 4) -> int:
    """A base port with `n` consecutive bindable ports (probed, so stray
    listeners from an earlier killed run can't collide)."""
    import socket

    while True:
        base = _port_counter[0]
        _port_counter[0] += n
        if _port_counter[0] > 60000:
            _port_counter[0] = 11000
        try:
            socks = []
            for i in range(n):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
        return base


def _run_threads(n_threads: int):
    """Run the current graph on an in-process thread cluster; returns the
    worker-0 RunContext."""
    sched = Scheduler(G.engine_graph, autocommit_ms=10)
    cluster = Cluster(threads=n_threads)
    try:
        return sched.run_cluster(cluster)
    finally:
        cluster.close()


def _wordcount_results(input_file, results):
    class S(pw.Schema):
        word: str

    t = pw.io.jsonlines.read(str(input_file), schema=S, mode="static")
    counts = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())

    def on_change(key, row, time, is_addition):
        if is_addition:
            results[row["word"]] = row["n"]
        elif results.get(row["word"]) == row["n"]:
            del results[row["word"]]

    pw.io.subscribe(counts, on_change=on_change)


@pytest.mark.parametrize("n_threads", [2, 4])
def test_thread_workers_wordcount_matches_single(tmp_path, n_threads):
    words = ["a", "b", "a", "c", "a", "b", "d", "a", "e", "b"] * 5
    input_file = tmp_path / "w.jsonl"
    input_file.write_text("\n".join(json.dumps({"word": w}) for w in words))

    expected = {}
    for w in words:
        expected[w] = expected.get(w, 0) + 1

    results: dict = {}
    _wordcount_results(input_file, results)
    _run_threads(n_threads)
    assert results == expected


@pytest.mark.parametrize("n_threads", [2, 3])
def test_thread_workers_join_matches_single(n_threads):
    from tests.utils import T

    left = T(
        """
        k | a
        x | 1
        y | 2
        z | 3
        """
    )
    right = T(
        """
        k | b
        x | 10
        y | 20
        w | 40
        """
    )
    joined = left.join(right, left.k == right.k).select(
        left.k, s=pw.left.a + pw.right.b
    )
    from pathway_tpu.engine.graph import CaptureNode

    cap = CaptureNode(G.engine_graph, joined._node)
    ctx = _run_threads(n_threads)
    rows = sorted(ctx.state(cap)["rows"].values())
    assert rows == [("x", 11), ("y", 22)]


def test_thread_workers_stateful_ops_match_single():
    """groupby+filter+concat+distinct pipeline over threads == single."""
    from tests.utils import T

    t = T(
        """
        grp | v
        a   | 1
        b   | 2
        a   | 3
        c   | 4
        b   | 6
        a   | 5
        """
    )
    red = t.groupby(t.grp).reduce(
        t.grp,
        total=pw.reducers.sum(t.v),
        mx=pw.reducers.max(t.v),
    )
    big = red.filter(red.total > 4)
    from pathway_tpu.engine.graph import CaptureNode

    cap = CaptureNode(G.engine_graph, big._node)
    ctx = _run_threads(4)
    rows = sorted(ctx.state(cap)["rows"].values())
    assert rows == [("a", 9, 5), ("b", 8, 6)]


def test_partitioned_reader_covers_all_rows(tmp_path):
    """Each worker's partitioned file reader emits a disjoint share whose
    union is the full input (parallel_readers semantics)."""
    from pathway_tpu.io.fs import _FilesSource
    from pathway_tpu.internals import schema as sch

    f = tmp_path / "data.txt"
    f.write_text("\n".join(f"line{i}" for i in range(100)))
    schema = sch.schema_from_types(data=str)

    class Sink:
        stopped = False

        def __init__(self):
            self.rows = []

        def add(self, key, values):
            self.rows.append((key, values))

        def commit(self):
            pass

        def close(self):
            pass

    src = _FilesSource(
        str(f), schema, parse_line=lambda l: {"data": l.rstrip("\n")} or None,
        mode="static", tag="t",
    )
    W = 3
    shares = []
    for w in range(W):
        sink = Sink()
        src.partition(w, W).run(sink)
        shares.append(sink.rows)
    all_keys = [k for share in shares for k, _ in share]
    assert len(all_keys) == 100
    assert len(set(all_keys)) == 100  # disjoint
    assert all(shares[w] for w in range(W))  # balanced enough to be nonempty


def test_steady_state_one_barrier_per_round(tmp_path):
    """Piggybacked epoch-cut consensus: the per-round status gather rides
    the data streams (``round_statuses``), so ``allgather`` stays an O(1)
    run-boundary primitive.  Counted directly — the steady-state path must
    not regress to a second rendezvous per round."""
    words = [f"w{i % 11}" for i in range(200)]
    input_file = tmp_path / "w.jsonl"
    input_file.write_text("\n".join(json.dumps({"word": w}) for w in words))

    results: dict = {}
    _wordcount_results(input_file, results)
    sched = Scheduler(G.engine_graph, autocommit_ms=5)
    cluster = Cluster(threads=2)
    allgather_slots: list = []
    orig_allgather = cluster.allgather

    def counting_allgather(slot, thread_id, obj):
        allgather_slots.append(slot)
        return orig_allgather(slot, thread_id, obj)

    cluster.allgather = counting_allgather  # type: ignore[method-assign]
    try:
        sched.run_cluster(cluster)
    finally:
        stats = cluster.exchange_stats()
        cluster.close()

    assert results  # the pipeline actually ran
    assert stats["status_rounds"] >= 2
    # every allgather is a known run-boundary slot — never a per-round one
    boundary = {("replay_len",), ("snap_presence",), ("errlog", "final")}
    assert set(allgather_slots) <= boundary, allgather_slots
    # O(1) per run: both threads call each boundary slot once
    assert len(allgather_slots) <= 2 * len(boundary)
    assert stats["allgather_calls"] <= len(boundary)


# ---------------------------------------------------------------------------
# multi-process TCP cluster

_WORDCOUNT_PROGRAM = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw

    class S(pw.Schema):
        word: str

    t = pw.io.jsonlines.read({input!r}, schema=S, mode={mode!r})
    counts = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
    pw.io.jsonlines.write(counts, {output!r})
    {persistence}
    pw.run(autocommit_duration_ms=20, persistence_config=pconf)
    """
)


def _spawn_program(tmp_path, input_file, output_file, *, processes, threads,
                   mode="static", persist_dir=None, first_port=None,
                   persist_mode="persisting"):
    persistence = (
        f"from pathway_tpu.persistence import Backend, Config, PersistenceMode\n"
        f"pconf = Config.simple_config(Backend.filesystem({str(persist_dir)!r}), "
        f"persistence_mode=PersistenceMode({persist_mode!r}))"
        if persist_dir
        else "pconf = None"
    )
    prog = tmp_path / "prog.py"
    prog.write_text(
        _WORDCOUNT_PROGRAM.format(
            repo=REPO,
            input=str(input_file),
            output=str(output_file),
            mode=mode,
            persistence=persistence,
        )
    )
    env = dict(os.environ)
    env["PATHWAY_THREADS"] = str(threads)
    env["PATHWAY_PROCESSES"] = str(processes)
    env["PATHWAY_FIRST_PORT"] = str(first_port or next_port(processes + 1))
    procs = []
    for pid in range(processes):
        e = dict(env)
        e["PATHWAY_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, str(prog)],
                env=e,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
            )
        )
    return procs


def _final_counts(output_file) -> dict:
    counts: dict = {}
    if not os.path.exists(output_file):
        return counts
    state: dict = {}
    with open(output_file) as f:
        for line in f:
            if not line.strip():
                continue
            row = json.loads(line)
            key = row["word"]
            if row["diff"] > 0:
                state[key] = row["n"]
            elif state.get(key) == row["n"]:
                del state[key]
    return state


def _wait_for_progress(output_file, timeout: float = 60.0) -> None:
    """Block until the pipeline demonstrably flowed end-to-end (output
    rows exist).  The kill/restart tests used to SIGKILL after a fixed
    wall-clock sleep, which raced suite load — killing before any commit
    made recovery trivially pass or the cluster handshake fail."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if os.path.getsize(output_file) > 0:
                # one more commit interval so persistence logs a commit
                # past the rows we just observed
                time.sleep(0.3)
                return
        except OSError:
            pass
        time.sleep(0.05)
    raise AssertionError(f"no pipeline progress within {timeout}s")


def test_two_process_cluster_wordcount(tmp_path):
    """spawn -n 2 -t 2: partitioned work, output identical to 1 worker."""
    words = ["apple", "pear", "apple", "plum", "apple", "pear"] * 10
    input_file = tmp_path / "w.jsonl"
    input_file.write_text("\n".join(json.dumps({"word": w}) for w in words))
    output_file = tmp_path / "out.jsonl"

    procs = _spawn_program(
        tmp_path, input_file, output_file, processes=2, threads=2
    )
    for p in procs:
        out, err = p.communicate(timeout=90)
        assert p.returncode == 0, err.decode()[-2000:]
    assert _final_counts(output_file) == {"apple": 30, "pear": 20, "plum": 10}


def test_process_kill_restart_recovers(tmp_path):
    """Kill one process mid-stream; restart the cluster; persistence
    resumes to exact counts (reference wordcount test_recovery)."""
    words = [f"w{i % 7}" for i in range(400)]
    input_file = tmp_path / "w.jsonl"
    input_file.write_text("\n".join(json.dumps({"word": w}) for w in words))
    output_file = tmp_path / "out.jsonl"
    persist_dir = tmp_path / "snap"

    port = next_port(4)
    procs = _spawn_program(
        tmp_path, input_file, output_file, processes=2, threads=1,
        mode="streaming", persist_dir=persist_dir, first_port=port,
    )
    # kill one worker only after output proves end-to-end progress
    _wait_for_progress(output_file)
    procs[1].send_signal(signal.SIGKILL)
    for p in procs:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()

    # restart: static mode completes the read; resume must not double-count
    output_file.unlink(missing_ok=True)
    procs = _spawn_program(
        tmp_path, input_file, output_file, processes=2, threads=1,
        mode="static", persist_dir=persist_dir, first_port=port + 8,
    )
    for p in procs:
        out, err = p.communicate(timeout=90)
        assert p.returncode == 0, err.decode()[-2000:]
    expected: dict = {}
    for w in words:
        expected[w] = expected.get(w, 0) + 1
    assert _final_counts(output_file) == expected


def test_cluster_operator_snapshot_kill_restart(tmp_path):
    """OPERATOR_PERSISTING in a 2-process cluster: kill one process
    mid-stream, restart, final counts exact with bounded replay."""
    words = [f"w{i % 5}" for i in range(300)]
    input_file = tmp_path / "w.jsonl"
    input_file.write_text("\n".join(json.dumps({"word": w}) for w in words))
    output_file = tmp_path / "out.jsonl"
    persist_dir = tmp_path / "snap"

    port = next_port(4)
    procs = _spawn_program(
        tmp_path, input_file, output_file, processes=2, threads=1,
        mode="streaming", persist_dir=persist_dir, first_port=port,
        persist_mode="operator_persisting",
    )
    _wait_for_progress(output_file)
    procs[0].send_signal(signal.SIGKILL)
    for p in procs:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()

    # restart in static mode; cumulative final state must be exact.
    # Operator snapshots give CONTINUATION semantics: only groups touched
    # after the restore re-fire, so merge both runs' outputs.
    state = _final_counts(output_file)
    output_file.unlink(missing_ok=True)
    procs = _spawn_program(
        tmp_path, input_file, output_file, processes=2, threads=1,
        mode="static", persist_dir=persist_dir, first_port=next_port(4),
        persist_mode="operator_persisting",
    )
    for p in procs:
        out, err = p.communicate(timeout=90)
        assert p.returncode == 0, err.decode()[-2000:]
    state.update(_final_counts(output_file))
    expected: dict = {}
    for w in words:
        expected[w] = expected.get(w, 0) + 1
    assert state == expected


_STATS_PROGRAM = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import pathway_tpu as pw

    class S(pw.Schema):
        word: str

    t = pw.io.jsonlines.read({input!r}, schema=S, mode="static")
    counts = t.groupby(t.word).reduce(t.word, n=pw.reducers.count())
    pw.io.jsonlines.write(counts, {output!r})
    ctx = pw.run(autocommit_duration_ms=20, monitoring_level="none")
    print("EXCHANGE_STATS=" + json.dumps(ctx.stats.get("exchange", {{}})))
    """
)


def _run_stats_cluster(tmp_path, words, *, threads, **env_extra) -> tuple[list, dict]:
    """Two processes over TCP on ``_STATS_PROGRAM``; each process's
    exchange stats and the final counts."""
    input_file = tmp_path / "w.jsonl"
    input_file.write_text("\n".join(json.dumps({"word": w}) for w in words))
    output_file = tmp_path / "out.jsonl"
    prog = tmp_path / "prog.py"
    prog.write_text(
        _STATS_PROGRAM.format(
            repo=REPO, input=str(input_file), output=str(output_file)
        )
    )
    env = dict(os.environ, **env_extra)
    env["PATHWAY_THREADS"] = str(threads)
    env["PATHWAY_PROCESSES"] = "2"
    env["PATHWAY_FIRST_PORT"] = str(next_port(3))
    procs = []
    for pid in range(2):
        e = dict(env)
        e["PATHWAY_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, str(prog)],
                env=e,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
            )
        )
    all_stats = []
    for p in procs:
        out, err = p.communicate(timeout=90)
        assert p.returncode == 0, err.decode()[-2000:]
        line = next(
            l for l in out.decode().splitlines() if l.startswith("EXCHANGE_STATS=")
        )
        all_stats.append(json.loads(line[len("EXCHANGE_STATS="):]))
    return all_stats, _final_counts(output_file)


def test_two_process_exchange_stats(tmp_path):
    """The pipelined transport reports its overhead probe: framed
    transmissions flowed, the status consensus rode them every round, and
    allgather stayed a run-boundary constant."""
    words = ["apple", "pear", "apple", "plum", "apple", "pear"] * 20
    all_stats, counts = _run_stats_cluster(tmp_path, words, threads=2)
    assert counts == {"apple": 60, "pear": 40, "plum": 20}

    for stats in all_stats:
        # data moved over the framed transport and was accounted for
        assert stats["transmissions"] > 0
        assert stats["frames_sent"] >= stats["transmissions"]
        assert stats["bytes_sent"] > 0 and stats["bytes_recv"] > 0
        assert stats["exchange_calls"] > 0
        # consensus piggybacked on the stream: many status rounds, but
        # allgather held to the run-boundary slots only
        assert stats["status_rounds"] >= 2
        assert stats["allgather_calls"] <= 3
        for key in ("pack_ms", "send_ms", "unpack_ms", "recv_wait_ms",
                    "status_wait_ms"):
            assert stats[key] >= 0.0


@pytest.fixture(scope="module")
def wire_runs(tmp_path_factory):
    """The same two-process wordcount over the columnar wire (``_K_FRAME``,
    the default) and over the row wire (``PATHWAY_DISABLE_COLUMNAR=1``,
    ``_K_UPDATES``): the exchange stats summed over both processes."""
    from pathway_tpu.internals import native

    if native.load() is None:
        pytest.skip("native extension unavailable (no g++?): no frames to ship")
    words = [f"a-rather-long-word-{i % 40:02d}" for i in range(20_000)]
    expected = {f"a-rather-long-word-{i:02d}": 500 for i in range(40)}
    runs = {}
    for wire, env in (("frame", {}), ("row", {"PATHWAY_DISABLE_COLUMNAR": "1"})):
        all_stats, counts = _run_stats_cluster(
            tmp_path_factory.mktemp(wire), words, threads=1, **env
        )
        assert counts == expected, wire
        runs[wire] = {
            key: sum(stats[key] for stats in all_stats)
            for key in ("strpool_hits", "strpool_misses", "bytes_sent", "transmissions")
        }
    return runs


@pytest.mark.parametrize("wire", ["frame", "row"])
def test_columnar_wire_engages_and_ships_fewer_bytes(wire_runs, wire):
    """``_K_FRAME`` really carries a cluster's updates (a silent fall-back
    to ``_K_UPDATES`` would pass every equivalence test): its string pool
    sees traffic and the same updates cost fewer bytes than on the row
    wire, where the pool sees none.  Counts from the exchange stats."""
    pool = wire_runs[wire]["strpool_hits"] + wire_runs[wire]["strpool_misses"]
    assert wire_runs[wire]["transmissions"] > 0
    if wire == "frame":
        assert pool > 0, wire_runs
        assert wire_runs["frame"]["bytes_sent"] < wire_runs["row"]["bytes_sent"], wire_runs
    else:
        assert pool == 0, wire_runs
