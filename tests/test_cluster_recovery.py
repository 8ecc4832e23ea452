"""Cluster fault tolerance (ISSUE 8): a seeded kill-a-worker drill must
recover automatically to sink output byte-identical to the fault-free
run; a dead peer must be *detected* within the liveness timeout instead
of hanging a ``recv`` forever; and link teardown must complete in
bounded time even with peers mid-conversation.

The drills go through ``testing.chaos.ClusterDrill``, the one place that
says what "recovered" means.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from pathway_tpu.testing.chaos import ClusterDrill, IndexDrill, chaos

_port_counter = [13000 + (os.getpid() % 500) * 16]


def next_port(n: int = 4) -> int:
    """A base port with `n` consecutive bindable ports (probed, so stray
    listeners from an earlier killed run can't collide)."""
    import socket

    while True:
        base = _port_counter[0]
        _port_counter[0] += n
        if _port_counter[0] > 60000:
            _port_counter[0] = 13000
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


# ---------------------------------------------------------------------------
# recovery drills


def _run_drill(tmp_path, processes: int, seed: int) -> dict:
    drill = ClusterDrill(str(tmp_path), seed=seed, processes=processes)
    report = drill.run()
    assert report["restarts"] >= 1, (
        f"chaos kill (rank {report['kill_rank']} at epoch "
        f"{report['kill_epoch']}) never triggered a restart: {report}"
    )
    assert report["ok"], f"cluster did not recover: {report['failures']}"
    assert report["identical"], (
        f"recovered sink output diverged from the fault-free run after "
        f"killing rank {report['kill_rank']} at epoch {report['kill_epoch']}:"
        f"\n fault-free: {report['baseline_output']!r}"
        f"\n recovered:  {report['recovered_output']!r}"
    )
    assert report["recovery_seconds"], "no recovery time recorded"
    return report


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [3, 11])
def test_kill_random_worker_2proc_output_identical(tmp_path, seed):
    """Property drill: kill a seeded-random rank at a seeded-random epoch
    on a 2-process cluster; the supervisor restarts the generation, the
    workers roll back to the last consistent checkpoint, and the final
    sink output must byte-match a fault-free run."""
    _run_drill(tmp_path, processes=2, seed=seed)


@pytest.mark.chaos
@pytest.mark.slow
def test_kill_random_worker_4proc_output_identical(tmp_path):
    """The same property at 4 workers — more ranks to kill, more peers
    whose sockets die mid-conversation, same byte-identical bar."""
    _run_drill(tmp_path, processes=4, seed=5)


@pytest.mark.chaos
def test_kill_worker_mid_merge_exactly_once(tmp_path):
    """Live-index churn drill (ISSUE 9): hard-kill the index-owning
    worker in the window between a finished background merge and its
    atomic commit.  The restarted worker restores the checkpointed
    (pre-merge) index and replays the tail; the recovered index must
    hold each doc exactly once — the lost merge dropped nothing, the
    replay double-applied nothing — and final query answers must reach
    recall >= 0.95 vs brute force over the post-churn corpus."""
    drill = IndexDrill(str(tmp_path), seed=7, processes=2)
    report = drill.run()
    assert report["restarts"] >= 1, (
        f"mid-merge kill never triggered a restart: {report}"
    )
    assert report["returncode"] == 0, (
        f"cluster did not recover: {report['failures']}"
    )
    assert report["exactly_once"], (
        f"recovered index holds {report['recovered_size']} docs, expected "
        f"{report['expected_size']} (lost or double-applied upserts): "
        f"{report}"
    )
    assert report["recall"] >= 0.95, (
        f"recovered recall {report['recall']:.3f} < 0.95 "
        f"(baseline {report['baseline_recall']:.3f}): {report}"
    )
    assert report["merges_total"] >= 1, report


# ---------------------------------------------------------------------------
# failure detection latency


def _link_pair(first_port: int, heartbeat_s: float, liveness_timeout_s: float):
    """Both ends of a 2-process TCP mesh, built in one process.  End 0
    blocks in its constructor waiting for end 1 to dial, so it goes on a
    thread."""
    from pathway_tpu.engine.cluster import _ProcessLinks

    out: dict[int, _ProcessLinks] = {}

    def build0() -> None:
        out[0] = _ProcessLinks(
            0,
            2,
            first_port,
            heartbeat_s=heartbeat_s,
            liveness_timeout_s=liveness_timeout_s,
        )

    t = threading.Thread(target=build0, daemon=True)
    t.start()
    out[1] = _ProcessLinks(
        1,
        2,
        first_port,
        heartbeat_s=heartbeat_s,
        liveness_timeout_s=liveness_timeout_s,
    )
    t.join(10.0)
    assert 0 in out, "mesh never completed"
    return out[0], out[1]


@pytest.mark.chaos
def test_muted_peer_detected_within_liveness_timeout():
    """Drop every transmission (heartbeats included) out of process 1;
    process 0 must declare the peer dead within the liveness timeout plus
    one io tick — not hang in ``recv`` forever.  The detector then closes
    its own sockets, so the muted side observes the EOF and fails too
    (socket-death detection, the fast path)."""
    liveness = 1.0
    links0, links1 = _link_pair(
        next_port(2), heartbeat_s=0.2, liveness_timeout_s=liveness
    )
    try:
        with chaos(seed=1) as c:
            c.drop_exchange_frames(after=0, process_id=1)
            t0 = time.monotonic()
            deadline = t0 + liveness + 3.0
            while links0._failed is None and time.monotonic() < deadline:
                time.sleep(0.02)
            detect_s = time.monotonic() - t0
            assert links0._failed is not None, (
                f"muted peer not detected after {detect_s:.1f}s"
            )
            assert "silent" in links0._failed or "lost" in links0._failed
            # bounded detection: liveness timeout + io tick + slack
            assert detect_s < liveness + 2.0, f"detection took {detect_s:.1f}s"
            # the failure must surface to a worker parked on the mailbox
            with pytest.raises(RuntimeError, match="cluster failure"):
                links0.recv_from_all(("never", 0))
            # ... and propagate to the muted side via socket death
            eof_deadline = time.monotonic() + 5.0
            while links1._failed is None and time.monotonic() < eof_deadline:
                time.sleep(0.02)
            assert links1._failed is not None, "peer EOF never detected"
    finally:
        links0.close()
        links1.close()


@pytest.mark.chaos
def test_idle_links_stay_alive_on_heartbeats():
    """The inverse guard: two healthy but completely idle links exchange
    only heartbeats and must NOT false-alarm past the liveness window."""
    liveness = 0.8
    links0, links1 = _link_pair(
        next_port(2), heartbeat_s=0.1, liveness_timeout_s=liveness
    )
    try:
        time.sleep(liveness * 2.5)
        assert links0._failed is None, links0._failed
        assert links1._failed is None, links1._failed
        with links0.stats_lock:
            sent = links0.stats["heartbeats_sent"]
        assert sent >= 1, "idle link never heartbeat"
    finally:
        links0.close()
        links1.close()


# ---------------------------------------------------------------------------
# bounded teardown


@pytest.mark.chaos
def test_close_is_bounded_with_live_peer():
    """``close()`` must return in bounded time — bounded sender joins,
    socket close to break parked reads, bounded re-join — even while the
    peer is still up and mid-heartbeat."""
    links0, links1 = _link_pair(
        next_port(2), heartbeat_s=0.1, liveness_timeout_s=5.0
    )
    links0.send_async(1, ("slot", 0), {"x": 1})  # traffic in flight
    t0 = time.monotonic()
    links0.close()
    links1.close()
    dt = time.monotonic() - t0
    assert dt < 8.0, f"teardown took {dt:.1f}s"
    for links in (links0, links1):
        for sender in links._senders.values():
            assert not sender.is_alive(), "sender thread survived close()"
        for reader in links._readers:
            reader.join(2.0)
            assert not reader.is_alive(), "reader thread survived close()"


# ---------------------------------------------------------------------------
# per-peer membership under the isolate fail policy (ISSUE 13)


def _isolate_link_pair(
    first_port: int,
    heartbeat_s: float = 0.1,
    liveness_timeout_s: float = 1.0,
):
    """2-process mesh with ``fail_policy='isolate'``: a peer's death
    quiesces only that peer's links instead of failing the whole mesh."""
    from pathway_tpu.engine.cluster import _ProcessLinks

    out: dict[int, "_ProcessLinks"] = {}

    def build0() -> None:
        out[0] = _ProcessLinks(
            0,
            2,
            first_port,
            heartbeat_s=heartbeat_s,
            liveness_timeout_s=liveness_timeout_s,
            fail_policy="isolate",
        )

    t = threading.Thread(target=build0, daemon=True)
    t.start()
    out[1] = _ProcessLinks(
        1,
        2,
        first_port,
        heartbeat_s=heartbeat_s,
        liveness_timeout_s=liveness_timeout_s,
        fail_policy="isolate",
    )
    t.join(10.0)
    assert 0 in out, "mesh never completed"
    return out[0], out[1]


def _wait_for(pred, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.02)


@pytest.mark.chaos
def test_isolate_peer_death_degrades_instead_of_failing():
    """One peer dies; the isolate-policy survivor marks ONLY that peer
    dead (``_failed`` stays None — the mesh is degraded, not down) and a
    collective over the survivors returns instead of raising."""
    from pathway_tpu.engine.cluster import PEER_DEAD

    links0, links1 = _isolate_link_pair(next_port(2))
    try:
        links1.close()  # rank 1 "dies": its sockets drop
        _wait_for(
            lambda: links0.peer_states().get(1) == PEER_DEAD,
            8.0,
            "survivor to declare peer 1 dead",
        )
        assert links0._failed is None, (
            f"isolate policy failed the whole mesh: {links0._failed}"
        )
        member = links0.membership()[1]
        assert member["state"] == PEER_DEAD and member["reason"]
        # a collective over zero live peers degrades to the empty answer
        assert links0.recv_from_all(("epoch", 0)) == {}
        assert links0.stats["peers_declared_dead"] == 1
    finally:
        links0.close()


@pytest.mark.chaos
def test_isolate_rejoin_with_bumped_incarnation():
    """A replacement rank dialing with a bumped incarnation is admitted
    by the survivor (generation handshake), after which both directions
    of the link carry traffic again and the membership view heals."""
    from pathway_tpu.engine.cluster import PEER_ALIVE, PEER_DEAD, _ProcessLinks

    first_port = next_port(2)
    links0, links1 = _isolate_link_pair(first_port)
    replacement = None
    try:
        links1.close()
        _wait_for(
            lambda: links0.peer_states().get(1) == PEER_DEAD,
            8.0,
            "survivor to declare peer 1 dead",
        )
        # in-process rebind gotcha: the dead listener's fd lingers until
        # its 1s accept timeout elapses (a real dead rank is a separate
        # process whose fds close on exit), so give the port time to free
        time.sleep(1.3)
        for attempt in range(10):
            try:
                replacement = _ProcessLinks(
                    1,
                    2,
                    first_port,
                    heartbeat_s=0.1,
                    liveness_timeout_s=1.0,
                    fail_policy="isolate",
                    incarnation=1,
                )
                break
            except OSError:
                time.sleep(0.5)
        assert replacement is not None, "replacement never bound its port"
        _wait_for(
            lambda: links0.peer_states().get(1) == PEER_ALIVE,
            8.0,
            "survivor to admit the rejoining rank",
        )
        assert links0.membership()[1]["incarnation"] == 1
        assert links0.stats["peers_rejoined"] == 1
        # traffic flows both ways across the healed link
        links0.send_async(1, ("x", 0), {"hello": 0})
        replacement.send_async(0, ("x", 0), {"hello": 1})
        got0 = links0.recv_from_all(("x", 0))
        got1 = replacement.recv_from_all(("x", 0))
        assert got0 == {1: {"hello": 1}} and got1 == {0: {"hello": 0}}
    finally:
        links0.close()
        if replacement is not None:
            replacement.close()


@pytest.mark.chaos
def test_asymmetric_partition_is_detected_not_hung():
    """Gray failure: ONE direction of one link goes dark (1 -> 0 frames
    dropped, 0 -> 1 perfect).  The starved side must still classify the
    silent peer dead within the liveness window — and under the isolate
    policy neither side fails its whole mesh."""
    from pathway_tpu.engine.cluster import PEER_DEAD

    liveness = 1.0
    links0, links1 = _isolate_link_pair(
        next_port(2), heartbeat_s=0.2, liveness_timeout_s=liveness
    )
    try:
        with chaos(seed=5) as c:
            c.asymmetric_partition(1, 0, mode="drop")
            t0 = time.monotonic()
            _wait_for(
                lambda: links0.peer_states().get(1) == PEER_DEAD,
                liveness + 4.0,
                "starved side to declare the silent peer dead",
            )
            detect_s = time.monotonic() - t0
            assert detect_s < liveness + 2.0, (
                f"one-way partition detection took {detect_s:.1f}s"
            )
            assert links0._failed is None and links1._failed is None
    finally:
        links0.close()
        links1.close()


@pytest.mark.chaos
def test_slow_peer_degrades_but_stays_alive():
    """A slowed (but alive) rank keeps making its liveness deadlines:
    seeded per-frame delay below the suspect threshold must not get the
    peer declared dead, and its frames still arrive."""
    from pathway_tpu.engine.cluster import PEER_DEAD

    links0, links1 = _isolate_link_pair(
        next_port(2), heartbeat_s=0.1, liveness_timeout_s=2.0
    )
    try:
        with chaos(seed=9) as c:
            c.slow_peer(1, delay_s=0.05, jitter_s=0.02)
            links1.send_async(0, ("y", 0), {"v": 42})
            got = links0.recv_from_all(("y", 0))
            assert got == {1: {"v": 42}}
            time.sleep(0.5)  # several heartbeat intervals under the delay
            assert links0.peer_states().get(1) != PEER_DEAD
            assert links0._failed is None
    finally:
        links0.close()
        links1.close()


# ---------------------------------------------------------------------------
# flight-recorder dumps under chaos (ISSUE 14)


def _load_merged_trace(report: dict) -> list[dict]:
    import json

    trace_file = report["trace_file"]
    assert trace_file and os.path.exists(trace_file), (
        f"no merged flight-recorder dump: {report}"
    )
    with open(trace_file) as f:
        return json.load(f)["traceEvents"]


@pytest.mark.chaos
def test_kill_worker_flight_recorder_stitches_all_ranks(tmp_path):
    """A traced 2-proc kill drill must leave ONE merged Chrome-trace
    file holding spans from every rank — including the killed one (the
    chaos kill flushes the ring before ``os._exit``) — with epoch traces
    stitched across processes on the shared monotonic timebase and
    exchange spans naming both sides (src + dst)."""
    from pathway_tpu.analysis import tracecrit

    drill = ClusterDrill(str(tmp_path), seed=3, processes=2, trace=True)
    report = drill.run()
    assert report["restarts"] >= 1, report
    assert report["ok"], f"cluster did not recover: {report['failures']}"
    events = _load_merged_trace(report)
    ranks = {int(e.get("pid", -1)) for e in events}
    assert ranks == {0, 1}, f"merged dump missing ranks: {sorted(ranks)}"
    assert report["kill_rank"] in ranks
    assert sorted(report["trace_ranks"]) == [0, 1]
    # cross-process stitch: at least one epoch trace carries spans
    # recorded by BOTH ranks under one trace id, and its parent chain
    # resolves (no orphaned fragments)
    traces = tracecrit.group_traces(events)
    multi = [
        tid for tid, spans in traces.items()
        if len({s.get("pid") for s in spans}) >= 2
    ]
    assert multi, "no trace stitched spans from more than one rank"
    conn = tracecrit.connected_traces(events)
    assert any(conn[tid] for tid in multi), (
        "every cross-rank trace has orphaned parents"
    )
    exch = [
        e for e in events
        if e["name"] in ("pack", "unpack", "exchange_recv", "status_wait_peer")
    ]
    assert exch, "no exchange spans survived into the dump"
    for e in exch:
        assert {"src", "dst"} <= set(e["args"]), e


@pytest.mark.chaos
def test_kill_worker_mid_merge_flight_recorder_dump(tmp_path):
    """The mid-merge kill drill (ISSUE 9 harness) with tracing on: the
    merged dump must exist and hold spans from every rank including the
    one hard-killed inside the merge-commit window."""
    drill = IndexDrill(str(tmp_path), seed=7, processes=2, trace=True)
    report = drill.run()
    assert report["restarts"] >= 1, report
    assert report["returncode"] == 0, report["failures"]
    events = _load_merged_trace(report)
    ranks = {int(e.get("pid", -1)) for e in events}
    assert ranks == {0, 1}, f"merged dump missing ranks: {sorted(ranks)}"
    assert drill.kill_rank in ranks
    # the dump is usable for attribution: spans have positive-duration
    # complete events with span identity in args
    assert all(e.get("ph") == "X" for e in events)
    assert all("span_id" in e.get("args", {}) for e in events)
