"""Overload drills: end-to-end backpressure and brownout shedding.

The pressure chain under test (ISSUE 16): a firehose source charges the
bytes-accounted ingest buffer (``PATHWAY_INGEST_BUFFER_BYTES``) and its
reader pauses/sheds/fails per ``on_overflow``; a slow-but-alive exchange
peer throttles producers through sender-side credit
(``PATHWAY_EXCHANGE_CREDIT_BYTES``) instead of being isolated; a stalled
sink holds the epoch cut so pressure propagates back to the sources; and
serving brownout sheds best-effort classes first while interactive
traffic keeps flowing.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import tracecrit
from pathway_tpu.engine.scheduler import (
    IngestCredit,
    IngestOverflow,
)
from pathway_tpu.internals import native, tracing
from pathway_tpu.testing.chaos import chaos

# ---------------------------------------------------------------------------
# ingest credit accounting (unit)


def test_ingest_credit_charge_consume_roundtrip():
    credit = IngestCredit(1000)
    s0 = credit.charge(7, 300, 2, "pause", None)
    s1 = credit.charge(7, 300, 1, "pause", None)
    assert (s0, s1) == (0, 1)
    t = credit.totals()
    assert t["buffered_bytes"] == 600
    assert t["buffered_rows"] == 3
    assert 0.0 < t["level"] <= 1.0
    assert credit.consume(7, s0) is True
    assert credit.consume(7, s1) is True
    t = credit.totals()
    assert t["buffered_bytes"] == 0
    assert t["buffered_rows"] == 0


def test_ingest_credit_always_admits_when_empty():
    # one oversized item passes an empty buffer: the cap bounds
    # *accumulation*, not item size — otherwise a single wide batch
    # could never be ingested at all
    credit = IngestCredit(100)
    seq = credit.charge(1, 5000, 1, "pause", None)
    assert credit.consume(1, seq) is True


def test_ingest_credit_shed_oldest_advances_floor():
    credit = IngestCredit(1000)
    s0 = credit.charge(1, 600, 3, "shed_oldest", None)
    # second charge overflows: the source's oldest buffered item is shed
    s1 = credit.charge(1, 600, 2, "shed_oldest", None)
    assert credit.consume(1, s0) is False, "shed item must be discarded"
    assert credit.consume(1, s1) is True
    snap = credit.snapshot()[1]
    assert snap["shed_rows"] == 3
    assert snap["shed_bytes"] == 600
    assert credit.totals()["buffered_bytes"] == 0


def test_ingest_credit_shed_only_touches_own_source():
    credit = IngestCredit(1000)
    other = credit.charge(2, 900, 1, "pause", None)
    # source 1 has nothing buffered to shed: it is admitted over-cap
    # rather than shedding source 2's data or deadlocking
    mine = credit.charge(1, 500, 1, "shed_oldest", None)
    assert credit.consume(2, other) is True
    assert credit.consume(1, mine) is True
    assert credit.snapshot().get(2, {}).get("shed_rows", 0) == 0


def test_ingest_credit_fail_mode_raises():
    credit = IngestCredit(100)
    credit.charge(1, 80, 1, "fail", None)
    with pytest.raises(IngestOverflow, match="PATHWAY_INGEST_BUFFER_BYTES"):
        credit.charge(1, 80, 1, "fail", None)


def test_ingest_credit_pause_blocks_until_consume():
    credit = IngestCredit(1000)
    s0 = credit.charge(1, 800, 1, "pause", None)
    stats: dict = {}
    admitted = threading.Event()

    def producer() -> None:
        credit.charge(1, 800, 1, "pause", None, stats)
        admitted.set()

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    assert not admitted.wait(0.2), "charge admitted past a full buffer"
    assert stats.get("paused") is True, "paused flag not raised while parked"
    assert credit.totals()["paused_sources"] == 1
    credit.consume(1, s0)  # drain frees room -> reader wakes
    assert admitted.wait(5.0), "consume never released the paused reader"
    t.join(5.0)
    assert stats.get("paused") is False
    assert stats.get("pauses", 0) >= 1
    assert credit.stalls_total >= 1
    assert credit.stall_ms_total > 0


def test_ingest_credit_pause_released_by_stop_event():
    credit = IngestCredit(100)
    credit.charge(1, 90, 1, "pause", None)
    stop = threading.Event()
    done = threading.Event()

    def producer() -> None:
        credit.charge(1, 90, 1, "pause", stop)
        done.set()

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    assert not done.wait(0.2)
    stop.set()  # shutdown must interrupt a paused reader
    assert done.wait(5.0), "stop event never released the paused reader"
    t.join(5.0)


# ---------------------------------------------------------------------------
# firehose -> ingest buffer -> drain (end to end, single process)


class _CountSchema(pw.Schema):
    word: str
    payload: str


def _firehose_pipeline(c: chaos, total_rows: int, on_overflow: str):
    src = c.firehose_source(
        None, total_rows, vocab=8, payload_bytes=64, commit_every=50
    )
    t = pw.io.python.read(src, schema=_CountSchema, on_overflow=on_overflow)
    return t.groupby(t.word).reduce(t.word, n=pw.reducers.count())


def _run_and_collect(table: pw.Table, tmp_path) -> dict[str, int]:
    import json

    out = tmp_path / "out.jsonl"
    pw.io.jsonlines.write(table, str(out))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    state: dict[str, int] = {}
    with open(out) as f:
        for line in f:
            if not line.strip():
                continue
            row = json.loads(line)
            if row["diff"] > 0:
                state[row["word"]] = row["n"]
            elif state.get(row["word"]) == row["n"]:
                del state[row["word"]]
    return state


def test_firehose_pause_is_lossless(tmp_path, monkeypatch):
    """An unpaced firehose into a small ingest buffer: the reader must
    pause (bounded memory) and every row must still arrive — pause mode
    trades latency for zero loss."""
    monkeypatch.setenv("PATHWAY_INGEST_BUFFER_BYTES", "16384")
    total = 1200
    pw.G.clear()
    with chaos(seed=11) as c:
        counts = _run_and_collect(
            _firehose_pipeline(c, total, "pause"), tmp_path
        )
    sched = pw.G.active_scheduler
    totals = sched.ingest_credit.totals()
    assert sum(counts.values()) == total, (
        f"rows lost under pause backpressure: {counts} (totals {totals})"
    )
    assert totals["stalls_total"] >= 1, (
        f"firehose never hit the buffer cap — not an overload run: {totals}"
    )
    assert totals["shed_rows_total"] == 0
    assert totals["buffered_bytes"] == 0, "drain left charged bytes behind"
    pressure = sched.ingest_pressure()
    assert "python" in pressure["sources"], pressure


def test_firehose_shed_oldest_accounts_every_row(tmp_path, monkeypatch):
    """Under shed_oldest nothing is *silently* lost: rows that arrive
    plus rows counted shed must equal rows produced."""
    monkeypatch.setenv("PATHWAY_INGEST_BUFFER_BYTES", "8192")
    total = 1500
    pw.G.clear()
    with chaos(seed=12) as c:
        # stall the sink briefly so the drain genuinely falls behind the
        # unpaced producer and the shed path actually fires
        c.stall_sink(0.05, limit=8)
        counts = _run_and_collect(
            _firehose_pipeline(c, total, "shed_oldest"), tmp_path
        )
    totals = pw.G.active_scheduler.ingest_credit.totals()
    arrived = sum(counts.values())
    assert arrived + totals["shed_rows_total"] == total, (
        f"{arrived} arrived + {totals['shed_rows_total']} shed != {total}"
    )
    assert totals["shed_rows_total"] >= 1, (
        f"overload never triggered shedding: {totals}"
    )
    assert totals["stalls_total"] == 0, "shed_oldest must not pause"


def test_stalled_sink_backpressures_to_source(tmp_path, monkeypatch):
    """A wedged sink writer holds the epoch cut (sinks are synchronous),
    the drain stops taking, the buffer fills, and the *reader* pauses —
    pressure propagates the whole way back with no loss."""
    monkeypatch.setenv("PATHWAY_INGEST_BUFFER_BYTES", "8192")
    total = 800
    pw.G.clear()
    with chaos(seed=13) as c:
        c.stall_sink(0.1, limit=6)
        counts = _run_and_collect(
            _firehose_pipeline(c, total, "pause"), tmp_path
        )
    totals = pw.G.active_scheduler.ingest_credit.totals()
    assert sum(counts.values()) == total, (
        f"rows lost behind a stalled sink: {counts}"
    )
    assert totals["stalls_total"] >= 1, (
        f"stalled sink never propagated to the reader: {totals}"
    )


def test_slow_consumer_rank_is_correct_and_complete(tmp_path):
    """slow_consumer drags a rank's epochs without breaking it: the run
    completes with exact results (degraded, never isolated)."""
    pw.G.clear()
    with chaos(seed=14) as c:
        c.slow_consumer(0, factor=1.5)
        counts = _run_and_collect(
            _firehose_pipeline(c, 400, "pause"), tmp_path
        )
        from pathway_tpu.engine.scheduler import Scheduler

        assert c.call_count(Scheduler, "run_epoch") >= 1
    assert sum(counts.values()) == 400


# ---------------------------------------------------------------------------
# exchange credit: slow-but-alive peers throttle, dead peers release

_port_counter = [17000 + (os.getpid() % 500) * 16]


def _next_port(n: int = 4) -> int:
    import socket

    while True:
        base = _port_counter[0]
        _port_counter[0] += n
        if _port_counter[0] > 60000:
            _port_counter[0] = 17000
        try:
            socks = []
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            for s in socks:
                s.close()
            return base
        except OSError:
            for s in socks:
                s.close()


def _link_pair(first_port: int):
    """Both ends of a 2-process TCP mesh built in one process (end 0
    blocks in its constructor, so it goes on a thread)."""
    from pathway_tpu.engine.cluster import _ProcessLinks

    out: dict[int, _ProcessLinks] = {}

    def build0() -> None:
        out[0] = _ProcessLinks(
            0, 2, first_port, heartbeat_s=0.1, liveness_timeout_s=5.0
        )

    t = threading.Thread(target=build0, daemon=True)
    t.start()
    out[1] = _ProcessLinks(
        1, 2, first_port, heartbeat_s=0.1, liveness_timeout_s=5.0
    )
    t.join(10.0)
    assert 0 in out, "mesh never completed"
    return out[0], out[1]


def _boxes(n_updates: int) -> list:
    # boxes[src_tid][dst_tid] of (int_key, values, diff) updates
    return [[[(i, ("v" * 40,), 1) for i in range(n_updates)]]]


@pytest.mark.chaos
def test_exchange_credit_throttles_slow_but_alive_peer(monkeypatch):
    """A peer that receives but does not consume parks the producer at
    the credit cap (bounded backlog, credit_stalls recorded) WITHOUT
    being isolated; consuming drains the window and the producer
    finishes."""
    monkeypatch.setenv("PATHWAY_EXCHANGE_CREDIT_BYTES", "8192")
    # loaded before the link threads race for it: a thread that asks
    # while another is mid-load is told there is no native module
    # (ROADMAP, Design: `internals/native.py` `load`)
    native.load()
    links0, links1 = _link_pair(_next_port(2))
    n_frames = 6
    try:
        sent = []
        t_mark = time.monotonic_ns()

        def producer() -> None:
            with tracing.use(tracing.new_trace(sampled=True)):
                for i in range(n_frames):
                    links0.send_updates_async(1, ("s", i), _boxes(60))
                    sent.append(i)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with links0.stats_lock:
                stalls = links0.stats["credit_stalls"]
            if stalls >= 1:
                break
            time.sleep(0.02)
        assert stalls >= 1, "producer never parked on the credit window"
        assert len(sent) < n_frames, "all frames sent without any throttle"
        # slow, not dead: bounded backlog, no isolation, no failure
        pressure = links0.exchange_pressure()
        assert pressure["peers"][1]["state"] == "alive", pressure
        assert pressure["peers"][1]["backlog_bytes"] <= 2 * 8192, (
            f"backlog exceeded the credit window: {pressure}"
        )
        assert links0._failed is None
        # the consumer drains -> grants flow back -> producer completes
        for i in range(n_frames):
            got = links1.recv_from_all(("s", i))
            assert 0 in got
        t.join(10.0)
        assert not t.is_alive(), "producer still parked after full drain"
        assert len(sent) == n_frames
        assert links0.pressure_level() >= 0.0
        with links0.stats_lock:
            assert links0.stats["credit_stall_ms"] > 0
        # the stall is on the producer's trace, where the attribution
        # files it under the exchange
        waits = [
            e
            for e in tracing.chrome_events(since_ns=t_mark, all_spans=True)
            if e["name"] == "credit_wait"
        ]
        assert waits and all(e["args"]["dst"] == 1 for e in waits)
        assert tracecrit.categorize("credit_wait") == "exchange"
    finally:
        links0.close()
        links1.close()


@pytest.mark.chaos
def test_exchange_credit_oversized_frame_passes_empty_window(monkeypatch):
    """One frame larger than the whole window must still transit when
    the window is empty — credit bounds accumulation, not frame size."""
    monkeypatch.setenv("PATHWAY_EXCHANGE_CREDIT_BYTES", "512")
    links0, links1 = _link_pair(_next_port(2))
    try:
        links0.send_updates_async(1, ("big", 0), _boxes(200))
        got = links1.recv_from_all(("big", 0))
        assert 0 in got
    finally:
        links0.close()
        links1.close()


@pytest.mark.chaos
def test_credit_waiter_released_by_link_failure(monkeypatch):
    """DEAD releases where SLOW parks: a producer parked on the credit
    window must escape promptly when the link fails rather than waiting
    for grants that will never come."""
    monkeypatch.setenv("PATHWAY_EXCHANGE_CREDIT_BYTES", "4096")
    links0, links1 = _link_pair(_next_port(2))
    try:
        released = threading.Event()

        def producer() -> None:
            for i in range(8):
                links0.send_updates_async(1, ("d", i), _boxes(60))
            released.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with links0.stats_lock:
                if links0.stats["credit_stalls"] >= 1:
                    break
            time.sleep(0.02)
        assert not released.is_set(), "producer never throttled"
        links1.close()  # peer death: socket EOF fails the link
        assert released.wait(10.0), (
            "producer stayed parked on a dead peer's credit window"
        )
        t.join(5.0)
    finally:
        links0.close()
        links1.close()


@pytest.mark.chaos
def test_close_drops_backlog_of_suspect_peer():
    """Regression (ISSUE 16 satellite): ``close()`` with a backlogged
    mailbox for a non-ALIVE peer must DROP the backlog, not drain it into
    a possibly-stalled socket — teardown stays bounded."""
    from pathway_tpu.engine.cluster import PEER_SUSPECT, _K_OBJ

    links0, links1 = _link_pair(_next_port(2))
    try:
        sender = links0._senders[1]
        gate = threading.Event()
        orig_transmit = sender._transmit
        data_frames_sent = []

        def blocking_transmit(body, n_frames):
            if n_frames:
                data_frames_sent.append(n_frames)
                gate.wait(10.0)  # wedge: a stalled sendall
            return orig_transmit(body, n_frames)

        sender._transmit = blocking_transmit
        sender.enqueue(("a", 0), _K_OBJ, {"x": 1})
        # wait for the sender to take frame A into the wedged transmit
        deadline = time.monotonic() + 5.0
        while not data_frames_sent and time.monotonic() < deadline:
            time.sleep(0.01)
        assert data_frames_sent, "sender never picked up the first frame"
        # B and C pile up behind the wedged transmission
        sender.enqueue(("b", 0), _K_OBJ, {"x": 2})
        sender.enqueue(("c", 0), _K_OBJ, {"x": 3})
        with links0._cv:
            links0._peer_state[1] = PEER_SUSPECT
        closer = threading.Thread(target=links0.close, daemon=True)
        closer.start()
        gate.set()  # release the wedge; the drop branch must fire
        closer.join(10.0)
        assert not closer.is_alive(), "close() hung behind the backlog"
        with links0.stats_lock:
            dropped = links0.stats["frames_dropped_on_close"]
        assert dropped >= 2, (
            f"suspect peer's backlog was drained, not dropped ({dropped})"
        )
        # only the first (pre-suspect) transmission carried data frames
        assert len(data_frames_sent) == 1, data_frames_sent
    finally:
        links0.close()
        links1.close()


# ---------------------------------------------------------------------------
# serving brownout: shed batch first, hold interactive


def _controller(clock):
    from pathway_tpu.serving.admission import AdmissionController, TenantPolicy

    return AdmissionController(
        {
            "live": TenantPolicy("interactive", rate_per_s=100, queue_cap=64),
            "bulk": TenantPolicy("batch", rate_per_s=100, queue_cap=64),
        },
        clock=clock,
    )


def test_brownout_sheds_batch_before_interactive():
    from pathway_tpu.io.http import RetryLater

    t = [0.0]
    ac = _controller(lambda: t[0])
    ac.set_pressure("engine", 0.6)

    live_ok = bulk_shed = 0
    retry_afters = []
    for _ in range(10):
        t[0] += 0.01
        ac.admit("live").release()  # interactive holds under brownout
        live_ok += 1
        try:
            ac.admit("bulk").release()
        except RetryLater as e:
            bulk_shed += 1
            retry_afters.append(e.retry_after)
    assert live_ok == 10
    assert bulk_shed >= 8, f"batch class not shed under pressure ({bulk_shed})"
    assert all(ra > 0 for ra in retry_afters), retry_afters
    stats = ac.stats()
    assert stats["pressure"]["level"] == pytest.approx(0.6)
    assert stats["pressure"]["brownout_shed_total"].get("batch", 0) >= 8
    assert stats["pressure"]["brownout_shed_total"].get("interactive", 0) == 0


def test_brownout_recovers_when_pressure_clears():
    t = [0.0]
    ac = _controller(lambda: t[0])
    ac.set_pressure("engine", 0.9)
    assert ac.try_admit("bulk") is None, "full brownout admitted batch"
    ac.set_pressure("engine", 0.0)  # pressure released: buckets re-arm
    t[0] += 0.1
    ticket = ac.try_admit("bulk")
    assert ticket is not None, "brownout outlived the pressure signal"
    ticket.release()
    assert ac.stats()["pressure"]["level"] == 0.0


def test_push_pressure_fans_out_to_live_controllers():
    from pathway_tpu import serving

    t = [0.0]
    ac = _controller(lambda: t[0])
    serving.push_pressure("engine", 0.7)
    assert ac.pressure_level() == pytest.approx(0.7)
    serving.push_pressure("engine", 0.0)
    assert ac.pressure_level() == 0.0


def test_slo_scheduler_pressure_stretches_light_classes():
    from pathway_tpu.serving import SloScheduler

    sched = SloScheduler()
    sched.set_pressure(0.8)
    assert sched.stats()["pressure"] == pytest.approx(0.8)
    sched.set_pressure(0.0)
    assert sched.stats()["pressure"] == 0.0
