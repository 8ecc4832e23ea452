"""Low-overhead distributed tracing with an always-on flight recorder.

Every span is one tuple appended into a **per-thread fixed-size ring
buffer** — the record path is a tuple build plus a list-slot assignment
and an index increment, with **no locks, no allocation beyond the tuple,
no syscalls** (``scripts/check_locks.py`` lints this file; the LK007
whole-repo lock graph must stay cycle-free and the locks here are two
leaves: the ring registry mutex, taken once per thread at ring creation
and on the dump path, and the chip account's, taken once a device
enqueue and once a wait — never per record).

Record layout (one tuple per span)::

    (trace_id, span_id, parent_id, stage, rank, t0_ns, t1_ns, sampled, args)

``t0_ns``/``t1_ns`` are ``time.monotonic_ns()`` — on Linux
CLOCK_MONOTONIC is machine-wide, so spans recorded by *different
processes on one host* share a timebase and stitch into one causal
timeline without clock translation (the 2-proc chaos drills rely on
this).  It is the one clock of the measured path: the native
``monotonic_ns`` behind ``LatencyProbe.now_ns`` (``steady_clock``) and
the benchmark harness's ``time.monotonic()`` read it too
(``tests/test_tracing.py`` holds the first two within a millisecond).
The harness writes a ``bench_mark`` annotation into its profile at a
CLOCK_MONOTONIC instant it records (``benchmark/trace.py``), so a span
lies on the profile's clock at
``profile_ns = span_ns + (bench_mark.start_ns - mark_mono_ns)``.
While a ``jax.profiler`` session runs, every :func:`span` block also
enters a ``jax.profiler.TraceAnnotation`` of its stage name, so the
stages lie in the ``.xplane.pb``'s host plane over the device lines
with no translation (a TraceMe costs 0.4 us outside a session; jax is
looked up in ``sys.modules``, never imported from here).

**Stage totals.**  Every recorded span also adds its duration to a
per-stage ``[count, total_ns, idle_ns]`` on the recording thread's ring
(no lock, no further clock read); :func:`stage_totals` and
:func:`stage_idle` sum them over rings.  They never wrap with the ring,
grow for the life of the process and stay zero under
``PATHWAY_TRACE=0``: what ``device_counters.snapshot()`` carries to the
benchmark as ``span_ns.<stage>`` / ``span_count.<stage>`` /
``span_idle_ns.<stage>``.

**The chip account** (:class:`ChipAccount`, one a process: :data:`chip`).
The program cannot see the chip's timeline, but it knows where it hands
the chip work it will wait on and where the wait returns: each such
enqueue takes a ticket (``chip.ticket()``) and each wait that returns
marks its ticket collected (``chip.collected(ticket)``).  A device runs its
programs in the order they were enqueued, so collecting ticket *t* says
every ticket up to *t* is done, and the chip has work while the highest
ticket taken is above the highest collected.  The account keeps the
cumulative time with no ticket outstanding and a ring of its last
transitions, so every span adds to its stage the idle time between its
two ends (``idle_ns``: the chip waited on the host while the stage ran).
A ``span`` block reads the account's current state at enter and exit, an
O(1) read; a span recorded after the fact with a past ``t0``
(``record_span``) looks its ends up in the transition ring.

**The stall watchdog** (:class:`StallWatchdog`, one daemon thread a
process, started with the first ring): it sleeps a 20 ms tick and, when
it wakes more than 100 ms late, records a ``process_stall`` span from the
wake it expected to the one it got, with the process's CPU time, the
machine's steal time and the major faults over it and the stage each
other thread had open (``ring.open``).  A stall with ``cpu_ms`` about its
length is a thread holding the GIL computing; with neither CPU nor steal,
the process was stopped or paged.

Sampling: the ring is **always on** (that is what makes it a flight
recorder — the last ``ring_size`` spans per thread are always there for
a post-mortem dump), so head sampling governs *export*, not recording:

- ``PATHWAY_TRACE_SAMPLE`` (0..1, default 1.0) — fraction of new traces
  marked ``sampled``; only sampled traces appear in on-demand exports
  (``/debug/trace``, ``chrome_events()``) unless ``all_spans=True``.
- ``PATHWAY_TRACE_TAIL_MS`` (default 250) — a request whose end-to-end
  latency exceeds this is **always kept**: :func:`finish_request` adds
  its trace id to a bounded tail-keep ring, resurrecting the trace in
  exports even when head sampling skipped it.  Slow requests are the
  ones worth attributing; the knob guarantees they survive sampling.

Other knobs: ``PATHWAY_TRACE=0`` disables recording entirely,
``PATHWAY_TRACE_RING`` sizes the per-thread ring (default 4096
spans), and ``PATHWAY_TRACE_DIR`` names
the flight-recorder spool: when set, :func:`flush` writes
``trace-r{rank}-*.json`` Chrome-trace files there (and an atexit hook
flushes on clean process exit).  Dump triggers wired elsewhere:
liveness trips (``engine/cluster.py`` ``_fail``/``_fail_peer``), chaos
kills (``testing/chaos.py`` flushes before ``os._exit``), supervisor
restarts (``internals/resilience.py`` merges the per-rank spool into
``merged_trace.json``), SIGUSR2 (:func:`install_sigusr2` — also dumps
all Python thread stacks), and ``/debug/trace?seconds=N`` on the
monitoring server.

Context propagation is ambient: :func:`use` pins a
:class:`TraceContext` to the current thread, :func:`span` opens a child
span under it (re-parenting nested spans), and the serving/cluster
layers carry contexts across thread and process hops explicitly —
serving requests on the request object, cluster epochs piggybacked on
the round-status exchange frames (``Cluster.round_statuses``).
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time
import traceback
from typing import Any, Callable, Iterator

__all__ = [
    "ChipAccount",
    "StallWatchdog",
    "TraceContext",
    "chrome_events",
    "configure",
    "current",
    "current_rank",
    "dump",
    "dump_stacks",
    "chip",
    "enabled",
    "finish_request",
    "flush",
    "install_sigusr2",
    "merge_trace_dir",
    "new_trace",
    "now_ns",
    "record_span",
    "record_spans",
    "reset",
    "set_ambient",
    "set_rank",
    "span",
    "stage_idle",
    "stage_totals",
    "stall_totals",
    "use",
]

_monotonic_ns = time.monotonic_ns

#: the span clock (machine-wide monotonic, so spans from different
#: processes on one host line up without translation)
now_ns = time.monotonic_ns

#: tail-keep ring capacity (trace ids of slow requests kept past sampling)
_KEPT_CAP = 4096


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class _Config:
    __slots__ = ("on", "sample", "tail_ns", "ring_size", "spool_dir")

    def __init__(self) -> None:
        self.reload()

    def reload(self) -> None:
        self.on = os.environ.get("PATHWAY_TRACE", "1") != "0"
        self.sample = min(1.0, max(0.0, _env_float("PATHWAY_TRACE_SAMPLE", 1.0)))
        self.tail_ns = int(_env_float("PATHWAY_TRACE_TAIL_MS", 250.0) * 1e6)
        self.ring_size = max(64, _env_int("PATHWAY_TRACE_RING", 4096))
        self.spool_dir = os.environ.get("PATHWAY_TRACE_DIR") or None


_cfg = _Config()

#: process rank stamped into every span (supervised workers inherit it
#: from the spawn env; in-process tests may override via set_rank)
_rank = _env_int("PATHWAY_PROCESS_ID", 0)

#: leaf lock: ring registration + dump/flush serialization only — NEVER
#: on the record path, and nothing is acquired while it is held
_registry_mutex = threading.Lock()
_rings: list["_Ring"] = []

#: bounded tail-keep ring: trace ids of requests over the tail threshold
#: (preallocated; racy slot assignment loses at most one id — benign)
_kept: list[int] = [0] * _KEPT_CAP
_kept_idx = 0

_atexit_installed = False


class _Ring:
    """One thread's span ring: preallocated slots, lock-free append."""

    __slots__ = ("buf", "idx", "cap", "thread_name", "id_next", "totals", "open")

    def __init__(self, cap: int, thread_name: str, id_seed: int):
        self.cap = cap
        self.buf: list[Any] = [None] * cap
        self.idx = 0
        self.thread_name = thread_name
        self.id_next = id_seed
        #: stage -> [count, total_ns, idle_ns]; written by the owning thread only
        self.totals: dict[str, list[int]] = {}
        #: the innermost ``span`` block open on the owning thread (the watchdog reads it)
        self.open: str | None = None

    def snapshot(self) -> list[tuple]:
        """Copy the live records in append order (dump path; the copy is
        a single C-level list() under the GIL, racing appends at worst
        tear the oldest slot, which is dropped by the None filter)."""
        buf = list(self.buf)
        i = self.idx
        if i <= self.cap:
            out = buf[:i]
        else:
            head = i % self.cap
            out = buf[head:] + buf[:head]
        return [r for r in out if r is not None]


class _Tls(threading.local):
    ring: "_Ring | None" = None
    ctx: "TraceContext | None" = None


_tls = _Tls()


# -------------------------------------------------------- the chip account

#: transitions the chip account keeps for looking up past idle time (two a
#: request in the retrieve cell: about 30 s of it)
_CHIP_RING = 4096


class ChipAccount:
    """When the chip has work of the program's, from the host's side.

    The ticket sites are the enqueues the program later waits on:
    ``JittedEncoder._dispatch`` (when a readback follows),
    ``ShardedKnnIndex.dispatch`` and each prefill chunk and decode step of
    ``JittedDecoder.generate``; the waits that collect are
    ``JittedEncoder._readback``, ``ShardedKnnIndex.collect`` and the two
    blocks of ``generate``.  Fire-and-forget enqueues take no ticket and
    count as idle: the slab's scatters (``slab_scatter``), the encoder's
    dispatches under ``encode_into``, uploads and eager ops; the error is
    at most their device time, a few ms an epoch.  A readback that finds
    the work long done counts the chip busy until it returns.  A ticket
    whose wait never comes (an exception in between) is cleared by the
    next collect of a later one.

    ``state`` is one immutable tuple ``(t_ns, idle_ns, idle)``: the instant
    of the last transition, the idle time accumulated before it, and
    whether the chip has been idle since; readers take it in one load and
    need no lock.  ``ring`` holds the last :data:`_CHIP_RING` states."""

    __slots__ = ("clock", "lock", "enq", "col", "state", "ring", "idx", "t_start")

    def __init__(self, clock: Callable[[], int] | None = None):
        self.clock = clock or time.monotonic_ns
        #: leaf lock: ticket and collect only, nothing acquired under it
        self.lock = threading.Lock()
        self.restart()

    def restart(self) -> None:
        """Start the account anew, idle from now (process start; tests)."""
        with self.lock:
            t = self.clock()
            self.t_start = t
            self.enq = self.col = 0
            self.state = (t, 0, True)
            self.ring: list[Any] = [None] * _CHIP_RING
            self.ring[0] = self.state
            self.idx = 1

    def _turn(self, idle: bool) -> None:
        t_last, base, was_idle = self.state
        if was_idle == idle:
            return
        t = max(self.clock(), t_last)
        s = (t, base + (t - t_last if was_idle else 0), idle)
        self.ring[self.idx % _CHIP_RING] = s
        self.state = s
        self.idx += 1

    def ticket(self) -> int:
        """Device work was just enqueued that a later wait will collect."""
        if not _cfg.on:
            return 0
        with self.lock:
            self.enq += 1
            self._turn(False)
            return self.enq

    def collected(self, ticket: int) -> None:
        """A wait for ``ticket`` returned: it and every earlier one are done."""
        if ticket <= self.col:
            return
        with self.lock:
            if ticket > self.col:
                self.col = min(ticket, self.enq)
                if self.col == self.enq:
                    self._turn(True)

    def outstanding(self) -> int:
        return self.enq - self.col

    def idle_at(self, t: int) -> int:
        """Cumulative idle ns up to instant ``t`` (an instant before the
        ring's oldest transition reads the oldest one's total)."""
        s = self.state
        if t < s[0]:  # the last transition at or before t, by bisection over the ring
            ring, hi = self.ring, self.idx - 1
            lo = max(self.idx - _CHIP_RING, 0)
            s = ring[lo % _CHIP_RING]
            if t < s[0]:
                return s[1]
            while lo < hi:
                mid = (lo + hi + 1) // 2
                if ring[mid % _CHIP_RING][0] <= t:
                    lo = mid
                else:
                    hi = mid - 1
            s = ring[lo % _CHIP_RING]
        return s[1] + (t - s[0] if s[2] else 0)

    def idle_intervals(self, since_ns: int | None = None) -> list[tuple[int, int]]:
        """The idle ``(t0_ns, t1_ns)`` intervals the ring still holds, the
        open one ending now."""
        states = list(self.ring)
        i = self.idx
        if i > _CHIP_RING:
            head = i % _CHIP_RING
            states = states[head:] + states[:head]
        states = [st for st in states if st is not None]
        now = self.clock()
        out = []
        for st, nxt in zip(states, states[1:] + [None]):
            if st[2]:
                t1 = nxt[0] if nxt is not None else now
                if since_ns is None or t1 >= since_ns:
                    out.append((st[0], t1))
        return out


#: the process's chip account
chip = ChipAccount()


#: the watchdog's totals (``device_counters.snapshot()``'s ``stall_*``)
_stalls: dict[str, int] = {"stall_count": 0, "stall_ns": 0, "stall_cpu_ns": 0, "stall_steal_ns": 0}


def stall_totals() -> dict[str, int]:
    """The stall watchdog's totals since the process started."""
    return dict(_stalls)


def _make_ring() -> _Ring:
    t = threading.current_thread()
    # seeded per ring so span ids are unique across threads/processes
    # without coordination: high bits random, low bits a local counter
    seed = (random.getrandbits(30) << 33) | (os.getpid() & 0xFFFF) << 17
    ring = _Ring(_cfg.ring_size, t.name, seed)
    with _registry_mutex:
        _rings.append(ring)
    _tls.ring = ring
    if _watchdog is None and _cfg.on:
        _start_watchdog()
    global _atexit_installed
    if _cfg.spool_dir and not _atexit_installed:
        _atexit_installed = True
        import atexit

        atexit.register(lambda: flush("exit"))
    return ring


class TraceContext:
    """One request's (or epoch's) propagated identity: which trace the
    next span belongs to and which span is its parent."""

    __slots__ = ("trace_id", "span_id", "sampled", "t0_ns")

    def __init__(self, trace_id: int, span_id: int = 0, sampled: bool = True,
                 t0_ns: int = 0):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled
        self.t0_ns = t0_ns

    def child(self, span_id: int) -> "TraceContext":
        return TraceContext(self.trace_id, span_id, self.sampled, self.t0_ns)

    def to_wire(self) -> tuple[int, int, bool]:
        """Compact form piggybacked on cluster exchange frames."""
        return (self.trace_id, self.span_id, self.sampled)

    @staticmethod
    def from_wire(wire: Any) -> "TraceContext | None":
        try:
            trace_id, span_id, sampled = wire
            return TraceContext(int(trace_id), int(span_id), bool(sampled))
        except (TypeError, ValueError):
            return None


# ----------------------------------------------------------------- config


def configure(**env: Any) -> None:
    """Apply env-style knobs programmatically and reload the config
    (tests use this instead of mutating os.environ ad hoc)."""
    for key, value in env.items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = str(value)
    _cfg.reload()


def enabled() -> bool:
    return _cfg.on


def set_rank(rank: int) -> None:
    global _rank
    _rank = int(rank)


def current_rank() -> int:
    return _rank


def reset() -> None:
    """Drop every registered ring and tail-keep entry (test isolation)."""
    global _kept_idx
    with _registry_mutex:
        _rings.clear()
    _tls.ring = None
    _tls.ctx = None
    for i in range(_KEPT_CAP):
        _kept[i] = 0
    _kept_idx = 0
    _cfg.reload()
    chip.restart()
    for key in _stalls:
        _stalls[key] = 0


# ------------------------------------------------------------ record path


def _next_id() -> int:
    ring = _tls.ring
    if ring is None:
        ring = _make_ring()
    ring.id_next += 1
    return ring.id_next


def new_trace(sampled: bool | None = None) -> TraceContext:
    """Open a new trace (one per serving request / epoch).  Draws the
    head-sampling decision unless ``sampled`` is forced."""
    trace_id = _next_id()
    if sampled is None:
        s = _cfg.sample
        sampled = s >= 1.0 or (s > 0.0 and random.random() < s)
    return TraceContext(trace_id, trace_id, sampled, _monotonic_ns())


def current() -> TraceContext | None:
    """The thread's ambient trace context (None outside any request)."""
    return _tls.ctx


class _Use:
    __slots__ = ("ctx", "prev")

    def __init__(self, ctx: TraceContext | None):
        self.ctx = ctx
        self.prev: TraceContext | None = None

    def __enter__(self) -> TraceContext | None:
        self.prev = _tls.ctx
        _tls.ctx = self.ctx
        return self.ctx

    def __exit__(self, *exc: Any) -> None:
        _tls.ctx = self.prev


def use(ctx: TraceContext | None) -> _Use:
    """Pin ``ctx`` as the thread's ambient context for a ``with`` block
    (stage workers adopt the request's context this way)."""
    return _Use(ctx)


def set_ambient(ctx: TraceContext | None) -> TraceContext | None:
    """Swap the thread's ambient context, returning the previous one.
    The try/finally flavor of :func:`use` for per-task hot loops where
    the CM's object + enter/exit dispatch is measurable."""
    tls = _tls
    prev = tls.ctx
    tls.ctx = ctx
    return prev


def record_span(
    stage: str,
    t0_ns: int,
    t1_ns: int,
    ctx: TraceContext | None = None,
    args: dict | None = None,
) -> int:
    """Record one completed span; returns its span id (0 when tracing is
    off).  THE hot path: no locks, no I/O — one tuple into the ring."""
    if not _cfg.on:
        return 0
    tls = _tls
    ring = tls.ring
    if ring is None:
        ring = _make_ring()
    span_id = ring.id_next = ring.id_next + 1
    if ctx is None:
        ctx = tls.ctx
    if ctx is not None:
        rec = (ctx.trace_id, span_id, ctx.span_id, stage, _rank,
               t0_ns, t1_ns, ctx.sampled, args)
    else:
        rec = (0, span_id, 0, stage, _rank, t0_ns, t1_ns, False, args)
    ring.buf[ring.idx % ring.cap] = rec
    ring.idx += 1
    s = chip.state
    if t0_ns >= s[0]:  # no transition since the span began: its idle is all or nothing
        idle = t1_ns - t0_ns if s[2] else 0
    else:
        idle = chip.idle_at(t1_ns) - chip.idle_at(t0_ns)
    tot = ring.totals.get(stage)
    if tot is None:
        ring.totals[stage] = [1, t1_ns - t0_ns, idle]
    else:
        tot[0] += 1
        tot[1] += t1_ns - t0_ns
        tot[2] += idle
    return span_id


def record_spans(
    ctx: TraceContext | None,
    spans: "list[tuple[str, int, int, dict | None]]",
) -> None:
    """Record a batch of completed ``(stage, t0_ns, t1_ns, args)`` spans
    under ``ctx`` in one call.  The serving path stamps raw timestamps as
    a request moves through its stages (it needs them for the latency
    probes anyway) and materializes all spans here at request end —
    one call per request instead of one per stage."""
    if not _cfg.on or ctx is None:
        return
    ring = _tls.ring
    if ring is None:
        ring = _make_ring()
    buf, cap = ring.buf, ring.cap
    i, nid = ring.idx, ring.id_next
    trace_id, parent, sampled = ctx.trace_id, ctx.span_id, ctx.sampled
    rank = _rank
    totals = ring.totals
    idle_at = chip.idle_at
    for stage, t0_ns, t1_ns, args in spans:
        nid += 1
        buf[i % cap] = (trace_id, nid, parent, stage, rank,
                        t0_ns, t1_ns, sampled, args)
        i += 1
        idle = idle_at(t1_ns) - idle_at(t0_ns)
        tot = totals.get(stage)
        if tot is None:
            totals[stage] = [1, t1_ns - t0_ns, idle]
        else:
            tot[0] += 1
            tot[1] += t1_ns - t0_ns
            tot[2] += idle
    ring.id_next = nid
    ring.idx = i


#: ``jax.profiler.TraceAnnotation`` once jax is loaded in this process
_trace_annotation: Any = None


def _find_trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` if this process has imported jax
    (found once, then kept), else None: the recorder never imports jax."""
    global _trace_annotation
    if "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # jax is mid-import on another thread
            return None
        _trace_annotation = TraceAnnotation
    return _trace_annotation


class _Span:
    """Hot-path span CM.  Doubles as the child TraceContext while the
    block runs (it carries trace_id/span_id/sampled/t0_ns, which is all
    record_span reads), so entering a span allocates no extra object."""

    __slots__ = ("stage", "args", "parent", "t0_ns", "prev",
                 "trace_id", "span_id", "sampled", "ann", "chip0", "open0")

    def __init__(self, stage: str, args: dict | None, ctx: TraceContext | None):
        self.stage = stage
        self.args = args
        self.parent = ctx

    def __enter__(self) -> "_Span":
        tls = _tls
        self.prev = tls.ctx
        if not _cfg.on:
            # tracing off: no id, no ambient swap, no clock read; the
            # zero t0 tells __exit__ to skip even if toggled on mid-block
            self.parent = None
            self.t0_ns = 0
            return self
        ctx = self.parent if self.parent is not None else self.prev
        self.parent = ctx
        ring = tls.ring
        if ring is None:
            ring = _make_ring()
        self.open0 = ring.open
        ring.open = self.stage
        if ctx is not None:
            # pre-allocate this span's id so children recorded inside the
            # block parent onto it (the record at exit reuses the id)
            ring.id_next += 1
            self.trace_id = ctx.trace_id
            self.span_id = ring.id_next
            self.sampled = ctx.sampled
            tls.ctx = self
        annotation = _trace_annotation or _find_trace_annotation()
        if annotation is not None:
            self.ann = annotation(self.stage)
            self.ann.__enter__()
        else:
            self.ann = None
        self.chip0 = chip.state
        self.t0_ns = _monotonic_ns()
        return self

    def __exit__(self, et: Any, ev: Any, tb: Any) -> None:
        tls = _tls
        tls.ctx = self.prev
        if not _cfg.on or self.t0_ns == 0:
            return
        t1 = _monotonic_ns()
        s1 = chip.state
        if self.ann is not None:
            self.ann.__exit__(et, ev, tb)
        ring = tls.ring
        if ring is None:
            ring = _make_ring()
        ring.open = self.open0
        t0, s0 = self.t0_ns, self.chip0
        if s1 is s0:  # no transition inside the block
            idle = t1 - t0 if s0[2] else 0
        else:  # the idle clock at each end, from the state read there
            idle = (s1[1] + (t1 - s1[0] if s1[2] and t1 > s1[0] else 0)
                    - s0[1] - (t0 - s0[0] if s0[2] else 0))
        parent = self.parent
        if parent is not None:
            rec = (self.trace_id, self.span_id, parent.span_id,
                   self.stage, _rank, self.t0_ns, t1, self.sampled,
                   self.args)
        else:
            ring.id_next += 1
            rec = (0, ring.id_next, 0, self.stage, _rank, self.t0_ns, t1,
                   False, self.args)
        ring.buf[ring.idx % ring.cap] = rec
        ring.idx += 1
        tot = ring.totals.get(self.stage)
        if tot is None:
            ring.totals[self.stage] = [1, t1 - t0, idle]
        else:
            tot[0] += 1
            tot[1] += t1 - t0
            tot[2] += idle


def span(stage: str, args: dict | None = None,
         ctx: TraceContext | None = None) -> _Span:
    """Time a ``with`` block as one span under the ambient (or given)
    context; nested ``span()`` calls inside the block parent onto it."""
    return _Span(stage, args, ctx)


def finish_request(ctx: TraceContext | None, t1_ns: int | None = None) -> None:
    """Mark a request finished: if its end-to-end latency crossed the
    tail threshold, keep its trace regardless of head sampling."""
    global _kept_idx
    if ctx is None or not _cfg.on:
        return
    t1 = t1_ns if t1_ns is not None else _monotonic_ns()
    if ctx.t0_ns and (t1 - ctx.t0_ns) >= _cfg.tail_ns:
        i = _kept_idx
        _kept[i % _KEPT_CAP] = ctx.trace_id
        _kept_idx = i + 1


# ------------------------------------------------------------- dump path


def snapshot_records() -> list[tuple]:
    """Every live ring's records, append order per ring."""
    with _registry_mutex:
        rings = list(_rings)
    out: list[tuple] = []
    for ring in rings:
        out.extend(ring.snapshot())
    return out


def stage_totals() -> dict[str, tuple[int, int]]:
    """``{stage: (count, total_ns)}`` of every span recorded since the
    process started, summed over threads.  Monotonic (only the test-only
    :func:`reset` zeroes it); a thread recording meanwhile is at worst
    read one span short."""
    with _registry_mutex:
        rings = list(_rings)
    out: dict[str, tuple[int, int]] = {}
    for ring in rings:
        # one C-level copy under the GIL: the owner may add a stage meanwhile
        for stage, (count, total_ns, _idle) in list(ring.totals.items()):
            c, t = out.get(stage, (0, 0))
            out[stage] = (c + count, t + total_ns)
    return out


def stage_idle() -> dict[str, int]:
    """``{stage: idle_ns}``: the chip account's idle time inside every span
    of each stage since the process started, summed over threads (the
    time the chip had no work of the program's while the stage ran)."""
    with _registry_mutex:
        rings = list(_rings)
    out: dict[str, int] = {}
    for ring in rings:
        for stage, (_count, _total_ns, idle_ns) in list(ring.totals.items()):
            out[stage] = out.get(stage, 0) + idle_ns
    return out


def _ring_names() -> dict[int, str]:
    with _registry_mutex:
        return {id(r): r.thread_name for r in _rings}


def chrome_events(
    since_ns: int | None = None, all_spans: bool = False
) -> list[dict]:
    """Render the rings as Chrome-trace / Perfetto ``traceEvents``
    (``ph: "X"`` complete events; ``pid`` = rank, ``tid`` = thread).

    Export filter: spans of sampled traces, spans of tail-kept traces,
    and context-free spans (``trace_id == 0`` — flight-recorder noise
    floor, and the watchdog's ``process_stall``) — or everything with
    ``all_spans=True``.  Once the process has handed the chip work, a
    track ``chip`` holds the chip account's idle intervals
    (``chip_idle``) that its transition ring still has, on the same clock
    as the stages."""
    kept = set(_kept) - {0}
    events: list[dict] = []
    with _registry_mutex:
        rings = list(_rings)
    for ring in rings:
        tid = ring.thread_name
        for rec in ring.snapshot():
            trace_id, span_id, parent, stage, rank, t0, t1, sampled, args = rec
            if since_ns is not None and t1 < since_ns:
                continue
            if not all_spans and trace_id and not sampled and trace_id not in kept:
                continue
            ev_args = {"trace_id": trace_id, "span_id": span_id,
                       "parent": parent}
            if args:
                ev_args.update(args)
            events.append({
                "ph": "X",
                "name": stage,
                "cat": "pathway",
                "pid": rank,
                "tid": tid,
                "ts": t0 / 1e3,
                "dur": max(t1 - t0, 0) / 1e3,
                "args": ev_args,
            })
    if chip.enq:
        for t0, t1 in chip.idle_intervals(since_ns):
            events.append({
                "ph": "X", "name": "chip_idle", "cat": "chip", "pid": _rank,
                "tid": "chip", "ts": t0 / 1e3, "dur": (t1 - t0) / 1e3, "args": {},
            })
    events.sort(key=lambda e: e["ts"])
    return events


def dump(path: str, *, since_ns: int | None = None,
         all_spans: bool = True) -> str:
    """Write a Chrome-trace JSON file (open it at ui.perfetto.dev or
    chrome://tracing).  Flight-recorder dumps default to ``all_spans``:
    a post-mortem wants everything the ring still holds."""
    doc = {
        "traceEvents": chrome_events(since_ns=since_ns, all_spans=all_spans),
        "displayTimeUnit": "ms",
        "otherData": {"rank": _rank, "pid": os.getpid()},
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


_flush_n = 0


def flush(reason: str = "manual") -> str | None:
    """Flight-recorder flush: dump this process's rings into the spool
    dir (``PATHWAY_TRACE_DIR``).  No-op (None) when no spool is set.
    Safe to call from failure paths — never raises."""
    global _flush_n
    spool = _cfg.spool_dir
    if not spool:
        return None
    try:
        os.makedirs(spool, exist_ok=True)
        with _registry_mutex:
            _flush_n += 1
            n = _flush_n
        path = os.path.join(
            spool, f"trace-r{_rank}-p{os.getpid()}-{n:03d}-{reason}.json"
        )
        return dump(path)
    except Exception:  # noqa: BLE001 — a failing dump must not mask the failure
        return None


def merge_trace_dir(spool: str, out_path: str | None = None) -> str | None:
    """Merge every per-rank ``trace-*.json`` in ``spool`` into ONE
    Chrome-trace file (default ``<spool>/merged_trace.json``) — the
    single stitched timeline the chaos drills assert on.  Events keep
    their per-rank ``pid``; duplicate (span_id, rank) pairs from repeat
    flushes of one ring collapse to the last occurrence."""
    try:
        names = sorted(
            f for f in os.listdir(spool)
            if f.startswith("trace-") and f.endswith(".json")
        )
    except OSError:
        return None
    if not names:
        return None
    by_key: dict[Any, dict] = {}
    loose: list[dict] = []
    for name in names:
        try:
            with open(os.path.join(spool, name)) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        for ev in doc.get("traceEvents", ()):
            sid = ev.get("args", {}).get("span_id")
            if sid:
                by_key[(ev.get("pid"), sid)] = ev
            elif ev.get("tid") == "chip":  # one idle interval, whichever flush saw it last
                by_key[(ev.get("pid"), "chip", ev.get("ts"))] = ev
            else:
                loose.append(ev)
    events = list(by_key.values()) + loose
    events.sort(key=lambda e: e.get("ts", 0))
    out_path = out_path or os.path.join(spool, "merged_trace.json")
    tmp = f"{out_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    os.replace(tmp, out_path)
    return out_path


# --------------------------------------------------- stacks + SIGUSR2


def dump_stacks() -> str:
    """Every Python thread's stack as text (hang diagnosis; served by
    ``/debug/stacks`` and written to stderr on SIGUSR2)."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    parts: list[str] = []
    for ident, frame in frames.items():
        name = names.get(ident, "?")
        parts.append(f"--- Thread {name} (ident {ident}) ---")
        parts.append("".join(traceback.format_stack(frame)).rstrip())
    return "\n".join(parts) + "\n"


_sigusr2_installed = False


def install_sigusr2() -> bool:
    """SIGUSR2 → dump all thread stacks to stderr AND flush the flight
    recorder to the spool dir.  Main-thread only (signal module rule);
    returns False when it cannot install."""
    global _sigusr2_installed
    if _sigusr2_installed:
        return True
    try:
        import signal

        def _handler(_signum: int, _frame: Any) -> None:
            try:
                sys.stderr.write(dump_stacks())
                sys.stderr.flush()
            except Exception:  # noqa: BLE001
                pass
            flush("sigusr2")

        signal.signal(signal.SIGUSR2, _handler)
        _sigusr2_installed = True
        return True
    except (ValueError, OSError, AttributeError):
        return False  # not the main thread, or no SIGUSR2 (non-POSIX)


# ------------------------------------------------------- stall watchdog

#: the watchdog's tick, and how much later than due a wake is a stall
STALL_TICK_NS = 20_000_000
STALL_LATE_NS = 100_000_000
#: faulthandler dumps every stack when the watchdog has not re-armed it for
#: this long; it re-arms at most every ``_REARM_NS`` (each arm starts an OS
#: thread)
_STALL_DUMP_S = 0.2
_REARM_NS = 100_000_000
#: /proc/stat is read this often between stalls (a read formats every CPU)
_STEAL_EVERY_NS = 200_000_000

try:
    _CLK_TCK = os.sysconf("SC_CLK_TCK")
except (AttributeError, ValueError, OSError):
    _CLK_TCK = 100


def _read_steal_ns() -> int | None:
    """The machine's steal time from ``/proc/stat``, in ns a CPU (a VM
    paused whole reads about the pause); None where there is none."""
    try:
        with open("/proc/stat", "rb") as f:
            lines = f.read().split(b"\n")
        fields = lines[0].split()
        ncpu = sum(1 for line in lines[1:] if line.startswith(b"cpu")) or 1
        return int(fields[8]) * (1_000_000_000 // _CLK_TCK) // ncpu
    except (OSError, IndexError, ValueError):
        return None


def _read_majflt() -> int:
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_majflt
    except (ImportError, OSError):
        return 0


class StallWatchdog:
    """Records whole-process stalls: a thread that sleeps a tick of
    :data:`STALL_TICK_NS` and, when it wakes more than
    :data:`STALL_LATE_NS` after it was due, records a ``process_stall``
    span from the due wake to the actual one and adds to ``_stalls``.
    Its args: ``cpu_ms``, the process's CPU time over the stall and the
    tick before it (about the stall's length: a thread held the GIL
    computing; the runtime's own threads add theirs); ``steal_ms``, the
    machine's steal time a CPU since a reading at most 200 ms before the
    stall (a paused VM); ``majflt``, major faults over it; ``open``, the
    innermost stage each other thread had open at the wake.

    Where ``PATHWAY_TRACE_DIR`` is set, the thread also keeps
    ``faulthandler.dump_traceback_later`` armed, so a stall of 100 ms
    more writes every thread's stack into the spool from a C thread while
    it lasts (the one way to see a GIL holder), and flushes the spool
    after it.  Without a spool it never touches ``faulthandler``.  The
    clocks are injectable: :meth:`tick` is the whole decision."""

    def __init__(self, clock: Callable[[], int] | None = None,
                 cpu: Callable[[], int] | None = None,
                 steal: Callable[[], "int | None"] | None = None,
                 majflt: Callable[[], int] | None = None,
                 totals: dict[str, int] | None = None):
        self.clock = clock or time.monotonic_ns
        self.cpu = cpu or time.process_time_ns
        self.steal = steal or _read_steal_ns
        self.majflt = majflt or _read_majflt
        self.totals = _stalls if totals is None else totals
        now = self.clock()
        self.last = (now, self.cpu(), self.majflt())
        self.steal_last = (now, self.steal())
        self.armed_ns = 0
        self.stacks: Any = None

    def tick(self, now: int) -> int:
        """One wake at ``now``: record a stall if it is one; returns the
        stall's ns (0 for a wake on time)."""
        t_prev, cpu_prev, flt_prev = self.last
        late = now - (t_prev + STALL_TICK_NS)
        cpu, flt = self.cpu(), self.majflt()
        stalled = 0
        if late > STALL_LATE_NS and _cfg.on:
            stalled = late
            steal_prev = self.steal_last[1]
            steal_now = self.steal()
            steal = steal_now - steal_prev if steal_now is not None and steal_prev is not None else 0
            self.steal_last = (now, steal_now)
            totals = self.totals
            totals["stall_count"] += 1
            totals["stall_ns"] += late
            totals["stall_cpu_ns"] += cpu - cpu_prev
            totals["stall_steal_ns"] += steal
            record_span("process_stall", now - late, now, ctx=None, args={
                "cpu_ms": (cpu - cpu_prev) / 1e6,
                "steal_ms": steal / 1e6,
                "majflt": flt - flt_prev,
                "open": self._open_elsewhere(),
            })
        elif now - self.steal_last[0] >= _STEAL_EVERY_NS:
            self.steal_last = (now, self.steal())
        self.last = (now, cpu, flt)
        return stalled

    @staticmethod
    def _open_elsewhere() -> dict[str, str]:
        me = _tls.ring
        with _registry_mutex:
            rings = list(_rings)
        return {r.thread_name: r.open for r in rings if r is not me and r.open is not None}

    def _arm(self, now: int) -> None:
        import faulthandler

        spool = _cfg.spool_dir
        if not spool:
            if self.stacks is not None:
                faulthandler.cancel_dump_traceback_later()
                self.stacks.close()
                self.stacks = None
            return
        if now - self.armed_ns < _REARM_NS:
            return
        if self.stacks is None:
            os.makedirs(spool, exist_ok=True)
            self.stacks = open(os.path.join(spool, f"stacks-r{_rank}-p{os.getpid()}.txt"), "a")
        faulthandler.dump_traceback_later(_STALL_DUMP_S, repeat=False, file=self.stacks)
        self.armed_ns = now

    def run(self) -> None:
        while True:
            time.sleep(STALL_TICK_NS / 1e9)
            now = self.clock()
            try:
                if self.tick(now) and _cfg.spool_dir:
                    flush("stall")
                    t = self.clock()  # the flush's own time is no stall
                    self.last = (t, self.cpu(), self.majflt())
                if _cfg.spool_dir or self.stacks is not None:
                    self._arm(now)
            except Exception:  # noqa: BLE001 — the watchdog must outlive a bad reading
                pass


_watchdog: StallWatchdog | None = None


def _start_watchdog() -> None:
    global _watchdog
    with _registry_mutex:
        if _watchdog is not None:
            return
        _watchdog = wd = StallWatchdog()
    threading.Thread(target=wd.run, name="pathway-stall-watchdog", daemon=True).start()


def _after_fork_in_child() -> None:
    """A forked child has neither the watchdog thread nor the parent's
    chip: start both anew (the locks may have been held at the fork)."""
    global _watchdog, _registry_mutex
    _watchdog = None
    _registry_mutex = threading.Lock()
    chip.lock = threading.Lock()
    chip.restart()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_after_fork_in_child)
