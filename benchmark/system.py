"""What every system kind shares: the process's clock, the log line, the
engine watcher, the port draw and the fault a system raises.

The system under test itself is a kind of its own, ``systems/<kind>.py``,
found by the ``system`` a configuration names (``benchmark/README.md``).
The corpus writer's rename, the engine watcher and the port draw are copied
from ``chip_smoke.py`` (PR 21), so that a later PR may change the program and
not this yardstick.
"""

from __future__ import annotations

import socket
import sys
import threading
import time

import jax  # noqa: F401
import numpy  # noqa: F401

# setup_s counts from here, as it has since PR 24: once jax and numpy are in
# and before anything of the program is imported or built
T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.2f}s] {msg}", file=sys.stderr, flush=True)


class EngineWatch:
    """Watches the server's engine thread so that nothing it swallows passes
    for health: ``run_server(threaded=True)`` runs ``pw.run`` on a daemon
    thread, where an uncaught exception only reaches ``threading``'s hook and
    an operator's exception is contained into the run's error log.  ``fault``
    names what it saw, or is ``None``; nothing here raises."""

    def __init__(self) -> None:
        self.thread: threading.Thread | None = None
        self.uncaught: list[str] = []
        self._prev_hook = threading.excepthook
        threading.excepthook = self._hook

    def _hook(self, args) -> None:
        self.uncaught.append(
            f"{getattr(args.thread, 'name', '?')}: {args.exc_type.__name__}: {args.exc_value}"
        )
        self._prev_hook(args)

    def fault(self) -> str | None:
        from pathway_tpu.internals.parse_graph import G

        if self.uncaught:
            return f"uncaught exception in a thread: {self.uncaught[:3]}"
        if self.thread is None or not self.thread.is_alive():
            return "the engine thread (pw.run) has exited"
        sched = getattr(G, "active_scheduler", None)
        errors = list(sched.ctx.error_log) if sched is not None else []
        if errors:
            return f"operator errors in the run's error log: {[str(e) for e in errors[:3]]}"
        return None

    def close(self) -> None:
        threading.excepthook = self._prev_hook


def free_port() -> int:
    """A loopback port that binds now; a taken port is another draw."""
    for _ in range(64):
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", 0))
            except OSError:
                continue
            return s.getsockname()[1]
    raise OSError("no loopback port would bind")


class SystemFault(Exception):
    """The system under test could not be built or has died; the runner
    turns this into a last line with ``correct`` false."""
