"""Live device-plane counters: jit compiles, host<->device bytes, and
what each dispatch carried.

The static device analyzer (``pathway_tpu/analysis/device.py``) PREDICTS
where recompiles and transfers happen; this module MEASURES them, the
same estimated-vs-measured join PR 15 gave memory capacity.  All
counters are monotonic:

- ``jit_compiles`` — one per actual XLA backend compile, observed via
  ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
  event (cache hits emit nothing, so a warmed, shape-stable serving loop
  holds this flat — the zero-recompile steady-state invariant
  ``tests/test_device_runtime.py`` holds).
- ``h2d_bytes`` / ``d2h_bytes`` — recorded at the repo's own transfer
  call sites (``parallel/sharded_knn.py`` dispatch/collect,
  ``parallel/executor.py`` chunk uploads/readbacks, ``parallel/
  ivf_knn.py``); jax has no public per-transfer hook, so these count the
  transfers *we* issue, which is exactly the set the analyzer reasons
  about.

- dispatch counters, :func:`bump`-ed once per dispatch where the work
  happens, never per row: ``encoder_*`` (``JittedEncoder._dispatch``:
  ``encoder_segments`` texts in ``encoder_rows`` rows that carry one or,
  packed, several; rows and tokens as given and as padded), ``search_*``
  (``ShardedKnnIndex.dispatch``), ``scatter_*`` (``add_batch`` /
  ``add_batch_device``), ``epochs`` / ``epoch_rows`` (the scheduler's
  cut), ``rest_requests`` / ``rest_responses`` (``io/http``) and, once a
  generated answer (``JittedDecoder.generate``): ``gen_*`` (the prompt's
  tokens as given and as padded to chunk buckets, its prefill dispatches,
  the tokens chosen and the decode steps; ``gen_logit_rows``, the rows of
  logits kept, and ``gen_logit_rows_early``, those in their host array
  while the answer's last step was still running), ``mtp_drafts`` /
  ``mtp_drafts_accepted`` (drafts the MTP layer made that a step verified /
  those it kept: in the drafting loop a step is one verify, and
  ``gen_decode_steps`` counts them), ``moe_rows_here`` /
  ``moe_rows_routed`` (token-expert pairs the experts held here computed /
  pairs the router chose anywhere) and ``dsa_keys_selected`` /
  ``dsa_keys_scored`` (keys the queries attended to / keys their indexer
  scored), the last four counted on the device by the programs themselves,
  as each further architecture's ``STATS`` are (``xdec_*``, ``swa_*``;
  ``moe_rows_zero``, the pairs whose expert computes nothing, and
  ``mla_keys_visible`` / ``mla_keys_multiplied``, the query-key pairs that
  count / those the attention multiplied: in a prompt, the fused kernel's
  query tiles against the key blocks each visits; ``moe_rows_multiplied``,
  the rows the expert product multiplied, its blocks' padding included;
  ``moe_grouped_calls``, the layers of a dispatch whose experts took the
  grouped product, one weight read an expert: prompt chunks only);
  and once a ``GroupByNode.process`` call that had dirty groups:
  ``groupby_groups_emitted`` (groups whose change it emitted) and
  ``groupby_groups_consolidated`` (those of them whose two rows could not
  tell it whether they differ, so ``consolidate`` hashed the pair).

:func:`snapshot` is the one door through which the benchmark reads the
program: the counters above, the span recorder's stage totals
(``internals/tracing.stage_totals`` / ``stage_idle``) as flat keys
``span_ns.<stage>`` / ``span_count.<stage>`` / ``span_idle_ns.<stage>``
(the chip account's idle time inside the stage's spans), and, present
from the process's start and zero under ``PATHWAY_TRACE=0``, the chip
account's ``chip_idle_ns`` (time with no device work of the program's
outstanding) over ``chip_watch_ns`` (time since the account started)
and the stall watchdog's ``stall_count`` / ``stall_ns`` /
``stall_cpu_ns`` / ``stall_steal_ns`` (``internals/tracing.py`` has
both).  Every key is exported on ``/metrics`` as
``pathway_tpu_<key>_total`` (the stage totals with a ``stage`` label)
and the counters are joined against the static prediction on ``/status``.
Importing this module never imports jax; ``install()`` is called lazily
by the first transfer-recording caller (all of which already have jax
loaded) and degrades to transfer-only counting when ``jax.monitoring``
is unavailable.
"""

from __future__ import annotations

import threading
from typing import Any

from pathway_tpu.internals import tracing

__all__ = [
    "bump",
    "install",
    "installed",
    "record_h2d",
    "record_d2h",
    "snapshot",
    "compile_count",
    "reset_for_tests",
]

_lock = threading.Lock()
_installed = False
_install_failed = False

# monotonic counters; ints under the GIL, guarded anyway for += races
_counters: dict[str, int] = {
    "jit_compiles": 0,
    "h2d_bytes": 0,
    "h2d_transfers": 0,
    "d2h_bytes": 0,
    "d2h_transfers": 0,
    "encoder_dispatches": 0,
    "encoder_segments": 0,
    "encoder_rows": 0,
    "encoder_rows_padded": 0,
    "encoder_tokens": 0,
    "encoder_tokens_padded": 0,
    "search_dispatches": 0,
    "search_queries": 0,
    "search_queries_padded": 0,
    "scatter_dispatches": 0,
    "scatter_rows": 0,
    "scatter_rows_padded": 0,
    "epochs": 0,
    "epoch_rows": 0,
    "rest_requests": 0,
    "rest_responses": 0,
    "gen_requests": 0,
    "gen_prompt_tokens": 0,
    "gen_prompt_tokens_padded": 0,
    "gen_prefill_dispatches": 0,
    "gen_new_tokens": 0,
    "gen_decode_steps": 0,
    "gen_logit_rows": 0,
    "gen_logit_rows_early": 0,
    "mtp_drafts": 0,
    "mtp_drafts_accepted": 0,
    "moe_rows_here": 0,
    "moe_rows_routed": 0,
    "moe_rows_multiplied": 0,
    "moe_grouped_calls": 0,
    "dsa_keys_selected": 0,
    "dsa_keys_scored": 0,
    "xdec_tokens_run": 0,
    "xdec_tokens_seen": 0,
    "swa_keys_in_window": 0,
    "swa_keys_multiplied": 0,
    "moe_rows_zero": 0,
    "mla_keys_visible": 0,
    "mla_keys_multiplied": 0,
    "groupby_groups_emitted": 0,
    "groupby_groups_consolidated": 0,
}


def bump(**amounts: int) -> None:
    """Add to several counters at once (one lock round per dispatch).
    An unknown name is a ``KeyError``: the names above are the interface."""
    with _lock:
        for key, amount in amounts.items():
            _counters[key] += int(amount)


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    # one backend_compile_duration per actual XLA compile; the sibling
    # jaxpr_trace / jaxpr_to_mlir events fire on cheap retraces too, so
    # only the backend event counts as "a compile happened"
    if event.endswith("backend_compile_duration"):
        bump(jit_compiles=1)


def install() -> bool:
    """Register the jit-compile listener (idempotent).  Returns whether
    compile counting is live; byte counters work either way."""
    global _installed, _install_failed
    if _installed:
        return True
    if _install_failed:
        return False
    with _lock:
        if _installed:
            return True
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            _install_failed = True
            return False
        _installed = True
    return True


def installed() -> bool:
    return _installed


def record_h2d(nbytes: int) -> None:
    """Count one host->device upload of ``nbytes`` (call at the repo's
    ``device_put``/np->jnp coercion sites)."""
    install()
    bump(h2d_bytes=nbytes, h2d_transfers=1)


def record_d2h(nbytes: int) -> None:
    """Count one device->host readback of ``nbytes``."""
    install()
    bump(d2h_bytes=nbytes, d2h_transfers=1)


def compile_count() -> int:
    """Current jit-compile total (installs the listener on first use so
    warm-up loops can bracket themselves)."""
    install()
    return _counters["jit_compiles"]


def snapshot() -> dict[str, int]:
    """Point-in-time copy of all counters and of the span recorder's
    stage totals (for /metrics, /status and the benchmark)."""
    with _lock:
        out = dict(_counters)
    out["listener_installed"] = 1 if _installed else 0
    if tracing.enabled():
        now = tracing.now_ns()
        out["chip_idle_ns"] = tracing.chip.idle_at(now)
        out["chip_watch_ns"] = now - tracing.chip.t_start
        out.update(tracing.stall_totals())
    else:
        out.update(chip_idle_ns=0, chip_watch_ns=0, stall_count=0, stall_ns=0, stall_cpu_ns=0, stall_steal_ns=0)
    idle = tracing.stage_idle()
    for stage, (count, total_ns) in tracing.stage_totals().items():
        out[f"span_ns.{stage}"] = total_ns
        out[f"span_count.{stage}"] = count
        out[f"span_idle_ns.{stage}"] = idle.get(stage, 0)
    return out


def reset_for_tests() -> None:
    """Zero the counters (the jax listener cannot be unregistered, so
    tests bracket with deltas or reset)."""
    with _lock:
        for k in _counters:
            _counters[k] = 0
