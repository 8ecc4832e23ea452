"""Traffic kind ``ingest_lag``: a backlogged source with bounded lag.

Files of exactly ``file_docs`` one-chunk documents wait in a staging
directory; the writer renames the next one into the watched directory
whenever (documents written - chunks searchable) <= ``lag_chunks``.  Chunks
searchable is ``SegmentedIndex.stats()``'s ``main_size + delta_size`` less
the filler, polled in-process.  Warm-up is the same stream: the window opens
at the visibility event that completes the first ``warm_files`` files and
closes at the last visibility event before ``--seconds`` is up, and
``ingest_chunks_per_s`` is every chunk made searchable between the two over
all the time between them, stalls and all.

Every file holds the same multiset of lengths (from ``schedule_seed``) in an
order of its own and words of its own (from ``--seed``), so every file is the
same work.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import corpus
from benchmark.system import SystemFault, log

DOC_STREAM = 21


class Traffic:
    def __init__(self, system, workload: dict, seed: int, seconds: float, tracer):
        self.system = system
        self.w = workload
        self.seed = seed
        self.seconds = seconds
        self.tracer = tracer
        self.texts: dict[str, str] = {}
        self.files: list[tuple[str, list[str]]] = []  # (staged path, ids)
        self.published: list[tuple[float, int]] = []  # (when, file index)
        self.events: list[tuple[float, int]] = []  # (when, chunks searchable)
        self.written = 0
        self.slice = None  # ((when, searchable), (when, searchable)): the traced slice, on visibility events
        self._publishing = True

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from pathway_tpu.internals import device_counters

        w, sysm = self.w, self.system
        n_files = w["warm_files"] + int(np.ceil(w["provision_chunks_per_s"] * self.seconds / w["file_docs"])) + 2
        for f in range(n_files):
            rng = np.random.default_rng([self.seed, DOC_STREAM, f])
            counts = corpus.lengths(w["docs"]["words"], w["file_docs"], w["schedule_seed"], self.seed * 1000 + f, DOC_STREAM)
            texts = corpus.make_texts("doc", f * w["file_docs"], counts, w["docs"]["vocab_words"], rng)
            path = os.path.join(sysm.staging_dir, f"part-{f:04d}.jsonl")
            corpus.write_jsonl(path, texts)
            ids = [corpus.doc_id(t) for t in texts]
            self.texts.update(zip(ids, texts))
            self.files.append((path, ids))
        log(f"{n_files} files of {w['file_docs']} documents staged")
        for rows, tokens in w["warm_grid"]["encoder"]:
            sysm.warm_encoder(rows, tokens)
        log(f"shape grid driven; {device_counters.compile_count()} compiles so far")
        # warm-up is the stream itself: the writer's rule from the first file on
        warm_chunks = w["warm_files"] * w["file_docs"]
        deadline = time.monotonic() + w["warm_timeout_s"]
        self._next = 0
        self._last_count = 0
        while True:
            now, count = self._poll()
            if count >= warm_chunks:
                break
            sysm.require_healthy()
            if now > deadline:
                raise SystemFault(f"warm-up: only {count} of {warm_chunks} chunks became searchable")
            time.sleep(w["poll_ms"] / 1000.0)

    def _poll(self) -> tuple[float, int]:
        """One turn of the source: note a visibility event, apply the rule."""
        count = self.system.searchable()
        now = time.monotonic()
        if count != self._last_count:
            self.events.append((now, count))
            self._last_count = count
        if self._publishing and self._next < len(self.files) and self.written - count <= self.w["lag_chunks"]:
            path, ids = self.files[self._next]
            corpus.publish(path, self.system.corpus_dir)
            self.published.append((now, self._next))
            self.written += len(ids)
            self._next += 1
        return now, count

    # ------------------------------------------------------------- window
    def run_window(self) -> dict:
        w, sysm = self.w, self.system
        t_open, c_open = self.events[-1]
        self.t_open = t_open
        t_end = t_open + self.seconds
        first_window_event = len(self.events)
        trace_at = t_open + w["trace"]["start_frac"] * self.seconds if self.tracer is not None else None
        slice_a = slice_b = None
        starved = 0
        fault = None
        while True:
            n_before = len(self.events)
            now, count = self._poll()
            if now >= t_end:
                break
            fault = sysm.watch.fault()
            if fault:
                log(f"engine fault, the window ends here: {fault}")
                break
            if self._next >= len(self.files) and self.written - count <= w["lag_chunks"]:
                starved += 1
            if self.tracer is not None:
                event = len(self.events) > n_before
                if trace_at is not None and now >= trace_at:
                    self.tracer.request_start()
                    trace_at = None
                elif trace_at is None and slice_a is None and event and self.tracer.started.is_set():
                    slice_a = self.events[-1]
                elif slice_a is not None and slice_b is None and event and now - slice_a[0] >= w["trace"]["slice_s"]:
                    slice_b = self.events[-1]
                    self.tracer.request_stop()
            time.sleep(w["poll_ms"] / 1000.0)
        self._publishing = False
        if self.tracer is not None:
            self.tracer.request_stop()
        if slice_a is not None and slice_b is not None:
            self.slice = (slice_a, slice_b)
        inside = [e for e in self.events[first_window_event:] if e[0] <= t_end]
        if starved:
            log(f"the source ran dry for {starved} polls: provision_chunks_per_s is too low for this system")
        metrics = {}
        if inside:
            t_last, c_last = inside[-1]
            metrics["ingest_chunks_per_s"] = (c_last - c_open) / (t_last - t_open)
            log(
                f"window: {c_last - c_open} chunks in {t_last - t_open:.2f} s over {len(inside)} visibility events "
                f"({metrics['ingest_chunks_per_s']:.1f} chunks/s)"
            )
        else:
            log("window: no visibility event inside it")
        order = [i for _when, f in self.published for i in self.files[f][1]]
        self.window_ids = order[c_open : inside[-1][1]] if inside else []
        return {
            "t_open": t_open,
            "metrics": metrics,
            "attempted": self.written,
            "failed": 0,  # settled after the drain
            "series": {},
            "notes": {"visibility_events": len(inside), "source_ran_dry_polls": starved, "fault": fault},
            "window_chunks": (inside[-1][1] - c_open) if inside else 0,
        }

    def drain(self) -> int:
        """Wait for everything written to become searchable; returns how
        many chunks never did."""
        deadline = time.monotonic() + self.w["drain_timeout_s"]
        while self.system.searchable() < self.written:
            if self.system.watch.fault() or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        missing = self.written - self.system.searchable()
        log(f"drained: {self.system.seg.stats()}, {missing} chunks missing")
        return max(missing, 0)

    def slice_readings(self, t_a: float, t_b: float) -> dict:
        if self.slice is None:
            return {"requests": 0, "latency_ms": [], "useful_tokens": [], "chunks": 0}
        (ta, ca), (tb, cb) = self.slice
        chunks = cb - ca
        # which chunks: files are made searchable in the order they were published
        order = [i for _when, f in self.published for i in self.files[f][1]]
        ids = order[ca:cb]
        tokens = [len(self.texts[i].split()) + 2 for i in ids]
        return {"requests": 0, "latency_ms": [], "useful_tokens": tokens, "chunks": chunks, "clip": (ta, tb)}

    def gap_label(self, t_rel_open: float) -> str:
        t = self.t_open + t_rel_open
        searchable = max([c for when, c in self.events if when <= t], default=0)
        written = sum(len(self.files[f][1]) for when, f in self.published if when <= t)
        return "files pending in the engine" if written > searchable else "no file pending"

    # -------------------------------------------------------------- check
    def check_sample(self) -> dict:
        """A sample of the chunks the window made searchable, drawn from the
        seed, with the longest in it.  The chunks asked for again at
        ``/v1/retrieve`` are drawn from two token buckets only, the longest
        chunk's and the commonest: every bucket is one more BGE-large program
        to load after the window (4 s each), in every run of every check."""
        c = self.w["check"]
        rng = np.random.default_rng([self.seed, 99])
        ids = self.window_ids or [i for _p, ids in self.files[: self._next] for i in ids]
        words = {i: len(self.texts[i].split()) for i in ids}
        longest = max(ids, key=words.get)
        drawn = [ids[int(i)] for i in rng.permutation(len(ids))]
        chunk_ids = [longest] + [i for i in drawn if i != longest][: c["sample_chunks"] - 1]

        def bucket(i: str) -> int:
            return max(16, 1 << (words[i] + 1).bit_length())  # power of two holding words + 2

        buckets = [bucket(i) for i in ids]
        commonest = max(set(buckets), key=buckets.count)
        ask_ids = [longest] + [i for i in chunk_ids[1:] if bucket(i) in (bucket(longest), commonest)][: c["ask"] - 1]
        return {
            "live_texts": self.texts,
            "reference_ids": chunk_ids,  # the reference embeds the sample, not the whole window
            "chunk_ids": chunk_ids,
            "ask": [self.texts[i] for i in ask_ids],
            "answers": [],
            "all_answers": [],
            "k": c["k"],
        }
