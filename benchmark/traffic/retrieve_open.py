"""Traffic kind ``retrieve_open``: an open loop of questions over a resident
corpus.

Set-up ingests the workload's live passages through the product path (one
file), drives every point of the workload's shape grid, and plays a few
seconds of the real stream.  The window sends the questions of a schedule
drawn from the seed beforehand: every seed gets the same inter-arrival gaps
and question lengths (from ``schedule_seed``), reordered in blocks of about a
second, the gaps scaled so that the last request is due at the window's end.  A request is
timed from the instant it was due.  When ``max_in_flight`` are outstanding a
due request is not sent and counts as failed; so does a time-out or an HTTP
error.  Nothing here raises on a request's fate.
"""

from __future__ import annotations

import concurrent.futures
import os
import threading
import time

import numpy as np

from benchmark import corpus
from benchmark.system import SystemFault, log

LIVE_STREAM, GAP_STREAM, PICK_STREAM = 11, 12, 14


class Traffic:
    def __init__(self, system, workload: dict, seed: int, seconds: float, tracer):
        self.system = system
        self.w = workload
        self.seed = seed
        self.seconds = seconds
        self.tracer = tracer
        self.k = workload["k"]
        self.texts: dict[str, str] = {}  # id -> text of every live passage
        self.results: list[dict] = []
        self._trace_stop_at = float("inf")

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from pathway_tpu.internals import device_counters

        w, sysm = self.w, self.system
        rng = np.random.default_rng([self.seed, LIVE_STREAM])
        counts = corpus.lengths(w["docs"]["words"], w["live_docs"], w["schedule_seed"], self.seed, LIVE_STREAM)
        live = corpus.make_texts("doc", 0, counts, w["docs"]["vocab_words"], rng)
        self.texts = {corpus.doc_id(t): t for t in live}
        staged = os.path.join(sysm.staging_dir, "live-000.jsonl")
        corpus.write_jsonl(staged, live)
        corpus.publish(staged, sysm.corpus_dir)
        deadline = time.monotonic() + w["ingest_timeout_s"]
        while sysm.searchable() < len(live):
            sysm.require_healthy()
            if time.monotonic() > deadline:
                raise SystemFault(f"only {sysm.searchable()} of {len(live)} live passages became searchable")
            time.sleep(0.02)
        log(f"live passages searchable: {sysm.seg.stats()}")

        grid = w["warm_grid"]
        for rows in grid["encoder_rows"]:
            for tokens in grid["token_buckets"]:
                sysm.warm_encoder(rows, tokens)
        for rows in grid["search_rows"]:
            sysm.warm_search(rows, self.k)
        log(f"shape grid driven; {device_counters.compile_count()} compiles so far")

        n_warm = max(1, round(w["rate_per_s"] * w["warm_stream_s"]))
        before = device_counters.compile_count()
        self._play(self._schedule(n_warm, w["warm_stream_s"], stream_offset=100), record=False)
        moved = device_counters.compile_count() - before
        log(f"warm stream: {n_warm} requests, compile count moved by {moved}")
        self.schedule = self._schedule(max(1, round(w["rate_per_s"] * self.seconds)), self.seconds)

    def _schedule(self, n: int, span_s: float, stream_offset: int = 0) -> list[tuple[float, str]]:
        """``n`` (due, question) pairs over ``span_s`` seconds.  Gaps, lengths
        and which questions repeat a passage are one fixed sequence (from
        ``schedule_seed``); the seed reorders it in whole blocks of about a
        second and chooses the words.  Bursts, which make the tail, are then
        the same bursts for every seed, in another order."""
        w = self.w
        base = np.random.default_rng([w["schedule_seed"], GAP_STREAM + stream_offset])
        gaps = base.exponential(1.0, size=n)
        q = w["questions"]
        draws = base.lognormal(np.log(q["words"]["median"]), q["words"]["sigma"], size=n)
        lens = np.clip(draws, q["words"]["min"], q["words"]["max"]).astype(int)
        repeats = base.random(n) < q["repeat_share"]
        block = max(1, round(w["rate_per_s"]))
        rng = np.random.default_rng([self.seed, PICK_STREAM + stream_offset])
        blocks = [np.arange(s, min(s + block, n)) for s in range(0, n, block)]
        order = np.concatenate([blocks[int(b)] for b in rng.permutation(len(blocks))])
        gaps, lens, repeats = gaps[order], lens[order], repeats[order]
        due = np.cumsum(gaps) * (span_s / gaps.sum())
        live = list(self.texts.values())
        fresh = corpus.make_texts("q", stream_offset * 1_000_000, lens, self.w["docs"]["vocab_words"], rng)
        out = []
        for i in range(n):
            if repeats[i]:
                words = live[int(rng.integers(len(live)))].split(" ")
                out.append((float(due[i]), " ".join(words[: int(lens[i])])))
            else:
                out.append((float(due[i]), fresh[i]))
        return out

    # ------------------------------------------------------------ sending
    def _play(self, schedule: list[tuple[float, str]], record: bool) -> list[dict]:
        from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient

        w, sysm = self.w, self.system
        client = VectorStoreClient(port=sysm.port, timeout=w["timeout_s"])
        lock = threading.Lock()
        outstanding = [0]
        results = [
            {"i": i, "due": due, "text": text, "status": "unsent", "sent": None, "done": None, "hits": None}
            for i, (due, text) in enumerate(schedule)
        ]

        def one(r: dict, t_open: float) -> None:
            r["sent"] = time.monotonic() - t_open
            try:
                r["hits"] = client.query(r["text"], self.k)
                r["status"] = "ok"
            except Exception as e:  # time-out, refused connection, HTTP error: counted
                r["status"] = f"error: {type(e).__name__}: {e}"[:200]
            r["done"] = time.monotonic() - t_open
            with lock:
                outstanding[0] -= 1

        pool = concurrent.futures.ThreadPoolExecutor(max_workers=w["max_in_flight"], thread_name_prefix="bench_client")
        futures = []
        t_open = time.monotonic()
        self.t_open = t_open
        trace_at = None
        if record and self.tracer is not None:
            trace_at = w["trace"]["start_frac"] * self.seconds
        try:
            for r in results:
                wait = t_open + r["due"] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                now = time.monotonic() - t_open
                if trace_at is not None and now >= trace_at:
                    self.tracer.request_start()
                    trace_at = None
                    self._trace_stop_at = now + w["trace"]["slice_s"]
                if record and self.tracer is not None and trace_at is None and now >= self._trace_stop_at:
                    self.tracer.request_stop()
                fault = sysm.watch.fault()
                if fault:
                    log(f"engine fault, the remaining requests count as failed: {fault}")
                    for rest in results[r["i"] :]:
                        rest["status"] = "engine dead"
                    break
                with lock:
                    full = outstanding[0] >= w["max_in_flight"]
                    if not full:
                        outstanding[0] += 1
                if full:
                    r["status"] = "refused: max_in_flight outstanding"
                    continue
                futures.append(pool.submit(one, r, t_open))
            concurrent.futures.wait(futures, timeout=w["timeout_s"] + 5)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
        if record and self.tracer is not None:
            self.tracer.request_stop()
        return results

    # ------------------------------------------------------------- window
    def run_window(self) -> dict:
        self.results = self._play(self.schedule, record=True)
        w = self.w
        timeout_ms = w["timeout_s"] * 1000.0
        ok = [r for r in self.results if r["status"] == "ok"]
        failed = [r for r in self.results if r["status"] != "ok"]
        for r in failed[:5]:
            log(f"request {r['i']} failed: {r['status']}")
        lat = [(r["done"] - r["due"]) * 1000.0 for r in ok]
        slowest = max([timeout_ms, *lat])
        all_lat = lat + [slowest] * len(failed)
        late = [(r["sent"] - r["due"]) * 1000.0 for r in self.results if r["sent"] is not None]
        in_flight = peak_in_flight(ok)
        log(
            f"window: {len(ok)} answered, {len(failed)} failed of {len(self.results)}; "
            f"peak in flight {in_flight}; lateness p95 {np.percentile(late, 95) if late else float('nan'):.2f} ms"
        )
        return {
            "t_open": self.t_open,
            "metrics": {"retrieve_p50_ms": float(np.percentile(all_lat, 50))},
            "attempted": len(self.results),
            "failed": len(failed),
            "series": {"lateness_ms": late, "latency_ms": all_lat},
            "notes": {
                "peak_in_flight": in_flight,
                "answered_per_s": len(ok) / self.seconds,
                "latency_percentiles_ms": {str(p): float(np.percentile(all_lat, p)) for p in (25, 50, 75, 90, 95, 99, 100)},
                "requests_over_2x_p50": int(sum(1 for x in all_lat if x > 2 * np.percentile(all_lat, 50))),
            },
        }

    def drain(self) -> None:
        pass  # every request was waited for in the window

    def slice_readings(self, t_a: float, t_b: float) -> dict:
        """What happened between two harness-clock instants of the window."""
        a, b = t_a - self.t_open, t_b - self.t_open
        done = [r for r in self.results if r["status"] == "ok" and a <= r["done"] <= b]
        lat = [(r["done"] - r["due"]) * 1000.0 for r in done]
        tokens = [len(r["text"].split()) + 2 for r in done]
        return {"requests": len(done), "latency_ms": lat, "useful_tokens": tokens, "chunks": 0}

    def gap_label(self, t_rel_open: float) -> str:
        """What the harness saw the host doing at an instant of the window."""
        n = sum(1 for r in self.results if r["sent"] is not None and r["sent"] <= t_rel_open and (r["done"] is None or r["done"] > t_rel_open))
        return "request in flight" if n else "no request in flight"

    # -------------------------------------------------------------- check
    def check_sample(self) -> dict:
        c = self.w["check"]
        rng = np.random.default_rng([self.seed, 99])
        ok = [r for r in self.results if r["status"] == "ok"]
        asked = []
        if ok:
            longest = max(ok, key=lambda r: len(r["text"].split()))
            picks = rng.permutation(len(ok))[: c["sample_requests"]]
            asked = [ok[int(i)] for i in picks]
            if longest not in asked:
                asked[0] = longest
        ids = list(self.texts)
        longest_id = max(ids, key=lambda i: len(self.texts[i].split()))
        chunk_ids = [ids[int(i)] for i in rng.permutation(len(ids))[: c["sample_chunks"]]]
        if longest_id not in chunk_ids:
            chunk_ids[0] = longest_id
        return {
            "live_texts": self.texts,
            "reference_ids": ids,  # the reference embeds every live passage
            "chunk_ids": chunk_ids,
            "answers": [(r["text"], r["hits"]) for r in asked],
            "all_answers": [(r["text"], r["hits"]) for r in ok],
            "k": self.k,
        }


def peak_in_flight(ok: list[dict]) -> int:
    edges = sorted([(r["sent"], 1) for r in ok] + [(r["done"], -1) for r in ok])
    peak = cur = 0
    for _t, d in edges:
        cur += d
        peak = max(peak, cur)
    return peak
