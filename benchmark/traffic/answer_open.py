"""Traffic kind ``answer_open``: an open loop of questions on the answer
route (``/v1/pw_ai_answer``) over a resident corpus.

Every request is a retrieve (the question embedded, the slab scanned, the
``search_topk`` best chunks returned) followed by a generation over the
prompt those chunks make, the whole answer in one response, asked with
``return_context_docs`` true so that the response carries the chunks too.
Set-up ingests the workload's live chunks through the product path, drives
the embedder's and the index's shape grid and every program of the
generator, and plays a few real requests.  The window is ``retrieve_open``'s:
one fixed sequence of gaps, question lengths and which questions repeat a
chunk's first words (from ``schedule_seed``), reordered in blocks by the
seed, which also chooses the words; a request is timed from the instant it
was due; when ``max_in_flight`` are outstanding a due request is not sent
and counts as failed, as does a time-out or an HTTP error.

What the window reports under ``retrieve_p50_ms`` is the median over all its
requests of due time -> whole answer received (PERF.md section 2 says why an
answer cell reports it under that name for now).
"""

from __future__ import annotations

import concurrent.futures
import json
import threading
import time
import urllib.request

import numpy as np

from benchmark import corpus
from benchmark.system import log
from benchmark.traffic import retrieve_open

ROUTE = "/v1/pw_ai_answer"


def prompt_text(question: str, chunks: list[str]) -> str:
    """The product's default answer template (``prompts.prompt_qa_geometric_rag``
    as ``BaseRAGQuestionAnswerer`` uses it), the yardstick's own copy."""
    return (
        "Use the below documents to answer the question. If the documents "
        "do not contain the answer, reply exactly: No information found."
        "\n\nDocuments:\n" + "\n\n".join(chunks) + f"\n\nQuestion: {question}\nAnswer:"
    )


def chunk_texts(answer) -> list[str] | None:
    """The retrieved chunks' texts of a response, or ``None`` where the
    response is not of the route's shape."""
    docs = answer.get("context_docs") if isinstance(answer, dict) else None
    if not isinstance(docs, list) or not all(isinstance(d, dict) and isinstance(d.get("text"), str) for d in docs):
        return None
    return [d["text"] for d in docs]


class Traffic(retrieve_open.Traffic):
    """``retrieve_open``'s set-up, schedule, window arithmetic and labels;
    the request itself and what a slice of the window did are the answer
    route's."""

    def __init__(self, system, workload: dict, seed: int, seconds: float, tracer):
        super().__init__(system, dict(workload, k=system.config["program"]["search_topk"]), seed, seconds, tracer)
        self.new_tokens = system.config["program"]["generator"]["max_new_tokens"]

    def setup(self) -> None:
        from pathway_tpu.internals import device_counters

        self.system.warm_generator()
        log(f"generator warmed; {device_counters.compile_count()} compiles so far; device memory {self.system_memory()}")
        super().setup()
        log(f"set-up done; device memory {self.system_memory()}")

    @staticmethod
    def system_memory() -> dict:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}

    # ------------------------------------------------------------ sending
    def _play(self, schedule: list[tuple[float, str]], record: bool) -> list[dict]:
        w, sysm = self.w, self.system
        url = f"http://127.0.0.1:{sysm.port}{ROUTE}"
        lock = threading.Lock()
        outstanding = [0]
        results = [
            {"i": i, "due": due, "text": text, "status": "unsent", "sent": None, "done": None, "answer": None}
            for i, (due, text) in enumerate(schedule)
        ]

        def one(r: dict, t_open: float) -> None:
            r["sent"] = time.monotonic() - t_open
            try:
                body = json.dumps({"prompt": r["text"], "return_context_docs": True}).encode()
                request = urllib.request.Request(url, data=body, headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(request, timeout=w["timeout_s"]) as response:
                    r["answer"] = json.loads(response.read())
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
        trace_at = w["trace"]["start_frac"] * self.seconds if record and self.tracer is not None else None
        try:
            for r in results:
                wait = t_open + r["due"] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                now = time.monotonic() - t_open
                if trace_at is not None and now >= trace_at:
                    self.tracer.request_start()
                    trace_at = None
                    # arrivals are a second or more apart: the slice is closed on the clock, not by the next arrival
                    closer = threading.Timer(w["trace"]["slice_s"], self.tracer.request_stop)
                    closer.daemon = True
                    closer.start()
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
        window = super().run_window()
        prompts = [self.prompt_tokens(r) for r in self.results if r["status"] == "ok"]
        window["notes"]["prompt_tokens"] = {"min": min(prompts, default=0), "mean": float(np.mean(prompts)) if prompts else 0.0, "max": max(prompts, default=0)}
        return window

    def prompt_tokens(self, r: dict) -> int:
        """One token a word: the words of the prompt the answer's chunks make."""
        chunks = chunk_texts(r["answer"]) or []
        return len(prompt_text(r["text"], chunks).split())

    def slice_readings(self, t_a: float, t_b: float) -> dict:
        """What happened between two harness-clock instants of the window,
        cut to whole requests: the engine answers one request at a time, in
        the order they came, so the device's work for a request lies between
        the answer before it and its own.  The clip runs from the first
        instant of the slice at which no request is being answered (the
        slice's start, or the first answer after it) to the last answer
        inside it."""
        a, b = t_a - self.t_open, t_b - self.t_open
        ok = sorted((r for r in self.results if r["status"] == "ok"), key=lambda r: r["done"])
        busy_at_a = any(r["sent"] < a < r["done"] for r in ok)
        inside = [r for r in ok if a <= r["done"] <= b]
        if busy_at_a and inside:
            a, inside = inside[0]["done"], inside[1:]
        if inside:
            b = inside[-1]["done"]
        return {
            "requests": len(inside),
            "latency_ms": [(r["done"] - r["due"]) * 1000.0 for r in inside],
            "useful_tokens": {
                "embedder": [len(r["text"].split()) + 2 for r in inside],
                "generator": [(self.prompt_tokens(r), self.new_tokens - 1) for r in inside],
            },
            "chunks": 0,
            "clip": (self.t_open + a, self.t_open + b),
        }

    # -------------------------------------------------------------- check
    def check_sample(self) -> dict:
        """Every answered request, and the ``sample_requests`` of them with
        the longest prompts for the generator's comparison."""
        ok = [r for r in self.results if r["status"] == "ok"]
        longest = sorted(ok, key=lambda r: -self.prompt_tokens(r))[: self.w["check"]["sample_requests"]]
        return {
            "live_texts": self.texts,
            "reference_ids": list(self.texts),
            "answers": [(r["text"], r["answer"]) for r in ok],
            "sampled": [(r["text"], r["answer"]) for r in longest],
            "k": self.k,
            "new_tokens": self.new_tokens,
        }
