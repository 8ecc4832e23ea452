"""The quickest proof that the system still starts on the chip.

One process drives the README's live-RAG path once, through the entry
points a user calls, at the full width and depth of BGE-large::

    pw.io.jsonlines.read -> VectorStoreServer(embedder=TPUEncoderEmbedder(
    "bge-large")) -> run_server(threaded=True) -> VectorStoreClient over HTTP

and checks what comes out by the repo's own means: every query is the
text of a chunk it indexed, so with seeded weights the top hit must be
that chunk at cosine ~1.0.  It refuses to run anywhere but on a TPU,
never sets ``JAX_PLATFORMS``, starts no child process, and prints one
JSON object as its last line of stdout only when every phase held.

    python chip_smoke.py          # on a machine with one chip, or four

``run_smoke`` is importable so the test suite can run the same body at a
toy size on the CPU (tests/test_chip_smoke.py); the device gate lives in
``main``.  Nothing it prints is a performance metric: ``setup_seconds``
is compilation and ingest of a cold process, reported as set-up.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import shutil
import socket
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
_T0 = time.monotonic()


class SmokeFailure(Exception):
    """A phase of the smoke did not hold."""


def _log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def write_corpus(path: str, start: int, n: int, seed: int, max_words: int) -> list[str]:
    """Write ``n`` one-chunk documents as one JSONL file and return their
    texts.  Lengths are lognormal (median ~60 words, a long tail clipped
    at ``max_words``), so a corpus of a few thousand fills every token
    bucket up to the encoder's limit; the leading ``doc<id>`` word makes
    each text unique."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.lognormal(np.log(60.0), 0.9, size=n), 4, max_words)
    texts = []
    for i, length in enumerate(lengths.astype(int)):
        words = rng.integers(0, 20_000, size=length - 1)
        texts.append(f"doc{start + i:06d} " + " ".join(f"w{w}" for w in words))
    tmp = path + ".writing"
    with open(tmp, "w") as f:
        for text in texts:
            f.write(json.dumps({"data": text}) + "\n")
    os.replace(tmp, path)  # the connector never sees a half-written file
    return texts


class _Engine:
    """Watches the server's engine thread so that nothing it swallows
    passes for health: ``run(threaded=True)`` runs ``pw.run`` on a daemon
    thread, where an uncaught exception only reaches ``threading``'s hook
    and an operator's exception (an XLA RESOURCE_EXHAUSTED in the
    embedder, say) is contained into the run's error log — either way
    the client would just sit in its timeout."""

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

    def check(self) -> None:
        from pathway_tpu.internals.parse_graph import G

        _require(not self.uncaught, f"uncaught exception in a thread: {self.uncaught}")
        _require(
            self.thread is not None and self.thread.is_alive(),
            "the engine thread (pw.run) has exited",
        )
        sched = getattr(G, "active_scheduler", None)
        errors = list(sched.ctx.error_log) if sched is not None else []
        _require(not errors, f"operator errors in the run's error log: {[str(e) for e in errors[:5]]}")

    def wait(self, future: concurrent.futures.Future, what: str, timeout_s: float):
        """The future's result, or a failure as soon as the engine shows
        one — a timeout is a failure, never a retry."""
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                return future.result(timeout=0.5)
            except concurrent.futures.TimeoutError:
                pass
            except Exception as e:  # the HTTP call itself failed
                self.check()
                raise SmokeFailure(f"{what}: {e!r}") from e
            self.check()
            _require(time.monotonic() < deadline, f"{what}: no answer in {timeout_s:.0f}s")

    def close(self) -> None:
        threading.excepthook = self._prev_hook


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_smoke(
    *,
    model: str = "bge-large",
    config=None,
    mesh=None,
    workdir: str,
    n_chunks: int = 4096,
    n_upsert: int = 256,
    n_queries: int = 32,
    k: int = 10,
    delta_cap: int | None = None,
    request_timeout_s: float = 600.0,
) -> dict:
    """Build the server, drive it, check it; returns the report dict.

    ``config`` overrides the architecture preset ``model`` names (the
    tier-1 test passes a 2-layer toy); ``mesh`` is threaded through both
    the embedder and the index.  Raises :class:`SmokeFailure` when a
    phase does not hold.
    """
    import jax
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.engine.external_index import ExternalIndexNode
    from pathway_tpu.internals import device_counters, native
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.ops.bucketing import bucket_size
    from pathway_tpu.parallel import ShardedKnnIndex
    from pathway_tpu.stdlib.indexing.segments import SegmentedIndex
    from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
    from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient, VectorStoreServer

    device = jax.devices()[0]
    _log(f"imports done; native extension loaded: {native.load() is not None}")
    shutil.rmtree(workdir, ignore_errors=True)
    corpus_dir = os.path.join(workdir, "corpus")
    os.makedirs(corpus_dir)

    # --- the embedder, by its defaults -----------------------------------
    embedder = TPUEncoderEmbedder(model, config=config, mesh=mesh)
    enc = embedder.encoder
    cfg = enc.config
    max_words = cfg.max_len - 2  # [CLS] + words + [SEP] fills the last bucket
    texts = write_corpus(
        os.path.join(corpus_dir, "part-000.jsonl"), 0, n_chunks, seed=0, max_words=max_words
    )

    def token_bucket(text: str) -> int:  # the padded length the tokenizer gives it
        return bucket_size(len(text.split()) + 2, min_bucket=16, max_bucket=cfg.max_len)

    buckets = [token_bucket(t) for t in texts]
    _log(
        f"embedder built; corpus: {n_chunks} chunks, token buckets "
        f"{ {b: buckets.count(b) for b in sorted(set(buckets))} }"
    )
    param_devices = {d for leaf in jax.tree.leaves(enc.params) for d in leaf.devices()}
    _require(
        all(d.platform == device.platform for d in param_devices),
        f"encoder params on {param_devices}, default device is {device}",
    )

    # --- the product path --------------------------------------------------
    class Doc(pw.Schema):
        data: str

    docs = pw.io.jsonlines.read(corpus_dir, schema=Doc, mode="streaming")
    capacity = bucket_size(n_chunks + n_upsert)  # no _grow during the run
    server = VectorStoreServer(
        docs,
        embedder=embedder,
        splitter=TokenCountSplitter(min_tokens=1, max_tokens=max_words),
        reserved_space=capacity,
        mesh=mesh,
        delta_cap=delta_cap,
    )

    engine = _Engine()
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=2)
    port = _free_port()
    seg = None
    try:
        engine.thread = server.run_server("127.0.0.1", port, threaded=True)
        client = VectorStoreClient(port=port, timeout=request_timeout_s)
        # run_server built the retrieve route, and with it the index operator
        index_nodes = [n for n in G.engine_graph.nodes if isinstance(n, ExternalIndexNode)]
        _require(len(index_nodes) == 1, f"expected one index operator, found {len(index_nodes)}")
        seg = index_nodes[0].adapter.index
        _require(
            isinstance(seg, SegmentedIndex) and isinstance(seg.main, ShardedKnnIndex),
            f"the index is {type(seg).__name__}({type(getattr(seg, 'main', None)).__name__}), "
            "not the HBM slab under its segment layer",
        )
        slab = seg.main
        # the webserver binds once pw.run has started its connectors
        deadline = time.monotonic() + 60
        while True:
            try:
                socket.create_connection(("127.0.0.1", port), timeout=1).close()
                break
            except OSError:
                engine.check()
                _require(time.monotonic() < deadline, "the REST port never opened")
                time.sleep(0.1)

        def ask(text: str, what: str) -> list[dict]:
            """One /v1/retrieve; the top hit must be ``text`` itself."""
            hits = engine.wait(pool.submit(client.query, text, k), what, request_timeout_s)
            _require(
                isinstance(hits, list) and len(hits) == k,
                f"{what}: expected {k} hits, got {hits!r:.300}",
            )
            top = hits[0]
            _require(
                top["text"] == text and abs(top["score"] - 1.0) < 0.01,
                f"{what}: top-1 is {top['text'][:30]!r} at {top['score']:.4f}, "
                f"wanted {text[:30]!r} at ~1.0",
            )
            scores = [h["score"] for h in hits]
            _require(
                all(np.isfinite(scores)) and scores == sorted(scores, reverse=True),
                f"{what}: scores not finite and descending: {scores}",
            )
            return hits

        # ingest: the first answer comes once the corpus epoch(s) are through
        while True:
            stats = engine.wait(
                pool.submit(client.get_vectorstore_statistics), "ingest", request_timeout_s
            )
            if stats["file_count"] >= n_chunks:
                break
            time.sleep(0.5)
        setup_s = time.monotonic() - _T0
        _log(f"ingest done: {seg.stats()}")

        # the queries: known chunks, at least one in every bucket from 128 up
        rng = np.random.default_rng(1)
        picks = list(rng.choice(n_chunks, size=n_queries, replace=False))
        for slot, want in enumerate(sorted({b for b in buckets if b >= 128})):
            if want not in {buckets[i] for i in picks}:
                picks[slot] = buckets.index(want)
        queries = [texts[i] for i in picks]
        query_buckets = sorted({buckets[i] for i in picks})
        needed = {b for b in (128, 512) if b <= cfg.max_len}
        _require(
            needed <= set(query_buckets),
            f"queries cover token buckets {query_buckets}, need {sorted(needed)}",
        )

        def query_pass(name: str) -> list[list[dict]]:
            return [ask(q, f"{name} request {i}") for i, q in enumerate(queries)]

        def memory_in_use() -> int | None:
            stats = device.memory_stats()
            return None if not stats else int(stats["bytes_in_use"])

        first = query_pass("pass 1")
        compiles_cold = device_counters.compile_count()
        mem_first = memory_in_use()
        _log(f"pass 1 ok: {len(first)} requests, {compiles_cold} compiles so far")
        # kept beside the corpus: a four-chip run is compared with a one-chip run
        doc_id = lambda hit: hit["text"].split(" ", 1)[0]  # noqa: E731
        with open(os.path.join(workdir, "top10.json"), "w") as f:
            json.dump([[(doc_id(h), round(h["score"], 5)) for h in hits] for hits in first], f)

        # an upsert batch lands while queries are in flight
        stop = threading.Event()

        def keep_asking() -> int:
            n = 0
            while not stop.is_set():
                for q in queries:
                    hits = client.query(q, k)
                    if hits[0]["text"] != q:
                        raise SmokeFailure(f"in-flight top-1 wrong for {q[:30]!r}")
                    n += 1
                    if stop.is_set():
                        break
            return n

        background = pool.submit(keep_asking)
        new_texts = write_corpus(
            os.path.join(corpus_dir, "part-001.jsonl"),
            n_chunks,
            n_upsert,
            seed=2,
            max_words=max_words,
        )
        # visible when the LAST new chunk answers for itself; its length
        # bucket is one the passes use, so the probe compiles nothing new
        probe = next(t for t in reversed(new_texts) if token_bucket(t) in query_buckets)
        deadline = time.monotonic() + request_timeout_s
        while True:
            hits = engine.wait(pool.submit(client.query, probe, k), "upsert probe", request_timeout_s)
            if hits and hits[0]["text"] == probe:
                break
            _require(time.monotonic() < deadline, "upserted chunks never became visible")
            time.sleep(0.2)
        stop.set()
        inflight_ok = engine.wait(background, "in-flight queries", request_timeout_s)
        if seg._maintenance is not None:
            seg._maintenance.drain(timeout=request_timeout_s)
        _log(f"upsert visible after {inflight_ok} in-flight requests: {seg.stats()}")

        # the window in which a warmed server must neither compile nor grow:
        # loaded programs count in bytes_in_use (~70 MB for one BGE-large
        # shape), so the baseline is taken after the upsert's own compiles
        compiles_before_second = device_counters.compile_count()
        mem_before_second = memory_in_use()
        second = query_pass("pass 2")
        compiles_second = device_counters.compile_count() - compiles_before_second
        mem_second = memory_in_use()
        engine.check()
    finally:
        sched = getattr(G, "active_scheduler", None)
        if sched is not None:
            sched.stop()
        if engine.thread is not None:
            engine.thread.join(timeout=30)
        pool.shutdown(wait=False, cancel_futures=True)
        engine.close()
        if seg is not None:
            seg.close()

    # --- did the chip do the work? -----------------------------------------
    seg_stats = seg.stats()
    total = n_chunks + n_upsert
    _require(seg_stats["size"] == total, f"index holds {seg_stats['size']} of {total} chunks")
    _require(
        seg_stats["main_size"] >= n_chunks - seg.delta_cap,
        f"main_size {seg_stats['main_size']} < corpus {n_chunks} - delta_cap {seg.delta_cap}: "
        "the host delta, not the slab, holds the corpus",
    )
    _require(seg_stats["merge_failures"] == 0, f"{seg_stats['merge_failures']} merges failed")
    _require(
        seg_stats["probes_dispatched"] >= 2 * n_queries,
        f"only {seg_stats['probes_dispatched']} searches were dispatched to the slab",
    )
    counters = device_counters.snapshot()
    _require(counters["h2d_bytes"] > 0, "no host->device bytes were counted")
    _require(
        compiles_second == 0,
        f"the second pass compiled {compiles_second} programs; a warmed server compiles none",
    )
    if mem_first is not None:
        # one query batch: 8 padded rows of ids/mask/types in, 8 embeddings
        # out, one top-k pair — everything else a request allocates is freed
        one_batch = 8 * cfg.max_len * 4 + 8 * cfg.hidden * 4 + 2 * 16 * 4
        _require(
            mem_second - mem_before_second <= one_batch,
            f"device bytes_in_use grew {mem_second - mem_before_second} over the second "
            f"pass ({mem_before_second} -> {mem_second}); one query batch is {one_batch}",
        )
    shards = slab._vectors.addressable_shards
    shard_devices = {s.device for s in shards}
    _require(
        all(d.platform == device.platform for d in shard_devices),
        f"index slab on {shard_devices}, default device is {device}",
    )
    n_dev = 1 if mesh is None else mesh.devices.size
    _require(
        len(shards) == n_dev
        and len(shard_devices) == n_dev
        and all(s.data.shape == (slab.capacity // n_dev, cfg.hidden) for s in shards),
        f"slab shards {[(s.device, s.data.shape) for s in shards]}: wanted {n_dev} "
        f"of {(slab.capacity // n_dev, cfg.hidden)} on distinct devices",
    )

    return {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_devices": len(jax.devices()),
        "mesh": None if mesh is None else dict(mesh.shape),
        "model": {
            "name": model,
            "layers": cfg.layers,
            "hidden": cfg.hidden,
            "heads": cfg.heads,
            "mlp_dim": cfg.mlp_dim,
            "dtype": np.dtype(cfg.dtype).name,
            "max_batch": enc.max_batch,
            "rows_per_dispatch": {
                str(b): enc._rows_per_dispatch(b) for b in sorted(set(buckets))
            },
        },
        "tokenizer": type(enc.tokenizer).__name__,
        "chunks_indexed": seg_stats["size"],
        "main_size": seg_stats["main_size"],
        "delta_cap": seg.delta_cap,
        "slab": {
            "capacity": slab.capacity,
            "dtype": np.dtype(slab.dtype).name,
            "shards": len(shards),
            "rows_per_shard": slab.capacity // n_dev,
        },
        "merges": seg_stats["merges_total"],
        "query_length_buckets": query_buckets,
        "requests_ok": len(first) + len(second) + inflight_ok,
        "requests_ok_in_flight_with_upsert": inflight_ok,
        "searches_dispatched": seg_stats["probes_dispatched"],
        "compiles_cold": compiles_cold,
        "compiles_total": counters["jit_compiles"],
        "compiles_second_pass": compiles_second,
        "h2d_bytes": counters["h2d_bytes"],
        "d2h_bytes": counters["d2h_bytes"],
        "device_bytes_in_use": {
            "after_pass_1": mem_first,
            "before_pass_2": mem_before_second,
            "after_pass_2": mem_second,
        },
        "setup_seconds": round(setup_s, 1),
        "wall_seconds": round(time.monotonic() - _T0, 1),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "native_extension_loaded": native.load() is not None,
    }


def main() -> int:
    # device first: before anything that builds a model is imported
    import jax

    devices = jax.devices()
    first = devices[0]
    print(
        f"backend={jax.default_backend()} device_kind={first.device_kind} "
        f"devices={len(devices)}",
        file=sys.stderr,
        flush=True,
    )
    if first.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found platform {first.platform!r} "
            f"({first.device_kind} x{len(devices)}); refusing to fall back",
            file=sys.stderr,
        )
        return 2

    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    try:
        from pathway_tpu.parallel import make_mesh
    except ImportError as e:
        print(f"chip_smoke: the program is not beside this script: {e}", file=sys.stderr)
        return 3

    try:
        report = run_smoke(
            mesh=make_mesh() if len(devices) > 1 else None,
            workdir=os.path.join(ROOT, ".chip_smoke"),
        )
        m = report["model"]
        _require(
            (m["layers"], m["hidden"], m["heads"], m["mlp_dim"], m["dtype"])
            == (24, 1024, 16, 4096, "bfloat16"),
            f"the encoder built is not BGE-large at full depth: {m}",
        )
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    # two lines: what was built and counted, then the result line, which
    # holds exactly "ok" and the device as JAX reports it and comes last
    print(json.dumps({"report": report, "claim": None}))
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": first.platform,
                    "kind": first.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
