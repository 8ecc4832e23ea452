"""System kind ``vector_store``: the README's live-RAG server, built as a
user builds it, and the few handles the harness needs on it.

``pw.io.jsonlines.read(dir, mode="streaming")`` ->
``VectorStoreServer(docs, embedder=TPUEncoderEmbedder(<preset>, params=...),
reserved_space=<rows>)`` -> ``run_server(threaded=True)`` ->
``VectorStoreClient`` over HTTP on loopback: product defaults throughout.
The way the index operator is found is copied from ``chip_smoke.py`` (PR 21).
The embedder is the configuration's ``model`` group; its family
(``families/<family>.py``) draws the parameters and says where the encoder
the program built differs from the file.

From the program the harness takes only this server, the index objects under
it (to put the filler in, to count what is searchable and to read back what
the timed path stored) and its counters.  Set-up goes through public doors
(``SegmentedIndex.add`` / ``search`` / ``stats``, ``ShardedKnnIndex
.add_batch_device``); what is still reached by a private name -- only to find
the index operator and to read stored rows back for the output check -- is
listed in ``benchmark/README.md`` as the contract a program change keeps.
"""

from __future__ import annotations

import os
import socket
import time

import numpy as np

from benchmark import doors, weights
from benchmark.system import EngineWatch, SystemFault, free_port, log


class System:
    def __init__(self, config: dict, seed: int, scratch: str, chips: int = 1):
        self.config = config
        self.chips = chips
        self.model = config["model"]  # the embedder
        self.family = doors.family(self.model, f"configs/{config.get('name')}.json `model.family`")
        self.slab_cfg = config["slab"]
        self.seed = seed
        self.corpus_dir = os.path.join(scratch, "corpus")
        self.staging_dir = os.path.join(scratch, "staging")
        os.makedirs(self.corpus_dir)
        os.makedirs(self.staging_dir)
        self.watch = EngineWatch()
        self.filler_rows = 0
        self.params = None
        self.seg = None
        self.slab = None
        self.node = None
        self.port = None
        self._stopped = False

    # ------------------------------------------------------------- build
    def start(self) -> None:
        import jax

        import pathway_tpu as pw
        from pathway_tpu.engine.external_index import ExternalIndexNode
        from pathway_tpu.internals.parse_graph import G
        from pathway_tpu.parallel import ShardedKnnIndex
        from pathway_tpu.stdlib.indexing.segments import SegmentedIndex
        from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
        from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter
        from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

        log("program imported")
        self.params = self.family.make_params(self.model, self.seed)
        jax.block_until_ready(self.params)
        log("parameters drawn on the device")
        program = self.config["program"]
        overrides = {}
        if "encoder_config" in program:  # toy rehearsal sizes only: no preset has them
            import dataclasses

            import jax.numpy as jnp

            from pathway_tpu.models import encoder as enc_mod

            fields = dict(program["encoder_config"])
            fields["dtype"] = getattr(jnp, fields["dtype"])
            overrides["config"] = dataclasses.replace(enc_mod.MINILM_L6, **fields)
        # a cell on more than one chip gets the program's own mesh over all of
        # them, threaded through embedder and index as chip_smoke.py does
        mesh = None
        if self.chips > 1:
            from pathway_tpu.parallel import make_mesh

            mesh = make_mesh()
        self.embedder = TPUEncoderEmbedder(program["preset"], params=self.params, mesh=mesh, **overrides)
        self._check_model(self.embedder.encoder.config)
        log("embedder built")

        class Doc(pw.Schema):
            data: str

        docs = pw.io.jsonlines.read(self.corpus_dir, schema=Doc, mode="streaming")
        split = program["splitter"]
        self.server = VectorStoreServer(
            docs,
            embedder=self.embedder,
            splitter=TokenCountSplitter(
                min_tokens=split["min_tokens"], max_tokens=split["max_tokens"]
            ),
            reserved_space=self.slab_cfg["capacity_rows"],
            mesh=mesh,
            delta_cap=program.get("delta_cap"),  # toy rehearsal sizes only; absent: the product's 1,024
        )
        log("server object built (the index factory probed the embedder's width: one (8, 16) dispatch)")
        self.port = free_port()
        self.watch.thread = self.server.run_server("127.0.0.1", self.port, threaded=True)
        nodes = [n for n in G.engine_graph.nodes if isinstance(n, ExternalIndexNode)]
        if len(nodes) != 1:
            raise SystemFault(f"expected one index operator, found {len(nodes)}")
        self.node = nodes[0]
        self.seg = self.node.adapter.index
        if not (isinstance(self.seg, SegmentedIndex) and isinstance(self.seg.main, ShardedKnnIndex)):
            raise SystemFault(
                f"the index is {type(self.seg).__name__}, not the HBM slab under its segment layer"
            )
        self.slab = self.seg.main
        want = (self.slab_cfg["capacity_rows"], self.slab_cfg["dim"], self.slab_cfg["itemsize"])
        have = (self.slab.capacity, self.slab.dim, np.dtype(self.slab.dtype).itemsize)
        if have != want:
            raise SystemFault(f"slab is (rows, dim, itemsize) {have}, the configuration states {want}")
        deadline = time.monotonic() + 60
        while True:  # the webserver binds once pw.run has started its connectors
            try:
                socket.create_connection(("127.0.0.1", self.port), timeout=1).close()
                break
            except OSError:
                self.require_healthy()
                if time.monotonic() > deadline:
                    raise SystemFault("the REST port never opened") from None
                time.sleep(0.05)
        log(f"server up on port {self.port}; slab {have}; mesh {None if self.slab.mesh is None else dict(self.slab.mesh.shape)}")

    def _check_model(self, cfg) -> None:
        wrong = self.family.built_differs(self.model, cfg)
        if wrong:
            raise SystemFault(f"the encoder built differs from the configuration file (built, file): {wrong}")

    def require_healthy(self) -> None:
        fault = self.watch.fault()
        if fault:
            raise SystemFault(fault)

    # ------------------------------------------------------------ filler
    def fill(self, workload: dict) -> None:
        """Put the workload's ``filler_rows`` seeded unit vectors into the slab
        the server built, under keys of their own, through public doors.
        Whole blocks go in on the device (``ShardedKnnIndex.add_batch_device``
        on ``seg.main``).  The last rows go through ``SegmentedIndex.add`` in
        batches of the sizes under ``warm_grid.scatter_rows`` (absent: one of
        ``delta_cap``): each is at least ``delta_cap`` with nothing buffered,
        so it takes the bulk-load branch the stream's epochs take, which
        compiles or loads the scatter program of that row bucket and brings
        the segment layer's key set up to date with the slab.  Nothing else
        touches the index yet: no document is written, no request sent."""
        rows, bulk_rows = workload["filler_rows"], workload["warm_grid"].get("scatter_rows")
        if rows == 0:
            return
        block = self.config["filler"]["block_rows"]
        dim = self.slab_cfg["dim"]
        bulk_rows = list(bulk_rows or [self.seg.delta_cap])
        if min(bulk_rows) < self.seg.delta_cap or sum(bulk_rows) > rows:
            raise SystemFault(
                f"filler: bulk batches {bulk_rows} must each reach delta_cap {self.seg.delta_cap} and fit in {rows} rows"
            )
        on_device = rows - sum(bulk_rows)
        cuts = np.cumsum([on_device, *bulk_rows])  # row where each bulk batch ends
        tail: list[tuple[int, np.ndarray]] = []
        for b in range(-(-rows // block)):
            first = b * block
            n = min(block, rows - first)
            vec = weights.filler_block(self.seed, b, block, dim)
            n_dev = min(max(on_device - first, 0), n)
            if n_dev:
                self.slab.add_batch_device([-(first + i) - 1 for i in range(n_dev)], vec, n_valid=n_dev)
            if n_dev < n:
                host = np.asarray(vec[n_dev:n])
                tail.extend((-(first + n_dev + i) - 1, host[i]) for i in range(n - n_dev))
        for a, z in zip(cuts[:-1], cuts[1:]):
            self.seg.add(tail[a - on_device : z - on_device])
        self.filler_rows = rows
        st = self.seg.stats()
        if st["main_size"] != rows or st["delta_size"]:
            raise SystemFault(f"filler: {rows} rows were to sit in the slab, the index reports {st}")
        log(f"filler: {rows} rows in the slab ({on_device} on the device, {bulk_rows} through the bulk path)")

    def searchable(self) -> int:
        """Live chunks a search can return now: main and delta, less filler."""
        st = self.seg.stats()
        return st["main_size"] + st["delta_size"] - self.filler_rows

    # ----------------------------------------------------------- warm-up
    def warm_encoder(self, rows: int, tokens: int) -> None:
        """One dispatch of the server's own encoder at (rows, tokens)."""
        text = " ".join(["w1"] * (tokens - 2))
        self.embedder.encoder.encode([text] * rows)

    def warm_search(self, rows: int, k: int) -> None:
        """One search of the server's own index with ``rows`` queries."""
        rng = np.random.default_rng(rows)
        self.seg.search(rng.standard_normal((rows, self.slab_cfg["dim"])).astype(np.float32), k)

    # -------------------------------------------------------- read-back
    def stored_vectors(self, ids: list[str]) -> dict[str, np.ndarray]:
        """What the timed path stored for the chunks whose text starts with
        each id: the slab's row, or the delta's vector where the chunk still
        sits there.  A chunk that is nowhere is left out."""
        from benchmark.corpus import doc_id
        from pathway_tpu.internals.parse_graph import G

        wanted = set(ids)
        docs = G.active_scheduler.ctx.state(self.node)["docs"]
        key_of = {}
        for key, (data, _meta) in list(docs.items()):
            text = data.get("text", "") if isinstance(data, dict) else ""
            ident = doc_id(text) if text else None
            if ident in wanted:
                key_of[ident] = key
        out: dict[str, np.ndarray] = {}
        slots, slot_ids = [], []
        for ident, key in key_of.items():  # the window has closed and drained: nothing else holds the index
            vec = self.seg._delta.get(key)
            if vec is None:
                vec = self.seg._frozen.get(key)
            if vec is not None:
                out[ident] = np.asarray(vec, np.float32)
            elif key in self.slab._slot_of:
                slots.append(self.slab._slot_of[key])
                slot_ids.append(ident)
        if slots:
            rows = np.asarray(self.slab._vectors[np.asarray(slots, np.int32)], np.float32)
            out.update(zip(slot_ids, rows))
        return out

    # ------------------------------------------------------------- stop
    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        from pathway_tpu.internals.parse_graph import G

        sched = getattr(G, "active_scheduler", None)
        if sched is not None:
            sched.stop()
        if self.watch.thread is not None:
            self.watch.thread.join(timeout=30)
        self.watch.close()
        if self.seg is not None:
            self.seg.close()

    def free(self) -> None:
        """Drop the program's device state (slab, compiled encoder); the
        parameters stay, they are the benchmark's."""
        from pathway_tpu.internals.parse_graph import G

        if self.slab is not None:
            for name in ("_vectors", "_valid"):  # by another name they go with the last reference
                try:
                    getattr(self.slab, name).delete()
                except Exception:  # renamed, already donated or deleted
                    pass
        self.server = self.embedder = self.seg = self.slab = self.node = None
        G.clear()
        import gc

        gc.collect()
