"""System kind ``rag_answer``: the answer route, built as a user builds it.

``pw.io.jsonlines.read(dir, mode="streaming")`` -> ``DocumentStore(docs,
BruteForceKnnFactory(embedder=TPUEncoderEmbedder(<preset>, params=...),
reserved_space=<rows>), splitter=TokenCountSplitter(...))`` ->
``BaseRAGQuestionAnswerer(llm=TPUDecoderChat(<preset>, params=...),
search_topk=<k>)`` -> ``QARestServer(...).run(threaded=True)`` -> HTTP on
loopback, ``/v1/pw_ai_answer``: product defaults throughout, the default
prompt template among them.

Two model groups: ``embedder`` (what ``vector_store`` calls ``model``) and
``generator``; each group's family draws its parameters and says where what
the program built differs from the file.  Everything that concerns the index
under the server -- finding it, the filler, what is searchable, the warm-up
dispatches, the stored rows, stopping and freeing -- is ``vector_store``'s,
inherited.

``TPUDecoderChat`` is imported as this file is: on a commit whose program
has no generation stage the run ends at once in its line, ``correct`` false.
"""

from __future__ import annotations

import dataclasses
import socket
import time

import numpy as np

from benchmark import doors
from benchmark.system import SystemFault, free_port, log
from benchmark.systems import vector_store
from pathway_tpu.xpacks.llm.llms import TPUDecoderChat


class System(vector_store.System):
    def __init__(self, config: dict, seed: int, scratch: str, chips: int = 1):
        import gc

        gc.collect()  # two runs' parameters do not fit side by side: what an earlier run of this process left goes first
        super().__init__(dict(config, model=config["embedder"]), seed, scratch, chips=chips)
        self.config = config
        self.generator = config["generator"]
        self.generator_family = doors.family(self.generator, f"configs/{config.get('name')}.json `generator.family`")
        self.chat = None

    # ------------------------------------------------------------- build
    def start(self) -> None:
        import jax

        import pathway_tpu as pw
        from pathway_tpu.engine.external_index import ExternalIndexNode
        from pathway_tpu.internals.parse_graph import G
        from pathway_tpu.models import decoder
        from pathway_tpu.parallel import ShardedKnnIndex
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.stdlib.indexing.segments import SegmentedIndex
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
        from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
        from pathway_tpu.xpacks.llm.servers import QARestServer
        from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter

        log("program imported")
        program = self.config["program"]
        embedder_params = self.family.make_params(self.model, self.seed)
        generator_params = self.generator_family.make_params(self.generator, self.seed)
        self.params = {"embedder": embedder_params, "generator": generator_params}
        jax.block_until_ready(self.params)
        log("parameters drawn on the device")

        overrides = {}
        if "encoder_config" in program["embedder"]:  # toy rehearsal sizes only: no preset has them
            import jax.numpy as jnp

            from pathway_tpu.models import encoder as enc_mod

            fields = dict(program["embedder"]["encoder_config"])
            fields["dtype"] = getattr(jnp, fields["dtype"])
            overrides["config"] = dataclasses.replace(enc_mod.MINILM_L6, **fields)
        self.embedder = TPUEncoderEmbedder(program["embedder"]["preset"], params=embedder_params, **overrides)
        self._check_model(self.embedder.encoder.config)
        log("embedder built")

        gen = program["generator"]
        share = dict(gen["share"])
        if "rope_scaling" in share:  # toy rehearsal sizes only
            share["rope_scaling"] = tuple(sorted(share["rope_scaling"].items()))
        if "dtype" in share:
            import jax.numpy as jnp

            share["dtype"] = getattr(jnp, share["dtype"])
        decoder_config = dataclasses.replace(decoder.DEEPSEEK_V32_EXP, **share)
        wrong = self.generator_family.built_differs(self.generator, decoder_config)
        if wrong:
            raise SystemFault(f"the decoder built differs from the configuration file (built, file): {wrong}")
        self.chat = TPUDecoderChat(
            gen["preset"], config=decoder_config, params=generator_params, max_new_tokens=gen["max_new_tokens"],
            slots=gen["slots"], positions=gen["positions"], chunk_buckets=tuple(gen["chunk_buckets"]),
        )
        log("generator built")

        class Doc(pw.Schema):
            data: str

        docs = pw.io.jsonlines.read(self.corpus_dir, schema=Doc, mode="streaming")
        split = program["splitter"]
        store = DocumentStore(
            docs,
            retriever_factory=BruteForceKnnFactory(
                embedder=self.embedder, reserved_space=self.slab_cfg["capacity_rows"], delta_cap=program.get("delta_cap")
            ),
            splitter=TokenCountSplitter(min_tokens=split["min_tokens"], max_tokens=split["max_tokens"]),
        )
        self.rag = BaseRAGQuestionAnswerer(self.chat, store, search_topk=program["search_topk"])
        self.port = free_port()
        self.server = QARestServer("127.0.0.1", self.port, self.rag)
        self.watch.thread = self.server.run(threaded=True)
        # every route that queries the store is an index operator with an index of its own: QARestServer builds the
        # answer route's first and /v1/retrieve's second (a second slab of the same capacity, fed the same chunks,
        # which no request of this kind's traffic reaches); the filler and the handles below are the answer route's
        nodes = [n for n in G.engine_graph.nodes if isinstance(n, ExternalIndexNode)]
        if len(nodes) != 2:
            raise SystemFault(f"expected the answer route's and /v1/retrieve's index operators, found {len(nodes)}")
        self.node = nodes[0]
        self.other_index = nodes[1].adapter.index
        self.seg = self.node.adapter.index
        if not (isinstance(self.seg, SegmentedIndex) and isinstance(self.seg.main, ShardedKnnIndex)):
            raise SystemFault(f"the index is {type(self.seg).__name__}, not the HBM slab under its segment layer")
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
        log(f"server up on port {self.port}; slab {have}")

    def warm_generator(self) -> None:
        """Every program of the generator, once, on this thread."""
        self.chat.decoder.warm()

    def free(self) -> None:
        """Drop the program's device state (both slabs, the caches, the compiled
        programs); the parameters stay with ``self.params``, they are the
        benchmark's.  The program's own references to the parameters are cut
        here: the REST server's thread outlives ``stop`` and would keep 10.6 GB
        alive into the next run of this process (``control.py`` makes several)."""
        decoder = self.chat.decoder if self.chat is not None else None
        held = list(decoder.cache.values()) if decoder is not None else []
        main = getattr(getattr(self, "other_index", None), "main", None)
        held += [getattr(main, name, None) for name in ("_vectors", "_valid")]  # /v1/retrieve's slab
        for array in held:
            try:
                array.delete()
            except Exception:  # renamed, already donated or deleted
                pass
        if decoder is not None:
            decoder.params = decoder.cache = None
        if self.embedder is not None:
            self.embedder.encoder.params = None
        self.chat = self.rag = self.other_index = None
        super().free()
