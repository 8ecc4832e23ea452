"""System kind ``rag_generator``: ``rag_answer``'s answer route, with the
generator's base configuration found by the name of its preset.

``rag_answer`` builds every generator from one architecture's published
configuration; here ``program.generator.preset`` is looked up among the
program's own decoder presets (``xpacks/llm/llms.py`` ``decoder_preset``)
and ``program.generator.share`` is laid over what it gives, so that a
further generator needs no system kind of its own.  The look-up comes first,
in the constructor: on a program that has no such preset (or no presets to
look up, as before this kind was added) the run ends at once in its line,
``correct`` false, before a parameter is drawn.

Everything else -- the embedder group, the slab, the filler, the two index
operators, warming, stopping, freeing -- is ``rag_answer``'s and
``vector_store``'s, inherited; ``start`` is ``rag_answer``'s wiring with the
one line that names an architecture replaced.
"""

from __future__ import annotations

import dataclasses
import socket
import time

import numpy as np

from benchmark.system import SystemFault, free_port, log
from benchmark.systems import rag_answer


class System(rag_answer.System):
    def __init__(self, config: dict, seed: int, scratch: str, chips: int = 1):
        preset = config["program"]["generator"]["preset"]
        try:
            from pathway_tpu.xpacks.llm.llms import decoder_preset
        except ImportError:
            raise SystemFault(f"this program has no decoder presets to look {preset!r} up in (xpacks/llm/llms.py decoder_preset)") from None
        try:
            self.base_config = decoder_preset(preset)
        except ValueError as e:
            raise SystemFault(f"configs/{config.get('name')}.json `program.generator.preset`: {e}") from None
        super().__init__(config, seed, scratch, chips=chips)

    def start(self) -> None:
        import jax
        import jax.numpy as jnp

        import pathway_tpu as pw
        from pathway_tpu.engine.external_index import ExternalIndexNode
        from pathway_tpu.internals.parse_graph import G
        from pathway_tpu.parallel import ShardedKnnIndex
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.stdlib.indexing.segments import SegmentedIndex
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
        from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
        from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
        from pathway_tpu.xpacks.llm.servers import QARestServer
        from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter

        log("program imported")
        program = self.config["program"]
        gen = program["generator"]
        share = dict(gen["share"])
        if "dtype" in share:  # toy rehearsal sizes only
            share["dtype"] = getattr(jnp, share["dtype"])
        decoder_config = dataclasses.replace(self.base_config, **share)
        wrong = self.generator_family.built_differs(self.generator, decoder_config)
        if wrong:
            raise SystemFault(f"the decoder built differs from the configuration file (built, file): {wrong}")
        embedder_params = self.family.make_params(self.model, self.seed)
        generator_params = self.generator_family.make_params(self.generator, self.seed)
        self.params = {"embedder": embedder_params, "generator": generator_params}
        jax.block_until_ready(self.params)
        log("parameters drawn on the device")

        overrides = {}
        if "encoder_config" in program["embedder"]:  # toy rehearsal sizes only: no preset has them
            from pathway_tpu.models import encoder as enc_mod

            fields = dict(program["embedder"]["encoder_config"])
            fields["dtype"] = getattr(jnp, fields["dtype"])
            overrides["config"] = dataclasses.replace(enc_mod.MINILM_L6, **fields)
        self.embedder = TPUEncoderEmbedder(program["embedder"]["preset"], params=embedder_params, **overrides)
        self._check_model(self.embedder.encoder.config)
        log("embedder built")
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
        # as in rag_answer: the answer route's index operator first, /v1/retrieve's (a slab of its own) second
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
