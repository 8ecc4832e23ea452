"""LLM xpack: embedders, splitters, rerankers, DocumentStore, RAG QA."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.models import BGE_RERANKER_BASE, MINILM_L6
from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
from pathway_tpu.xpacks.llm.document_store import DocumentStore
from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
from pathway_tpu.xpacks.llm.question_answering import (
    AdaptiveRAGQuestionAnswerer,
    BaseRAGQuestionAnswerer,
    answer_with_geometric_rag_strategy,
)
from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker, rerank_topk_filter
from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter, null_splitter
from tests.utils import T, run_to_rows

TINY = dataclasses.replace(
    MINILM_L6, layers=2, hidden=64, heads=4, mlp_dim=128, dtype=jnp.float32
)
TINY_CROSS = dataclasses.replace(
    BGE_RERANKER_BASE, layers=2, hidden=64, heads=4, mlp_dim=128, dtype=jnp.float32
)


class FakeChat:
    """Deterministic chat stub for QA tests."""

    def __init__(self, answer_if=None):
        self.calls = []
        self.answer_if = answer_if  # substring of prompt that unlocks answer

    def __wrapped__(self, messages):
        prompt = messages[-1]["content"]
        self.calls.append(prompt)
        if self.answer_if is None or self.answer_if in prompt:
            return "The answer is 42."
        return "No information found."


@pytest.fixture(scope="module")
def tiny_embedder():
    return TPUEncoderEmbedder(config=TINY)


def test_embedder_batches_per_epoch(tiny_embedder):
    docs = T(
        """
    d | text
    1 | apple pie
    2 | banana bread
    3 | cherry cake
    """
    )
    out = docs.select(emb=tiny_embedder(pw.this.text))
    rows = run_to_rows(out)
    assert len(rows) == 3
    assert np.asarray(rows[0][0]).shape == (64,)
    assert tiny_embedder.get_embedding_dimension() == 64


def test_splitters():
    text = "One sentence here. " * 30
    chunks = TokenCountSplitter(min_tokens=10, max_tokens=30).__wrapped__(text)
    assert len(chunks) > 1
    assert all(isinstance(c, tuple) and isinstance(c[1], dict) for c in chunks)
    assert null_splitter("abc") == [("abc", {})]


def test_rerank_topk_filter():
    docs = [{"text": f"d{i}"} for i in range(5)]
    scores = [0.1, 0.9, 0.5, 0.3, 0.8]
    kept, ks = rerank_topk_filter.__wrapped_fun__(docs, scores, 2)
    assert [d["text"] for d in kept] == ["d1", "d4"]
    assert ks == [0.9, 0.8]


def test_cross_encoder_reranker_batch():
    rr = CrossEncoderReranker(config=TINY_CROSS)
    scores = rr.__batch__(
        [{"text": "doc one"}, {"text": "doc two"}], ["q", "q"]
    )
    assert len(scores) == 2 and all(isinstance(s, float) for s in scores)


def _doc_store(tiny_embedder):
    docs = T(
        """
    d | data
    1 | apples grow on trees in the orchard
    2 | bananas are yellow tropical fruit
    3 | the tpu runs matrix multiplications fast
    """
    ).select(
        data=pw.this.data,
        _metadata=pw.apply(lambda d: {"path": f"/docs/{d}.txt"}, pw.this.d),
    )
    factory = BruteForceKnnFactory(embedder=tiny_embedder, reserved_space=32)
    return DocumentStore(docs, retriever_factory=factory)


def test_document_store_retrieve(tiny_embedder):
    store = _doc_store(tiny_embedder)
    queries = T(
        """
    q
    bananas
    """
    ).select(
        query=pw.this.q,
        k=pw.apply(lambda _q: 2, pw.this.q),
        metadata_filter=pw.apply(lambda _q: None, pw.this.q),
        filepath_globpattern=pw.apply(lambda _q: None, pw.this.q),
    )
    res = store.retrieve_query(queries)
    rows = run_to_rows(res)
    docs = rows[0][-1]
    assert len(docs) == 2
    assert all("text" in d and "score" in d and "metadata" in d for d in docs)
    # embedding is deterministic: the same text embeds to the same vector,
    # and 'bananas...' contains the query token so it should rank well —
    # but with random weights we only require the structure, not ranking.


def test_document_store_statistics_and_inputs(tiny_embedder):
    store = _doc_store(tiny_embedder)
    stats_q = T(
        """
    dummy
    x
    """
    ).select()
    stats = store.statistics_query(stats_q)
    rows = run_to_rows(stats)
    assert rows[0][0]["file_count"] == 3

    inputs_q = T(
        """
    dummy
    x
    """
    ).select(
        metadata_filter=pw.apply(lambda _d: None, pw.this.dummy),
        filepath_globpattern=pw.apply(lambda _d: "*1.txt", pw.this.dummy),
    )
    inputs = store.inputs_query(inputs_q)
    rows = run_to_rows(inputs)
    assert [f["path"] for f in rows[0][-1]] == ["/docs/1.txt"]


def test_base_rag_answerer(tiny_embedder):
    store = _doc_store(tiny_embedder)
    chat = FakeChat()
    rag = BaseRAGQuestionAnswerer(chat, store, search_topk=2)
    queries = T(
        """
    p
    what color are bananas?
    """
    ).select(
        prompt=pw.this.p,
        filters=pw.apply(lambda _p: None, pw.this.p),
        model=pw.apply(lambda _p: None, pw.this.p),
        return_context_docs=pw.apply(lambda _p: True, pw.this.p),
    )
    res = rag.answer_query(queries)
    rows = run_to_rows(res)
    out = rows[0][-1]
    assert out["response"] == "The answer is 42."
    assert len(out["context_docs"]) == 2
    assert len(chat.calls) == 1 and "bananas" in chat.calls[0]


def test_geometric_rag_strategy_escalates():
    chat = FakeChat(answer_if="doc3")
    answers = answer_with_geometric_rag_strategy(
        ["q"], [["doc1", "doc2", "doc3", "doc4"]], chat,
        n_starting_documents=1, factor=2, max_iterations=4,
    )
    assert answers == ["The answer is 42."]
    # escalation: 1 doc -> 2 docs -> 4 docs (includes doc3)
    assert len(chat.calls) == 3


def test_hybrid_index_with_embedder(tiny_embedder):
    """Hybrid KNN+BM25 over raw text: each child must apply its own
    embedding (regression: child embedders were ignored)."""
    from pathway_tpu.stdlib.indexing import HybridIndexFactory, TantivyBM25Factory

    docs = T(
        """
    d | text
    1 | apples grow on trees
    2 | bananas are yellow
    """
    )
    queries = T(
        """
    q
    bananas
    """
    )
    factory = HybridIndexFactory(
        retriever_factories=[
            BruteForceKnnFactory(embedder=tiny_embedder, reserved_space=16),
            TantivyBM25Factory(),
        ]
    )
    index = factory.build_data_index(docs.text, docs)
    res = index.query_as_of_now(queries.q, number_of_matches=2)
    rows = run_to_rows(res)
    returned = [d["text"] for d in rows[0][-1]]
    assert len(returned) == 2
    # BM25 leg guarantees the exact-token match ranks first under RRF
    assert returned[0] == "bananas are yellow"


def test_batch_udf_screens_errors():
    """One None/ERROR row must not poison the epoch batch."""
    calls = []

    @pw.udfs.batch_udf(return_type=float, propagate_none=True)
    def length(texts):
        calls.append(list(texts))
        assert all(t is not None for t in texts)
        return [float(len(t)) for t in texts]

    t = T(
        """
    a | b
    1 | hello
    2 | __none__
    """
    ).select(b=pw.apply(lambda b: None if b == "__none__" else b, pw.this.b))
    out = t.select(n=length(pw.this.b))
    rows = run_to_rows(out)
    assert sorted(rows, key=str) == sorted([(5.0,), (None,)], key=str)
    assert calls == [["hello"]]


def test_adaptive_rag_answerer(tiny_embedder):
    store = _doc_store(tiny_embedder)
    chat = FakeChat()
    rag = AdaptiveRAGQuestionAnswerer(
        chat, store, n_starting_documents=1, factor=2, max_iterations=2
    )
    queries = T(
        """
    p
    what is a tpu?
    """
    ).select(
        prompt=pw.this.p,
        filters=pw.apply(lambda _p: None, pw.this.p),
        model=pw.apply(lambda _p: None, pw.this.p),
        return_context_docs=pw.apply(lambda _p: False, pw.this.p),
    )
    rows = run_to_rows(rag.answer_query(queries))
    assert rows[0][-1]["response"] == "The answer is 42."


def test_document_store_ingests_html_and_docx(tiny_embedder):
    """DocumentStore ingests binary .html/.docx via ParseUnstructured's
    built-in extractors; chunks carry element-category metadata."""
    from tests.test_parsers import _HTML, _minimal_docx
    from pathway_tpu.xpacks.llm.parsers import ParseUnstructured

    files = [("page.html", _HTML), ("report.docx", _minimal_docx())]
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=bytes, _metadata=dict),
        [(data, {"path": f"/in/{name}"}) for name, data in files],
    )
    factory = BruteForceKnnFactory(embedder=tiny_embedder, reserved_space=32)
    store = DocumentStore(
        docs,
        retriever_factory=factory,
        parser=ParseUnstructured(mode="elements"),
    )
    inputs_q = T(
        """
    dummy
    x
    """
    ).select(
        metadata_filter=pw.apply(lambda _q: None, pw.this.dummy),
        filepath_globpattern=pw.apply(lambda _q: None, pw.this.dummy),
    )
    listing = run_to_rows(store.inputs_query(inputs_q))
    paths = {d["path"] for d in listing[0][0]}
    assert paths == {"/in/page.html", "/in/report.docx"}

    queries = T(
        """
    q
    revenue
    """
    ).select(
        query=pw.this.q,
        k=pw.apply(lambda _q: 4, pw.this.q),
        metadata_filter=pw.apply(lambda _q: None, pw.this.q),
        filepath_globpattern=pw.apply(lambda _q: None, pw.this.q),
    )
    res = run_to_rows(store.retrieve_query(queries))
    docs_out = res[0][-1]
    assert docs_out, "retrieval returned nothing"
    texts = " ".join(d["text"] for d in docs_out)
    all_meta = [d["metadata"] for d in docs_out]
    # chunks originate from parsed blocks with category metadata
    assert any(m.get("category") in
               ("Title", "NarrativeText", "ListItem", "Table")
               for m in all_meta), all_meta
    assert "Revenue" in texts or "Apples" in texts
