"""Check kind ``retrieval``: a vector store's stored vectors and answers.

What the timed path produced -- the vectors it stored for the window's
chunks, and the answers the client received -- is held against the plain
reference of the embedder's family (``families/<family>.py``: float32 at
``highest`` precision over the same seeded parameters, texts and filler) and
an exact top-k over everything the slab holds.  Four numbers, each with a
limit of its own from the workload's file (PERF.md section 2 gives the
readings each was set from):

- ``emb_gap``   widest distance between a stored vector and the reference's
  embedding of that chunk (both unit length);
- ``score_gap`` widest gap between a returned score and the reference's score
  for the same question and chunk;
- ``rank_gap``  widest amount by which the reference's score of the hit
  returned at rank i lies below the reference's own i-th best over everything
  the slab holds, filler included (where the reference embeds only a sample
  of the live chunks, as in the ingest cell, over that sample, the returned
  chunks and the filler);
- ``wrong``     answers that are malformed, name a chunk that does not exist
  or the filler, or are out of order, sampled chunks stored nowhere, and a
  slab that grew.  The limit is 0.

``collect`` runs while the system still stands, on a ``vector_store`` system
and a traffic kind whose ``check_sample`` draws the sample from the seed;
``numbers`` runs once the system is freed.  With a ``precision`` other than
the reference's, ``numbers`` gives the control's side: the reference put in
the program's place at the nearest precision below the configuration's (fp8
for bfloat16).  It has to fail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import corpus, doors, weights
from benchmark.system import log


def ask_after_window(system, texts: list[str], k: int, timeout_s: float) -> list:
    """The ingest cell reads its own chunks back through the user's door."""
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient

    client = VectorStoreClient(port=system.port, timeout=timeout_s)
    answers = []
    for text in texts:
        try:
            answers.append((text, client.query(text, k)))
        except Exception as e:  # counted as a wrong answer by the parser
            log(f"read-back failed: {type(e).__name__}: {e}")
            answers.append((text, None))
    return answers


def parse_answers(answers, live_texts: dict, k: int):
    """REST answers -> ([(question, [(id, score), ...])], wrong count)."""
    out, wrong = [], 0
    for question, hits in answers:
        bad = not isinstance(hits, list) or len(hits) != k
        pairs = []
        for h in hits if isinstance(hits, list) else []:
            text = h.get("text") if isinstance(h, dict) else None
            ident = corpus.doc_id(text) if text else None
            score = h.get("score") if isinstance(h, dict) else None
            if ident not in live_texts or live_texts[ident] != text or not isinstance(score, float) or not np.isfinite(score):
                bad = True  # the filler carries no text: it lands here too
                continue
            pairs.append((ident, score))
        scores = [s for _i, s in pairs]
        if scores != sorted(scores, reverse=True) or len({i for i, _s in pairs}) != len(pairs):
            bad = True
        wrong += bad
        out.append((question, pairs))
    return out, wrong


def _filler_blocks(seed: int, config: dict, rows: int, first: int):
    block = config["filler"]["block_rows"]
    for b in range(-(-rows // block)):
        n = min(block, rows - b * block)
        yield first + b * block, weights.filler_block(seed, b, block, config["slab"]["dim"])[:n]


def reference_side(params, config: dict, seed: int, filler_rows: int, live_texts: dict, ids: list, questions: list, k: int, precision: str = "f32"):
    """Embeddings of ``ids`` and ``questions`` and the exact top-k of each
    question over those embeddings and the filler."""
    model = config["model"]
    fam = doors.family(model, f"configs/{config.get('name')}.json `model.family`")
    stacked = fam.stack_layers(params, model["num_hidden_layers"])
    emb = fam.embed(stacked, [live_texts[i] for i in ids], model, precision=precision)
    q_emb = fam.embed(stacked, questions, model, precision=precision) if questions else np.zeros((0, emb.shape[1]), np.float32)
    top_s = top_i = None
    if questions:
        blocks = [(0, emb)] if len(ids) else []
        top_s, top_i = exact_topk(
            q_emb, [*blocks, *_filler_blocks(seed, config, filler_rows, len(ids))], min(k, len(ids) + filler_rows)
        )
    return {"ids": list(ids), "row": {i: n for n, i in enumerate(ids)}, "emb": emb, "q_emb": q_emb, "top_s": top_s, "top_i": top_i}


def control_side(ctrl: dict, chunk_ids: list, k: int):
    """The control's side of the comparison: what it would have stored and
    answered, in the shape ``compare`` takes from the program."""
    stored = {i: ctrl["emb"][ctrl["row"][i]] for i in chunk_ids}
    answers = []
    n_live = len(ctrl["ids"])
    for qi in range(ctrl["q_emb"].shape[0]):
        pairs = [
            (ctrl["ids"][int(r)] if r < n_live else None, float(s))
            for s, r in zip(ctrl["top_s"][qi][:k], ctrl["top_i"][qi][:k])
        ]
        answers.append((None, pairs))
    return stored, answers


def compare(stored: dict, chunk_ids: list, answers: list, ref: dict, wrong: int) -> dict:
    """The four numbers.  ``answers`` are ``(question, [(id, score), ...])``
    aligned with ``ref["q_emb"]``; an id of ``None`` is a filler row."""
    emb_gap = 0.0
    for ident in chunk_ids:
        vec = stored.get(ident)
        if vec is None:
            wrong += 1
            continue
        r = ref["emb"][ref["row"][ident]].astype(np.float64)
        emb_gap = max(emb_gap, float(np.linalg.norm(np.asarray(vec, np.float64) - r)))
    score_gap = rank_gap = 0.0
    for qi, (_question, pairs) in enumerate(answers):
        q = ref["q_emb"][qi].astype(np.float64)
        for rank, (ident, score) in enumerate(pairs):
            if ident is None:
                wrong += 1
                continue
            true = float(q @ ref["emb"][ref["row"][ident]].astype(np.float64))
            score_gap = max(score_gap, abs(score - true))
            if rank < ref["top_s"].shape[1]:
                rank_gap = max(rank_gap, float(ref["top_s"][qi][rank]) - true)
    return {"emb_gap": emb_gap, "score_gap": score_gap, "rank_gap": rank_gap, "wrong": wrong}


@jax.jit
def _block_scores(q, block):
    return jnp.einsum(
        "qd,nd->qn", q, block, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def exact_topk(queries: np.ndarray, blocks, k: int):
    """Exact top-k of ``queries @ rows.T`` over an iterable of
    ``(first_row_id, rows[n, d])`` blocks, one block on the device at a time.
    Returns (scores [q, k] descending, row ids [q, k])."""
    q = jnp.asarray(queries, jnp.float32)
    best_s = np.full((queries.shape[0], 0), -np.inf, np.float32)
    best_i = np.zeros((queries.shape[0], 0), np.int64)
    for first, rows in blocks:
        s = _block_scores(q, jnp.asarray(rows, jnp.float32))
        kk = min(k, s.shape[1])
        top_s, top_i = jax.lax.top_k(s, kk)
        best_s = np.concatenate([best_s, np.asarray(top_s)], axis=1)
        best_i = np.concatenate([best_i, np.asarray(top_i).astype(np.int64) + first], axis=1)
        keep = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(best_s, keep, axis=1)
        best_i = np.take_along_axis(best_i, keep, axis=1)
    return best_s, best_i


def collect(system, traffic, workload: dict) -> dict:
    """What the timed path produced, taken while the system still stands: the
    traffic kind's sample, the sampled chunks asked again at the user's door
    where the sample says so, the rows stored for them, and whether the slab
    grew."""
    grew = system.slab.capacity != system.config["slab"]["capacity_rows"]
    sample = traffic.check_sample()
    if sample.get("ask"):
        if system.watch.fault():  # nobody answers: every read-back counts as wrong
            sample["answers"] = [(text, None) for text in sample["ask"]]
        else:
            sample["answers"] = ask_after_window(system, sample["ask"], sample["k"], workload["check"]["timeout_s"])
    return {"sample": sample, "stored": system.stored_vectors(sample["chunk_ids"]), "grew": grew}


def numbers(collected: dict, params, config: dict, workload: dict, seed: int, precision: str = "f32") -> dict:
    """The four numbers: of the program's side against the reference or, at a
    ``precision`` below the reference's, of the control's side.  The
    reference's side is reckoned once and kept in ``collected``."""
    sample = collected["sample"]
    answers, wrong = parse_answers(sample["answers"], sample["live_texts"], sample["k"])
    returned = {i for _q, pairs in answers for i, _s in pairs}
    ref_ids = list(dict.fromkeys([*sample["reference_ids"], *sorted(returned)]))

    def side(precision: str) -> dict:
        return reference_side(
            params, config, seed, workload["filler_rows"], sample["live_texts"], ref_ids,
            [q for q, _p in answers], sample["k"], precision=precision,
        )

    if "reference" not in collected:
        collected["reference"] = side("f32")
    ref = collected["reference"]
    if precision != "f32":
        c_stored, c_answers = control_side(side(precision), sample["chunk_ids"], sample["k"])
        return compare(c_stored, sample["chunk_ids"], c_answers, ref, 0)
    _, wrong_all = parse_answers(sample["all_answers"], sample["live_texts"], sample["k"])
    return compare(collected["stored"], sample["chunk_ids"], answers, ref, wrong + wrong_all + int(collected["grew"]))
