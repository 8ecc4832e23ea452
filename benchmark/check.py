"""The comparison that decides ``correct``.

What the timed path produced -- the vectors it stored for the window's
chunks, and the answers the client received -- is held against the plain
reference (``reference.py``): float32 at ``highest`` precision over the same
seeded parameters, texts and filler.  Four numbers, each with a limit of its
own from the workload's file (PERF.md section 2 gives the readings each was
set from):

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
  or the filler, or are out of order, and sampled chunks stored nowhere.
  The limit is 0.

``control_side`` puts the reference in the program's place at the nearest
precision below the configuration's (fp8 for bfloat16): it has to fail.
"""

from __future__ import annotations

import numpy as np

from benchmark import corpus, reference, weights


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
    stacked = reference.stack_layers(params, model["num_hidden_layers"])
    emb = reference.embed(stacked, [live_texts[i] for i in ids], model, precision=precision)
    q_emb = reference.embed(stacked, questions, model, precision=precision) if questions else np.zeros((0, emb.shape[1]), np.float32)
    top_s = top_i = None
    if questions:
        blocks = [(0, emb)] if len(ids) else []
        top_s, top_i = reference.exact_topk(
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


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    compared = {name: {"value": numbers[name], "limit": limits[name]} for name in limits}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared
