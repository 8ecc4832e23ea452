"""Check kind ``answer``: what the answer route returned, held against the
plain references of both model groups.

Three numbers, each with a limit of its own from the workload's file:

- ``logit_gap``   for the ``sample_requests`` answered requests with the
  longest prompts: the widest distance between the float32 logits the timed
  path chose a token from (``TPUDecoderChat.recent_generations()``: what the
  program kept of the very generations it served) and the generator's
  reference, the full forward over the prompt and the ids the program
  emitted, at the prompt's last position and at every generated one, over
  the reference logits' standard deviation.  Logits and not tokens: with
  random weights the largest logit changes on rounding.  The median over the
  compared positions: a bfloat16 program and a float32 reference now and
  then route a token to another expert, or select another key, at a near
  tie (the 8th and 9th of 256 router scores; the 2,048th indexer score),
  which moves that position's logits by more than rounding does and says
  nothing of the arithmetic; ``logit_gap_max`` is given beside it.
- ``context_gap`` for ``context_requests`` answered requests drawn from the
  seed: how far the embedder's reference scores the chunk returned at rank i
  below the reference's own i-th best, over the chunks those requests
  returned, ``sample_chunks`` more and the filler (as ``retrieval``'s
  ``rank_gap`` where the reference embeds a sample).
- ``wrong``       (limit 0) over every answered request: a response of other
  than ``max_new_tokens`` tokens, one the program kept no generation for, an
  emitted id that is not the largest of the program's own kept logits or
  lies outside the held vocabulary, a prompt that is not the template over
  the returned chunks, other than ``search_topk`` chunks, a chunk that is
  not a live one or comes twice, and a slab that grew.

``collect`` runs while the system still stands; ``numbers`` once it is freed
(the parameters stay).  At a ``precision`` below the references' it gives the
control's side: both references at that precision in the program's place.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import corpus, doors
from benchmark.checks import retrieval
from benchmark.system import log
from benchmark.traffic.answer_open import chunk_texts, prompt_text

_TOKEN = re.compile(r"t(\d+)")


def collect(system, traffic, workload: dict) -> dict:
    return {
        "sample": traffic.check_sample(),
        "generations": system.chat.recent_generations(),
        "grew": system.slab.capacity != system.config["slab"]["capacity_rows"],
    }


def _emitted(text) -> list[int] | None:
    words = text.split(" ") if isinstance(text, str) else []
    matches = [_TOKEN.fullmatch(w) for w in words]
    return [int(m.group(1)) for m in matches] if words and all(matches) else None


def audit(answers: list, generations: list, family, group: dict, live_texts: dict, k: int, new_tokens: int):
    """Every answered request against the program's own record of it.
    Returns (how many are wrong, question -> its generation)."""
    by_prompt = {tuple(g["prompt_ids"]): g for g in generations}
    wrong, matched = 0, {}
    for question, answer in answers:
        chunks = chunk_texts(answer)
        ids = _emitted(answer.get("response")) if isinstance(answer, dict) else None
        bad = chunks is None or ids is None or len(ids) != new_tokens or len(chunks) != k
        if not bad:
            names = [corpus.doc_id(c) for c in chunks]
            bad = len(set(names)) != k or any(live_texts.get(n) != c for n, c in zip(names, chunks))
        gen = None if bad else by_prompt.get(tuple(family.token_ids(prompt_text(question, chunks), group["vocab_size"])))
        if gen is None:
            bad = True
        else:
            chosen = np.asarray(gen["ids"])
            bad = (
                list(chosen) != ids
                or gen["logits"].shape != (new_tokens, group["vocab_size"])
                or not np.array_equal(gen["logits"].argmax(axis=1), chosen)
                or chosen.min() < 0 or chosen.max() >= group["vocab_size"]
            )
        wrong += int(bad)
        if not bad:
            matched[question] = gen
    return wrong, matched


def logit_gaps(truth: list, got: list) -> dict:
    """Per compared position: the largest difference over the held
    vocabulary, over the reference logits' standard deviation."""
    gaps = np.concatenate([np.abs(g - t).max(axis=1) / t.std() for t, g in zip(truth, got)]) if truth else np.zeros(1)
    return {"logit_gap": float(np.median(gaps)), "logit_gap_max": float(gaps.max())}


def context_side(params, config: dict, workload: dict, seed: int, sample: dict, asked: list, precision: str):
    """The embedder's reference over the candidate chunks and the questions
    asked, and its exact top-k over them and the filler."""
    c = workload["check"]
    returned = sorted({corpus.doc_id(t) for _q, a in asked for t in chunk_texts(a)})
    rng = np.random.default_rng([seed, 98])
    others = [i for i in sample["reference_ids"] if i not in set(returned)]
    extra = [others[int(i)] for i in rng.permutation(len(others))[: c["sample_chunks"]]]
    as_store = dict(config, model=config["embedder"])
    return retrieval.reference_side(
        params["embedder"], as_store, seed, workload["filler_rows"], sample["live_texts"], [*returned, *extra],
        [q for q, _a in asked], sample["k"], precision=precision,
    )


def context_gap(ref: dict, ranked: list) -> float:
    """``ranked``: for each question the chunk ids in the order returned
    (``None``: a filler row).  ``retrieval``'s ``rank_gap`` over them."""
    answers = [(None, [(ident, 0.0) for ident in ids]) for ids in ranked]
    return retrieval.compare({}, [], answers, ref, 0)["rank_gap"]


def numbers(collected: dict, params, config: dict, workload: dict, seed: int, precision: str = "f32") -> dict:
    sample, group = collected["sample"], config["generator"]
    family = doors.family(group, f"configs/{config.get('name')}.json `generator.family`")
    wrong, matched = audit(sample["answers"], collected["generations"], family, group, sample["live_texts"], sample["k"], sample["new_tokens"])
    compared = [matched[q] for q, _a in sample["sampled"] if q in matched]
    wrong += len(sample["sampled"]) - len(compared)  # a sampled request with nothing to compare
    sequences = [np.concatenate([g["prompt_ids"], g["ids"][:-1]]).astype(np.int32) for g in compared]
    positions = [list(range(len(g["prompt_ids"]) - 1, len(g["prompt_ids"]) - 1 + len(g["ids"]))) for g in compared]
    positions_cache = config["program"]["generator"]["positions"]  # one padded length: one compiled program a layer

    def generator_side(precision: str) -> list:
        if not sequences:
            return []
        return family.reference_logits(params["generator"], group, sequences, positions, precision=precision, pad_to=positions_cache)

    rng = np.random.default_rng([seed, 97])
    ok = [(q, a) for q, a in sample["answers"] if q in matched]
    asked = [ok[int(i)] for i in rng.permutation(len(ok))[: workload["check"]["context_requests"]]]
    if "reference" not in collected:
        collected["reference"] = {
            "logits": generator_side("f32"),
            "context": context_side(params, config, workload, seed, sample, asked, "f32") if asked else None,
        }
        log(f"references: {len(sequences)} generations of {[len(s) for s in sequences]} tokens, {len(asked)} retrieves")
    ref = collected["reference"]
    if precision != "f32":
        out = logit_gaps(ref["logits"], generator_side(precision))
        out["context_gap"] = 0.0
        if asked:
            ctrl = context_side(params, config, workload, seed, sample, asked, precision)
            _stored, answers = retrieval.control_side(ctrl, [], sample["k"])
            out["context_gap"] = context_gap(ref["context"], [[ident for ident, _score in pairs] for _q, pairs in answers])
        return {**out, "wrong": 0}
    out = logit_gaps(ref["logits"], [g["logits"] for g in compared])
    out["context_gap"] = context_gap(ref["context"], [[corpus.doc_id(t) for t in chunk_texts(a)] for _q, a in asked]) if asked else 0.0
    return {**out, "wrong": wrong + int(collected["grew"])}
