"""Check kind ``answer_drafted``: the answer route of a generator that drafts
with an MTP layer, held against the plain references of both model groups.

``answer``'s three numbers, by ``answer``'s functions (``logit_gap`` over the
kept main rows, ``context_gap``, ``wrong``), and one more:

- ``draft_gap``   for the same sampled requests: at every draft the program
  kept (``TPUDecoderChat.recent_generations()``' ``drafts``: the position it
  was made at and its ``DRAFT_KEPT`` largest logits with their ids), the
  widest distance between those logits and the reference's draft row at the
  same ids -- the family's MTP layer over the reference's own final-normed
  rows, from the same forward as the main rows -- over that row's standard
  deviation; the median over the drafts, as ``logit_gap`` is over positions
  (``draft_gap_max`` beside it).  With seeded weights the main model almost
  never keeps a draft, so the emitted ids and their rows would be the same
  with the MTP layer broken or skipped: only the drafts show it.

``wrong`` also counts a compared generation whose drafts are missing or out
of shape: a first draft not at the prompt's last position, positions not
increasing or past the answer's last but one, kept logits not in descending
order.
"""

from __future__ import annotations

import numpy as np

from benchmark import corpus, doors
from benchmark.checks import answer, retrieval
from benchmark.system import log
from benchmark.traffic.answer_open import chunk_texts

collect = answer.collect


def draft_gaps(truth: list, got: list) -> dict:
    """``truth``: per generation the reference's draft rows [n, vocab];
    ``got``: per generation ``(ids [n, k], logits [n, k])``."""
    gaps = [
        np.abs(values[i] - rows[i][ids[i]]).max() / rows[i].std()
        for rows, (ids, values) in zip(truth, got) for i in range(len(ids))
    ]
    return {"draft_gap": float(np.median(gaps)), "draft_gap_max": float(np.max(gaps))} if gaps else {"draft_gap": 0.0, "draft_gap_max": 0.0}


def _drafts_wrong(gen: dict, new_tokens: int) -> bool:
    d = gen.get("drafts")
    if d is None:
        return True
    at, P = np.asarray(d["positions"]), len(gen["prompt_ids"])
    return (
        at.size == 0 or at[0] != P - 1 or np.any(np.diff(at) <= 0) or at[-1] > P + new_tokens - 2
        or d["ids"].shape != d["logits"].shape or d["ids"].shape[0] != at.size or np.any(np.diff(d["logits"], axis=1) > 0)
    )


def numbers(collected: dict, params, config: dict, workload: dict, seed: int, precision: str = "f32") -> dict:
    sample, group = collected["sample"], config["generator"]
    family = doors.family(group, f"configs/{config.get('name')}.json `generator.family`")
    wrong, matched = answer.audit(sample["answers"], collected["generations"], family, group, sample["live_texts"], sample["k"], sample["new_tokens"])
    malformed = [q for q, gen in matched.items() if _drafts_wrong(gen, sample["new_tokens"])]
    wrong += len(malformed)
    compared = [matched[q] for q, _a in sample["sampled"] if q in matched and q not in malformed]
    wrong += len(sample["sampled"]) - len(compared)  # a sampled request with nothing to compare
    sequences = [np.concatenate([g["prompt_ids"], g["ids"]]).astype(np.int32) for g in compared]
    positions = [list(range(len(g["prompt_ids"]) - 1, len(g["prompt_ids"]) - 1 + len(g["ids"]))) for g in compared]
    at = [list(g["drafts"]["positions"]) for g in compared]
    got = [(g["drafts"]["ids"], g["drafts"]["logits"]) for g in compared]
    positions_cache = config["program"]["generator"]["positions"]  # one padded length: one compiled program a layer

    def generator_side(precision: str) -> tuple[list, list]:
        if not sequences:
            return [], []
        return family.reference_draft_logits(params["generator"], group, sequences, positions, at, precision=precision, pad_to=positions_cache)

    rng = np.random.default_rng([seed, 97])
    ok = [(q, a) for q, a in sample["answers"] if q in matched]
    asked = [ok[int(i)] for i in rng.permutation(len(ok))[: workload["check"]["context_requests"]]]
    if "reference" not in collected:
        main, drafts = generator_side("f32")
        collected["reference"] = {
            "logits": main, "drafts": drafts,
            "context": answer.context_side(params, config, workload, seed, sample, asked, "f32") if asked else None,
        }
        log(f"references: {len(sequences)} generations of {[len(s) for s in sequences]} tokens, {sum(map(len, at))} drafts, {len(asked)} retrieves")
    ref = collected["reference"]
    if precision != "f32":
        main, drafts = generator_side(precision)
        out = {**answer.logit_gaps(ref["logits"], main), **draft_gaps(ref["drafts"], [(ids, rows[np.arange(len(ids))[:, None], ids]) for (ids, _v), rows in zip(got, drafts)])}
        out["context_gap"] = 0.0
        if asked:
            ctrl = answer.context_side(params, config, workload, seed, sample, asked, precision)
            _stored, answers = retrieval.control_side(ctrl, [], sample["k"])
            out["context_gap"] = answer.context_gap(ref["context"], [[ident for ident, _score in pairs] for _q, pairs in answers])
        return {**out, "wrong": 0}
    out = {**answer.logit_gaps(ref["logits"], [g["logits"] for g in compared]), **draft_gaps(ref["drafts"], got)}
    out["context_gap"] = answer.context_gap(ref["context"], [[corpus.doc_id(t) for t in chunk_texts(a)] for _q, a in asked]) if asked else 0.0
    return {**out, "wrong": wrong + int(collected["grew"])}
