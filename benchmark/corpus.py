"""Seeded text: documents, questions and their length distributions.

Every seed gets the same multiset of lengths (drawn once from the
workload's ``schedule_seed``) in another order, and other words: two seeds
then offer the same amount of work, so a metric's spread across seeds is
the system's and not the draw's.
"""

from __future__ import annotations

import json
import os

import numpy as np


def lengths(spec: dict, n: int, schedule_seed: int, seed: int, stream: int) -> np.ndarray:
    """``n`` word counts from ``spec`` = {"median", "sigma", "min", "max"}
    (lognormal, clipped): the multiset is fixed by ``schedule_seed``, its
    order by ``seed``."""
    base = np.random.default_rng([schedule_seed, stream])
    draws = base.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    words = np.clip(draws, spec["min"], spec["max"]).astype(int)
    return np.random.default_rng([seed, stream]).permutation(words)


def make_texts(prefix: str, start: int, word_counts, vocab_words: int, rng) -> list[str]:
    """One text per count: a unique leading ``<prefix><id>`` word, then
    ``count - 1`` words ``w<n>`` drawn uniformly from ``vocab_words``."""
    texts = []
    for i, count in enumerate(word_counts):
        words = rng.integers(0, vocab_words, size=max(int(count) - 1, 0))
        texts.append(f"{prefix}{start + i:07d} " + " ".join(f"w{w}" for w in words))
    return texts


def doc_id(text: str) -> str:
    """The unique leading word of a generated text."""
    return text.split(" ", 1)[0]


def write_jsonl(path: str, texts: list[str]) -> None:
    """One ``{"data": text}`` per line.  Written under a name the connector
    does not see; ``publish`` renames it in whole."""
    with open(path, "w") as f:
        for text in texts:
            f.write(json.dumps({"data": text}) + "\n")


def publish(staged: str, watched_dir: str) -> None:
    os.replace(staged, os.path.join(watched_dir, os.path.basename(staged)))
