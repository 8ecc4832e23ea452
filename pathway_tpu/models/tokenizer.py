"""Tokenizers feeding the TPU encoders.

Two implementations behind one interface:

- :class:`HFTokenizer` — wraps a locally cached HuggingFace tokenizer
  when one is available (the environment has no network egress, so this
  is gated on the local cache: :func:`local_tokenizer_dir` looks for its
  files, and ``transformers`` is imported only once they are found).
- :class:`HashTokenizer` — deterministic hashing WordPiece stand-in:
  lowercase, split on non-alphanumerics, id = stable 64-bit hash of the
  token folded into the vocab.  Preserves the shapes/FLOPs of the real
  pipeline (exactly what benchmarking and tests need offline).

Both produce bucketed, padded ``(ids, mask)`` int32 batches — static
shapes for XLA (see :mod:`pathway_tpu.ops.bucketing`).
"""

from __future__ import annotations

import hashlib
import os
import re
from typing import Sequence

import numpy as np

from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.ops.bucketing import bucket_size

__all__ = [
    "Tokenizer",
    "HashTokenizer",
    "HFTokenizer",
    "get_tokenizer",
    "hub_cache_roots",
    "local_tokenizer_dir",
]

_WORD_RE = re.compile(r"[a-z0-9]+", re.UNICODE)

PAD_ID = 0
CLS_ID = 101
SEP_ID = 102
_RESERVED = 1000  # ids below this are reserved for specials


class Tokenizer:
    def encode_batch(
        self,
        texts: Sequence[str],
        *,
        max_len: int = 512,
        pair: Sequence[str] | None = None,
        bucket_len: bool = True,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Returns (ids, mask, type_ids), each int32 [B, L]."""
        raise NotImplementedError

    def count_tokens(self, text: str) -> int:
        raise NotImplementedError


class HashTokenizer(Tokenizer):
    def __init__(self, vocab_size: int = 30522):
        self.vocab_size = vocab_size

    def _token_id(self, tok: str) -> int:
        h = int.from_bytes(hashlib.blake2b(tok.encode(), digest_size=8).digest(), "little")
        return _RESERVED + h % (self.vocab_size - _RESERVED)

    def _tokens(self, text: str) -> list[int]:
        return [self._token_id(t) for t in _WORD_RE.findall(text.lower())]

    def count_tokens(self, text: str) -> int:
        return len(_WORD_RE.findall(text.lower()))

    def word_ids(self, text: str) -> list[int]:
        """One id a word and nothing else: what a decoder's prompt is."""
        return self._tokens(text)

    def encode_batch(
        self,
        texts: Sequence[str],
        *,
        max_len: int = 512,
        pair: Sequence[str] | None = None,
        bucket_len: bool = True,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        rows: list[list[int]] = []
        types: list[list[int]] = []
        for i, text in enumerate(texts):
            ids = [CLS_ID] + self._tokens(text)[: max_len - 2] + [SEP_ID]
            tps = [0] * len(ids)
            if pair is not None:
                second = self._tokens(pair[i])[: max_len - len(ids) - 1] + [SEP_ID]
                ids += second
                tps += [1] * len(second)
            rows.append(ids[:max_len])
            types.append(tps[:max_len])
        longest = max((len(r) for r in rows), default=1)
        width = bucket_size(longest, min_bucket=16, max_bucket=max_len) if bucket_len else max_len
        width = max(width, longest)
        b = len(rows)
        ids_arr = np.full((b, width), PAD_ID, dtype=np.int32)
        mask = np.zeros((b, width), dtype=np.int32)
        type_arr = np.zeros((b, width), dtype=np.int32)
        for i, (r, t) in enumerate(zip(rows, types)):
            ids_arr[i, : len(r)] = r
            mask[i, : len(r)] = 1
            type_arr[i, : len(t)] = t
        return ids_arr, mask, type_arr


class HFTokenizer(Tokenizer):
    """Locally cached HuggingFace tokenizer (no downloads attempted)."""

    def __init__(self, name: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(name, local_files_only=True)

    def count_tokens(self, text: str) -> int:
        return len(self._tok.encode(text, add_special_tokens=False))

    def encode_batch(
        self,
        texts: Sequence[str],
        *,
        max_len: int = 512,
        pair: Sequence[str] | None = None,
        bucket_len: bool = True,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        enc = self._tok(
            list(texts),
            text_pair=list(pair) if pair is not None else None,
            truncation=True,
            max_length=max_len,
            padding=True,
            return_tensors="np",
        )
        ids = enc["input_ids"].astype(np.int32)
        mask = enc["attention_mask"].astype(np.int32)
        if bucket_len:
            width = min(max(bucket_size(ids.shape[1], min_bucket=16), ids.shape[1]), max_len)
            if width > ids.shape[1]:
                pad = width - ids.shape[1]
                ids = np.pad(ids, ((0, 0), (0, pad)))
                mask = np.pad(mask, ((0, 0), (0, pad)))
        tps = enc.get("token_type_ids")
        tps = (
            tps.astype(np.int32)
            if tps is not None and tps.shape == ids.shape
            else np.zeros_like(ids)
        )
        return ids, mask, tps


#: a directory holds a tokenizer if it holds one of these
_TOKENIZER_FILES = (
    "tokenizer.json",
    "tokenizer_config.json",
    "vocab.txt",
    "vocab.json",
    "spiece.model",
    "sentencepiece.bpe.model",
)


def _holds_tokenizer(path: str) -> bool:
    return any(os.path.isfile(os.path.join(path, f)) for f in _TOKENIZER_FILES)


#: where a hub cache is kept, first to last: (variable, what its value is joined with)
_HUB_CACHE_VARS = (
    ("HF_HUB_CACHE", ()),
    ("HUGGINGFACE_HUB_CACHE", ()),
    ("TRANSFORMERS_CACHE", ()),
    ("HF_HOME", ("hub",)),
    ("XDG_CACHE_HOME", ("huggingface", "hub")),
)


def hub_cache_roots() -> list[str]:
    """The HuggingFace hub-cache directories that exist here, in the order
    :func:`local_tokenizer_dir` looks through them: ``$HF_HUB_CACHE``,
    ``$HUGGINGFACE_HUB_CACHE``, ``$TRANSFORMERS_CACHE``, ``$HF_HOME/hub``,
    ``$XDG_CACHE_HOME/huggingface/hub``, ``~/.cache/huggingface/hub``."""
    candidates = [
        os.path.join(os.environ[var], *below) for var, below in _HUB_CACHE_VARS if os.environ.get(var)
    ]
    candidates.append(os.path.join(os.path.expanduser("~"), ".cache", "huggingface", "hub"))
    roots: list[str] = []
    for root in candidates:
        if root not in roots and os.path.isdir(root):
            roots.append(root)
    return roots


def local_tokenizer_dir(name: str) -> str | None:
    """Where ``AutoTokenizer.from_pretrained(name, local_files_only=True)``
    would find its files, or ``None`` where it would find none; decided with
    ``os`` alone (a handful of ``stat`` calls), so that a process with no
    local tokenizer never imports ``transformers``.

    ``name`` is a directory that holds a tokenizer file (``tokenizer.json``,
    ``tokenizer_config.json``, ``vocab.txt``, ``vocab.json``,
    ``spiece.model``, ``sentencepiece.bpe.model``), or a hub name
    (``org/name``) with a snapshot ``models--org--name/snapshots/*/`` that
    holds one, under one of :func:`hub_cache_roots`.  A cache kept anywhere
    else is not looked at: point ``HF_HUB_CACHE`` at it, pass its snapshot
    directory as the name, or pass a ``tokenizer=``."""
    if os.path.isdir(name):
        return name if _holds_tokenizer(name) else None
    repo = "models--" + name.replace("/", "--")
    for root in hub_cache_roots():
        snapshots = os.path.join(root, repo, "snapshots")
        if os.path.isdir(snapshots):
            for rev in sorted(os.listdir(snapshots)):
                path = os.path.join(snapshots, rev)
                if _holds_tokenizer(path):
                    return path
    return None


def get_tokenizer(model_name: str | None = None, vocab_size: int = 30522) -> Tokenizer:
    """HF tokenizer if its files are here (:func:`local_tokenizer_dir`),
    else the deterministic hash stand-in, with nothing imported.  Recorded
    as one ``tokenizer_resolve`` span: ``args.kind`` says which it was."""
    with _tracing.span("tokenizer_resolve") as sp:
        sp.args = {"kind": "hash", "roots": hub_cache_roots()}
        if model_name and local_tokenizer_dir(model_name):
            try:
                tok = HFTokenizer(model_name)
            except Exception:
                # files that transformers will not load (another format, a
                # half-written cache): the stand-in, as with none at all
                pass
            else:
                sp.args["kind"] = "hf"
                return tok
        return HashTokenizer(vocab_size)
