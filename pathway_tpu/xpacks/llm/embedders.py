"""Embedders — text -> vector UDFs (reference ``xpacks/llm/embedders.py``).

TPU re-design: :class:`TPUEncoderEmbedder` (and its reference-named alias
:class:`SentenceTransformerEmbedder`, reference ``embedders.py:270-327``
which runs per-row torch ``model.encode``) runs a flax encoder jitted in
bf16, **one batched call per engine epoch** (``BatchUDF`` contract), with
tensor/data-parallel sharding when given a mesh.

API-based embedders (OpenAI/LiteLLM/Gemini, reference ``:85/:180/:330``)
keep the reference's async-UDF shape (capacity/retry/cache composition)
and are gated on their client packages — this environment has no network
egress.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from pathway_tpu.internals import udfs
from pathway_tpu.internals.udfs import UDF

__all__ = [
    "BaseEmbedder",
    "TPUEncoderEmbedder",
    "SentenceTransformerEmbedder",
    "OpenAIEmbedder",
    "LiteLLMEmbedder",
    "GeminiEmbedder",
]

_PRESETS = {
    "all-minilm-l6-v2": "MINILM_L6",
    "sentence-transformers/all-minilm-l6-v2": "MINILM_L6",
    "baai/bge-small-en-v1.5": "BGE_SMALL",
    "bge-small": "BGE_SMALL",
    "baai/bge-base-en-v1.5": "BGE_BASE",
    "bge-base": "BGE_BASE",
    "baai/bge-large-en-v1.5": "BGE_LARGE",
    "bge-large": "BGE_LARGE",
    "intfloat/e5-base-v2": "E5_BASE",
    "e5-base": "E5_BASE",
}


def _resolve_config(model: str):
    from pathway_tpu.models import encoder as enc

    name = _PRESETS.get(model.lower())
    if name is None:
        raise ValueError(
            f"unknown encoder model {model!r}: not a local checkpoint "
            f"directory and not one of the presets {sorted(_PRESETS)}"
        )
    return getattr(enc, name)


class BaseEmbedder(UDF):
    def get_embedding_dimension(self, **kwargs: Any) -> int:
        """Probe: embed a short string, report its width (reference
        ``BaseEmbedder.get_embedding_dimension``)."""
        out = self._embed_batch(["."])[0]
        return int(np.asarray(out).reshape(-1).shape[0])

    def _embed_batch(self, texts: list[str]) -> list:
        raise NotImplementedError


class TPUEncoderEmbedder(BaseEmbedder):
    """Flax sentence encoder on TPU; one jitted call per epoch.

    ``model`` picks an architecture preset (MiniLM/BGE/E5 family) with
    random deterministic weights unless ``params`` (a flax pytree) is
    passed, or names a local HF checkpoint directory (weights, config and
    ``vocab.txt``).  The tokenizer of a preset is a local HuggingFace one
    where its files are found under that name, as a directory or as a
    hub-cache snapshot under ``$HF_HUB_CACHE``, ``$HUGGINGFACE_HUB_CACHE``,
    ``$TRANSFORMERS_CACHE``, ``$HF_HOME/hub``,
    ``$XDG_CACHE_HOME/huggingface/hub`` or ``~/.cache/huggingface/hub``
    (``models.tokenizer.local_tokenizer_dir``); ``transformers`` is imported
    only then.  Where none is found the tokenizer is the hashing stand-in
    and neither ``transformers`` nor ``torch`` is loaded.
    """

    def __init__(
        self,
        model: str = "all-MiniLM-L6-v2",
        *,
        mesh: Any = None,
        max_batch_size: int | None = 1024,
        call_kwargs: dict | None = None,
        params: Any = None,
        config: Any = None,
        sequence_axis: str | None = None,
        **kwargs: Any,
    ):
        super().__init__(max_batch_size=max_batch_size, **kwargs)
        import os

        from pathway_tpu.parallel import JittedEncoder

        # a local directory means a real HF checkpoint (weights + vocab);
        # otherwise an architecture preset with deterministic random init.
        # With a checkpoint, config.json decides pooling etc. unless the
        # caller explicitly passed a config.
        checkpoint_dir = model if os.path.isdir(model) else None
        if config is None:
            cfg = None if checkpoint_dir else _resolve_config(model)
        else:
            cfg = config
        self.model = model
        self.encoder = JittedEncoder(
            cfg, mesh=mesh, model_name=model, params=params,
            max_batch=max_batch_size or 1024, checkpoint_dir=checkpoint_dir,
            sequence_axis=sequence_axis,
        )

    def _embed_batch(self, texts: list[str]) -> list:
        emb = self.encoder.encode([t if t else "." for t in texts])
        return [row for row in emb]

    def __batch__(self, texts: list[str]) -> list:
        return self._embed_batch([str(t) for t in texts])

    def __wrapped__(self, text: str) -> Any:
        return self._embed_batch([str(text)])[0]


#: reference-compatible name — in the reference this wraps torch
#: SentenceTransformers (``embedders.py:270``); here it is the TPU encoder
SentenceTransformerEmbedder = TPUEncoderEmbedder


class _ApiEmbedder(BaseEmbedder):
    """Shared shape of the network API embedders."""

    _client_pkg = ""

    def __init__(
        self,
        *,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = None,
        **call_kwargs: Any,
    ):
        executor = udfs.async_executor(
            capacity=capacity, retry_strategy=retry_strategy
        )
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        self.model = model
        self.call_kwargs = call_kwargs
        try:
            __import__(self._client_pkg)
        except ImportError as e:
            raise ImportError(
                f"{type(self).__name__} needs the {self._client_pkg!r} package "
                "(and network access); use TPUEncoderEmbedder for local "
                "TPU embedding"
            ) from e

    def _embed_batch(self, texts: list[str]) -> list:
        import asyncio

        async def run_all() -> list:
            return await asyncio.gather(*[self.__wrapped__(t) for t in texts])

        return asyncio.run(run_all())


class OpenAIEmbedder(_ApiEmbedder):
    """reference ``embedders.py:85``"""

    _client_pkg = "openai"

    async def __wrapped__(self, input: str, **kwargs: Any) -> Any:
        import openai

        client = openai.AsyncOpenAI()
        kw = {**self.call_kwargs, **kwargs}
        if self.model is not None:
            kw.setdefault("model", self.model)
        ret = await client.embeddings.create(input=[input or "."], **kw)
        return np.asarray(ret.data[0].embedding)


class LiteLLMEmbedder(_ApiEmbedder):
    """reference ``embedders.py:180``"""

    _client_pkg = "litellm"

    async def __wrapped__(self, input: str, **kwargs: Any) -> Any:
        import litellm

        kw = {**self.call_kwargs, **kwargs}
        if self.model is not None:
            kw.setdefault("model", self.model)
        ret = await litellm.aembedding(input=[input or "."], **kw)
        return np.asarray(ret.data[0]["embedding"])


class GeminiEmbedder(_ApiEmbedder):
    """reference ``embedders.py:330``"""

    _client_pkg = "google.generativeai"

    async def __wrapped__(self, input: str, **kwargs: Any) -> Any:
        import google.generativeai as genai

        kw = {**self.call_kwargs, **kwargs}
        if self.model is not None:
            kw.setdefault("model", self.model)
        ret = genai.embed_content(content=input or ".", **kw)
        return np.asarray(ret["embedding"])
