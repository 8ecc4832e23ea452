"""Chat model wrappers (reference ``xpacks/llm/llms.py``).

``BaseChat`` (reference ``llms.py:27``) is the UDF contract:
``__wrapped__(messages) -> str`` where messages is a list of
``{"role": ..., "content": ...}`` dicts.  Network chats
(OpenAI/LiteLLM/Cohere, reference ``:84/:313/:544``) are gated on their
client packages; :class:`HFPipelineChat` (``:441``) on a locally cached
model.  ``prompt_chat_single_qa`` matches the reference helper.

:class:`TPUDecoderChat` is the chat that stays on the chip: a causal
decoder (one of the architectures of ``models/``: ``decoder``, which
serves DeepSeek-V3.2-Exp and GLM-4.7-Flash, the latter drafting with its MTP
layer, ``hybrid_decoder``, ``shortcut_moe_decoder``, ``window_moe_decoder``,
each named by its presets) behind :class:`JittedDecoder`, so that
``BaseRAGQuestionAnswerer(llm=TPUDecoderChat(...))`` answers without the
request leaving the device that retrieved for it.
"""

from __future__ import annotations

import collections
from typing import Any

from pathway_tpu.internals import udfs
from pathway_tpu.internals.udfs import UDF

__all__ = [
    "BaseChat",
    "OpenAIChat",
    "LiteLLMChat",
    "HFPipelineChat",
    "CohereChat",
    "TPUDecoderChat",
    "decoder_preset",
    "prompt_chat_single_qa",
]


def prompt_chat_single_qa(question: str) -> list[dict]:
    """Wrap a plain question into the single-turn message format
    (reference ``llms.py prompt_chat_single_qa``)."""
    return [{"role": "user", "content": str(question)}]


class BaseChat(UDF):
    """Base chat UDF (reference ``llms.py:27``)."""

    def __init__(
        self,
        *,
        capacity: int | None = None,
        retry_strategy: udfs.AsyncRetryStrategy | None = None,
        cache_strategy: udfs.CacheStrategy | None = None,
        model: str | None = None,
        **call_kwargs: Any,
    ):
        executor = (
            udfs.async_executor(capacity=capacity, retry_strategy=retry_strategy)
            if (capacity is not None or retry_strategy is not None)
            else None
        )
        super().__init__(executor=executor, cache_strategy=cache_strategy)
        self.model = model
        self.call_kwargs = call_kwargs

    def _accepts_call_arg(self, arg: str) -> bool:
        return True


class _GatedChat(BaseChat):
    _client_pkg = ""

    def __init__(self, **kwargs: Any):
        super().__init__(**kwargs)
        try:
            __import__(self._client_pkg)
        except ImportError as e:
            raise ImportError(
                f"{type(self).__name__} needs the {self._client_pkg!r} package "
                "(and network access)"
            ) from e


class OpenAIChat(_GatedChat):
    """reference ``llms.py:84``"""

    _client_pkg = "openai"

    async def __wrapped__(self, messages: list[dict], **kwargs: Any) -> str | None:
        import openai

        client = openai.AsyncOpenAI()
        kw = {**self.call_kwargs, **kwargs}
        if self.model is not None:
            kw.setdefault("model", self.model)
        ret = await client.chat.completions.create(messages=messages, **kw)
        return ret.choices[0].message.content


class LiteLLMChat(_GatedChat):
    """reference ``llms.py:313``"""

    _client_pkg = "litellm"

    async def __wrapped__(self, messages: list[dict], **kwargs: Any) -> str | None:
        import litellm

        kw = {**self.call_kwargs, **kwargs}
        if self.model is not None:
            kw.setdefault("model", self.model)
        ret = await litellm.acompletion(messages=messages, **kw)
        return ret.choices[0]["message"]["content"]


class CohereChat(_GatedChat):
    """reference ``llms.py:544``"""

    _client_pkg = "cohere"

    async def __wrapped__(self, messages: list[dict], **kwargs: Any) -> str | None:
        import cohere

        client = cohere.AsyncClient()
        kw = {**self.call_kwargs, **kwargs}
        if self.model is not None:
            kw.setdefault("model", self.model)
        query = messages[-1]["content"]
        ret = await client.chat(message=query, **kw)
        return ret.text


class HFPipelineChat(BaseChat):
    """Local HuggingFace text-generation pipeline (reference ``llms.py:441``;
    torch-cpu). Requires a locally cached model — no downloads attempted."""

    def __init__(self, model: str | None = None, device: str = "cpu", **kwargs: Any):
        super().__init__(model=model, **kwargs)
        from transformers import pipeline

        self.pipeline = pipeline(
            "text-generation",
            model=model,
            device=device,
            model_kwargs={"local_files_only": True},
        )

    def __wrapped__(self, messages: list[dict] | str, **kwargs: Any) -> str | None:
        if isinstance(messages, str):
            prompt = messages
        else:
            prompt = "\n".join(m.get("content", "") for m in messages)
        out = self.pipeline(prompt, **{**self.call_kwargs, **kwargs})
        text = out[0]["generated_text"]
        return text[len(prompt) :] if text.startswith(prompt) else text


#: preset name (lower case) -> the architecture's module under ``pathway_tpu.models`` and its configuration there
_DECODER_PRESETS = {
    "deepseek-ai/deepseek-v3.2-exp": ("decoder", "DEEPSEEK_V32_EXP"),
    "deepseek-v3.2-exp": ("decoder", "DEEPSEEK_V32_EXP"),
    "microsoft/phi-4-mini-flash-reasoning": ("hybrid_decoder", "PHI4_MINI_FLASH"),
    "phi-4-mini-flash-reasoning": ("hybrid_decoder", "PHI4_MINI_FLASH"),
    "meituan-longcat/longcat-flash-chat": ("shortcut_moe_decoder", "LONGCAT_FLASH_CHAT"),
    "longcat-flash-chat": ("shortcut_moe_decoder", "LONGCAT_FLASH_CHAT"),
    "powerinfer/smallthinker-21ba3b-instruct": ("window_moe_decoder", "SMALLTHINKER_21BA3B"),
    "smallthinker-21ba3b-instruct": ("window_moe_decoder", "SMALLTHINKER_21BA3B"),
    "zai-org/glm-4.7-flash": ("decoder", "GLM_4_7_FLASH"),
    "glm-4.7-flash": ("decoder", "GLM_4_7_FLASH"),
}


def decoder_preset(model: str) -> Any:
    """The published configuration a decoder preset's name stands for (a
    frozen dataclass of its architecture's module; ``dataclasses.replace``
    gives a share of it or a small size)."""
    import importlib

    preset = _DECODER_PRESETS.get(model.lower())
    if preset is None:
        raise ValueError(f"unknown decoder model {model!r}: not one of the presets {sorted(_DECODER_PRESETS)}")
    return getattr(importlib.import_module(f"pathway_tpu.models.{preset[0]}"), preset[1])


#: generations :meth:`TPUDecoderChat.recent_generations` keeps
_KEPT_GENERATIONS = 64


class TPUDecoderChat(BaseChat):
    """A causal decoder on the TPU; one greedy generation a call.

    ``model`` names an architecture preset (:func:`decoder_preset`);
    ``config`` (that architecture's configuration class) takes its place for
    a share of a layer or a small size.  ``params`` is
    the decoder's parameter tree, handed in as ``TPUEncoderEmbedder`` takes
    one: no checkpoint of this family can be read here yet.  The prompt is
    the messages' contents, one token a word (the hashing tokenizer over the
    held slice of the vocabulary); with no vocabulary to print from, the
    answer is rendered from the ids chosen: ``t<id>`` a token.
    """

    def __init__(
        self,
        model: str = "deepseek-ai/DeepSeek-V3.2-Exp",
        *,
        params: Any = None,
        config: Any = None,
        max_new_tokens: int = 32,
        slots: int = 8,
        positions: int = 8704,
        chunk_buckets: tuple = (512, 2048, 2560),
        **kwargs: Any,
    ):
        super().__init__(model=model, **kwargs)
        from pathway_tpu.models.tokenizer import HashTokenizer
        from pathway_tpu.parallel import JittedDecoder

        if config is None:
            config = decoder_preset(model)
        if params is None:
            raise ValueError("TPUDecoderChat needs params=: the decoder's parameter tree (the architecture's module names its leaves)")
        self.max_new_tokens = max_new_tokens
        self.tokenizer = HashTokenizer(config.vocab_held)
        self.decoder = JittedDecoder(config, params=params, slots=slots, positions=positions, chunk_buckets=chunk_buckets)
        self._recent: collections.deque = collections.deque(maxlen=_KEPT_GENERATIONS)

    def __wrapped__(self, messages: list[dict] | str, **kwargs: Any) -> str:
        from pathway_tpu.internals import tracing

        if isinstance(messages, str):
            messages = prompt_chat_single_qa(messages)
        with tracing.span("generate_tokenize"):
            prompt_ids = self.tokenizer.word_ids("\n".join(str(m.get("content", "")) for m in messages))
        out = self.decoder.generate(prompt_ids, self.max_new_tokens)
        with tracing.span("generate_detokenize"):
            text = " ".join(f"t{i}" for i in out["ids"])
        kept = {"prompt_ids": prompt_ids, "ids": out["ids"], "logits": out["logits"], "text": text}
        if "drafts" in out:
            kept["drafts"] = out["drafts"]
        self._recent.append(kept)
        return text

    def recent_generations(self) -> list[dict]:
        """The last 64 generations, oldest first: the prompt's ids, the ids
        chosen, the float32 logits over the held vocabulary each was chosen
        from (what a ``logprobs`` caller is given) and the text returned;
        where the decoder drafts with an MTP layer, also each draft's
        signature (``drafts``: its position and its largest logits and
        their ids, as :meth:`JittedDecoder.generate` returns them)."""
        return list(self._recent)
