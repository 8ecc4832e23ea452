"""TPU model zoo: the encoders behind the RAG numeric plane.

The reference runs SentenceTransformer / cross-encoder models per-row on
torch (``python/pathway/xpacks/llm/embedders.py:270-327``,
``rerankers.py:186-235``).  Here the same model families are brand-new
flax modules, jit-compiled in bf16, batched per epoch, and shardable
(tensor-parallel param rules + data-parallel batches) over a
``jax.sharding.Mesh``.
"""

from pathway_tpu.models.decoder import DEEPSEEK_V32_EXP, DecoderConfig
from pathway_tpu.models.encoder import (
    BGE_BASE,
    BGE_LARGE,
    BGE_RERANKER_BASE,
    BGE_SMALL,
    E5_BASE,
    MINILM_L6,
    CrossEncoderModel,
    EncoderConfig,
    TextEncoderModel,
    encoder_param_specs,
)
from pathway_tpu.models.shortcut_moe_decoder import LONGCAT_FLASH_CHAT, ShortcutMoEDecoderConfig
from pathway_tpu.models.tokenizer import HashTokenizer, Tokenizer, get_tokenizer
from pathway_tpu.models.vision import SIGLIP_BASE, DualEncoderModel, VisionConfig

__all__ = [
    "EncoderConfig",
    "DecoderConfig",
    "DEEPSEEK_V32_EXP",
    "ShortcutMoEDecoderConfig",
    "LONGCAT_FLASH_CHAT",
    "TextEncoderModel",
    "CrossEncoderModel",
    "VisionConfig",
    "DualEncoderModel",
    "encoder_param_specs",
    "MINILM_L6",
    "BGE_SMALL",
    "BGE_BASE",
    "BGE_LARGE",
    "E5_BASE",
    "BGE_RERANKER_BASE",
    "SIGLIP_BASE",
    "Tokenizer",
    "HashTokenizer",
    "get_tokenizer",
]
