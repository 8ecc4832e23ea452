"""BERT-family text encoders and cross-encoders, TPU-first.

Brand-new flax implementation of the model families the reference drives
through torch SentenceTransformers (MiniLM, BGE, E5 —
``xpacks/llm/embedders.py:270``) and torch CrossEncoder
(``xpacks/llm/rerankers.py:186``).  Design for the MXU:

- bf16 activations / f32 params (configurable), static shapes via
  bucketed rows and lengths (see :mod:`pathway_tpu.ops.bucketing`); a row
  holds one padded text, or several short ones end to end
  (:class:`TextEncoderModel`'s ``first``), each attending within itself;
- post-LN BERT blocks expressed as einsum-shaped flax modules so XLA
  fuses bias+gelu+residual into the matmuls;
- tensor-parallel sharding RULES (:func:`encoder_param_specs`) mapping
  each param to a ``PartitionSpec`` over a mesh "model" axis: attention
  heads and MLP hidden dim are split, embeddings/LN replicated.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pathway_tpu.ops.pooling import (
    cls_pool,
    masked_mean_pool,
    packed_cls_pool,
    packed_mean_pool,
)

__all__ = [
    "EncoderConfig",
    "TextEncoderModel",
    "CrossEncoderModel",
    "encoder_param_specs",
    "MINILM_L6",
    "BGE_SMALL",
    "BGE_BASE",
    "BGE_LARGE",
    "E5_BASE",
    "BGE_RERANKER_BASE",
]


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Architecture hyperparameters (BERT-style post-LN encoder)."""

    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 6
    heads: int = 12
    mlp_dim: int = 1536
    max_len: int = 512
    type_vocab: int = 2
    pool: str = "mean"  # mean | cls
    normalize: bool = True  # L2-normalize sentence embedding
    num_labels: int = 0  # >0 => cross-encoder classification head
    dtype: Any = jnp.bfloat16  # activation dtype
    param_dtype: Any = jnp.float32
    ln_eps: float = 1e-12
    #: tanh-approximated gelu (faster on MXU); HF "gelu" is the exact erf
    #: form — the checkpoint converter sets this from config.json
    gelu_approx: bool = True
    #: sequence-parallel long-document attention: when a Mesh is set,
    #: every SelfAttention runs ops.ring_attention with the sequence
    #: dimension sharded over ``seq_axis`` (K/V blocks rotate over ICI
    #: via ppermute; exact flash-style running softmax).  Sequences may
    #: then exceed one device's attention memory; max_len still bounds
    #: the position table.  Meshes hash by identity, so the config stays
    #: a valid static jit argument.
    seq_mesh: Any = None
    seq_axis: str = "data"

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


# Presets mirroring the model families in the reference's xpack docs/tests.
MINILM_L6 = EncoderConfig(hidden=384, layers=6, heads=12, mlp_dim=1536)
BGE_SMALL = EncoderConfig(hidden=384, layers=12, heads=12, mlp_dim=1536, pool="cls")
BGE_BASE = EncoderConfig(hidden=768, layers=12, heads=12, mlp_dim=3072, pool="cls")
BGE_LARGE = EncoderConfig(hidden=1024, layers=24, heads=16, mlp_dim=4096, pool="cls")
E5_BASE = EncoderConfig(hidden=768, layers=12, heads=12, mlp_dim=3072, pool="mean")
BGE_RERANKER_BASE = dataclasses.replace(
    BGE_BASE, num_labels=1, pool="cls", normalize=False
)


class SelfAttention(nn.Module):
    """``mask`` is a key mask [B, L], or [B, L, L]: which keys each query
    may see (packed rows, where a token sees its own text only)."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            features=(cfg.heads, cfg.head_dim),
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name=name,
        )
        q = dense("query")(x)  # [B, L, h, d]
        k = dense("key")(x)
        v = dense("value")(x)
        if cfg.seq_mesh is not None:
            # long-document path: sequence-parallel ring attention
            # (ops/ring_attention.py) — same math, K/V ring over ICI
            from pathway_tpu.ops.ring_attention import ring_attention

            ctx = ring_attention(
                q, k, v, mask, mesh=cfg.seq_mesh, axis=cfg.seq_axis
            )
        else:
            scale = 1.0 / jnp.sqrt(jnp.float32(cfg.head_dim))
            logits = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) * scale
            seen = mask.astype(bool)
            seen = seen[:, None, None, :] if mask.ndim == 2 else seen[:, None]
            bias = jnp.where(seen, 0.0, -1e30)
            probs = jax.nn.softmax(logits + bias, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum("bhlm,bmhd->blhd", probs, v)
        out = nn.DenseGeneral(
            features=cfg.hidden,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="out",
        )(ctx)
        return out


class EncoderBlock(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array) -> jax.Array:
        cfg = self.cfg
        a = SelfAttention(cfg, name="attention")(x, mask)
        x = nn.LayerNorm(
            epsilon=cfg.ln_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="attention_ln",
        )(x + a)
        h = nn.Dense(
            cfg.mlp_dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_up"
        )(x)
        h = nn.gelu(h, approximate=cfg.gelu_approx)
        h = nn.Dense(
            cfg.hidden, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_down"
        )(h)
        return nn.LayerNorm(
            epsilon=cfg.ln_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="mlp_ln",
        )(x + h)


class Embeddings(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(
        self,
        ids: jax.Array,
        type_ids: jax.Array | None,
        positions: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(ids.shape[1])[None, :]
        emb = nn.Embed(
            cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="word",
        )(ids)
        pos = nn.Embed(
            cfg.max_len, cfg.hidden, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="position",
        )(positions)
        emb = emb + pos
        if cfg.type_vocab:
            t = type_ids if type_ids is not None else jnp.zeros_like(ids)
            emb = emb + nn.Embed(
                cfg.type_vocab, cfg.hidden, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="type",
            )(t)
        return nn.LayerNorm(
            epsilon=cfg.ln_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="ln",
        )(emb)


def _packed_positions(segments: jax.Array) -> jax.Array:
    """Token positions that restart at 0 where the segment id changes."""
    at = jnp.arange(segments.shape[1])[None, :]
    starts = jnp.pad(segments[:, 1:] != segments[:, :-1], ((0, 0), (1, 0)))
    return at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1)


class TextEncoderModel(nn.Module):
    """Sentence encoder: token ids -> pooled (optionally normalized)
    embedding [B, hidden].

    With ``first`` [T] the rows are packed: ``mask`` then holds a segment
    id per token (a row's texts numbered from 1, padding 0) and ``first``
    each text's first token in the flattened [B * L]; a token attends
    within its own segment, positions restart with every segment, and the
    result is one embedding per text, [T, hidden], in ``first``'s order."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(
        self,
        ids: jax.Array,
        mask: jax.Array,
        type_ids: jax.Array | None = None,
        first: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        if first is None:
            x = Embeddings(cfg, name="embeddings")(ids, type_ids)
            seen = mask
        else:
            x = Embeddings(cfg, name="embeddings")(
                ids, type_ids, _packed_positions(mask)
            )
            seen = mask[:, :, None] == mask[:, None, :]
        for i in range(cfg.layers):
            x = EncoderBlock(cfg, name=f"layer_{i}")(x, seen)
        if first is None:
            pooled = cls_pool(x) if cfg.pool == "cls" else masked_mean_pool(x, mask)
        elif cfg.pool == "cls":
            pooled = packed_cls_pool(x, first)
        else:
            pooled = packed_mean_pool(x, mask, first)
        if cfg.normalize:
            norm = jnp.sqrt(
                jnp.sum(pooled.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
            )
            pooled = (pooled.astype(jnp.float32) / jnp.maximum(norm, 1e-12))
        return pooled.astype(jnp.float32)


class CrossEncoderModel(nn.Module):
    """(query, doc) pair scorer: encoder + classification head -> [B] or
    [B, num_labels] logits (reference CrossEncoderReranker's model)."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(
        self,
        ids: jax.Array,
        mask: jax.Array,
        type_ids: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        x = Embeddings(cfg, name="embeddings")(ids, type_ids)
        for i in range(cfg.layers):
            x = EncoderBlock(cfg, name=f"layer_{i}")(x, mask)
        cls = cls_pool(x)
        h = nn.Dense(
            cfg.hidden, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="pooler"
        )(cls)
        h = jnp.tanh(h)
        logits = nn.Dense(
            max(cfg.num_labels, 1), dtype=jnp.float32,
            param_dtype=cfg.param_dtype, name="classifier",
        )(h)
        return logits[:, 0] if max(cfg.num_labels, 1) == 1 else logits


# ---------------------------------------------------------------------------
# Tensor-parallel sharding rules


def encoder_param_specs(params: Any, model_axis: str = "model") -> Any:
    """PartitionSpec tree for encoder params: heads + MLP hidden split over
    ``model_axis``, everything else replicated.

    Works for both :class:`TextEncoderModel` and :class:`CrossEncoderModel`
    (and the towers of :class:`DualEncoderModel`), because the rules key on
    leaf path names, not tree structure.
    """
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf) -> Any:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        joined = "/".join(str(n) for n in names)
        nd = leaf.ndim
        if "kernel" in joined:
            if any(s in joined for s in ("query", "key", "value")):
                # [hidden, heads, head_dim] -> split heads
                return P(*([None] * (nd - 2)), model_axis, None)
            if "attention/out" in joined or joined.endswith("out/kernel"):
                # [heads, head_dim, hidden] -> split heads
                return P(model_axis, *([None] * (nd - 1)))
            if "mlp_up" in joined:
                return P(*([None] * (nd - 1)), model_axis)
            if "mlp_down" in joined:
                return P(model_axis, *([None] * (nd - 1)))
        if "bias" in joined:
            if any(s in joined for s in ("query", "key", "value")):
                return P(model_axis, *([None] * (nd - 1)))
            if "mlp_up" in joined:
                return P(*([None] * (nd - 1)), model_axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
