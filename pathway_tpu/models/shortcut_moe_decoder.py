"""A causal decoder of double layers with a shortcut-connected routed branch
-- the generation stage's third architecture.

The architecture is LongCat-Flash-Chat's (``config.json`` keys keep their
published names in :class:`ShortcutMoEDecoderConfig`; the three keys of
:class:`pathway_tpu.models.decoder.DecoderConfig` say which share of a layer
this chip holds: ``experts_held`` / ``expert_offset`` / ``vocab_held``).  One
of its ``num_layers`` layers is two sublayers and one branch that spans them,
with the residual stream ``h`` in float32::

    a_0 = h   + MLA_0(RMS(h))
    x_0 = RMS(a_0)
    m   = MoE(x_0)                 # the branch leaves after the first attention ...
    h_1 = a_0 + SwiGLU_0(x_0)      # dense, ``ffn_hidden_size`` wide
    a_1 = h_1 + MLA_1(RMS(h_1))
    x_1 = RMS(a_1)
    h'  = a_1 + SwiGLU_1(x_1) + m  # ... and comes back after the second dense block

- ``MLA_i``: latent attention with weights and a cache of its own a
  sublayer (``2 * num_layers`` caches of ``[c_kv | k_rope]`` rows, 576 values
  a token), every visible key attended.  The low-rank rows are scaled after
  their norm (``mla_scale_q_lora``: ``sqrt(hidden / q_lora_rank)``,
  ``mla_scale_kv_lora``: ``sqrt(hidden / kv_lora_rank)``); the cache holds the
  scaled row, so the core of :mod:`pathway_tpu.models.decoder` (expanded by
  blocks through ``ops/selected_attention.py`` in :func:`prefill`, absorbed in
  :func:`decode_step`) is called as it stands, the causal mask where that
  module passes its indexer's selection.
- ``MoE``: a softmax router ``n_routed_experts + zero_expert_num`` wide; the
  ``moe_topk`` largest of ``score + bias`` are chosen, gated by
  ``routed_scaling_factor * score`` with no renormalisation.  An expert past
  ``n_routed_experts`` holds no parameters and returns its input: its whole
  cost is one multiply-add a row, added here for every token (in a
  deployment by the token's own rank).  The experts held here are
  :func:`pathway_tpu.models.decoder._experts_here`'s: ids outside
  ``[expert_offset, expert_offset + experts_held)``, the zero-computation
  experts among them, are not this chip's; what the absent experts would add
  is left out and nothing stands in for their exchange.

The same two programs as :mod:`pathway_tpu.models.decoder` over the one
cache, for :class:`pathway_tpu.parallel.JittedDecoder`.  Weights and caches
are ``config.dtype`` (bfloat16); products accumulate in float32; the residual
stream, norms, the router and the softmax are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.models.decoder import _decode_core, _experts_here, _grouped, _logits, _mm, _prefill_core, _rms, _rotate, _rows_of, _swiglu

__all__ = ["ShortcutMoEDecoderConfig", "LONGCAT_FLASH_CHAT", "init_cache", "prefill", "decode_step", "STATS", "DISPATCH_TOKENS"]

#: what both programs count, in the order of the vector they return: token-expert
#: pairs the experts held here computed / pairs the router chose anywhere, the
#: zero-computation experts among them / those of them whose expert computes
#: nothing / whether the experts took the grouped product (a layer a dispatch:
#: :func:`pathway_tpu.models.decoder._grouped`); query-key pairs that count (live
#: queries, visible keys) / pairs the attention multiplies (a prompt chunk: the fused kernel's query tiles, each
#: against the key blocks its own last row can see and a tile of padding
#: against none, as :func:`pathway_tpu.ops.selected_attention.query_tiles`
#: plans them for the kernel; a decode step: every position of the cache)
STATS = ("moe_rows_here", "moe_rows_routed", "moe_rows_zero", "moe_grouped_calls", "mla_keys_visible", "mla_keys_multiplied")

#: what one more prefill dispatch costs beside its tokens, in tokens.  Little:
#: the chunk's own products hide the read of the weights, and what a further
#: dispatch repeats is the sequence's keys and values expanded again, which
#: grows with the context (on a v5e at the published widths and this chip's
#: share, a chunk of 512 / 2,048 / 2,560 takes 26.8 / 101.5 / 132.9 ms at a
#: sequence's start and 42.7 / 149.4 / 194.2 ms behind 5,120 tokens: 0.052 and
#: 0.074 ms a token, and 0.25 and 4.8 ms beside them)
DISPATCH_TOKENS = 64


@dataclasses.dataclass(frozen=True)
class ShortcutMoEDecoderConfig:
    hidden_size: int = 6144
    num_layers: int = 28  # double layers: two attentions and two dense blocks each
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    n_routed_experts: int = 512  # the experts that hold parameters; the router is ``zero_expert_num`` wider
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-5
    vocab_size: int = 131072  # published; ``vocab_held`` rows of it live here
    # --- this chip's share of a layer
    experts_held: int = 512
    expert_offset: int = 0
    vocab_held: int = 131072
    dtype: Any = jnp.bfloat16
    # --- blocking (no width): keys a block of the prefill's attention loop,
    # token-expert pairs a block of the expert loop
    key_block: int = 512
    expert_block: int = 128

    def __post_init__(self):
        if self.zero_expert_type != "identity":
            raise ValueError(f"zero-computation experts of type {self.zero_expert_type!r}: only 'identity' is computed here")

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def q_lora_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.q_lora_rank) if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.kv_lora_rank) if self.mla_scale_kv_lora else 1.0

    def inv_freq(self) -> np.ndarray:
        dim = self.qk_rope_head_dim
        return self.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


#: the published configuration, uncut
LONGCAT_FLASH_CHAT = ShortcutMoEDecoderConfig()


def init_cache(config: ShortcutMoEDecoderConfig, slots: int, positions: int) -> dict:
    """The latent rows of every attention sublayer (two a layer), for
    ``slots`` sequences of up to ``positions`` tokens, zeroed."""
    return {"latent": jnp.zeros((2 * config.num_layers, slots, positions, config.latent_width), config.dtype)}


# ------------------------------------------------------------------ pieces
def _attention_inputs(h, ap, pos, cfg: ShortcutMoEDecoderConfig):
    """An attention sublayer's queries and the row each token adds to its
    cache, the low-rank rows scaled after their norms."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    inv_freq = jnp.asarray(cfg.inv_freq(), jnp.float32)
    x = _rms(h, ap["attn_norm"], eps).astype(dt)
    cq = (_rms(_mm("tc,cr->tr", x, ap["q_a"]), ap["q_norm"], eps) * cfg.q_lora_scale).astype(dt)
    q = _mm("tr,rd->td", cq, ap["q_b"]).reshape(h.shape[0], cfg.num_attention_heads, nope + cfg.qk_rope_head_dim)
    kva = _mm("tc,cr->tr", x, ap["kv_a"])
    latent = jnp.concatenate(
        [_rms(kva[:, :rank], ap["kv_norm"], eps) * cfg.kv_lora_scale, _rotate(kva[:, rank:], pos, inv_freq)], axis=-1
    ).astype(dt)
    return q[..., :nope].astype(dt), _rotate(q[..., nope:], pos, inv_freq).astype(dt), latent


def _route(x, lp, cfg: ShortcutMoEDecoderConfig):
    """Each token's chosen experts (published numbers; ``n_routed_experts``
    and above compute nothing) and their gates."""
    s = jax.nn.softmax(
        jnp.einsum("tc,ce->te", x.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST), axis=-1
    )
    chosen = jax.lax.top_k(s + lp["router_bias"], cfg.moe_topk)[1]
    return chosen, jnp.take_along_axis(s, chosen, axis=1) * cfg.routed_scaling_factor


def _moe(x, lp, live, cfg: ShortcutMoEDecoderConfig):
    """The routed branch of the normed rows ``x``: what the experts held here
    give and the identity term, and the counts of the first four
    :data:`STATS` (pairs computed here, chosen anywhere, chosen among the
    zero-computation experts; whether the grouped product ran)."""
    chosen, gates = _route(x, lp, cfg)
    routed, rows_here = _experts_here(x, chosen, gates, live, lp["experts"], cfg)
    zero = chosen >= cfg.n_routed_experts
    identity = jnp.sum(jnp.where(zero, gates, 0.0), axis=1, keepdims=True) * x.astype(jnp.float32)
    rows_routed = jnp.sum(live).astype(jnp.int32) * cfg.moe_topk
    grouped = jnp.int32(_grouped(chosen.size, lp["experts"], cfg))
    return routed + identity, jnp.stack([rows_here, rows_routed, jnp.sum(zero & live[:, None]).astype(jnp.int32), grouped])


def _layer(h, lp, cache, first, attend, live, cfg: ShortcutMoEDecoderConfig):
    """One double layer.  ``attend(h, ap, cache, sublayer)`` gives what an
    attention sublayer adds and the cache with its rows written; the layer's
    two are sublayers ``first`` and ``first + 1`` of the cache.  Returns the
    layer's output, the cache and the branch's counts."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    attended, cache = attend(h, lp["attn"][0], cache, first)
    a = h + attended
    x = _rms(a, lp["mlp_norm"][0], eps).astype(dt)
    branch, counted = _moe(x, lp, live, cfg)  # leaves here
    h = a + _swiglu(x, lp["mlp"][0], dt)
    attended, cache = attend(h, lp["attn"][1], cache, first + 1)
    a = h + attended
    x = _rms(a, lp["mlp_norm"][1], eps).astype(dt)
    return a + _swiglu(x, lp["mlp"][1], dt) + branch, cache, counted  # and comes back here


# ----------------------------------------------------------------- prefill
def prefill(params, ids, cache, slot, start, length, last=True, *, config: ShortcutMoEDecoderConfig):
    """One bucket of a prompt: ``ids`` [C] (``length`` of them real, the rest
    padding) are the tokens ``start .. start + C`` of the sequence in
    ``slot``.  Returns float32 logits over the held vocabulary at the last
    real token, the cache with the chunk's rows written, and the counts of
    :data:`STATS`.  ``start + C`` may not pass the cache's positions.
    ``last`` (whether the prompt ends in this chunk) is the executor's to
    say and changes nothing here: every layer runs for every token."""
    from pathway_tpu.ops.selected_attention import query_tiles  # Pallas: a second to import, so only where a prompt is traced

    cfg = config
    C = ids.shape[0]
    pos = start + jnp.arange(C, dtype=jnp.int32)
    live = jnp.arange(C) < length
    n_blocks = (start + C + cfg.key_block - 1) // cfg.key_block
    latent_all = cache["latent"]
    visible = jnp.arange(latent_all.shape[2])[None, :] <= pos[:, None]
    rows, visits = query_tiles(start, length, C, block_k=cfg.key_block)
    keys = jnp.stack([jnp.sum(visible & live[:, None]).astype(jnp.int32), (rows * cfg.key_block * jnp.sum(visits)).astype(jnp.int32)])

    def attend(h, ap, latent_all, sublayer):
        q_nope, q_rope, latent = _attention_inputs(h, ap, pos, cfg)
        latent_all = jax.lax.dynamic_update_slice(latent_all, latent[None, None], (sublayer, slot, start, 0))
        rows_here = _rows_of(latent_all, sublayer, slot)
        return _prefill_core(q_nope, q_rope, rows_here, visible, n_blocks, ap, cfg, start=start, length=length), latent_all

    h = params["embed"][ids].astype(jnp.float32)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    for li, lp in enumerate(params["layers"]):
        h, latent_all, counted = _layer(h, lp, latent_all, 2 * li, attend, live, cfg)
        stats = stats + jnp.concatenate([counted, 2 * keys])
    return _logits(jax.lax.dynamic_slice_in_dim(h, length - 1, 1), params, cfg)[0], {"latent": latent_all}, stats


# ------------------------------------------------------------------ decode
def decode_step(params, ids, cache, slots, lengths, *, config: ShortcutMoEDecoderConfig):
    """One new token for each of ``ids`` [B]: sequence ``slots[b]`` holds
    ``lengths[b]`` tokens and ``ids[b]`` becomes its next.  Returns float32
    logits [B, vocab_held], the cache with one more row a sequence in every
    sublayer, and the counts of :data:`STATS`."""
    cfg = config
    B = ids.shape[0]
    live = jnp.ones((B,), bool)
    latent_all = cache["latent"]
    L = latent_all.shape[2]
    visible = jnp.arange(L)[None, :] <= lengths[:, None]
    keys = jnp.stack([jnp.sum(visible).astype(jnp.int32), jnp.int32(B * L)])

    def attend(h, ap, latent_all, sublayer):
        q_nope, q_rope, latent = _attention_inputs(h, ap, lengths, cfg)
        outs = []
        for b in range(B):  # a row written and a sequence's rows read, each in place: no copy of a cache
            latent_all = jax.lax.dynamic_update_slice(latent_all, latent[b][None, None, None], (sublayer, slots[b], lengths[b], 0))
            outs.append(_decode_core(q_nope[b], q_rope[b], _rows_of(latent_all, sublayer, slots[b]), visible[b : b + 1], ap, cfg))
        return _mm("td,dc->tc", jnp.stack(outs), ap["o"]), latent_all

    h = params["embed"][ids].astype(jnp.float32)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    for li, lp in enumerate(params["layers"]):
        h, latent_all, counted = _layer(h, lp, latent_all, 2 * li, attend, live, cfg)
        stats = stats + jnp.concatenate([counted, 2 * keys])
    return _logits(h, params, cfg), {"latent": latent_all}, stats
