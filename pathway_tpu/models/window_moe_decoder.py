"""A causal decoder of grouped-query attention, windowed in most layers, with
a router that reads the layer's input and ReGLU experts -- the generation
stage's fourth architecture.

The architecture is SmallThinker-21BA3B-Instruct's (``config.json`` keys
keep their published names in :class:`WindowMoEDecoderConfig`;
``experts_held`` / ``expert_offset`` say which of the router's experts this
chip holds, as :class:`pathway_tpu.models.decoder.DecoderConfig`'s do; the
vocabulary is held whole).  For a layer, with the residual stream ``h`` in
float32::

    r  = softmax(h W_r)                    # the router reads the layer's input, before attention
    E  = the k largest of r;  g_e = r_e / sum over E of r
    a  = h + Attn(RMS(h))
    h' = a + sum over e in E held here of g_e ReGLU_e(RMS(a))
    ReGLU_e(x) = (relu(x W_g) * x W_u) W_d  # no shared expert, no dense block

``Attn``: ``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim`` (query head ``j`` reads K/V head ``j // (H /
G)``), no bias, no query or key norm, the softmax scale ``1/sqrt(head_dim)``.
Where ``sliding_window_layout`` marks a layer it sees the
``sliding_window_size`` keys up to its own, its own among them, and any
earlier where it does not; where ``rope_layout`` marks one, queries and keys
are rotated at their positions (``rope_theta`` over all ``head_dim``
dimensions, the two halves paired).  In the published layouts the two marks
coincide: in every four layers one global layer with no positional encoding
and three window layers with rope.

Two kinds of attention state lie side by side in a slot: a global layer's
keys and values by position, ``positions`` long, and a window layer's ring
of ``sliding_window_size`` positions (position ``p`` at ``p mod W``, keys
stored rotated at their absolute position).

- :func:`prefill` -- a bucket of prompt tokens at any ``start`` (a chunk may
  begin anywhere inside a window).  A window layer attends over the ring as
  it stood before the chunk, laid out in position order, and the chunk's own
  keys; the ring is then rewritten with the last ``W`` real positions.  A
  global layer attends over its cache by position.  On a TPU both go
  through the fused kernel (``ops/selected_attention.grouped_attention``: a
  K/V head read once for the query heads that share it, each query tile from
  the first key block its window reaches); elsewhere the same masked softmax
  in ``jax.numpy``.
- :func:`decode_step` -- one new token for each of a few sequences over ring
  and cache.

The routed experts go through :func:`pathway_tpu.models.decoder._experts_here`
with ReGLU as their activation: a prompt chunk's through its grouped product
(``ops/grouped_experts.py`` on a TPU: every expert's matrices read once a
chunk), a decode step's through its block loop.  Weights and caches are
``config.dtype`` (bfloat16); products accumulate in float32; the residual
stream, norms, the router and the softmax are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.models.decoder import _expert_counts, _experts_here, _grouped, _logits, _mm, _rms

__all__ = ["WindowMoEDecoderConfig", "SMALLTHINKER_21BA3B", "init_cache", "prefill", "decode_step", "STATS", "DISPATCH_TOKENS"]

#: what both programs count, in the order of the vector they return: token-expert
#: pairs the experts held here computed / pairs the router chose / rows the expert
#: product multiplied (its blocks of ``expert_block``, padding included, the same
#: on either path of :func:`pathway_tpu.models.decoder._experts_here`) / whether it
#: took the grouped product (a layer a dispatch); query-key pairs inside a live
#: query's window / pairs the window layers multiplied (a prompt
#: chunk: the fused kernel's query tiles against the key blocks each visits, as
#: :func:`pathway_tpu.ops.selected_attention.window_tiles` plans them; a decode
#: step: the whole ring)
STATS = ("moe_rows_here", "moe_rows_routed", "moe_rows_multiplied", "moe_grouped_calls", "swa_keys_in_window", "swa_keys_multiplied")

#: what one more prefill dispatch costs beside its tokens, in tokens: every weight
#: is read again (6.4 GB of eight whole layers at the published widths, 7.8 ms at
#: 819 GB/s), about what 512 tokens of a chunk cost by count
DISPATCH_TOKENS = 512

_NEG = -1e30
_PERIOD = (0, 1, 1, 1) * 13  # the published layouts: 52 layers, a period of four


@dataclasses.dataclass(frozen=True)
class WindowMoEDecoderConfig:
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64  # the router's width
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_theta: float = 1500000.0
    rope_layout: tuple = _PERIOD
    sliding_window_layout: tuple = _PERIOD
    sliding_window_size: int = 4096
    rms_norm_eps: float = 1e-6
    vocab_size: int = 151936
    max_position_embeddings: int = 16384
    tie_word_embeddings: bool = False
    # --- this chip's share of a layer
    experts_held: int = 64
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16
    # --- blocking (no width): keys a block of the prefill's attention kernel,
    # token-expert pairs a block of the expert loop
    key_block: int = 512
    expert_block: int = 128

    def __post_init__(self):
        for layout in ("rope_layout", "sliding_window_layout"):  # as a configuration file gives them, lists: a static argument is hashed
            object.__setattr__(self, layout, tuple(getattr(self, layout)))
        if not (self.moe_primary_router_apply_softmax and self.norm_topk_prob) or self.tie_word_embeddings:
            raise ValueError("only a softmax router with renormalised gates and an untied head are computed here")
        if min(len(self.rope_layout), len(self.sliding_window_layout)) < self.num_hidden_layers:
            raise ValueError(f"the layouts name fewer than the {self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads or self.sliding_window_size % self.key_block:
            raise ValueError("query heads must share K/V heads evenly and the window be whole key blocks")

    @property
    def vocab_held(self) -> int:
        return self.vocab_size

    @property
    def kinds(self) -> tuple:
        """For each layer held: (windowed, its number among the layers of its kind)."""
        seen = {True: 0, False: 0}
        out = []
        for flag in self.sliding_window_layout[: self.num_hidden_layers]:
            out.append((bool(flag), seen[bool(flag)]))
            seen[bool(flag)] += 1
        return tuple(out)

    def inv_freq(self) -> np.ndarray:
        d = self.head_dim
        return self.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


#: the published configuration, uncut
SMALLTHINKER_21BA3B = WindowMoEDecoderConfig()


def init_cache(config: WindowMoEDecoderConfig, slots: int, positions: int) -> dict:
    """Keys and values of every global layer by position and of every window
    layer in its ring, for ``slots`` sequences of up to ``positions``
    tokens, zeroed: ``[layers of the kind, slots, K/V heads, positions or
    window, head_dim]``."""
    c = config
    window = sum(w for w, _ in c.kinds)
    heads = (slots, c.num_key_value_heads)
    by_position = (c.num_hidden_layers - window, *heads, positions, c.head_dim)
    ring = (window, *heads, c.sliding_window_size, c.head_dim)
    zeros = lambda shape: jnp.zeros(shape, c.dtype)
    return {"k": zeros(by_position), "v": zeros(by_position), "ring_k": zeros(ring), "ring_v": zeros(ring)}


# ------------------------------------------------------------------ pieces
def _row(cache, n: int, slot):
    """One sequence's ``[K/V heads, positions, head_dim]`` of layer ``n`` of a kind."""
    _, _, G, L, d = cache.shape
    return jax.lax.dynamic_slice(cache, (n, slot, 0, 0, 0), (1, 1, G, L, d))[0, 0]


def _put(cache, rows, at):
    """``rows`` [K/V heads, P, head_dim] into ``cache`` from ``at`` = (layer, slot, position)."""
    n, slot, position = at
    return jax.lax.dynamic_update_slice(cache, rows[None, None], (n, slot, 0, position, 0))


def _rope(x, pos, inv_freq):
    """Rope on the last axis of ``x`` [T, heads, dim], the two halves paired."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _reglu(x, p, dt):
    hidden = jax.nn.relu(_mm("tc,cf->tf", x, p["gate"])) * _mm("tc,cf->tf", x, p["up"])
    return _mm("tf,fc->tc", hidden.astype(dt), p["down"])


def _route(h, lp, cfg: WindowMoEDecoderConfig):
    """Each token's chosen experts and their gates, from the layer's input:
    a softmax over the router's width, the largest chosen, their gates
    renormalised to sum to one."""
    s = jax.nn.softmax(jnp.einsum("tc,ce->te", h.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST), axis=-1)
    weight, chosen = jax.lax.top_k(s, cfg.moe_num_active_primary_experts)
    return chosen, weight / jnp.sum(weight, axis=1, keepdims=True)


def _qkv(h, lp, pos, rope: bool, cfg: WindowMoEDecoderConfig):
    """Queries [T, H, d] (the softmax scale in them), keys and values [T, G, d]."""
    dt, d, T = cfg.dtype, cfg.head_dim, h.shape[0]
    x = _rms(h, lp["attn_norm"], cfg.rms_norm_eps).astype(dt)
    q = _mm("tc,cd->td", x, lp["q"]).reshape(T, cfg.num_attention_heads, d)
    k = _mm("tc,cd->td", x, lp["k"]).reshape(T, cfg.num_key_value_heads, d)
    v = _mm("tc,cd->td", x, lp["v"], dt).reshape(T, cfg.num_key_value_heads, d)
    if rope:
        inv_freq = jnp.asarray(cfg.inv_freq(), jnp.float32)
        q, k = _rope(q, pos, inv_freq), _rope(k, pos, inv_freq)
    return (q * d**-0.5).astype(dt), k.astype(dt), v


def _moe(a, lp, chosen, gates, live, cfg: WindowMoEDecoderConfig):
    """What the experts held here add to the rows ``a``, and the counts of the
    first four :data:`STATS`: the pairs computed here, the pairs chosen, the
    rows the expert product multiplies (each held expert's pairs in whole
    blocks, on either path), whether it took the grouped product."""
    x = _rms(a, lp["mlp_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    added, rows_here = _experts_here(x, chosen, gates, live, lp["experts"], cfg, activation=_reglu)
    B = cfg.expert_block
    multiplied = jnp.sum((_expert_counts(chosen, live, cfg)[1] + B - 1) // B) * B
    routed = jnp.sum(live) * cfg.moe_num_active_primary_experts
    grouped = _grouped(chosen.size, lp["experts"], cfg)
    return added, jnp.stack([rows_here, routed, multiplied, jnp.int32(grouped)]).astype(jnp.int32)


def _attend(q, keys, values, visible, start, first_key, length, window, cfg: WindowMoEDecoderConfig):
    """A chunk's queries [C, H, d] over ``keys`` / ``values`` [G, L, d] where
    ``visible`` [C, L] marks them: [C, H * d].  Query row ``t`` is key
    ``start + t``, no key before ``first_key`` is visible, ``length`` rows
    are real, and ``window`` (or ``None``) bounds how far back a row sees."""
    C, H, d = q.shape
    G = keys.shape[0]
    if jax.default_backend() == "tpu":
        from pathway_tpu.ops.selected_attention import grouped_attention  # Pallas: a second to import, so only where it runs

        out = grouped_attention(q.transpose(1, 0, 2), keys, values, visible, start, first_key, length, window=window, block_k=cfg.key_block)
        return out.transpose(1, 0, 2).reshape(C, H * d)
    q5 = q.reshape(C, G, H // G, d)
    s = _mm("cgjd,gsd->gjcs", q5, keys)
    p = jax.nn.softmax(jnp.where(visible[None, None], s, _NEG), axis=-1)
    return _mm("gjcs,gsd->gjcd", p.astype(cfg.dtype), values, cfg.dtype).transpose(2, 0, 1, 3).reshape(C, H * d)


def _attend_one(q, keys, values, visible, cfg: WindowMoEDecoderConfig):
    """One query [H, d] over ``keys`` / ``values`` [G, L, d] where ``visible`` [L] marks them: [H * d]."""
    G = keys.shape[0]
    q3 = q.reshape(G, -1, q.shape[-1])
    s = _mm("gjd,gsd->gjs", q3, keys)
    p = jax.nn.softmax(jnp.where(visible[None, None], s, _NEG), axis=-1)
    return _mm("gjs,gsd->gjd", p.astype(cfg.dtype), values, cfg.dtype).reshape(-1)


# ----------------------------------------------------------------- prefill
def _ring_and_chunk(ring, new, start, length, W: int):
    """A window layer's keys (or values) for a chunk at ``start`` with
    ``length`` real rows: what the chunk attends over, [G, W + C, d], the ring
    as it stood before the chunk laid out in position order (key ``i`` is
    position ``start - W + i``) and then the chunk's own; and the ring the
    chunk leaves, the last ``W`` real positions, position ``p`` at ``p mod W``."""
    keys = jnp.concatenate([jnp.roll(ring, -(start % W), axis=1), new], axis=1)
    return keys, jnp.roll(jax.lax.dynamic_slice_in_dim(keys, length, W, axis=1), (start + length) % W, axis=1)


def prefill(params, ids, cache, slot, start, length, last=True, *, config: WindowMoEDecoderConfig):
    """One bucket of a prompt: ``ids`` [C] (``length`` of them real, the rest
    padding) are the tokens ``start .. start + C`` of the sequence in
    ``slot``.  Returns float32 logits over the vocabulary at the last real
    token, the cache with the chunk's keys and values written, and the counts
    of :data:`STATS`.  ``start + C`` may not pass the cache's positions.
    ``last`` (whether the prompt ends in this chunk) is the executor's to say
    and changes nothing here: every layer runs for every token."""
    from pathway_tpu.ops.selected_attention import window_tiles  # Pallas: a second to import, so only where a prompt is traced

    cfg = config
    C, W = ids.shape[0], cfg.sliding_window_size
    pos = start + jnp.arange(C, dtype=jnp.int32)
    live = jnp.arange(C) < length
    k_all, v_all, ring_k, ring_v = cache["k"], cache["v"], cache["ring_k"], cache["ring_v"]
    # a global layer's keys are its cache by position; a window layer's the W positions before the chunk, then the
    # chunk's own (_ring_and_chunk): key i is position start - W + i, and none before position 0 is of this sequence
    by_position = jnp.arange(k_all.shape[3])[None, :] <= pos[:, None]
    i, t = jnp.arange(W + C)[None, :], jnp.arange(C)[:, None]
    first_key = jnp.maximum(W - start, 0)
    in_ring = (i <= W + t) & (i > t) & (i >= first_key)
    rows, first, ends = window_tiles(W, length, C, W, first_key, block_k=cfg.key_block)
    window_keys = jnp.stack([jnp.sum(jnp.where(live, jnp.minimum(pos + 1, W), 0)), rows * cfg.key_block * jnp.sum(ends - first)])

    h = params["embed"][ids].astype(jnp.float32)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    for li, (lp, (windowed, n)) in enumerate(zip(params["layers"], cfg.kinds)):
        chosen, gates = _route(h, lp, cfg)  # before attention: the layer's input
        q, k, v = _qkv(h, lp, pos, bool(cfg.rope_layout[li]), cfg)
        k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
        if windowed:
            (keys, left_k), (values, left_v) = (_ring_and_chunk(_row(ring, n, slot), new, start, length, W) for ring, new in ((ring_k, k), (ring_v, v)))
            out = _attend(q, keys, values, in_ring, W, first_key, length, W, cfg)
            ring_k, ring_v = _put(ring_k, left_k, (n, slot, 0)), _put(ring_v, left_v, (n, slot, 0))
        else:
            k_all, v_all = _put(k_all, k, (n, slot, start)), _put(v_all, v, (n, slot, start))
            out = _attend(q, _row(k_all, n, slot), _row(v_all, n, slot), by_position, start, 0, length, None, cfg)
        a = h + _mm("td,dc->tc", out, lp["o"])
        added, counted = _moe(a, lp, chosen, gates, live, cfg)
        h = a + added
        stats = stats + jnp.concatenate([counted, window_keys.astype(jnp.int32) * int(windowed)])
    logits = _logits(jax.lax.dynamic_slice_in_dim(h, length - 1, 1), params, cfg)[0]
    return logits, {"k": k_all, "v": v_all, "ring_k": ring_k, "ring_v": ring_v}, stats


# ------------------------------------------------------------------ decode
def decode_step(params, ids, cache, slots, lengths, *, config: WindowMoEDecoderConfig):
    """One new token for each of ``ids`` [B]: sequence ``slots[b]`` holds
    ``lengths[b]`` tokens and ``ids[b]`` becomes its next.  Returns float32
    logits [B, vocab], the cache with one more key and value a sequence in
    every layer, and the counts of :data:`STATS`."""
    cfg = config
    B, W = ids.shape[0], cfg.sliding_window_size
    live = jnp.ones((B,), bool)
    k_all, v_all, ring_k, ring_v = cache["k"], cache["v"], cache["ring_k"], cache["ring_v"]
    ring = jnp.arange(W)
    window_keys = jnp.stack([sum(jnp.minimum(lengths[b] + 1, W) for b in range(B)), jnp.int32(B * W)])
    h = params["embed"][ids].astype(jnp.float32)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    for li, (lp, (windowed, n)) in enumerate(zip(params["layers"], cfg.kinds)):
        chosen, gates = _route(h, lp, cfg)
        q, k, v = _qkv(h, lp, lengths, bool(cfg.rope_layout[li]), cfg)
        outs = []
        for b in range(B):  # a key written and a sequence's keys read, each in place: no copy of a cache
            t = lengths[b]
            if windowed:
                at = (n, slots[b], t % W)
                ring_k, ring_v = _put(ring_k, k[b][:, None], at), _put(ring_v, v[b][:, None], at)
                visible = t - (t - ring) % W >= 0  # the position entry j holds is of this sequence
                outs.append(_attend_one(q[b], _row(ring_k, n, slots[b]), _row(ring_v, n, slots[b]), visible, cfg))
            else:
                at = (n, slots[b], t)
                k_all, v_all = _put(k_all, k[b][:, None], at), _put(v_all, v[b][:, None], at)
                visible = jnp.arange(k_all.shape[3]) <= t
                outs.append(_attend_one(q[b], _row(k_all, n, slots[b]), _row(v_all, n, slots[b]), visible, cfg))
        a = h + _mm("td,dc->tc", jnp.stack(outs), lp["o"])
        added, counted = _moe(a, lp, chosen, gates, live, cfg)
        h = a + added
        stats = stats + jnp.concatenate([counted, window_keys.astype(jnp.int32) * int(windowed)])
    return _logits(h, params, cfg), {"k": k_all, "v": v_all, "ring_k": ring_k, "ring_v": ring_v}, stats
