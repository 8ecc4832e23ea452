"""A causal decoder with a latent (MLA) cache, optional lightning-indexer
sparse attention, routed experts of which this chip holds a share, and an
optional multi-token-prediction (MTP) layer that drafts the next token but
one -- the generation stage.

Two architectures share this module.  DeepSeek-V3.2-Exp's
(:data:`DEEPSEEK_V32_EXP`; ``config.json`` keys keep their published names in
:class:`DecoderConfig`) has the indexer; GLM-4.7-Flash's
(:data:`GLM_4_7_FLASH`) has none (``index_topk`` 0: no indexer weights, no
``index_k`` cache, every earlier key visible), plain rope (``rope_scaling``
``None``) and one MTP layer (``num_nextn_predict_layers`` 1).  Three more
keys say which share of a layer this chip holds: ``experts_held`` /
``expert_offset`` (the router keeps its published width and its experts per
token, the chip computes its own experts' part of the result and leaves out
what the absent ones would add) and ``vocab_held`` (embedding, head, logits
and the greedy choice are over that slice).

Two programs over one pre-sized cache, both pure functions of ``(params,
..., cache)`` that return the cache they were given, updated in place when
the caller donates it (:class:`pathway_tpu.parallel.JittedDecoder` does):

- :func:`prefill` -- a bucket of prompt tokens of one sequence, written into
  its slot from ``start`` on.  Keys and values are expanded from the latent
  rows per head (the compute-bound form), block of keys by block with a
  running softmax, over as many blocks as the chunk's last token can see:
  on a TPU in one fused kernel (``ops/selected_attention.py``), whose query
  tiles each visit only the blocks their own last row can see and none where
  the tile is padding, elsewhere as the same loop written in ``jax.numpy``
  over every block for every query.
- :func:`decode_step` -- one new token for each of a few sequences, in the
  absorbed form: the query heads against one 576-wide latent row a token.

A configuration with an MTP layer is served by two more programs in their
place, which the executor takes where the module offers them
(:func:`prefill_drafted`, :func:`verify_step`): the prompt's chunks fill the
MTP layer's own latent cache beside the main one and the last returns the
first draft; each step then runs the pending token and the draft through
the main stack at once, two queries causal between them, keeps the draft
where the main model would have chosen it, and drafts again (below).

Per layer the cache holds the latent row ``[cKV; k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` values a token) and, where there is an
indexer, the indexer's key (``index_head_dim`` values a token).  A query
attends to the ``index_topk`` keys its indexer scores highest among those
before it; the selection is a mask at the exact k-th largest score (a
bisection over the scores' bit patterns, no sort), so prefill and decode
share one rule.

Weights and caches are ``config.dtype`` (bfloat16); products accumulate in
float32; the residual stream, norms, the router, the indexer's scores and
the softmax are float32.

Four architectures give :class:`pathway_tpu.parallel.JittedDecoder` the five
names ``init_cache``, ``prefill``, ``decode_step``, ``STATS`` and
``DISPATCH_TOKENS``: this module, :mod:`pathway_tpu.models.hybrid_decoder`,
:mod:`pathway_tpu.models.shortcut_moe_decoder` and
:mod:`pathway_tpu.models.window_moe_decoder`.  The third calls what this
module has after the selection as it stands: the latent-attention core
(:func:`_prefill_core`, :func:`_decode_core`, with the causal mask in the
selection's place) and :func:`_experts_here`, with ``_logits``, ``_swiglu``,
``_rms``, ``_rotate``, ``_mm`` and ``_rows_of``; the fourth calls
:func:`_experts_here` with its experts' activation and every expert held,
with ``_logits``, ``_rms`` and ``_mm``.  A change to one of them for one
generator is measured on the others.

**The MTP layer** (DeepSeek-V3 report section 2.2; vLLM's
``deepseek_mtp.py``).  Position ``i`` pairs the main model's final-normed
hidden state ``ĥ_i`` with the next token ``t_{i+1}``: ``x_i = [RMS(E
t_{i+1}; w_e) ; RMS(ĥ_i; w_h)] W_eh`` (the embedding half zero at position
0), ``u_i`` one full routed layer over ``x_i`` with its own latent cache row
at ``i``, and ``draft_i = argmax RMS(u_i; w_sh) W_head``, the guess for
``t_{i+2}``; embedding and head are the main model's.  A verify step holds a
length ``L``, the pending token ``t_L`` and a draft ``d``: the main stack runs
``[t_L, d]`` at ``L`` and ``L + 1``; where ``argmax row_L`` is ``d`` both ``d``
and ``argmax row_{L+1}`` are emitted and ``L' = L + 2``, else ``argmax row_L``
alone and ``L' = L + 1`` (the rows at ``L + 1`` are written again by the next
step, the cache being indexed by position); the MTP layer runs at ``L`` and
``L + 1`` and the next draft is the one from ``L' - 1``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "DecoderConfig", "DEEPSEEK_V32_EXP", "GLM_4_7_FLASH", "init_cache", "prefill", "decode_step", "prefill_drafted", "verify_step", "STATS",
    "DISPATCH_TOKENS",
]

#: what both programs count, in the order of the vector they return
STATS = ("moe_rows_here", "moe_rows_routed", "dsa_keys_selected", "dsa_keys_scored", "moe_grouped_calls")

#: what one more prefill dispatch costs beside its tokens, in tokens: every
#: weight is read again and the sequence's keys and values are expanded again
#: (on a v5e at the published widths 25 ms, where 512 tokens of a chunk cost 33)
DISPATCH_TOKENS = 512

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256  # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048  # 0: no indexer, every earlier key visible
    rope_theta: float = 10000.0
    rope_scaling: tuple | None = (  # None: plain rope, and the plain softmax scale
        ("beta_fast", 32), ("beta_slow", 1), ("factor", 40), ("mscale", 1),
        ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096), ("type", "yarn"),
    )
    rms_norm_eps: float = 1e-6
    vocab_size: int = 129280  # published; ``vocab_held`` rows of it live here
    # MTP layers served; DeepSeek-V3.2-Exp publishes one, which is not served here (its configuration says so)
    num_nextn_predict_layers: int = 0
    # --- this chip's share of a layer
    experts_held: int = 256
    expert_offset: int = 0
    vocab_held: int = 129280
    dtype: Any = jnp.bfloat16
    # --- blocking (no width): keys a block of the prefill's attention loop,
    # token-expert pairs a block of the expert loop
    key_block: int = 512
    expert_block: int = 128

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        if self.rope_scaling is None:
            return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = dict(self.rope_scaling)
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def inv_freq(self) -> np.ndarray:
        """YaRN: the published frequencies where a rotation completes often
        within the original context, the interpolated ones where it does not,
        a linear ramp between; the published ones alone without
        ``rope_scaling``."""
        dim = self.qk_rope_head_dim
        freq = self.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        if self.rope_scaling is None:
            return freq
        rs = dict(self.rope_scaling)

        def dim_of(rotations: float) -> float:
            return dim * math.log(rs["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(self.rope_theta))

        low = max(math.floor(dim_of(rs["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
        interpolated = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
        return freq * (1.0 - interpolated) + freq / rs["factor"] * interpolated


#: the published configuration, uncut
DEEPSEEK_V32_EXP = DecoderConfig()

#: GLM-4.7-Flash's published configuration (``glm4_moe_lite``), uncut: MLA with
#: no indexer and plain rope over all 64 rope dimensions, one dense layer, 64
#: routed experts of 1,536 with 4 a token in one group, one MTP layer
GLM_4_7_FLASH = DecoderConfig(
    hidden_size=2048, num_hidden_layers=47, first_k_dense_replace=1, intermediate_size=10240, moe_intermediate_size=1536,
    n_routed_experts=64, n_shared_experts=1, num_experts_per_tok=4, n_group=1, topk_group=1, routed_scaling_factor=1.8,
    num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    index_n_heads=0, index_head_dim=0, index_topk=0, rope_theta=1000000.0, rope_scaling=None, rms_norm_eps=1e-5,
    vocab_size=154880, num_nextn_predict_layers=1, experts_held=64, vocab_held=154880,
)


def init_cache(config: DecoderConfig, slots: int, positions: int) -> dict:
    """The state of every layer, for ``slots`` sequences of up to
    ``positions`` tokens, zeroed: latent rows, the indexer's keys where there
    is an indexer, and the MTP layer's own latent rows where there is one."""
    shape = (config.num_hidden_layers, slots, positions)
    cache = {"latent": jnp.zeros((*shape, config.latent_width), config.dtype)}
    if config.index_topk:
        cache["index_k"] = jnp.zeros((*shape, config.index_head_dim), config.dtype)
    if config.num_nextn_predict_layers:
        # one MTP layer drafts one token a step (a second, chained, is not served)
        cache["mtp"] = jnp.zeros((1, slots, positions, config.latent_width), config.dtype)
    return cache


# ------------------------------------------------------------------ pieces
def _mm(spec: str, a, b, out=None):
    """A product of ``config.dtype`` inputs accumulated in float32."""
    y = jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return y if out is None else y.astype(out)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotate(x, pos, inv_freq):
    """Rope on the last axis of ``x`` [T, ..., dim], neighbouring pairs."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],))
    x = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    re, im = x[..., 0], x[..., 1]
    out = jnp.stack([re * jnp.cos(ang) - im * jnp.sin(ang), re * jnp.sin(ang) + im * jnp.cos(ang)], axis=-1)
    return out.reshape(*out.shape[:-2], -1)


def _swiglu(x, p, dt):
    hidden = jax.nn.silu(_mm("tc,cf->tf", x, p["gate"])) * _mm("tc,cf->tf", x, p["up"])
    return _mm("tf,fc->tc", hidden.astype(dt), p["down"])


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def _select(scores, visible, k: int):
    """For each row the keys it attends to: the ``k`` largest ``scores``
    among the ``visible``, all of them where fewer are.  The threshold is
    the exact k-th largest, found bit by bit from the top: 32 counts."""
    bits = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))

    def narrow(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(bits >= trial[:, None], axis=-1) >= k
        return jnp.where(enough, trial, found)

    kth = jax.lax.fori_loop(0, 32, narrow, jnp.zeros(bits.shape[:1], jnp.uint32))
    return visible & (bits >= kth[:, None])


def _attention_inputs(h, lp, pos, cfg: DecoderConfig):
    """What both forms of attention share: the queries, the indexer's
    queries and weights, and the two rows each token adds to the cache (the
    indexer's three ``None`` where there is no indexer)."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    T = h.shape[0]
    inv_freq = jnp.asarray(cfg.inv_freq(), jnp.float32)
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    x = _rms(h, lp["attn_norm"], eps).astype(dt)
    cq = _rms(_mm("tc,cr->tr", x, lp["q_a"]), lp["q_norm"], eps).astype(dt)
    q = _mm("tr,rd->td", cq, lp["q_b"]).reshape(T, cfg.num_attention_heads, nope + rope)
    q_nope, q_rope = q[..., :nope].astype(dt), _rotate(q[..., nope:], pos, inv_freq).astype(dt)
    kva = _mm("tc,cr->tr", x, lp["kv_a"])
    latent = jnp.concatenate(
        [_rms(kva[:, : cfg.kv_lora_rank], lp["kv_norm"], eps), _rotate(kva[:, cfg.kv_lora_rank :], pos, inv_freq)], axis=-1
    ).astype(dt)
    if not cfg.index_topk:
        return q_nope, q_rope, latent, None, None, None
    qi = _mm("tr,rd->td", cq, lp["idx_q"]).reshape(T, cfg.index_n_heads, cfg.index_head_dim)
    qi = jnp.concatenate([_rotate(qi[..., :rope], pos, inv_freq), qi[..., rope:]], axis=-1).astype(dt)
    ki = _mm("tc,cd->td", x, lp["idx_k"])
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    ki = (ki - mean) * jax.lax.rsqrt(jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True) + 1e-6)
    ki = ki * lp["idx_k_norm"]["scale"] + lp["idx_k_norm"]["bias"]
    ki = jnp.concatenate([_rotate(ki[:, :rope], pos, inv_freq), ki[:, rope:]], axis=-1).astype(dt)
    wi = _mm("tc,cj->tj", x, lp["idx_w"]) * (cfg.index_n_heads**-0.5 * cfg.index_head_dim**-0.5)
    return q_nope, q_rope, latent, qi, ki, wi


def _route(x, lp, cfg: DecoderConfig):
    """Each token's chosen experts (published numbers) and their gates."""
    s = jax.nn.sigmoid(jnp.einsum("tc,ce->te", x.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
    biased = s + lp["router_bias"]
    T, E = s.shape
    per = E // cfg.n_group
    grouped = biased.reshape(T, cfg.n_group, per)
    group_rank = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    best = jax.lax.top_k(group_rank, cfg.topk_group)[1]
    keep = jnp.any(best[:, :, None] == jnp.arange(cfg.n_group)[None, None, :], axis=1)
    eligible = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
    chosen = jax.lax.top_k(eligible, cfg.num_experts_per_tok)[1]
    weight = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, weight / jnp.sum(weight, axis=1, keepdims=True) * cfg.routed_scaling_factor


#: bytes of VMEM the grouped product's kernel may hold (``ops/grouped_experts.py``):
#: an expert's matrices twice, the run's and the next expert's fetched while it
#: computes, in half of it, the tiles of rows and their temporaries in the rest
_GROUPED_VMEM = 64 * 2**20


def _tiles(pairs: int, cfg) -> int:
    """The most tiles of ``expert_block`` rows that ``pairs`` token-expert
    pairs fill when each held expert's run is cut into whole tiles: the
    grouped product's grid and the block loop's longest run."""
    E, B = cfg.experts_held, cfg.expert_block
    return min(pairs, (pairs + E * (B - 1)) // B)


def _grouped(pairs: int, experts, cfg) -> bool:
    """Whether :func:`_experts_here` multiplies ``pairs`` (the static ``T *
    K``) by the grouped product rather than the block loop: where the pairs
    can fill more tiles than there are experts held, so that the loop would
    read some expert's matrices more than once (a prompt chunk, not a decode
    step's few pairs), and an expert's matrices fit twice in half of the
    kernel's VMEM (SmallThinker's 11.8 MB do, DeepSeek's 88 MB and LongCat's
    75 MB do not: their share of a chunk's pairs fills about one block an
    expert, which the loop reads once)."""
    expert_bytes = sum(math.prod(w.shape[1:]) * jnp.dtype(w.dtype).itemsize for w in jax.tree.leaves(experts))
    return _tiles(pairs, cfg) > cfg.experts_held and 4 * expert_bytes <= _GROUPED_VMEM


def _expert_counts(chosen, live, cfg):
    """Each token-expert pair's expert among those held here, flat
    (``experts_held`` where it is not this chip's: another chip's expert, an
    expert that holds nothing, a padding row), and each held expert's pairs."""
    E = cfg.experts_held
    local = chosen - cfg.expert_offset
    expert_of = jnp.where((local >= 0) & (local < E) & live[:, None], local, E).reshape(-1)
    return expert_of, jnp.sum(expert_of[:, None] == jnp.arange(E)[None, :], axis=0).astype(jnp.int32)


def _experts_here(x, chosen, gates, live, experts, cfg: DecoderConfig, activation=_swiglu):
    """The part of the routed result that the experts held here give:
    ``sum over chosen experts e held here of gates_e FFN_e(x)``, where the
    expert's feed-forward is ``activation(x, expert, dtype)`` (SwiGLU unless
    the architecture's experts are another gated unit).

    A grouped product over uneven groups with nothing dropped: the
    token-expert pairs that fall to this chip are ordered by expert and each
    expert's run is cut into blocks of ``expert_block`` pairs (the last one
    part empty).  Where :func:`_grouped` says so (a prompt chunk), the rows
    are gathered once in that layout, each expert's matrices multiply all of
    its blocks in one read (``ops/grouped_experts.py`` on a TPU, the same
    tiles in ``jax.numpy`` elsewhere), and each token sums its pairs' rows;
    otherwise (a decode step) a loop over exactly the blocks in use
    multiplies each by its expert's three matrices and adds it in.  Either
    way work follows the pairs that came, not the worst case, and the blocks
    multiplied are the same.  Expert ids outside the held range are not this
    chip's, whatever they are: another chip's experts, or experts that hold
    nothing (a router wider than ``n_routed_experts``, whose caller adds what
    those give).  Returns the result [T, hidden] float32 and the pairs
    computed."""
    T, K = chosen.shape
    E, B, dt = cfg.experts_held, cfg.expert_block, cfg.dtype
    expert_of, count = _expert_counts(chosen, live, cfg)
    order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
    first_pair = jnp.cumsum(count) - count
    blocks = (count + B - 1) // B
    last_block = jnp.cumsum(blocks)
    flat_gates = gates.reshape(-1)
    if _grouped(T * K, experts, cfg):
        return _grouped_product(x, expert_of, order, first_pair, blocks, last_block, flat_gates, experts, cfg, activation), jnp.sum(count)

    def one_block(b, out):
        e = jnp.sum(b >= last_block).astype(jnp.int32)  # the expert whose run holds block b
        within = (b - (last_block[e] - blocks[e])) * B + jnp.arange(B, dtype=jnp.int32)
        valid = within < count[e]
        pair = order[jnp.clip(first_pair[e] + within, 0, T * K - 1)]
        token = pair // K
        p = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(w, e, keepdims=False), experts)
        y = activation(x[token], p, dt) * jnp.where(valid, flat_gates[pair], 0.0)[:, None]
        return out.at[token].add(y)

    out = jax.lax.fori_loop(0, last_block[-1], one_block, jnp.zeros((T, x.shape[1]), jnp.float32))
    return out, jnp.sum(count)


def _grouped_product(x, expert_of, order, first_pair, blocks, last_block, flat_gates, experts, cfg, activation):
    """:func:`_experts_here`'s product for a prompt chunk: every pair here
    gets a row of a buffer of :func:`_tiles` tiles, expert by expert from the
    first tile, each expert's run starting a tile (the rows left in its last
    tile carry a zero gate); a tile's rows are multiplied by its expert's
    matrices and scaled by their gates, and each token sums its pairs' rows.
    [T, hidden] float32."""
    (T, H), N = x.shape, expert_of.shape[0]
    E, B, dt = cfg.experts_held, cfg.expert_block, cfg.dtype
    n_tiles = _tiles(N, cfg)
    pair = jnp.arange(N, dtype=jnp.int32)
    rank = jnp.zeros(N, jnp.int32).at[order].set(pair)  # where each pair stands in the expert order
    here = expert_of < E
    e = jnp.minimum(expert_of, E - 1)
    row = jnp.where(here, (last_block[e] - blocks[e]) * B + rank - first_pair[e], n_tiles * B)  # past the end: not here
    token = jnp.zeros(n_tiles * B, jnp.int32).at[row].set(pair // (N // T), mode="drop")
    row_gates = jnp.zeros((n_tiles * B, 1), jnp.float32).at[row, 0].set(flat_gates, mode="drop")
    used = last_block[-1]
    # a step past the tiles in use reads the last one's blocks again: nothing is fetched, nothing computed
    at = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(jnp.sum(at[:, None] >= last_block[None, :], axis=1), E - 1).astype(jnp.int32)
    xs = x[token]
    if jax.default_backend() == "tpu":
        from pathway_tpu.ops.grouped_experts import grouped_experts  # Pallas: a second to import, so only where it runs

        ys = grouped_experts(
            xs, row_gates, tile_expert, at, used[None], experts, activation=activation, dtype=dt, block=B, vmem_bytes=_GROUPED_VMEM
        )
    else:

        def one_tile(args):
            rows, g, e = args
            p = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(w, e, keepdims=False), experts)
            return activation(rows, p, dt) * g

        ys = jax.lax.map(one_tile, (xs.reshape(n_tiles, B, H), row_gates.reshape(n_tiles, B, 1), tile_expert)).reshape(n_tiles * B, H)
    mine = jnp.where(here[:, None], ys[jnp.minimum(row, n_tiles * B - 1)], 0.0)
    return jnp.sum(mine.reshape(T, N // T, H), axis=1)


def _mlp(h, lp, live, cfg: DecoderConfig):
    """The layer's feed-forward half; returns what it adds, the
    token-expert pairs (computed here, chosen anywhere) and whether the
    experts took the grouped product (1 or 0)."""
    x = _rms(h, lp["mlp_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    if "mlp" in lp:
        return _swiglu(x, lp["mlp"], cfg.dtype), jnp.int32(0), jnp.int32(0), 0
    chosen, gates = _route(x, lp, cfg)
    routed, rows_here = _experts_here(x, chosen, gates, live, lp["experts"], cfg)
    rows_routed = jnp.sum(live).astype(jnp.int32) * cfg.num_experts_per_tok
    return _swiglu(x, lp["shared"], cfg.dtype) + routed, rows_here, rows_routed, int(_grouped(chosen.size, lp["experts"], cfg))


def _logits(h, params, cfg: DecoderConfig):
    x = _rms(h, params["final_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    return _mm("tc,cv->tv", x, params["head"])


def _rows_of(cache, layer: int, slot):
    """One sequence's rows of one layer, [positions, width], sliced out of
    the whole cache (never a layer's worth of slots)."""
    _, _, positions, width = cache.shape
    return jax.lax.dynamic_slice(cache, (layer, slot, 0, 0), (1, 1, positions, width))[0, 0]


# ----------------------------------------------------------------- prefill
def _prefill_core(q_nope, q_rope, latent_rows, mask, n_blocks, lp, cfg, start=None, length=None):
    """Latent attention of a chunk's queries over the cached rows ``mask``
    [C, L] marks, in the expanded form, through ``W_o``: [C, hidden].  The
    chunk's queries are positions ``start .. start + C`` of the sequence,
    ``length`` of them real (without ``start``: the latest start that
    ``n_blocks`` allows; without ``length``: every row), and the mask marks
    no key after a query's position.  Keys and values are expanded over the
    ``n_blocks`` key blocks the chunk's last token can see; on a TPU the
    kernel's query tiles visit fewer (a tile the blocks its own last row can
    see, a tile of padding none: :func:`pathway_tpu.ops.selected_attention.
    query_tiles`), and a row of such a tile comes out zero.  Every query's
    mask holds a key.  The core of every architecture with a latent cache
    (this module passes its indexer's selection,
    :mod:`pathway_tpu.models.shortcut_moe_decoder` the causal mask); of
    ``cfg`` it reads the head sizes, ``kv_lora_rank``, ``key_block``,
    ``dtype`` and ``softmax_scale``, of ``lp`` ``kv_b`` and ``o``."""
    C, KB, dt = q_nope.shape[0], cfg.key_block, cfg.dtype
    H, nope, vd, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    L = latent_rows.shape[0]
    if jax.default_backend() == "tpu":
        # the fused kernel (ops/selected_attention.py): keys and values expanded block by block into buffers per
        # head, then scores, softmax and weighted sum with nothing of a score tile leaving the chip
        from pathway_tpu.ops.selected_attention import selected_attention  # Pallas: a second to import, so only where it runs

        kv_b = lp["kv_b"].reshape(rank, H, nope + vd)

        def expand_block(b, buffers):
            rows = jax.lax.dynamic_slice_in_dim(latent_rows, b * KB, KB)[:, :rank]
            parts = (_mm("sr,rhd->hsd", rows, kv_b[..., :nope], dt), _mm("sr,rhd->hsd", rows, kv_b[..., nope:], dt))
            return tuple(jax.lax.dynamic_update_slice_in_dim(buf, part, b * KB, axis=1) for buf, part in zip(buffers, parts))

        k_nope, v = jax.lax.fori_loop(0, n_blocks, expand_block, (jnp.zeros((H, L, nope), dt), jnp.zeros((H, L, vd), dt)))
        scaled = lambda q: (q.astype(jnp.float32) * cfg.softmax_scale).astype(dt).transpose(1, 0, 2)
        start = n_blocks * KB - C if start is None else start
        length = C if length is None else length
        out = selected_attention(scaled(q_nope), scaled(q_rope), k_nope, latent_rows[:, rank:], v, mask, start, length, block_k=KB)
        return _mm("td,dc->tc", out.transpose(1, 0, 2).reshape(C, H * vd), lp["o"])

    def attend_block(b, carry):
        top, mass, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(latent_rows, b * KB, KB)
        kv = _mm("sr,rd->sd", rows[:, :rank], lp["kv_b"], dt).reshape(KB, H, nope + vd)
        s = (_mm("thd,shd->hts", q_nope, kv[..., :nope]) + _mm("thd,sd->hts", q_rope, rows[:, rank:])) * cfg.softmax_scale
        sel = jax.lax.dynamic_slice_in_dim(mask, b * KB, KB, axis=1)[None]
        new_top = jnp.maximum(top, jnp.max(jnp.where(sel, s, _NEG), axis=-1))
        p = jnp.where(sel, jnp.exp(s - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        acc = acc * shrink[..., None] + _mm("hts,shd->htd", p.astype(dt), kv[..., nope:])
        return new_top, mass * shrink + jnp.sum(p, axis=-1), acc

    start = (jnp.full((H, C), _NEG, jnp.float32), jnp.zeros((H, C), jnp.float32), jnp.zeros((H, C, vd), jnp.float32))
    _, mass, acc = jax.lax.fori_loop(0, n_blocks, attend_block, start)
    out = (acc / mass[..., None]).astype(dt).transpose(1, 0, 2).reshape(C, H * vd)
    return _mm("td,dc->tc", out, lp["o"])


def _prefill_attention(q_nope, q_rope, qi, wi, latent_rows, index_rows, pos, n_blocks, lp, cfg: DecoderConfig, start, length):
    """Attention of a chunk's queries over their sequence's cached rows: the
    indexer's scores over the ``n_blocks`` key blocks the chunk's last token
    can see, the selection, and the core over the keys selected (the chunk
    at ``start`` with ``length`` real rows, as :func:`_prefill_core` takes
    them)."""
    C, KB = q_nope.shape[0], cfg.key_block
    L = latent_rows.shape[0]

    def score_block(b, scores):
        keys = jax.lax.dynamic_slice_in_dim(index_rows, b * KB, KB)
        per_head = jax.nn.relu(_mm("tjd,sd->tjs", qi, keys))
        return jax.lax.dynamic_update_slice_in_dim(scores, jnp.sum(per_head * wi[:, :, None], axis=1), b * KB, axis=1)

    scores = jax.lax.fori_loop(0, n_blocks, score_block, jnp.full((C, L), _NEG, jnp.float32))
    visible = jnp.arange(L)[None, :] <= pos[:, None]
    selected = _select(scores, visible, cfg.index_topk)
    return _prefill_core(q_nope, q_rope, latent_rows, selected, n_blocks, lp, cfg, start, length), selected, visible


def _chunk_layer(h, lp, rows_all, layer: int, slot, pos, n_blocks, live, start, length, cfg: DecoderConfig):
    """One layer with no indexer over a prompt chunk at positions ``pos``
    (``live`` of them real): its latent rows written into layer ``layer`` of
    ``rows_all`` from ``start`` on, each query over every key up to its own
    (:func:`_prefill_core` with the causal mask), the feed-forward half.
    Returns the residual stream after the layer, ``rows_all`` and the
    layer's counts of :data:`STATS`."""
    q_nope, q_rope, latent, _, _, _ = _attention_inputs(h, lp, pos, cfg)
    rows_all = jax.lax.dynamic_update_slice(rows_all, latent[None, None], (layer, slot, start, 0))
    rows = _rows_of(rows_all, layer, slot)
    visible = jnp.arange(rows.shape[0])[None, :] <= pos[:, None]
    h = h + _prefill_core(q_nope, q_rope, rows, visible, n_blocks, lp, cfg, start, length)
    added, rows_here, rows_routed, grouped = _mlp(h, lp, live, cfg)
    return h + added, rows_all, jnp.stack([rows_here, rows_routed, jnp.int32(0), jnp.int32(0), jnp.int32(grouped)])


def _prompt_layers(params, ids, cache, slot, start, length, cfg: DecoderConfig):
    """The main stack over one bucket of a prompt (what :func:`prefill` and
    :func:`prefill_drafted` share): the residual stream [C, hidden] after
    the last layer, the cache with the chunk's rows written, the counts."""
    C = ids.shape[0]
    pos = start + jnp.arange(C, dtype=jnp.int32)
    live = jnp.arange(C) < length
    n_blocks = (start + C + cfg.key_block - 1) // cfg.key_block
    h = params["embed"][ids].astype(jnp.float32)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    latent_all, index_all = cache["latent"], cache.get("index_k")
    for li, lp in enumerate(params["layers"]):
        if index_all is None:
            h, latent_all, counts = _chunk_layer(h, lp, latent_all, li, slot, pos, n_blocks, live, start, length, cfg)
            stats = stats + counts
            continue
        q_nope, q_rope, latent, qi, ki, wi = _attention_inputs(h, lp, pos, cfg)
        latent_all = jax.lax.dynamic_update_slice(latent_all, latent[None, None], (li, slot, start, 0))
        index_all = jax.lax.dynamic_update_slice(index_all, ki[None, None], (li, slot, start, 0))
        latent_rows, index_rows = _rows_of(latent_all, li, slot), _rows_of(index_all, li, slot)
        attended, selected, visible = _prefill_attention(
            q_nope, q_rope, qi, wi, latent_rows, index_rows, pos, n_blocks, lp, cfg, start=start, length=length
        )
        h = h + attended
        added, rows_here, rows_routed, grouped = _mlp(h, lp, live, cfg)
        h = h + added
        stats = stats + jnp.stack([
            rows_here, rows_routed,
            jnp.sum(selected & live[:, None]).astype(jnp.int32), jnp.sum(visible & live[:, None]).astype(jnp.int32), jnp.int32(grouped),
        ])
    cache = dict(cache, latent=latent_all)
    if index_all is not None:
        cache["index_k"] = index_all
    return h, cache, stats


def prefill(params, ids, cache, slot, start, length, last=True, *, config: DecoderConfig):
    """One bucket of a prompt: ``ids`` [C] (``length`` of them real, the rest
    padding) are the tokens ``start .. start + C`` of the sequence in
    ``slot``.  Returns float32 logits over the held vocabulary at the last
    real token, the cache with the chunk's rows written, and the counts of
    :data:`STATS`.  ``start + C`` may not pass the cache's positions.
    ``last`` (whether the prompt ends in this chunk) is the executor's to
    say and changes nothing here: every layer runs for every token."""
    h, cache, stats = _prompt_layers(params, ids, cache, slot, start, length, config)
    last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1)
    return _logits(last, params, config)[0], cache, stats


# --------------------------------------------------------------- the MTP layer
#: logits of each draft kept beside its id: its largest, the draft's among them
DRAFT_KEPT = 8


def _mtp_input(params, next_ids, normed, pos, cfg: DecoderConfig):
    """The MTP layer's input at positions ``pos``: ``[RMS(E t_{i+1}; w_e) ;
    RMS(ĥ_i; w_h)] W_eh`` from the next tokens ``next_ids`` and the main
    model's final-normed rows ``normed`` (float32), the embedding half zero
    at position 0.  [T, hidden] float32."""
    mp, eps = params["mtp"], cfg.rms_norm_eps
    embedded = jnp.where((pos == 0)[:, None], 0.0, params["embed"][next_ids].astype(jnp.float32))
    x = jnp.concatenate([_rms(embedded, mp["enorm"], eps), _rms(normed, mp["hnorm"], eps)], axis=-1).astype(cfg.dtype)
    return _mm("tc,cd->td", x, mp["eh_proj"])


def _draft(u, params, cfg: DecoderConfig):
    """The draft from one row ``u`` [hidden] of the MTP layer's output: the
    ``DRAFT_KEPT`` largest logits of ``RMS(u; w_sh) W_head`` over the held
    vocabulary and their ids, the largest (the draft) first."""
    x = _rms(u, params["mtp"]["head_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    return jax.lax.top_k(_mm("c,cv->v", x, params["head"]), DRAFT_KEPT)


def prefill_drafted(params, ids, next_id, cache, slot, start, length, last, *, config: DecoderConfig):
    """:func:`prefill` for a configuration with an MTP layer, which also
    runs over the chunk: position ``i`` pairs ``ĥ_i`` with ``t_{i+1}`` -- the
    chunk's own next id, at its last real position ``next_id`` (the next
    chunk's first) or, where the prompt ends here (``last``), the token
    chosen from the logits -- and writes its own latent row.  Returns the
    token chosen (int32 [1]), the float32 logits it was chosen from, the
    draft made at the last real position (:func:`_draft`), the cache and
    the counts of :data:`STATS`, the MTP layer's among them."""
    cfg = config
    C = ids.shape[0]
    h, cache, stats = _prompt_layers(params, ids, cache, slot, start, length, cfg)
    logits = _logits(jax.lax.dynamic_slice_in_dim(h, length - 1, 1), params, cfg)[0]
    token = jnp.argmax(logits).astype(jnp.int32)
    following = jnp.where(last, token, next_id)
    next_ids = jnp.where(jnp.arange(C) == length - 1, following, jnp.roll(ids, -1))
    pos = start + jnp.arange(C, dtype=jnp.int32)
    x = _mtp_input(params, next_ids, _rms(h, params["final_norm"], cfg.rms_norm_eps), pos, cfg)
    n_blocks = (start + C + cfg.key_block - 1) // cfg.key_block
    u, mtp, counts = _chunk_layer(x, params["mtp"]["layer"], cache["mtp"], 0, slot, pos, n_blocks, jnp.arange(C) < length, start, length, cfg)
    draft = _draft(jax.lax.dynamic_index_in_dim(u, length - 1, keepdims=False), params, cfg)
    return token[None], logits, draft, dict(cache, mtp=mtp), stats + counts


# ------------------------------------------------------------------ decode
def _decode_core(q_nope, q_rope, latent_rows, mask, lp, cfg):
    """One query against the cached rows ``mask`` [1, L] marks, in the
    absorbed form: the query is carried into the latent space (``q_nope
    W_kvb^K``), scores and the weighted sum are taken over the latent rows
    themselves, and the result is carried out again (``W_kvb^V``):
    [heads * v_head_dim], before ``W_o``.  Shared as :func:`_prefill_core` is."""
    dt, rank, nope = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv_b = lp["kv_b"].reshape(rank, cfg.num_attention_heads, nope + cfg.v_head_dim)
    q_latent = _mm("hd,rhd->hr", q_nope, kv_b[..., :nope], dt)
    s = (_mm("hr,sr->hs", q_latent, latent_rows[:, :rank]) + _mm("hd,sd->hs", q_rope, latent_rows[:, rank:])) * cfg.softmax_scale
    p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1)
    mixed = _mm("hs,sr->hr", p.astype(dt), latent_rows[:, :rank], dt)
    return _mm("hr,rhd->hd", mixed, kv_b[..., nope:], dt).reshape(-1)


def _decode_attention(q_nope, q_rope, qi, wi, latent_rows, index_rows, pos, lp, cfg: DecoderConfig):
    """One query against its sequence's cached rows: the indexer's scores,
    the selection, and the core over the keys selected."""
    L = latent_rows.shape[0]
    index = jnp.sum(jax.nn.relu(_mm("jd,sd->js", qi, index_rows)) * wi[:, None], axis=0)
    visible = (jnp.arange(L) <= pos)[None, :]
    selected = _select(index[None, :], visible, cfg.index_topk)
    out = _decode_core(q_nope, q_rope, latent_rows, selected, lp, cfg)
    return out, jnp.sum(selected).astype(jnp.int32), jnp.sum(visible).astype(jnp.int32)


def decode_step(params, ids, cache, slots, lengths, *, config: DecoderConfig):
    """One new token for each of ``ids`` [B]: sequence ``slots[b]`` holds
    ``lengths[b]`` tokens and ``ids[b]`` becomes its next.  Returns float32
    logits [B, vocab_held], the cache with one more row a sequence, and the
    counts of :data:`STATS`."""
    cfg = config
    B = ids.shape[0]
    h = params["embed"][ids].astype(jnp.float32)
    live = jnp.ones((B,), bool)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    latent_all, index_all = cache["latent"], cache.get("index_k")
    for li, lp in enumerate(params["layers"]):
        q_nope, q_rope, latent, qi, ki, wi = _attention_inputs(h, lp, lengths, cfg)
        outs = []
        for b in range(B):  # a row written and a sequence's rows read, each in place: no copy of a cache
            latent_all = jax.lax.dynamic_update_slice(latent_all, latent[b][None, None, None], (li, slots[b], lengths[b], 0))
            if index_all is None:  # every key up to the token's own
                rows = _rows_of(latent_all, li, slots[b])
                visible = (jnp.arange(rows.shape[0]) <= lengths[b])[None, :]
                outs.append((_decode_core(q_nope[b], q_rope[b], rows, visible, lp, cfg), jnp.int32(0), jnp.int32(0)))
                continue
            index_all = jax.lax.dynamic_update_slice(index_all, ki[b][None, None, None], (li, slots[b], lengths[b], 0))
            outs.append(_decode_attention(
                q_nope[b], q_rope[b], qi[b], wi[b], _rows_of(latent_all, li, slots[b]), _rows_of(index_all, li, slots[b]), lengths[b], lp, cfg
            ))
        out, selected, visible = (jnp.stack(parts) for parts in zip(*outs))
        h = h + _mm("td,dc->tc", out, lp["o"])
        added, rows_here, rows_routed, grouped = _mlp(h, lp, live, cfg)
        h = h + added
        stats = stats + jnp.stack([rows_here, rows_routed, jnp.sum(selected), jnp.sum(visible), jnp.int32(grouped)])
    cache = dict(cache, latent=latent_all)
    if index_all is not None:
        cache["index_k"] = index_all
    return _logits(h, params, cfg), cache, stats


def _step_layer(h, lp, rows_all, layer: int, slot, pos, cfg: DecoderConfig):
    """One layer with no indexer over ``T`` consecutive new tokens of the
    sequence in ``slot`` at positions ``pos`` [T]: their latent rows written
    into layer ``layer`` of ``rows_all``, each query in the absorbed form
    (:func:`_decode_core`) over every key up to its own, so the tokens are
    causal among themselves; the feed-forward half over the ``T`` rows."""
    q_nope, q_rope, latent, _, _, _ = _attention_inputs(h, lp, pos, cfg)
    rows_all = jax.lax.dynamic_update_slice(rows_all, latent[None, None], (layer, slot, pos[0], 0))
    rows = _rows_of(rows_all, layer, slot)
    visible = (jnp.arange(rows.shape[0])[None, :] <= pos[:, None])[:, None, :]
    out = jax.vmap(lambda qn, qr, mask: _decode_core(qn, qr, rows, mask, lp, cfg))(q_nope, q_rope, visible)
    h = h + _mm("td,dc->tc", out, lp["o"])
    added, rows_here, rows_routed, grouped = _mlp(h, lp, jnp.ones(h.shape[:1], bool), cfg)
    return h + added, rows_all, jnp.stack([rows_here, rows_routed, jnp.int32(0), jnp.int32(0), jnp.int32(grouped)])


def verify_step(params, token, draft, cache, slot, length, *, config: DecoderConfig):
    """One step of drafting decode for the sequence in ``slot``, which holds
    ``length`` tokens (int32 scalar): its pending token ``token`` [1] and the
    draft ``draft`` [1] through the main stack at ``length`` and ``length +
    1``, then the MTP layer at both.  Returns how many tokens the step emits
    (1 where the main row at ``length`` chooses another token than the
    draft, else 2), the two ids the main rows choose and those rows (float32
    [2, vocab_held]; the second counts only where the draft was kept), the
    next draft (:func:`_draft`, from position ``length' - 1``), the next
    ``(length', token', draft')``, the cache and the counts of
    :data:`STATS`.  ``length'`` is held at ``positions - 2`` or under, so
    that a step past the answer's end, whose tokens the executor drops,
    writes nothing past the cache."""
    cfg = config
    positions = cache["latent"].shape[2]
    pos = length + jnp.arange(2, dtype=jnp.int32)
    h = params["embed"][jnp.concatenate([token, draft])].astype(jnp.float32)
    stats = jnp.zeros((len(STATS),), jnp.int32)
    latent_all = cache["latent"]
    for li, lp in enumerate(params["layers"]):
        h, latent_all, counts = _step_layer(h, lp, latent_all, li, slot, pos, cfg)
        stats = stats + counts
    rows = _logits(h, params, cfg)
    chosen = jnp.argmax(rows, axis=-1).astype(jnp.int32)
    kept = chosen[0] == draft[0]
    x = _mtp_input(params, chosen, _rms(h, params["final_norm"], cfg.rms_norm_eps), pos, cfg)
    u, mtp, counts = _step_layer(x, params["mtp"]["layer"], cache["mtp"], 0, slot, pos, cfg)
    values, ids = _draft(jnp.where(kept, u[1], u[0]), params, cfg)
    emitted = 1 + kept.astype(jnp.int32)
    state = (jnp.minimum(length + emitted, positions - 2), jnp.where(kept, chosen[1:], chosen[:1]), ids[:1])
    return emitted, chosen, rows, (values, ids), state, dict(cache, latent=latent_all, mtp=mtp), stats + counts
