"""A decoder-hybrid-decoder: state-space, window-attention, full-attention,
gated-memory and cross-attention layers in one stack -- the generation
stage's second architecture.

The architecture is Phi-4-mini-flash-reasoning's (``config.json`` keys keep
their published names in :class:`HybridDecoderConfig`; the sizes the file does
not give are Mamba-1's defaults).  With ``N`` layers, ``N`` a multiple of 4:

- the **self-decoder**, layers ``0 .. N/2 + 1``: Mamba layers (even) and
  window-attention layers (odd, ``sliding_window`` keys, the query's own
  among them) in turn, then one more Mamba layer (``N/2``, which also
  publishes its scan's output ``m``) and one full-attention layer
  (``N/2 + 1``) whose keys and values are kept;
- the **cross-decoder**, the other ``N/2 - 2``: gated memory units (even:
  ``W_out (m * silu(W_in u))``, no state of their own) and cross-attention
  layers (odd: queries of their own against layer ``N/2 + 1``'s keys and
  values) in turn.

Every layer is ``x += Mix(LN(x)); x += SwiGLU(LN'(x))``.  Attention is
differential: consecutive query heads pair up, consecutive key heads pair up,
two value heads make one value of twice the width, and a pair's result is
``softmax(q1 k1) v - lambda softmax(q2 k2) v``, RMS-normed.  There is no
positional encoding: the recurrence carries order.

A request holds four kinds of state (:func:`init_cache`): each Mamba layer's
recurrent state and convolution tail (overwritten a token, not indexed by
position: a slot's new request starts them from zero, inside :func:`prefill`
at ``start == 0``); each window layer's ring of ``sliding_window`` keys and
values (position ``p`` lives at ``p mod window``; with no positional encoding
the order inside the ring does not matter, only which entries are of this
request, which the position says); layer ``N/2 + 1``'s keys and values by
position; and nothing for the gated memory units, which read ``m`` of the
same token.

The same two programs as :mod:`pathway_tpu.models.decoder`, over that cache:

- :func:`prefill` -- a bucket of prompt tokens through the self-decoder only
  (a prompt token's cross-decoder output is read by nothing); the chunk the
  caller marks ``last`` sends its last real token through the cross-decoder
  and the head.  Padding past ``length`` advances neither the recurrent
  state (``dt`` 0) nor the tail nor the rings.  Window layers multiply each
  block of queries against two blocks of keys (the ring's and their own);
  the full layer against the blocks a query block can see.
- :func:`decode_step` -- one new token through all the layers.

Same-kind layers are stacked and scanned, so the programs do not grow with
depth.  Weights and caches are ``config.dtype`` (bfloat16), the recurrent
state float32; products accumulate in float32; the residual stream, norms,
softmax, ``dt`` and ``exp(dt A)`` are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["HybridDecoderConfig", "PHI4_MINI_FLASH", "init_cache", "prefill", "decode_step", "STATS", "DISPATCH_TOKENS"]

#: what both programs count, in the order of the vector they return: rows sent
#: through the cross-decoder / tokens seen; keys inside a window query's window /
#: keys its layer multiplied
STATS = ("xdec_tokens_run", "xdec_tokens_seen", "swa_keys_in_window", "swa_keys_multiplied")

#: what one more prefill dispatch costs beside its tokens, in tokens: the
#: self-decoder's weights are read again (3.9 GB; on a v5e 5.4 ms, where 512
#: tokens of a chunk cost 16.6: chunks of 512, 2,048 and 2,560 take 22.0, 71.9
#: and 95.3 ms)
DISPATCH_TOKENS = 160

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    vocab_size: int = 200064
    # --- not in the published file: Mamba-1's defaults
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160  # ceil(hidden_size / 16)
    vocab_held: int = 200064  # the whole vocabulary lives here
    dtype: Any = jnp.bfloat16
    # --- blocking (no width): queries and keys a block of the full layer's attention loop
    key_block: int = 512

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8 or self.mb_per_layer != 2:
            raise ValueError("the layer pattern needs mb_per_layer 2 and a multiple of 4 layers, 8 or more")
        if self.num_attention_heads != 2 * self.num_key_value_heads or self.num_key_value_heads % 2:
            raise ValueError("differential attention here pairs two query pairs with one key pair")
        if self.key_block % self.sliding_window:
            raise ValueError(f"the key block {self.key_block} must be a multiple of the window {self.sliding_window}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def key_pairs(self) -> int:
        return self.num_key_value_heads // 2

    @property
    def self_pairs(self) -> int:
        return self.num_hidden_layers // 4

    @property
    def cross_pairs(self) -> int:
        return self.num_hidden_layers // 4 - 1


#: the published configuration, uncut
PHI4_MINI_FLASH = HybridDecoderConfig()


def init_cache(config: HybridDecoderConfig, slots: int, positions: int) -> dict:
    """The four kinds of state of ``slots`` sequences of up to ``positions``
    tokens, zeroed: recurrent states and convolution tails of the
    ``N/4 + 1`` Mamba layers, rings of the ``N/4`` window layers, and the one
    full-attention layer's keys and values."""
    c = config
    mamba, window = c.self_pairs + 1, c.self_pairs
    return {
        "ssm": jnp.zeros((mamba, slots, c.mamba_d_state, c.d_inner), jnp.float32),
        "conv": jnp.zeros((mamba, slots, c.mamba_d_conv - 1, c.d_inner), c.dtype),
        "ring_k": jnp.zeros((window, slots, c.key_pairs, c.sliding_window, 2 * c.head_dim), c.dtype),
        "ring_v": jnp.zeros((window, slots, c.key_pairs, c.sliding_window, 2 * c.head_dim), c.dtype),
        "k": jnp.zeros((slots, c.key_pairs, positions, 2 * c.head_dim), c.dtype),
        "v": jnp.zeros((slots, c.key_pairs, positions, 2 * c.head_dim), c.dtype),
    }


# ------------------------------------------------------------------ pieces
def _mm(spec: str, a, b):
    """A product of ``config.dtype`` inputs accumulated in float32."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _ln(x, p, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    return centred * jax.lax.rsqrt(jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) * p["scale"] + p["bias"]


def _mlp(h, lp, cfg):
    x = _ln(h, lp["mlp_norm"], cfg.layer_norm_eps).astype(cfg.dtype)
    p = lp["mlp"]
    hidden = jax.nn.silu(_mm("tc,cf->tf", x, p["gate"])) * _mm("tc,cf->tf", x, p["up"])
    return _mm("tf,fc->tc", hidden.astype(cfg.dtype), p["down"])


def _lambda_init(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(jnp.float32))


def _lambda(lp, layer):
    f32 = lambda v: v.astype(jnp.float32)
    return jnp.exp(jnp.sum(f32(lp["lambda_q1"]) * f32(lp["lambda_k1"]))) - jnp.exp(jnp.sum(f32(lp["lambda_q2"]) * f32(lp["lambda_k2"]))) + _lambda_init(layer)


def _split_q(q, cfg):
    """[T, heads * d] -> [T, key pairs, 2 query pairs, 2 (q1, q2), d], the softmax scale in."""
    return (q * cfg.head_dim**-0.5).astype(cfg.dtype).reshape(q.shape[0], cfg.key_pairs, 2, 2, cfg.head_dim)


def _by_pair(rows, cfg):
    """Keys or values by token [T, kv heads * d] -> as the caches hold them,
    [key pairs, T, 2d]: a key pair's ``[k1; k2]`` (a value pair's ``[v1;
    v2]``) is one row of 128, so that a decode step reads a sequence's keys
    and values in the layout its products want (held by token, the compiler
    copies every cache whole into another layout and back at every step:
    1.7 ms of a 13 ms step on a v5e)."""
    return rows.reshape(rows.shape[0], cfg.key_pairs, 2 * cfg.head_dim).transpose(1, 0, 2)


def _by_token(rows):
    """:func:`_by_pair`'s inverse: [key pairs, T, 2d] -> [T, kv heads * d]."""
    return rows.transpose(1, 0, 2).reshape(rows.shape[1], -1)


def _scores(q5, k, cfg):
    """Queries [Q, G, 2, 2, d] against keys -> [G, 2, 2, Q, S] float32: a
    query pair's q1 against its key pair's k1, q2 against k2.  Keys by token
    [S, kv heads * d] (a prompt chunk's) or by pair [G, S, 2d] (a decode
    step's, straight from the cache: q1 then meets the first half of a row,
    q2 the second, the other half of each query zero)."""
    if k.ndim == 2:
        return _mm("qgpwd,sgwd->gpwqs", q5, k.reshape(k.shape[0], cfg.key_pairs, 2, cfg.head_dim))
    Q, G, P, W, d = q5.shape
    zero = jnp.zeros_like(q5[..., 0, :])
    halves = jnp.stack([jnp.concatenate([q5[..., 0, :], zero], axis=-1), jnp.concatenate([zero, q5[..., 1, :]], axis=-1)], axis=-2)
    return _mm("gre,gse->grs", halves.transpose(1, 2, 3, 0, 4).reshape(G, P * W * Q, 2 * d), k).reshape(G, P, W, Q, -1)


def _weighted(p, v, cfg):
    """Softmax weights [G, 2, 2, Q, S] over values by token or by pair -> [Q, G, 2, 2, 2d] float32."""
    if v.ndim == 2:
        return _mm("gpwqs,sge->qgpwe", p.astype(v.dtype), v.reshape(v.shape[0], cfg.key_pairs, 2 * cfg.head_dim))
    G, P, W, Q, S = p.shape
    return _mm("grs,gse->gre", p.astype(v.dtype).reshape(G, P * W * Q, S), v).reshape(G, P, W, Q, -1).transpose(3, 0, 1, 2, 4)


def _difference(o, lp, layer, cfg):
    """``o`` [Q, G, 2, 2, 2d]: both softmaxes' results of every query pair ->
    the layer's attention output [Q, hidden] in ``config.dtype``, before ``W_o``."""
    a = o[..., 0, :] - _lambda(lp, layer) * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + cfg.layer_norm_eps) * lp["subln"]
    return (a * (1.0 - _lambda_init(layer))).reshape(o.shape[0], -1).astype(cfg.dtype)


def _attend(q5, k, v, visible, lp, layer, cfg):
    """Differential attention of queries over keys and values (by token or by pair), ``visible`` [Q, S]."""
    p = jax.nn.softmax(jnp.where(visible, _scores(q5, k, cfg), _NEG), axis=-1)
    return _difference(_weighted(p, v, cfg), lp, layer, cfg)


def _qkv(h, lp, cfg):
    x = _ln(h, lp["norm"], cfg.layer_norm_eps).astype(cfg.dtype)
    qkv = _mm("tc,cf->tf", x, lp["qkv"]) + lp["qkv_b"]
    kv = cfg.num_key_value_heads * cfg.head_dim
    return qkv[:, : -2 * kv], qkv[:, -2 * kv : -kv].astype(cfg.dtype), qkv[:, -kv:].astype(cfg.dtype)


def _project_out(a, lp):
    return _mm("td,dc->tc", a, lp["o"]) + lp["o_b"]


def _row(cache, index):
    """``cache[*index]``, sliced out of the whole cache (never a layer's worth of slots)."""
    rest = cache.shape[len(index) :]
    return jax.lax.dynamic_slice(cache, (*index, *(0,) * len(rest)), (1,) * len(index) + rest).reshape(rest)


def _put(cache, rows, index):
    """``rows`` written into ``cache`` in place from ``index`` on (its leading entries; the rest 0)."""
    index = (*index, *(0,) * (cache.ndim - len(index)))
    return jax.lax.dynamic_update_slice(cache, rows.reshape((1,) * (cache.ndim - rows.ndim) + rows.shape).astype(cache.dtype), index)


# ------------------------------------------------------------------- Mamba
def _mamba_in(h, lp, cfg):
    u = _ln(h, lp["norm"], cfg.layer_norm_eps).astype(cfg.dtype)
    xz = _mm("tc,cf->tf", u, lp["in"])
    return xz[:, : cfg.d_inner], xz[:, cfg.d_inner :]


def _ssm_inputs(xc, lp, cfg):
    """``dt``, ``B``, ``C`` of the convolution's outputs ``xc`` [T, d_inner]."""
    R, S, dt = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.dtype
    proj = _mm("tf,fr->tr", xc.astype(dt), lp["x"])
    delta = jax.nn.softplus(_mm("tr,rf->tf", proj[:, :R].astype(dt), lp["dt_w"]) + lp["dt_b"])
    return delta, proj[:, R : R + S], proj[:, R + S :]


def _mamba_out(y, z, lp, cfg):
    return _mm("tf,fc->tc", (y * jax.nn.silu(z)).astype(cfg.dtype), lp["out"])


def _a(lp):
    return -jnp.exp(lp["A_log"].astype(jnp.float32)).T  # [states, channels]


def _mamba_chunk(h, lp, tail, state, start, length, cfg):
    """A chunk through a Mamba mixer: what it adds, its scan's output, and the
    tail and state the chunk's ``length`` real tokens leave."""
    C, K = h.shape[0], cfg.mamba_d_conv
    x, z = _mamba_in(h, lp, cfg)
    fresh = start == 0  # the slot's last request left a state: this one starts from none
    tail = jnp.where(fresh, 0.0, tail.astype(jnp.float32))
    state = jnp.where(fresh, 0.0, state)
    padded = jnp.concatenate([tail, x], axis=0)
    w = lp["conv_w"].astype(jnp.float32)
    xc = jax.nn.silu(sum(w[k] * jax.lax.dynamic_slice_in_dim(padded, k, C) for k in range(K)) + lp["conv_b"])
    delta, b, c = _ssm_inputs(xc, lp, cfg)
    delta = jnp.where((jnp.arange(C) < length)[:, None], delta, 0.0)  # padding leaves the state as it is
    from pathway_tpu.ops import selective_scan as ops  # Pallas: a second to import, so only where this architecture runs

    fused = jax.default_backend() == "tpu" and cfg.d_inner % ops.CHANNEL_BLOCK == 0  # elsewhere the same recurrence in jax.numpy
    y, state = (ops.selective_scan if fused else ops.selective_scan_reference)(xc, delta, _a(lp), b, c, lp["D"], state)
    return _mamba_out(y, z, lp, cfg), y, jax.lax.dynamic_slice_in_dim(padded, length, K - 1), state


def _mamba_token(h, lp, conv, ssm, layer, slots, cfg):
    """One token a sequence through a Mamba mixer, its states read and written in place."""
    x, z = _mamba_in(h, lp, cfg)
    w = lp["conv_w"].astype(jnp.float32)
    tails = [jnp.concatenate([_row(conv, (layer, s)).astype(jnp.float32), x[i : i + 1]], axis=0) for i, s in enumerate(slots)]
    xc = jax.nn.silu(jnp.stack([jnp.sum(w * t, axis=0) for t in tails]) + lp["conv_b"])
    delta, b, c = _ssm_inputs(xc, lp, cfg)
    a, ys = _a(lp), []
    for i, s in enumerate(slots):
        state = jnp.exp(delta[i][None, :] * a) * _row(ssm, (layer, s)) + (delta[i] * xc[i])[None, :] * b[i][:, None]
        ys.append(jnp.sum(state * c[i][:, None], axis=0) + lp["D"] * xc[i])
        ssm, conv = _put(ssm, state, (layer, s)), _put(conv, tails[i][1:], (layer, s))
    y = jnp.stack(ys)
    return _mamba_out(y, z, lp, cfg), y, conv, ssm


# --------------------------------------------------------- window attention
def _window_chunk(h, lp, ring_k, ring_v, layer, start, length, cfg):
    """A chunk through a window-attention mixer.  The ring holds the ``W``
    positions before ``start`` in order (``start`` is a multiple of ``W``);
    a block of ``W`` queries sees the block of keys before its own and its
    own, no other.  Returns what the layer adds and the ring its ``length``
    real tokens leave."""
    C, W = h.shape[0], cfg.sliding_window
    q, k, v = _qkv(h, lp, cfg)
    keys, values = jnp.concatenate([_by_token(ring_k), k]), jnp.concatenate([_by_token(ring_v), v])
    q5 = _split_q(q, cfg)
    q5 = q5.reshape(C // W, W, *q5.shape[1:])
    r = jnp.arange(W)[:, None]
    j = jnp.arange(2 * W)[None, :]

    def block(args):
        qb, n = args
        position = start - W + n * W + j  # of the 2W keys this block of queries is multiplied with
        visible = (j > r) & (j <= r + W) & (position >= 0)
        kb, vb = (jax.lax.dynamic_slice_in_dim(a, n * W, 2 * W) for a in (keys, values))
        return _attend(qb, kb, vb, visible, lp, layer, cfg)

    a = jax.lax.map(block, (q5, jnp.arange(C // W))).reshape(C, -1)
    # the ring after the chunk: position p at p mod W, the last W positions up to start + length - 1
    rows = length + (jnp.arange(W) - length) % W  # in keys/values, whose row i is position start - W + i
    return _project_out(a, lp), _by_pair(keys[rows], cfg), _by_pair(values[rows], cfg)


def _window_token(h, lp, ring_k, ring_v, index, layer, slots, lengths, cfg):
    W = cfg.sliding_window
    q, k, v = _qkv(h, lp, cfg)
    q5 = _split_q(q, cfg)
    outs = []
    for i, s in enumerate(slots):
        at = lengths[i] % W
        ring_k, ring_v = _put(ring_k, _by_pair(k[i : i + 1], cfg), (index, s, 0, at)), _put(ring_v, _by_pair(v[i : i + 1], cfg), (index, s, 0, at))
        r = jnp.arange(W)
        visible = (lengths[i] - (lengths[i] - r) % W >= 0)[None, :]  # the position entry r holds is of this request
        outs.append(_attend(q5[i : i + 1], _row(ring_k, (index, s)), _row(ring_v, (index, s)), visible, lp, layer, cfg))
    return _project_out(jnp.concatenate(outs), lp), ring_k, ring_v


# ----------------------------------------------------- full and cross attention
def _full_chunk(q, rows_k, rows_v, lp, layer, start, cfg):
    """A chunk's queries over their sequence's keys and values by position,
    block of queries by block, each over the key blocks it can see, with a
    running softmax."""
    C, KB, G = q.shape[0], cfg.key_block, cfg.key_pairs
    q5 = _split_q(q, cfg)
    q5 = q5.reshape(C // KB, KB, *q5.shape[1:])

    def block(args):
        qb, n = args
        pos = start + n * KB + jnp.arange(KB)

        def keys(b, carry):
            top, mass, acc = carry
            kb, vb = (jax.lax.dynamic_slice_in_dim(a, b * KB, KB) for a in (rows_k, rows_v))
            visible = (b * KB + jnp.arange(KB))[None, :] <= pos[:, None]
            s = jnp.where(visible, _scores(qb, kb, cfg), _NEG)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            p = jnp.where(visible, jnp.exp(s - new_top[..., None]), 0.0)
            shrink = jnp.exp(top - new_top)
            acc = acc * jnp.moveaxis(shrink, 3, 0)[..., None] + _weighted(p, vb, cfg)
            return new_top, mass * shrink + jnp.sum(p, axis=-1), acc

        first = (jnp.full((G, 2, 2, KB), _NEG, jnp.float32), jnp.zeros((G, 2, 2, KB), jnp.float32), jnp.zeros((KB, G, 2, 2, 2 * cfg.head_dim), jnp.float32))
        _, mass, acc = jax.lax.fori_loop(0, start // KB + n + 1, keys, first)
        return _difference(acc / jnp.moveaxis(mass, 3, 0)[..., None], lp, layer, cfg)

    return jax.lax.map(block, (q5, jnp.arange(C // KB))).reshape(C, -1)


def _over_positions(q, k_all, v_all, lp, layer, slots, last, cfg):
    """One query a sequence over the keys and values by position of its slot,
    those up to ``last[i]``."""
    q5 = _split_q(q, cfg)
    outs = []
    for i, s in enumerate(slots):
        visible = (jnp.arange(k_all.shape[2]) <= last[i])[None, :]
        outs.append(_attend(q5[i : i + 1], _row(k_all, (s,)), _row(v_all, (s,)), visible, lp, layer, cfg))
    return jnp.concatenate(outs)


def _cross_decoder(params, h, m, k_all, v_all, slots, last, cfg):
    """Rows ``h`` [T, hidden] (row i of the sequence in ``slots[i]``, at
    position ``last[i]``) through the gated memory units, which read ``m``
    [T, d_inner], and the cross-attention layers, and the head."""
    first = cfg.num_hidden_layers // 2 + 2

    def pair(h, xs):
        layers, n = xs
        gmu, cross = layers["gmu"], layers["cross"]
        u = _ln(h, gmu["norm"], cfg.layer_norm_eps).astype(cfg.dtype)
        h = h + _mm("tf,fc->tc", (m * jax.nn.silu(_mm("tc,cf->tf", u, gmu["in"]))).astype(cfg.dtype), gmu["out"])
        h = h + _mlp(h, gmu, cfg)
        x = _ln(h, cross["norm"], cfg.layer_norm_eps).astype(cfg.dtype)
        q = _mm("tc,cf->tf", x, cross["q"]) + cross["q_b"]
        h = h + _project_out(_over_positions(q, k_all, v_all, cross, first + 2 * n + 1, slots, last, cfg), cross)
        return h + _mlp(h, cross, cfg), None

    h, _ = jax.lax.scan(pair, h, (params["cross_pairs"], jnp.arange(cfg.cross_pairs)))
    x = _ln(h, params["final_norm"], cfg.layer_norm_eps).astype(cfg.dtype)
    return _mm("tc,vc->tv", x, params["embed"])


# ----------------------------------------------------------------- prefill
def prefill(params, ids, cache, slot, start, length, last=True, *, config: HybridDecoderConfig):
    """One bucket of a prompt: ``ids`` [C] (``length`` of them real, the rest
    padding) are the tokens ``start .. start + C`` of the sequence in
    ``slot``; ``start`` is a multiple of the key block.  Returns float32
    logits over the vocabulary at the last real token where ``last`` (zeros
    where more of the prompt follows: nothing reads them), the cache the
    chunk leaves, and the counts of :data:`STATS`."""
    cfg = config
    C, W = ids.shape[0], cfg.sliding_window
    if C % cfg.key_block:
        raise ValueError(f"a chunk of {C} tokens is not a multiple of the key block {cfg.key_block}")
    h = params["embed"][ids].astype(jnp.float32)
    n_pairs, mid = cfg.self_pairs, cfg.num_hidden_layers // 2

    def pair(carry, xs):
        h, ssm, conv, ring_k, ring_v = carry
        layers, n = xs
        mamba, window = layers["mamba"], layers["window"]
        added, _, tail, state = _mamba_chunk(h, mamba, _row(conv, (n, slot)), _row(ssm, (n, slot)), start, length, cfg)
        h = h + added
        h = h + _mlp(h, mamba, cfg)
        added, rk, rv = _window_chunk(h, window, _row(ring_k, (n, slot)), _row(ring_v, (n, slot)), 2 * n + 1, start, length, cfg)
        h = h + added
        h = h + _mlp(h, window, cfg)
        return (h, _put(ssm, state, (n, slot)), _put(conv, tail, (n, slot)), _put(ring_k, rk, (n, slot)), _put(ring_v, rv, (n, slot))), None

    carry = (h, cache["ssm"], cache["conv"], cache["ring_k"], cache["ring_v"])
    (h, ssm, conv, ring_k, ring_v), _ = jax.lax.scan(pair, carry, (params["self_pairs"], jnp.arange(n_pairs)))
    mamba, full = params["mamba_last"], params["full"]
    added, m, tail, state = _mamba_chunk(h, mamba, _row(conv, (n_pairs, slot)), _row(ssm, (n_pairs, slot)), start, length, cfg)
    ssm, conv = _put(ssm, state, (n_pairs, slot)), _put(conv, tail, (n_pairs, slot))
    h = h + added
    h = h + _mlp(h, mamba, cfg)
    q, k, v = _qkv(h, full, cfg)
    k_all, v_all = _put(cache["k"], _by_pair(k, cfg), (slot, 0, start)), _put(cache["v"], _by_pair(v, cfg), (slot, 0, start))
    rows_k, rows_v = _by_token(_row(k_all, (slot,))), _by_token(_row(v_all, (slot,)))
    h = h + _project_out(_full_chunk(q, rows_k, rows_v, full, jnp.int32(mid + 1), start, cfg), full)
    h = h + _mlp(h, full, cfg)

    def finish(h, m):
        row = lambda a: jax.lax.dynamic_slice_in_dim(a, length - 1, 1)
        at = jnp.reshape(start + length - 1, (1,))
        return _cross_decoder(params, row(h), row(m), k_all, v_all, [slot], at, cfg)[0]

    logits = jax.lax.cond(last, finish, lambda h, m: jnp.zeros((params["embed"].shape[0],), jnp.float32), h, m)
    t = start + jnp.arange(C)
    in_window = jnp.sum(jnp.where(jnp.arange(C) < length, jnp.minimum(t + 1, W), 0))
    stats = jnp.stack([
        jnp.asarray(last, jnp.int32), length,
        n_pairs * in_window, n_pairs * C * 2 * W,
    ]).astype(jnp.int32)
    return logits, {"ssm": ssm, "conv": conv, "ring_k": ring_k, "ring_v": ring_v, "k": k_all, "v": v_all}, stats


# ------------------------------------------------------------------ decode
def decode_step(params, ids, cache, slots, lengths, *, config: HybridDecoderConfig):
    """One new token for each of ``ids`` [B]: sequence ``slots[b]`` holds
    ``lengths[b]`` tokens and ``ids[b]`` becomes its next.  Returns float32
    logits [B, vocab_held], the cache with every kind of state one token on,
    and the counts of :data:`STATS`."""
    cfg = config
    B, W = ids.shape[0], cfg.sliding_window
    n_pairs, mid = cfg.self_pairs, cfg.num_hidden_layers // 2
    slots, lengths = [slots[b] for b in range(B)], [lengths[b] for b in range(B)]
    h = params["embed"][ids].astype(jnp.float32)

    def pair(carry, xs):
        h, ssm, conv, ring_k, ring_v = carry
        layers, n = xs
        mamba, window = layers["mamba"], layers["window"]
        added, _, conv, ssm = _mamba_token(h, mamba, conv, ssm, n, slots, cfg)
        h = h + added
        h = h + _mlp(h, mamba, cfg)
        added, ring_k, ring_v = _window_token(h, window, ring_k, ring_v, n, 2 * n + 1, slots, lengths, cfg)
        h = h + added
        return (h + _mlp(h, window, cfg), ssm, conv, ring_k, ring_v), None

    carry = (h, cache["ssm"], cache["conv"], cache["ring_k"], cache["ring_v"])
    (h, ssm, conv, ring_k, ring_v), _ = jax.lax.scan(pair, carry, (params["self_pairs"], jnp.arange(n_pairs)))
    mamba, full = params["mamba_last"], params["full"]
    added, m, conv, ssm = _mamba_token(h, mamba, conv, ssm, n_pairs, slots, cfg)
    h = h + added
    h = h + _mlp(h, mamba, cfg)
    q, k, v = _qkv(h, full, cfg)
    k_all, v_all = cache["k"], cache["v"]
    for b in range(B):
        k_all, v_all = _put(k_all, _by_pair(k[b : b + 1], cfg), (slots[b], 0, lengths[b])), _put(v_all, _by_pair(v[b : b + 1], cfg), (slots[b], 0, lengths[b]))
    h = h + _project_out(_over_positions(q, k_all, v_all, full, jnp.int32(mid + 1), slots, lengths, cfg), full)
    h = h + _mlp(h, full, cfg)
    logits = _cross_decoder(params, h, m, k_all, v_all, slots, lengths, cfg)
    in_window = sum(jnp.minimum(n + 1, W) for n in lengths)
    stats = jnp.stack([
        jnp.int32(B), jnp.int32(B), n_pairs * in_window, jnp.int32(n_pairs * B * W),
    ]).astype(jnp.int32)
    return logits, {"ssm": ssm, "conv": conv, "ring_k": ring_k, "ring_v": ring_v, "k": k_all, "v": v_all}, stats
