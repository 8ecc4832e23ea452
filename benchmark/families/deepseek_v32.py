"""Model family ``deepseek_v32``: a causal decoder with latent (MLA)
attention, a lightning indexer that selects the keys each query attends to,
and routed experts of which this chip holds a share.

Everything the yardstick knows of this family, in one file, found by the
``family`` a configuration's model group names:

- ``make_params(group, seed)`` -- the parameter tree drawn from the seed in
  one jitted call on the device, bfloat16, under the names the program's
  ``models/decoder.py`` takes (``TPUDecoderChat(..., params=tree)``).  An
  expert's weights depend on its number among the published 256 and a row of
  the vocabulary on its number among the published 129,280, so another
  ``expert_offset`` / ``vocab_offset`` draws another share of the same model.
- the plain forward (``reference_logits``): the layer equations below in
  float32 ``jax.numpy`` at ``highest`` matmul precision over the same tree
  (each layer's weights widened to float32 while that layer is applied and
  dropped after it), full forward with no cache, no chunking and no absorbed
  form, attention in query blocks so that it fits, the expert share given by
  the group's ``n_routed_experts`` (held here) / ``expert_offset``.  It imports nothing of
  the program.  ``precision`` selects the arithmetic of every matrix product
  as in ``bert_encoder``: ``"f32"`` the reference, ``"fp8"`` the control.
- ``flops(group, useful_tokens)`` and ``decode_bytes(group, context)`` --
  the work a request needs and the bytes a decode step touches, from the
  group's numbers, never from what the program dispatches.
- ``built_differs(group, built)``.

**The layer equations** (ISSUE 28 section 1; DeepSeek-V3 report section 2.1,
DeepSeek-V3.2-Exp report section 2.1 and the published ``inference/model.py``).
For the hidden states ``h_t`` of a layer, ``x = RMSNorm(h)``:

- MLA: ``cQ = RMSNorm(W_qa x)``; ``q = W_qb cQ`` -> heads of ``[q_nope;
  q_rope]``; ``[cKV; k_rope] = W_kva x``, ``cKV <- RMSNorm(cKV)``; YaRN rope
  (pairs of neighbouring elements rotated) on ``q_rope`` of every head and on
  the one shared ``k_rope``; ``[k_nope_i; v_i] = W_kvb cKV`` per head; score
  ``(q_nope.k_nope + q_rope.k_rope) * (nope+rope)^-1/2 * m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; softmax over the selected
  ``s <= t``; ``o = W_o [sum_s p v]``.
- lightning indexer: ``qI = W_Iq cQ`` -> ``index_n_heads`` heads, rope on the
  first ``qk_rope_head_dim`` elements of each; ``kI = LayerNorm(W_Ik x)``,
  rope on its first ``qk_rope_head_dim``; ``w = W_Iw x * n_heads^-1/2 *
  head_dim^-1/2``; ``I_ts = sum_j w_tj ReLU(qI_tj . kI_s)``; the selected set
  of ``t`` is the ``index_topk`` largest ``I_ts`` over ``s <= t`` (all of
  them while ``t < index_topk``).
- routed experts: ``s = sigmoid(W_g x)``; for choosing only ``s' = s + b``;
  groups ranked by the sum of their two largest ``s'``, the best
  ``topk_group`` kept, the ``num_experts_per_tok`` largest ``s'`` in them
  chosen; gates ``s_e / sum_chosen s * routed_scaling_factor``, normalised
  over all chosen, held here or not; ``y = SwiGLU_shared(x) + sum over the
  chosen experts held here of g_e SwiGLU_e(x)``.  What the absent experts
  would add is left out.  The leading dense layers: one SwiGLU.
- head: final RMSNorm, logits over the held rows of the vocabulary.

Departures from the release (each configuration's ``assumed`` lists them):
no Hadamard rotation and no FP8 in the indexer, bfloat16 weights.
"""

from __future__ import annotations

import functools
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import seed_key

_FP8_MAX = 448.0
_HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------- the group
def _dims(g: dict) -> dict:
    """The sizes the draw and the work functions need, from the group."""
    heads = g["num_attention_heads"]
    return {
        "hidden": g["hidden_size"],
        "layers": g["num_hidden_layers"],
        "dense_layers": g["first_k_dense_replace"],
        "heads": heads,
        "q_rank": g["q_lora_rank"],
        "kv_rank": g["kv_lora_rank"],
        "nope": g["qk_nope_head_dim"],
        "rope": g["qk_rope_head_dim"],
        "v": g["v_head_dim"],
        "idx_heads": g["index_n_heads"],
        "idx_dim": g["index_head_dim"],
        "dense_mlp": g["intermediate_size"],
        "expert_mlp": g["moe_intermediate_size"],
        "experts": g["n_routed_experts_published"],
        "experts_held": g["n_routed_experts"],
        "expert_offset": g["expert_offset"],
        "shared": g["n_shared_experts"],
        "vocab_held": g["vocab_size"],
        "vocab_offset": g.get("vocab_offset", 0),
    }


# ---------------------------------------------------------------- the draw
def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(jnp.bfloat16)


def _rows(key, first, rows, width, std):
    """``rows`` rows of a table, each from a key of its own number."""
    draw = lambda i: jax.random.normal(jax.random.fold_in(key, i), (width,), jnp.float32) * std
    return jax.vmap(draw)(first + jnp.arange(rows, dtype=jnp.uint32)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dims",))
def _draw(key, *, dims):
    d = dict(dims)
    h, heads = d["hidden"], d["heads"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for li in range(d["layers"]):
        ks = dict(zip(
            ["q_a", "q_b", "kv_a", "kv_b", "o", "idx_q", "idx_k", "idx_w", "gate", "up", "down", "router", "experts"],
            jax.random.split(jax.random.fold_in(k_layers, li), 13),
        ))
        layer = {
            "attn_norm": ones(h),
            "q_a": _normal(ks["q_a"], (h, d["q_rank"]), h),
            "q_norm": ones(d["q_rank"]),
            "q_b": _normal(ks["q_b"], (d["q_rank"], heads * (d["nope"] + d["rope"])), d["q_rank"]),
            "kv_a": _normal(ks["kv_a"], (h, d["kv_rank"] + d["rope"]), h),
            "kv_norm": ones(d["kv_rank"]),
            "kv_b": _normal(ks["kv_b"], (d["kv_rank"], heads * (d["nope"] + d["v"])), d["kv_rank"]),
            "o": _normal(ks["o"], (heads * d["v"], h), heads * d["v"]),
            "idx_q": _normal(ks["idx_q"], (d["q_rank"], d["idx_heads"] * d["idx_dim"]), d["q_rank"]),
            "idx_k": _normal(ks["idx_k"], (h, d["idx_dim"]), h),
            "idx_k_norm": {"scale": ones(d["idx_dim"]), "bias": jnp.zeros((d["idx_dim"],), jnp.float32)},
            "idx_w": _normal(ks["idx_w"], (h, d["idx_heads"]), h),
            "mlp_norm": ones(h),
        }
        if li < d["dense_layers"]:
            f = d["dense_mlp"]
            layer["mlp"] = {
                "gate": _normal(ks["gate"], (h, f), h),
                "up": _normal(ks["up"], (h, f), h),
                "down": _normal(ks["down"], (f, h), f),
            }
        else:
            f, fs = d["expert_mlp"], d["expert_mlp"] * d["shared"]
            layer["router"] = _normal(ks["router"], (h, d["experts"]), h)
            layer["router_bias"] = jnp.zeros((d["experts"],), jnp.float32)
            layer["shared"] = {
                "gate": _normal(ks["gate"], (h, fs), h),
                "up": _normal(ks["up"], (h, fs), h),
                "down": _normal(ks["down"], (fs, h), fs),
            }

            def expert(e):
                kg, ku, kd = jax.random.split(jax.random.fold_in(ks["experts"], e), 3)
                return {"gate": _normal(kg, (h, f), h), "up": _normal(ku, (h, f), h), "down": _normal(kd, (f, h), f)}

            held = d["expert_offset"] + jnp.arange(d["experts_held"], dtype=jnp.uint32)
            layer["experts"] = jax.vmap(expert)(held)
        layers.append(layer)
    return {
        "embed": _rows(k_embed, d["vocab_offset"], d["vocab_held"], h, 1.0),
        "head": _rows(k_head, d["vocab_offset"], d["vocab_held"], h, 1.0 / np.sqrt(h)).T,
        "final_norm": ones(h),
        "layers": layers,
    }


def make_params(group: dict, seed: int):
    """The decoder's parameter tree for a configuration file's model group,
    drawn from ``seed`` on the default device."""
    return _draw(seed_key(seed, stream=3), dims=tuple(sorted(_dims(group).items())))


# ----------------------------------------------------------- the tokenizer
_WORD = re.compile(r"[a-z0-9]+")
RESERVED = 1000  # ids below this are never a word's


def token_ids(text: str, vocab_held: int) -> list[int]:
    """One id a word, drawn from the held slice of the vocabulary:
    ``1000 + blake2b64(word) mod (vocab_held - 1000)``."""
    ids = []
    for word in _WORD.findall(text.lower()):
        h = int.from_bytes(hashlib.blake2b(word.encode(), digest_size=8).digest(), "little")
        ids.append(RESERVED + h % (vocab_held - RESERVED))
    return ids


# ------------------------------------------------------------ the forward
def _round_inputs(x, precision: str):
    if precision == "f32":
        return x
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec, _round_inputs(a, precision), _round_inputs(b, precision),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def yarn_inv_freq(g: dict) -> np.ndarray:
    """YaRN's blend of the published and the interpolated frequencies."""
    dim, base, rs = g["qk_rope_head_dim"], g["rope_theta"], g["rope_scaling"]
    factor, original = rs["factor"], rs["original_max_position_embeddings"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def softmax_scale(g: dict) -> float:
    rs = g["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (g["qk_nope_head_dim"] + g["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, angles):
    """Rotate neighbouring pairs of the last axis; ``angles`` [T, dim/2]
    broadcast over any axes between."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    shape = (angles.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[1],)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _swiglu(x, p, precision):
    gate = _mm("tc,cf->tf", x, p["gate"], precision)
    up = _mm("tc,cf->tf", x, p["up"], precision)
    return _mm("tf,fc->tc", jax.nn.silu(gate) * up, p["down"], precision)


def _widen(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("gkey", "precision", "q_block"))
def _attention(h, lp, *, gkey, precision, q_block):
    """One layer's attention over a whole sequence: ``h`` [T, hidden] ->
    what the layer adds to it.  Keys and values stand for the whole
    sequence; queries, their indexer scores and their attention are taken a
    block of ``q_block`` positions at a time, so that nothing of size
    ``T x heads x T`` or ``T x heads x width`` in float32 has to fit."""
    g = dict(gkey)
    lp = _widen({k: v for k, v in lp.items() if k not in ("experts", "shared", "mlp", "router")})
    T = h.shape[0]
    heads, nope, rope, vd = g["num_attention_heads"], g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    rank, eps = g["kv_lora_rank"], g["rms_norm_eps"]
    inv_freq = jnp.asarray(g["inv_freq"], jnp.float32)
    pos = jnp.arange(T)
    angles = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    x = _rms_norm(h, lp["attn_norm"], eps)
    cq = _rms_norm(_mm("tc,cr->tr", x, lp["q_a"], precision), lp["q_norm"], eps)
    kva = _mm("tc,cr->tr", x, lp["kv_a"], precision)
    ckv = _rms_norm(kva[:, :rank], lp["kv_norm"], eps)
    k_rope = _rope(kva[:, rank:], angles)
    kv_b = lp["kv_b"].reshape(rank, heads, nope + vd)
    k_nope = _mm("tr,rhd->thd", ckv, kv_b[..., :nope], precision)
    v = _mm("tr,rhd->thd", ckv, kv_b[..., nope:], precision)
    ih, idim = g["index_n_heads"], g["index_head_dim"]
    ki = _layer_norm(_mm("tc,cd->td", x, lp["idx_k"], precision), lp["idx_k_norm"])
    ki = jnp.concatenate([_rope(ki[:, :rope], angles), ki[:, rope:]], axis=-1)
    wi = _mm("tc,cj->tj", x, lp["idx_w"], precision) * (ih**-0.5 * idim**-0.5)
    topk = min(g["index_topk"], T)
    pad = (-T) % q_block

    def block(args):
        cqb, wib, t = args  # a block of positions: their compressed queries, indexer weights and numbers
        ang = t.astype(jnp.float32)[:, None] * inv_freq[None, :]
        causal = pos[None, :] <= t[:, None]
        qi = _mm("tr,rd->td", cqb, lp["idx_q"], precision).reshape(-1, ih, idim)
        qi = jnp.concatenate([_rope(qi[..., :rope], ang), qi[..., rope:]], axis=-1)
        index = jnp.einsum("tj,tjs->ts", wib, jax.nn.relu(_mm("tjd,sd->tjs", qi, ki, precision)), precision=_HIGHEST)
        index = jnp.where(causal, index, -jnp.inf)
        kth = jax.lax.top_k(index, topk)[0][:, -1:]
        selected = causal & (index >= kth)
        q = _mm("tr,rd->td", cqb, lp["q_b"], precision).reshape(-1, heads, nope + rope)
        scores = _mm("thd,shd->hts", q[..., :nope], k_nope, precision) + _mm("thd,sd->hts", _rope(q[..., nope:], ang), k_rope, precision)
        probs = jax.nn.softmax(jnp.where(selected[None], scores * g["softmax_scale"], -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", probs, v, precision)

    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(-1, q_block, *a.shape[1:]) for a in (cq, wi, pos))
    out = jax.lax.map(block, blocks).reshape(T + pad, heads * vd)[:T]
    return _mm("td,dc->tc", out, lp["o"], precision)


@functools.partial(jax.jit, static_argnames=("gkey", "precision"))
def _dense_mlp(h, lp, *, gkey, precision):
    g = dict(gkey)
    return _swiglu(_rms_norm(h, lp["mlp_norm"].astype(jnp.float32), g["rms_norm_eps"]), _widen(lp["mlp"]), precision)


@functools.partial(jax.jit, static_argnames=("gkey", "precision"))
def _route(h, lp, *, gkey, precision):
    """The normed input, the shared expert's result, and each token's chosen
    experts (their published numbers) with their gates."""
    g = dict(gkey)
    x = _rms_norm(h, lp["mlp_norm"].astype(jnp.float32), g["rms_norm_eps"])
    s = jax.nn.sigmoid(_mm("tc,ce->te", x, lp["router"].astype(jnp.float32), precision))
    biased = s + lp["router_bias"]
    T, E = s.shape
    groups = biased.reshape(T, g["n_group"], E // g["n_group"])
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, g["topk_group"])[1]
    group_mask = jnp.zeros((T, g["n_group"]), bool).at[jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(group_mask, E // g["n_group"], axis=1), biased, -jnp.inf)
    chosen = jax.lax.top_k(masked, g["num_experts_per_tok"])[1]
    picked = jnp.take_along_axis(s, chosen, axis=1)
    gates = picked / jnp.sum(picked, axis=1, keepdims=True) * g["routed_scaling_factor"]
    return x, _swiglu(x, _widen(lp["shared"]), precision), chosen, gates


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(x, rows, gates, p, *, precision):
    """One expert over the rows that chose it (``gates`` 0 on padding)."""
    return _swiglu(x[rows], _widen(p), precision) * gates[:, None]


def _routed(x, chosen, gates, lp, g: dict, precision: str):
    """What the experts held here add: expert by expert, its tokens gathered,
    run through it, weighted and added back."""
    chosen, gates_np = np.asarray(chosen), np.asarray(gates)
    out = jnp.zeros_like(x)
    for local in range(g["n_routed_experts"]):
        tok, slot = np.nonzero(chosen == g["expert_offset"] + local)
        if tok.size == 0:
            continue
        n = max(64, 1 << int(tok.size - 1).bit_length())  # few shapes, so few compiles
        rows = np.zeros(n, np.int32)
        rows[: tok.size] = tok
        gt = np.zeros(n, np.float32)
        gt[: tok.size] = gates_np[tok, slot]
        p = jax.tree.map(lambda a: a[local], lp["experts"])
        out = out.at[rows].add(_expert(x, jnp.asarray(rows), jnp.asarray(gt), p, precision=precision))
    return out


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, norm, head, *, eps, precision):
    return _mm("tc,cv->tv", _rms_norm(h, norm, eps), head.astype(jnp.float32), precision)


def _group_key(g: dict):
    """The group's numbers as a hashable static argument."""
    keys = (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "index_n_heads",
        "index_head_dim", "index_topk", "rms_norm_eps", "n_group", "topk_group", "num_experts_per_tok", "routed_scaling_factor",
    )
    static = {k: g[k] for k in keys}
    static["inv_freq"] = tuple(float(f) for f in yarn_inv_freq(g))
    static["softmax_scale"] = softmax_scale(g)
    return tuple(sorted(static.items()))


def reference_logits(params, group: dict, sequences: list, positions: list, *, precision: str = "f32", q_block: int = 128, pad_to: int | None = None):
    """Logits [len(positions[i]), vocab_held] of each sequence of ids at the
    positions asked for: the full forward, layer by layer over all the
    sequences, so that a layer's float32 weights live once.  Every sequence
    is padded at its end to one length (``pad_to``, or the longest): under a
    causal mask the padding changes no position before it, and one length is
    one compiled program a layer."""
    gkey = _group_key(group)
    g = dict(group)
    length = max(pad_to or 0, max(len(s) for s in sequences))
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        hs = [embed[jnp.asarray(np.pad(np.asarray(s, np.int32), (0, length - len(s))))].astype(jnp.float32) for s in sequences]
        for li, lp in enumerate(params["layers"]):
            for i, h in enumerate(hs):
                h = h + _attention(h, lp, gkey=gkey, precision=precision, q_block=q_block)
                if li < g["first_k_dense_replace"]:
                    h = h + _dense_mlp(h, lp, gkey=gkey, precision=precision)
                else:
                    x, shared, chosen, gates = _route(h, lp, gkey=gkey, precision=precision)
                    h = h + shared + _routed(x, chosen, gates, lp, g, precision)
                hs[i] = h
        return [
            np.asarray(_head(h[jnp.asarray(np.asarray(p, np.int32))], params["final_norm"], params["head"], eps=g["rms_norm_eps"], precision=precision))
            for h, p in zip(hs, positions)
        ]


# ----------------------------------------------------------------- the work
def parameter_counts(g: dict) -> dict:
    """Parameters by part, from the group's numbers."""
    d = _dims(g)
    h, heads = d["hidden"], d["heads"]
    mla = h * d["q_rank"] + d["q_rank"] * heads * (d["nope"] + d["rope"]) + h * (d["kv_rank"] + d["rope"]) + d["kv_rank"] * heads * (d["nope"] + d["v"]) + heads * d["v"] * h
    indexer = d["q_rank"] * d["idx_heads"] * d["idx_dim"] + h * d["idx_dim"] + h * d["idx_heads"]
    expert = 3 * h * d["expert_mlp"]
    routed_layer = mla + indexer + h * d["experts"] + d["shared"] * expert + d["experts_held"] * expert
    dense_layer = mla + indexer + 3 * h * d["dense_mlp"]
    n_dense = d["dense_layers"]
    return {
        "mla": mla, "indexer": indexer, "router": h * d["experts"], "expert": expert, "dense_mlp": 3 * h * d["dense_mlp"],
        "routed_layer": routed_layer, "dense_layer": dense_layer, "vocabulary": 2 * d["vocab_held"] * h,
        "total": n_dense * dense_layer + (d["layers"] - n_dense) * routed_layer + 2 * d["vocab_held"] * h,
    }


def _expected_experts_here(g: dict) -> float:
    return g["num_experts_per_tok"] * g["n_routed_experts"] / g["n_routed_experts_published"]


def linear_flops_per_token(g: dict) -> float:
    """Multiply-adds x 2 of one token through every matrix of the layers
    held, the routed experts at their expected share (``num_experts_per_tok``
    x held / published a token); the head is counted where logits are taken."""
    c, d = parameter_counts(g), _dims(g)
    routed = d["layers"] - d["dense_layers"]
    per_routed = c["mla"] + c["indexer"] + c["router"] + (d["shared"] + _expected_experts_here(g)) * c["expert"]
    return 2.0 * (d["dense_layers"] * c["dense_layer"] + routed * per_routed)


def token_flops(g: dict, context: int) -> float:
    """One token whose query sees ``context`` keys (itself among them): the
    linear work, the indexer's scores over the context and MLA's core over
    the keys selected."""
    d = _dims(g)
    indexer = 2.0 * d["idx_heads"] * d["idx_dim"] * context
    core = 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * min(context, g["index_topk"])
    return linear_flops_per_token(g) + d["layers"] * (indexer + core)


def prompt_flops(g: dict, tokens: int) -> float:
    """A prompt of ``tokens`` tokens and the logits at its last position."""
    d = _dims(g)
    contexts = np.arange(1, tokens + 1, dtype=np.float64)
    per_layer = 2.0 * d["idx_heads"] * d["idx_dim"] * contexts.sum() + 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * np.minimum(contexts, g["index_topk"]).sum()
    return tokens * linear_flops_per_token(g) + d["layers"] * per_layer + 2.0 * d["vocab_held"] * d["hidden"]


def attention_core_flops(g: dict, tokens: int) -> float:
    """MLA's core over a prompt of ``tokens`` tokens, every layer: scores and
    weighted sum of each query over the keys selected for it (the work the
    fused prefill kernel is there to do; a kernel that also multiplies the
    keys it then masks out does more and is read against this)."""
    d = _dims(g)
    selected = np.minimum(np.arange(1, tokens + 1, dtype=np.float64), g["index_topk"]).sum()
    return d["layers"] * 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * selected


def flops(group: dict, useful_tokens) -> float:
    """The requests of a slice: one ``(prompt tokens, decode steps)`` each.
    Every decode step takes one token at its context and gives logits."""
    d = _dims(group)
    total = 0.0
    for prompt, steps in useful_tokens:
        total += prompt_flops(group, prompt)
        total += sum(token_flops(group, prompt + i + 1) for i in range(steps)) + steps * 2.0 * d["vocab_held"] * d["hidden"]
    return total


def decode_bytes(group: dict, context: int) -> float:
    """Least bytes one decode step of one sequence moves at ``context`` keys:
    every weight the token touches (the dense layers, each routed layer's
    attention, indexer, router, shared expert and its expected share of
    routed experts, the head's slice), the indexer's keys over the context
    and the selected latent rows, in every layer, at two bytes a value."""
    c, d = parameter_counts(group), _dims(group)
    routed = d["layers"] - d["dense_layers"]
    per_routed = c["mla"] + c["indexer"] + c["router"] + (d["shared"] + _expected_experts_here(group)) * c["expert"]
    weights = d["dense_layers"] * c["dense_layer"] + routed * per_routed + d["vocab_held"] * d["hidden"]
    state = d["layers"] * (context * d["idx_dim"] + min(context, group["index_topk"]) * (d["kv_rank"] + d["rope"]))
    return 2.0 * (weights + state)


def built_differs(group: dict, built) -> dict:
    """``built`` is the program's ``DecoderConfig``; returns key -> (built,
    file) for every key on which the two differ."""
    same_name = (
        "hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim", "index_topk", "intermediate_size",
        "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor",
        "rms_norm_eps", "rope_theta", "expert_offset",
    )
    stated = {k: getattr(built, k) for k in same_name}
    stated["n_routed_experts"], stated["n_routed_experts_published"] = built.experts_held, built.n_routed_experts
    stated["vocab_size"], stated["vocab_size_published"] = built.vocab_held, built.vocab_size
    stated["rope_scaling"] = dict(built.rope_scaling)
    stated["param_dtype"] = np.dtype(built.dtype).name
    return {k: (v, group.get(k)) for k, v in stated.items() if group.get(k) != v}
