"""Model family ``longcat_flash``: a causal decoder of double layers (two
latent attentions and two dense feed-forwards a layer) with a
shortcut-connected routed branch whose router also chooses among experts that
compute nothing, and of whose routed experts this chip holds a share.

Everything the yardstick knows of this family, in one file, found by the
``family`` a configuration's model group names, as ``deepseek_v32`` is:

- ``make_params(group, seed)`` -- the parameter tree drawn from the seed in
  one jitted call on the device, bfloat16, under the names the program's
  ``models/shortcut_moe_decoder.py`` takes.  An expert's weights depend on its
  number among the published 512 and a row of the vocabulary on its number
  among the published 131,072, so another ``expert_offset`` / ``vocab_offset``
  draws another share of the same model.
- the plain forward (``reference_logits``): the layer equations below in
  float32 ``jax.numpy`` at ``highest`` matmul precision over the same tree,
  the whole sequence at once with no cache, no chunks and no absorbed form,
  sublayer by sublayer over all the sequences (one sublayer's float32 weights
  live at a time), attention in query blocks so that it fits.  It imports
  nothing of the program.  ``precision``: ``"f32"`` the reference, ``"fp8"``
  the control (every matrix product's inputs rounded to float8_e4m3fn under a
  per-tensor scale).
- ``prompt_flops`` / ``flops`` / ``decode_bytes`` / ``attention_core_flops``
  -- the work a request needs and the bytes a decode step touches, from the
  group's numbers, never from what the program dispatches.
- ``built_differs(group, built)``.

**The layer equations** (ISSUE 37 section 1; the LongCat-Flash technical
report's shortcut-connected MoE and zero-computation experts, and the
family's published modelling code for what ``config.json`` does not say).
``RMS(x; w) = x rsqrt(mean(x^2) + eps) w``; for the residual stream ``h``::

    a_0 = h   + MLA_0(RMS(h;   w_in_0))
    x_0 = RMS(a_0; w_post_0)
    m   = MoE(x_0)
    h_1 = a_0 + SwiGLU_0(x_0)
    a_1 = h_1 + MLA_1(RMS(h_1; w_in_1))
    x_1 = RMS(a_1; w_post_1)
    h'  = a_1 + SwiGLU_1(x_1) + m

- ``MLA_i(x)`` at position t: ``c_q = alpha_q RMS(x W_qa; w_q)``, ``alpha_q =
  sqrt(hidden / q_lora_rank)``; ``q = c_q W_qb`` -> heads of ``[q_nope;
  q_rope]``, rope on ``q_rope``; ``kva = x W_kva``; ``c_kv = alpha_kv
  RMS(kva[:rank]; w_kv)``, ``alpha_kv = sqrt(hidden / kv_lora_rank)``;
  ``k_rope = rope(kva[rank:])``, one for all heads; per head ``k = [c_kv
  W_kvb^K; k_rope]``, ``v = c_kv W_kvb^V``; softmax over every ``s <= t`` of
  ``q . k / sqrt(nope + rope)``; ``W_o`` over the heads' results.  Rope:
  ``rope_theta`` over the rope dimensions, neighbouring pairs, no scaling.
- ``MoE(x)``: ``s = softmax(x W_r)`` over ``n_routed_experts_published +
  zero_expert_num``; the ``moe_topk`` largest ``s + b`` are chosen; ``g_e =
  routed_scaling_factor s_e``, not renormalised; ``sum over chosen e held
  here of g_e SwiGLU_e(x) + (sum over chosen e >= published of g_e) x``.
  What the absent experts would add is left out.
- head: final RMS, logits over the held rows of the vocabulary.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# what every decoder family of the yardstick shares, defined where the first of them is: the seeded draws, the hashing
# tokenizer's copy (token_ids: what checks/answer.py asks a family for), products at a precision, and the loop over the
# experts held here
from benchmark.families.deepseek_v32 import _head, _mm, _normal, _rms_norm, _rope, _routed, _rows, _swiglu, _widen, token_ids  # noqa: F401
from benchmark.weights import seed_key


# --------------------------------------------------------------- the group
def _dims(g: dict) -> dict:
    """The sizes the draw and the work functions need, from the group."""
    return {
        "hidden": g["hidden_size"],
        "layers": g["num_layers"],
        "heads": g["num_attention_heads"],
        "q_rank": g["q_lora_rank"],
        "kv_rank": g["kv_lora_rank"],
        "nope": g["qk_nope_head_dim"],
        "rope": g["qk_rope_head_dim"],
        "v": g["v_head_dim"],
        "dense_mlp": g["ffn_hidden_size"],
        "expert_mlp": g["expert_ffn_hidden_size"],
        "experts": g["n_routed_experts_published"],
        "experts_held": g["n_routed_experts"],
        "expert_offset": g["expert_offset"],
        "zero_experts": g["zero_expert_num"],
        "vocab_held": g["vocab_size"],
        "vocab_offset": g.get("vocab_offset", 0),
    }


# ---------------------------------------------------------------- the draw
def _swiglu_params(key, h, f):
    kg, ku, kd = jax.random.split(key, 3)
    return {"gate": _normal(kg, (h, f), h), "up": _normal(ku, (h, f), h), "down": _normal(kd, (f, h), f)}


@functools.partial(jax.jit, static_argnames=("dims",))
def _draw(key, *, dims):
    d = dict(dims)
    h, heads = d["hidden"], d["heads"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    ones = lambda n: jnp.ones((n,), jnp.float32)

    def attention(key):
        ks = dict(zip(["q_a", "q_b", "kv_a", "kv_b", "o"], jax.random.split(key, 5)))
        return {
            "attn_norm": ones(h),
            "q_a": _normal(ks["q_a"], (h, d["q_rank"]), h),
            "q_norm": ones(d["q_rank"]),
            "q_b": _normal(ks["q_b"], (d["q_rank"], heads * (d["nope"] + d["rope"])), h),  # of a scaled row: see make_params
            "kv_a": _normal(ks["kv_a"], (h, d["kv_rank"] + d["rope"]), h),
            "kv_norm": ones(d["kv_rank"]),
            "kv_b": _normal(ks["kv_b"], (d["kv_rank"], heads * (d["nope"] + d["v"])), h),
            "o": _normal(ks["o"], (heads * d["v"], h), heads * d["v"]),
        }

    layers = []
    for li in range(d["layers"]):
        k_attn, k_mlp, k_router, k_experts = jax.random.split(jax.random.fold_in(k_layers, li), 4)
        held = d["expert_offset"] + jnp.arange(d["experts_held"], dtype=jnp.uint32)
        width = d["experts"] + d["zero_experts"]
        layers.append({
            "attn": [attention(k) for k in jax.random.split(k_attn, 2)],
            "mlp_norm": [ones(h), ones(h)],
            "mlp": [_swiglu_params(k, h, d["dense_mlp"]) for k in jax.random.split(k_mlp, 2)],
            "router": _normal(k_router, (h, width), h),
            "router_bias": jnp.zeros((width,), jnp.float32),
            "experts": jax.vmap(lambda e: _swiglu_params(jax.random.fold_in(k_experts, e), h, d["expert_mlp"]))(held),
        })
    return {
        "embed": _rows(k_embed, d["vocab_offset"], d["vocab_held"], h, 0.02),
        "head": _rows(k_head, d["vocab_offset"], d["vocab_held"], h, 1.0 / np.sqrt(h)).T,
        "final_norm": ones(h),
        "layers": layers,
    }


def make_params(group: dict, seed: int):
    """The decoder's parameter tree for a configuration file's model group,
    drawn from ``seed`` on the default device.  Matrices are normal with std
    ``1/sqrt(fan_in)``; for ``q_b`` and ``kv_b`` the fan-in is the hidden
    size, not the rank: their input is the low-rank row that
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` have scaled to the variance of
    a hidden-wide one, which is what the scale is for.  With ``1/sqrt(rank)``
    the scale is counted twice: queries of std 2 and keys of std sqrt(12) at
    the published sizes, scores of std 5.7, a softmax that is an argmax over
    6,000 keys, which no 8-bit mantissa holds (at the published widths on
    the chip, PR 37: ``logit_gap`` 2.40-2.48 of the bfloat16 program and 2.40
    of this file's float32 reference with its products' inputs rounded to
    bfloat16, against 0.026 with this draw)."""
    return _draw(seed_key(seed, stream=3), dims=tuple(sorted(_dims(group).items())))


# ------------------------------------------------------------ the forward
def inv_freq(g: dict) -> np.ndarray:
    dim = g["qk_rope_head_dim"]
    return 1.0 / g["rope_theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


@functools.partial(jax.jit, static_argnames=("gkey", "precision", "q_block"))
def _attention(h, ap, *, gkey, precision, q_block):
    """One attention sublayer over a whole sequence: ``h`` [T, hidden] ->
    what it adds.  Keys and values stand expanded for the whole sequence;
    queries and their attention are taken ``q_block`` positions at a time."""
    g = dict(gkey)
    ap = _widen(ap)
    T = h.shape[0]
    heads, nope, rope, vd = g["num_attention_heads"], g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    rank, eps = g["kv_lora_rank"], g["rms_norm_eps"]
    freq = jnp.asarray(g["inv_freq"], jnp.float32)
    pos = jnp.arange(T)
    x = _rms_norm(h, ap["attn_norm"], eps)
    cq = g["q_lora_scale"] * _rms_norm(_mm("tc,cr->tr", x, ap["q_a"], precision), ap["q_norm"], eps)
    kva = _mm("tc,cr->tr", x, ap["kv_a"], precision)
    ckv = g["kv_lora_scale"] * _rms_norm(kva[:, :rank], ap["kv_norm"], eps)
    k_rope = _rope(kva[:, rank:], pos.astype(jnp.float32)[:, None] * freq[None, :])
    kv_b = ap["kv_b"].reshape(rank, heads, nope + vd)
    k_nope = _mm("tr,rhd->thd", ckv, kv_b[..., :nope], precision)
    v = _mm("tr,rhd->thd", ckv, kv_b[..., nope:], precision)
    pad = (-T) % q_block

    def block(args):
        cqb, t = args  # a block of positions: their compressed queries and numbers
        q = _mm("tr,rd->td", cqb, ap["q_b"], precision).reshape(-1, heads, nope + rope)
        q_rope = _rope(q[..., nope:], t.astype(jnp.float32)[:, None] * freq[None, :])
        scores = _mm("thd,shd->hts", q[..., :nope], k_nope, precision) + _mm("thd,sd->hts", q_rope, k_rope, precision)
        causal = pos[None, :] <= t[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores * (nope + rope) ** -0.5, -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", probs, v, precision)

    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(-1, q_block, *a.shape[1:]) for a in (cq, pos))
    out = jax.lax.map(block, blocks).reshape(T + pad, heads * vd)[:T]
    return _mm("td,dc->tc", out, ap["o"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _dense(a, norm, p, *, eps, precision):
    """The normed rows of ``a`` and what the sublayer's dense block gives for them."""
    x = _rms_norm(a, norm.astype(jnp.float32), eps)
    return x, _swiglu(x, _widen(p), precision)


@functools.partial(jax.jit, static_argnames=("gkey", "precision"))
def _route(x, router, bias, *, gkey, precision):
    """Each token's chosen experts (their published numbers; the zero-computation
    ones follow the routed ones), their gates, and the identity term."""
    g = dict(gkey)
    s = jax.nn.softmax(_mm("tc,ce->te", x, router.astype(jnp.float32), precision), axis=-1)
    chosen = jax.lax.top_k(s + bias, g["moe_topk"])[1]
    gates = jnp.take_along_axis(s, chosen, axis=1) * g["routed_scaling_factor"]
    zero = chosen >= g["n_routed_experts_published"]
    return chosen, gates, jnp.sum(jnp.where(zero, gates, 0.0), axis=1, keepdims=True) * x


def moe(x, lp, g: dict, precision: str = "f32"):
    """The routed branch of the normed rows ``x`` as this share gives it:
    (the held experts' part, the identity term, the chosen experts)."""
    chosen, gates, identity = _route(x, lp["router"], lp["router_bias"], gkey=_group_key(g), precision=precision)
    return _routed(x, chosen, gates, lp, g, precision), identity, chosen


def _group_key(g: dict):
    """The group's numbers as a hashable static argument."""
    keys = (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps", "moe_topk",
        "routed_scaling_factor", "n_routed_experts_published",
    )
    static = {k: g[k] for k in keys}
    static["inv_freq"] = tuple(float(f) for f in inv_freq(g))
    static["q_lora_scale"] = math.sqrt(g["hidden_size"] / g["q_lora_rank"]) if g["mla_scale_q_lora"] else 1.0
    static["kv_lora_scale"] = math.sqrt(g["hidden_size"] / g["kv_lora_rank"]) if g["mla_scale_kv_lora"] else 1.0
    return tuple(sorted(static.items()))


def reference_logits(params, group: dict, sequences: list, positions: list, *, precision: str = "f32", q_block: int = 128, pad_to: int | None = None):
    """Logits [len(positions[i]), vocab_held] of each sequence of ids at the
    positions asked for: the full forward, sublayer by sublayer over all the
    sequences.  Every sequence is padded at its end to one length (``pad_to``,
    or the longest): under a causal mask the padding changes no position
    before it, and one length is one compiled program a sublayer."""
    gkey, g = _group_key(group), dict(group)
    eps = g["rms_norm_eps"]
    length = max(pad_to or 0, max(len(s) for s in sequences))
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        hs = [embed[jnp.asarray(np.pad(np.asarray(s, np.int32), (0, length - len(s))))].astype(jnp.float32) for s in sequences]
        for lp in params["layers"]:
            branch = [None] * len(hs)
            for i in (0, 1):
                for n, h in enumerate(hs):
                    a = h + _attention(h, lp["attn"][i], gkey=gkey, precision=precision, q_block=q_block)
                    x, dense = _dense(a, lp["mlp_norm"][i], lp["mlp"][i], eps=eps, precision=precision)
                    if i == 0:  # the branch leaves after the first attention ...
                        routed, identity, _chosen = moe(x, lp, g, precision)
                        branch[n] = routed + identity
                        hs[n] = a + dense
                    else:  # ... and comes back after the second dense block
                        hs[n] = a + dense + branch[n]
        return [
            np.asarray(_head(h[jnp.asarray(np.asarray(p, np.int32))], params["final_norm"], params["head"], eps=eps, precision=precision))
            for h, p in zip(hs, positions)
        ]


# ----------------------------------------------------------------- the work
def parameter_counts(g: dict) -> dict:
    """Parameters by part, from the group's numbers, norm scales and the
    router's bias among them."""
    d = _dims(g)
    h, heads = d["hidden"], d["heads"]
    attention = (
        h * d["q_rank"] + d["q_rank"] * heads * (d["nope"] + d["rope"]) + h * (d["kv_rank"] + d["rope"])
        + d["kv_rank"] * heads * (d["nope"] + d["v"]) + heads * d["v"] * h + d["q_rank"] + d["kv_rank"]
    )
    dense = 3 * h * d["dense_mlp"]
    width = d["experts"] + d["zero_experts"]
    router = h * width + width
    expert = 3 * h * d["expert_mlp"]
    outside = 2 * attention + 2 * dense + router + 4 * h
    layer = outside + d["experts_held"] * expert
    return {
        "attention": attention, "dense_mlp": dense, "router": router, "expert": expert, "layer_outside_experts": outside, "layer": layer,
        "vocabulary": 2 * d["vocab_held"] * h, "total": d["layers"] * layer + 2 * d["vocab_held"] * h + h,
    }


def _expected_experts_here(g: dict) -> float:
    """Of a token's ``moe_topk`` choices over the router's whole width, those that fall to an expert held here."""
    return g["moe_topk"] * g["n_routed_experts"] / (g["n_routed_experts_published"] + g["zero_expert_num"])


def _matrices(g: dict) -> float:
    """Matrix parameters one token passes in the layers held (no norms, no
    bias), the routed experts at their expected share."""
    c, d = parameter_counts(g), _dims(g)
    width = d["experts"] + d["zero_experts"]
    per_layer = 2 * (c["attention"] - d["q_rank"] - d["kv_rank"]) + 2 * c["dense_mlp"] + d["hidden"] * width + _expected_experts_here(g) * c["expert"]
    return d["layers"] * per_layer


def linear_flops_per_token(g: dict) -> float:
    """Multiply-adds x 2 of one token through every matrix of the layers
    held; the head is counted where logits are taken.  A zero-computation
    expert's multiply-add a row is not counted."""
    return 2.0 * _matrices(g)


def _core_per_pair(g: dict) -> float:
    d = _dims(g)
    return 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"])


def token_flops(g: dict, context: int) -> float:
    """One token whose query sees ``context`` keys (itself among them) in
    each of the two attention sublayers of every layer."""
    return linear_flops_per_token(g) + 2 * _dims(g)["layers"] * _core_per_pair(g) * context


def attention_core_flops(g: dict, tokens: int) -> float:
    """MLA's core over a prompt of ``tokens`` tokens: scores and weighted sum
    of each query over every key visible to it, in every sublayer (the work
    the fused prefill kernel is there to do)."""
    visible = tokens * (tokens + 1) / 2.0
    return 2 * _dims(g)["layers"] * _core_per_pair(g) * visible


def prompt_flops(g: dict, tokens: int) -> float:
    """A prompt of ``tokens`` tokens and the logits at its last position."""
    d = _dims(g)
    return tokens * linear_flops_per_token(g) + attention_core_flops(g, tokens) + 2.0 * d["vocab_held"] * d["hidden"]


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
    every matrix the token touches (both attentions, both dense blocks, the
    router, its expected share of the experts held here, the head's slice)
    and the latent rows of the context in every sublayer, at two bytes a value."""
    d = _dims(group)
    state = 2 * d["layers"] * context * (d["kv_rank"] + d["rope"])
    return 2.0 * (_matrices(group) + d["vocab_held"] * d["hidden"] + state)


def built_differs(group: dict, built) -> dict:
    """``built`` is the program's ``ShortcutMoEDecoderConfig``; returns key ->
    (built, file) for every key on which the two differ."""
    same_name = (
        "hidden_size", "num_layers", "ffn_hidden_size", "expert_ffn_hidden_size", "zero_expert_num", "zero_expert_type", "moe_topk",
        "routed_scaling_factor", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "mla_scale_q_lora", "mla_scale_kv_lora", "rope_theta", "rms_norm_eps", "expert_offset",
    )
    stated = {k: getattr(built, k) for k in same_name}
    stated["n_routed_experts"], stated["n_routed_experts_published"] = built.experts_held, built.n_routed_experts
    stated["vocab_size"], stated["vocab_size_published"] = built.vocab_held, built.vocab_size
    stated["param_dtype"] = np.dtype(built.dtype).name
    return {k: (v, group.get(k)) for k, v in stated.items() if group.get(k) != v}
