"""Model family ``glm4_moe_lite``: GLM-4.7-Flash, a causal decoder with
latent (MLA) attention over every earlier key, a sigmoid router over 64
routed experts with a shared one, and one multi-token-prediction (MTP) layer
that drafts the token after next.

Everything the yardstick knows of this family, in one file, found by the
``family`` a configuration's model group names, as ``deepseek_v32`` is (the
seeded draws, the products at a precision, the router, the experts, the
head and the hashing tokenizer are that family's, imported):

- ``make_params(group, seed)`` -- the parameter tree drawn from the seed in
  one jitted call on the device, bfloat16, under the names the program's
  ``models/decoder.py`` takes: matrices normal with std ``1/sqrt(fan_in)``,
  embedding rows 1, head rows ``1/sqrt(hidden)``, the head untied, every norm
  scale ``1 + N(0, 0.1)`` and the router's bias ``N(0, 0.05)`` (drawn off
  their resting values so that a norm or the bias left out shows: with unit
  scales ``RMS(ĥ; w_h)`` of a row the final norm has already normed is the
  row itself).  An expert's weights depend on its number among the router's,
  so another ``expert_offset`` draws another share of the same model.
- the plain forward (``reference_logits``) and the MTP layer over the
  reference's own final-normed rows (``reference_draft_logits``): the
  equations below in float32 ``jax.numpy`` at ``highest`` matmul precision
  over the same tree, full forward with no cache, no chunks, no absorbed form
  and no kernel, attention in query blocks so that 8,704 positions fit.  It
  imports nothing of the program.  ``precision``: ``"f32"`` the reference,
  ``"fp8"`` the control.
- ``parameter_counts`` and the work: ``prompt_flops``, ``step_flops``,
  ``flops``, ``attention_core_flops``, ``decode_bytes`` -- a prompt through
  the main stack and the MTP layer, and a decode step as the program takes
  it: one verify step, two tokens through the main stack, the MTP layer over
  both positions and the head twice, from the group's numbers.
- ``built_differs(group, built)``.

**The layer equations** (the published ``config.json``, ``glm4_moe_lite``;
DeepSeek-V3's layer as ``DeepseekV3Config`` gives it where the config is
silent).  ``RMS(x; w) = x rsqrt(mean(x^2) + eps) w``; for a layer and the
residual stream ``h``, ``x = RMS(h; w_in)``:

- MLA: ``cQ = RMS(x W_qa; w_qn)``; ``q = cQ W_qb`` -> 20 heads of ``[q_nope
  (192); q_rope (64)]``; ``[cKV; k_rope] = x W_kva``, ``cKV <- RMS(cKV;
  w_kvn)``; rope with ``rope_theta`` over all 64 rope dimensions,
  neighbouring pairs rotated (``rope_interleave``, the default), on
  ``q_rope`` of every head and the one shared ``k_rope``; ``[k_nope; v] =
  cKV W_kvb`` per head (192 and 256); scores ``(q_nope.k_nope +
  q_rope.k_rope) / sqrt(256)`` over every ``s <= t`` (``rope_scaling`` null:
  no YaRN factor); ``o = W_o [sum_s p v]`` (5,120 -> 2,048).
- routed experts: ``s = sigmoid(W_r RMS(a; w_post))``; the 4 largest ``s +
  b`` chosen (one group: ``n_group`` = ``topk_group`` = 1); gates ``s_e /
  sum_chosen s * 1.8``; ``y = SwiGLU_shared + sum over the chosen experts
  held here of g_e SwiGLU_e``.  Layer 0 is one dense SwiGLU of 10,240.
- head: final RMS, logits over all 154,880 rows.
- MTP layer (vLLM's ``deepseek_mtp.py``): position ``i`` pairs the main
  model's final-normed row ``ĥ_i`` with the next token ``t_{i+1}``: ``x_i =
  [RMS(E t_{i+1}; w_e) ; RMS(ĥ_i; w_h)] W_eh`` (4,096 -> 2,048), the
  embedding half zero at position 0; ``u = Layer(x)``, a routed layer as
  above with its own attention over the MTP layer's rows ``s <= i``; the
  draft's logits ``RMS(u_i; w_sh) W_head``, the main model's head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# what every decoder family of the yardstick shares, defined where the first of them is
from benchmark.families.deepseek_v32 import (  # noqa: F401
    _dense_mlp, _head, _mm, _normal, _rms_norm, _rope, _route, _routed, _rows, _widen, token_ids,
)
from benchmark.weights import seed_key



# --------------------------------------------------------------- the group
def _dims(g: dict) -> dict:
    """The sizes the draw and the work functions need, from the group."""
    return {
        "hidden": g["hidden_size"],
        "layers": g["num_hidden_layers"],
        "dense_layers": g["first_k_dense_replace"],
        "heads": g["num_attention_heads"],
        "q_rank": g["q_lora_rank"],
        "kv_rank": g["kv_lora_rank"],
        "nope": g["qk_nope_head_dim"],
        "rope": g["qk_rope_head_dim"],
        "v": g["v_head_dim"],
        "dense_mlp": g["intermediate_size"],
        "expert_mlp": g["moe_intermediate_size"],
        "experts": g["n_routed_experts"],
        "experts_held": g["experts_held"],
        "expert_offset": g["expert_offset"],
        "topk": g["num_experts_per_tok"],
        "shared": g["n_shared_experts"],
        "vocab": g["vocab_size"],
        "mtp": g["num_nextn_predict_layers"],
    }


# ---------------------------------------------------------------- the draw
def _scale(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _layer(key, d: dict, dense: bool) -> dict:
    """One layer's weights: MLA, then the dense SwiGLU or the router, the
    shared expert and the experts held here."""
    h, heads = d["hidden"], d["heads"]
    ks = dict(zip(
        ["q_a", "q_b", "kv_a", "kv_b", "o", "gate", "up", "down", "router", "bias", "experts", "norms"], jax.random.split(key, 12)
    ))
    norms = jax.random.split(ks["norms"], 4)
    layer = {
        "attn_norm": _scale(norms[0], h),
        "q_a": _normal(ks["q_a"], (h, d["q_rank"]), h),
        "q_norm": _scale(norms[1], d["q_rank"]),
        "q_b": _normal(ks["q_b"], (d["q_rank"], heads * (d["nope"] + d["rope"])), d["q_rank"]),
        "kv_a": _normal(ks["kv_a"], (h, d["kv_rank"] + d["rope"]), h),
        "kv_norm": _scale(norms[2], d["kv_rank"]),
        "kv_b": _normal(ks["kv_b"], (d["kv_rank"], heads * (d["nope"] + d["v"])), d["kv_rank"]),
        "o": _normal(ks["o"], (heads * d["v"], h), heads * d["v"]),
        "mlp_norm": _scale(norms[3], h),
    }
    if dense:
        f = d["dense_mlp"]
        layer["mlp"] = {"gate": _normal(ks["gate"], (h, f), h), "up": _normal(ks["up"], (h, f), h), "down": _normal(ks["down"], (f, h), f)}
        return layer
    f, fs = d["expert_mlp"], d["expert_mlp"] * d["shared"]
    layer["router"] = _normal(ks["router"], (h, d["experts"]), h)
    layer["router_bias"] = 0.05 * jax.random.normal(ks["bias"], (d["experts"],), jnp.float32)
    layer["shared"] = {"gate": _normal(ks["gate"], (h, fs), h), "up": _normal(ks["up"], (h, fs), h), "down": _normal(ks["down"], (fs, h), fs)}

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(ks["experts"], e), 3)
        return {"gate": _normal(kg, (h, f), h), "up": _normal(ku, (h, f), h), "down": _normal(kd, (f, h), f)}

    layer["experts"] = jax.vmap(expert)(d["expert_offset"] + jnp.arange(d["experts_held"], dtype=jnp.uint32))
    return layer


@functools.partial(jax.jit, static_argnames=("dims",))
def _draw(key, *, dims):
    d = dict(dims)
    h = d["hidden"]
    k_embed, k_head, k_layers, k_mtp, k_norm = jax.random.split(key, 5)
    tree = {
        "embed": _rows(k_embed, 0, d["vocab"], h, 1.0),
        "head": _rows(k_head, 0, d["vocab"], h, 1.0 / np.sqrt(h)).T,
        "final_norm": _scale(k_norm, h),
        "layers": [_layer(jax.random.fold_in(k_layers, li), d, li < d["dense_layers"]) for li in range(d["layers"])],
    }
    if d["mtp"]:
        ks = jax.random.split(k_mtp, 5)
        tree["mtp"] = {
            "enorm": _scale(ks[0], h), "hnorm": _scale(ks[1], h), "eh_proj": _normal(ks[2], (2 * h, h), 2 * h),
            "head_norm": _scale(ks[3], h), "layer": _layer(ks[4], d, dense=False),
        }
    return tree


def make_params(group: dict, seed: int):
    """The decoder's parameter tree for a configuration file's model group,
    drawn from ``seed`` on the default device."""
    return _draw(seed_key(seed, stream=3), dims=tuple(sorted(_dims(group).items())))


# ------------------------------------------------------------ the forward
def inv_freq(g: dict) -> np.ndarray:
    dim = g["qk_rope_head_dim"]
    return 1.0 / g["rope_theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


@functools.partial(jax.jit, static_argnames=("gkey", "precision", "q_block"))
def _attention(h, lp, *, gkey, precision, q_block):
    """One layer's attention over a whole sequence: ``h`` [T, hidden] ->
    what the layer adds.  Keys and values stand for the whole sequence;
    queries and their attention are taken ``q_block`` positions at a time,
    each over every key up to its own."""
    g = dict(gkey)
    lp = _widen({k: v for k, v in lp.items() if k not in ("experts", "shared", "mlp", "router", "router_bias")})
    T = h.shape[0]
    heads, nope, rope, vd = g["num_attention_heads"], g["qk_nope_head_dim"], g["qk_rope_head_dim"], g["v_head_dim"]
    rank, eps = g["kv_lora_rank"], g["rms_norm_eps"]
    freq = jnp.asarray(g["inv_freq"], jnp.float32)
    pos = jnp.arange(T)
    x = _rms_norm(h, lp["attn_norm"], eps)
    cq = _rms_norm(_mm("tc,cr->tr", x, lp["q_a"], precision), lp["q_norm"], eps)
    kva = _mm("tc,cr->tr", x, lp["kv_a"], precision)
    ckv = _rms_norm(kva[:, :rank], lp["kv_norm"], eps)
    k_rope = _rope(kva[:, rank:], pos.astype(jnp.float32)[:, None] * freq[None, :])
    kv_b = lp["kv_b"].reshape(rank, heads, nope + vd)
    k_nope = _mm("tr,rhd->thd", ckv, kv_b[..., :nope], precision)
    v = _mm("tr,rhd->thd", ckv, kv_b[..., nope:], precision)
    pad = (-T) % q_block

    def block(args):
        cqb, t = args  # a block of positions: their compressed queries and numbers
        q = _mm("tr,rd->td", cqb, lp["q_b"], precision).reshape(-1, heads, nope + rope)
        q_rope = _rope(q[..., nope:], t.astype(jnp.float32)[:, None] * freq[None, :])
        scores = _mm("thd,shd->hts", q[..., :nope], k_nope, precision) + _mm("thd,sd->hts", q_rope, k_rope, precision)
        causal = pos[None, :] <= t[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores * (nope + rope) ** -0.5, -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", probs, v, precision)

    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(-1, q_block, *a.shape[1:]) for a in (cq, pos))
    out = jax.lax.map(block, blocks).reshape(T + pad, heads * vd)[:T]
    return _mm("td,dc->tc", out, lp["o"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _mtp_input(embedded, normed, mp, *, eps, precision):
    """``[RMS(E t_{i+1}; w_e) ; RMS(ĥ_i; w_h)] W_eh``, the embedding half
    zero at position 0."""
    embedded = embedded.astype(jnp.float32).at[0].set(0.0)
    x = jnp.concatenate([_rms_norm(embedded, mp["enorm"], eps), _rms_norm(normed, mp["hnorm"], eps)], axis=-1)
    return _mm("tc,cd->td", x, mp["eh_proj"].astype(jnp.float32), precision)


def _group_key(g: dict):
    """The group's numbers as a hashable static argument."""
    keys = (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps", "n_group",
        "topk_group", "num_experts_per_tok", "routed_scaling_factor",
    )
    static = {k: g[k] for k in keys}
    static["inv_freq"] = tuple(float(f) for f in inv_freq(g))
    return tuple(sorted(static.items()))


def _layer_forward(h, lp, dense: bool, g: dict, gkey, precision: str, q_block: int):
    h = h + _attention(h, lp, gkey=gkey, precision=precision, q_block=q_block)
    if dense:
        return h + _dense_mlp(h, lp, gkey=gkey, precision=precision)
    x, shared, chosen, gates = _route(h, lp, gkey=gkey, precision=precision)
    return h + shared + _routed(x, chosen, gates, lp, dict(g, n_routed_experts=g["experts_held"]), precision)


def _stack(params, g: dict, sequences: list, precision: str, q_block: int, pad_to: int | None):
    """Each sequence padded at its end to one length (``pad_to``, or the
    longest; under a causal mask the padding changes no position before
    it), its ids and the residual stream after the main stack, layer by
    layer over all the sequences, so that a layer's float32 weights live
    once."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    gkey = _group_key(g)
    length = max(pad_to or 0, max(len(s) for s in sequences))
    padded = [jnp.asarray(np.pad(np.asarray(s, np.int32), (0, length - len(s)))) for s in sequences]
    hs = [params["embed"][ids].astype(jnp.float32) for ids in padded]
    for li, lp in enumerate(params["layers"]):
        hs = [_layer_forward(h, lp, li < g["first_k_dense_replace"], g, gkey, precision, q_block) for h in hs]
    return padded, hs


def _at(positions) -> jnp.ndarray:
    return jnp.asarray(np.asarray(positions, np.int32))


def reference_logits(params, group: dict, sequences: list, positions: list, *, precision: str = "f32", q_block: int = 128, pad_to: int | None = None):
    """Logits [len(positions[i]), vocab] of each sequence of ids at the
    positions asked for: the main stack's full forward and the head."""
    g = dict(group)
    with jax.default_matmul_precision("highest"):
        _ids, hs = _stack(params, g, sequences, precision, q_block, pad_to)
        return [
            np.asarray(_head(h[_at(p)], params["final_norm"], params["head"], eps=g["rms_norm_eps"], precision=precision))
            for h, p in zip(hs, positions)
        ]


def reference_draft_logits(
    params, group: dict, sequences: list, positions: list, draft_positions: list, *, precision: str = "f32", q_block: int = 128,
    pad_to: int | None = None,
):
    """From one forward: the main logits at ``positions`` (as
    :func:`reference_logits`) and the MTP layer's draft logits [n, vocab] at
    ``draft_positions`` -- position ``i`` over the reference's own ``ĥ_i``
    and ``t_{i+1}``, so ``i`` is at most the sequence's length less 2."""
    g = dict(group)
    eps = g["rms_norm_eps"]
    mp = params["mtp"]
    gkey = _group_key(g)
    with jax.default_matmul_precision("highest"):
        padded, hs = _stack(params, g, sequences, precision, q_block, pad_to)
        main, drafts = [], []
        for ids, h, p, d in zip(padded, hs, positions, draft_positions):
            main.append(np.asarray(_head(h[_at(p)], params["final_norm"], params["head"], eps=eps, precision=precision)))
            normed = _rms_norm(h, params["final_norm"].astype(jnp.float32), eps)
            x = _mtp_input(params["embed"][jnp.roll(ids, -1)], normed, _widen({k: mp[k] for k in ("enorm", "hnorm", "eh_proj")}), eps=eps, precision=precision)
            u = _layer_forward(x, mp["layer"], False, g, gkey, precision, q_block)
            drafts.append(np.asarray(_head(u[_at(d)], mp["head_norm"], params["head"], eps=eps, precision=precision)))
        return main, drafts


# ----------------------------------------------------------------- the work
def parameter_counts(g: dict) -> dict:
    """Parameters by part, from the group's numbers, norm scales and the
    router's bias among them."""
    d = _dims(g)
    h, heads = d["hidden"], d["heads"]
    mla = (
        h * d["q_rank"] + d["q_rank"] + d["q_rank"] * heads * (d["nope"] + d["rope"]) + h * (d["kv_rank"] + d["rope"]) + d["kv_rank"]
        + d["kv_rank"] * heads * (d["nope"] + d["v"]) + heads * d["v"] * h
    )
    expert = 3 * h * d["expert_mlp"]
    router = h * d["experts"] + d["experts"]
    routed_layer = mla + 2 * h + router + d["shared"] * expert + d["experts_held"] * expert
    dense_layer = mla + 2 * h + 3 * h * d["dense_mlp"]
    mtp = routed_layer + 3 * h + 2 * h * h
    n_dense = d["dense_layers"]
    vocabulary = 2 * d["vocab"] * h + h
    return {
        "mla": mla, "router": router, "expert": expert, "routed_layer": routed_layer, "dense_layer": dense_layer, "mtp_layer": mtp,
        "vocabulary": vocabulary,
        "total": n_dense * dense_layer + (d["layers"] - n_dense) * routed_layer + d["mtp"] * mtp + vocabulary,
    }


def _matrices(d: dict) -> dict:
    """Matrix parameters (no norms, no bias) one token passes in each kind of
    layer, the routed experts apart."""
    h, heads = d["hidden"], d["heads"]
    mla = (
        h * d["q_rank"] + d["q_rank"] * heads * (d["nope"] + d["rope"]) + h * (d["kv_rank"] + d["rope"])
        + d["kv_rank"] * heads * (d["nope"] + d["v"]) + heads * d["v"] * h
    )
    return {"mla": mla, "dense_mlp": 3 * h * d["dense_mlp"], "router": h * d["experts"], "expert": 3 * h * d["expert_mlp"], "eh_proj": 2 * h * h}


def _per_token(g: dict, experts: float) -> tuple[float, float]:
    """Matrix parameters a token (or the tokens of a step, naming ``experts``
    distinct experts a layer) passes: the main stack's layers held, and the
    MTP layer's with its input projection."""
    d = _dims(g)
    m = _matrices(d)
    routed = m["mla"] + m["router"] + (d["shared"] + experts) * m["expert"]
    main = d["dense_layers"] * (m["mla"] + m["dense_mlp"]) + (d["layers"] - d["dense_layers"]) * routed
    return main, (m["eh_proj"] + routed) * d["mtp"]


def _core_per_pair(g: dict) -> float:
    d = _dims(g)
    return 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"])


def _attention_layers(g: dict) -> int:
    d = _dims(g)
    return d["layers"] + d["mtp"]


def attention_core_flops(g: dict, tokens: int) -> float:
    """MLA's core over a prompt of ``tokens`` tokens in every layer that
    attends, the MTP layer's among them: scores and weighted sum of each
    query over every key up to its own (the work the fused prefill kernel is
    there to do)."""
    return _attention_layers(g) * _core_per_pair(g) * tokens * (tokens + 1) / 2.0


def _head_flops(g: dict) -> float:
    d = _dims(g)
    return 2.0 * d["vocab"] * d["hidden"]


def _share(g: dict) -> float:
    """Experts one token names here: its ``num_experts_per_tok`` at the share
    of the router's experts held."""
    d = _dims(g)
    return d["topk"] * d["experts_held"] / d["experts"]


def prompt_flops(g: dict, tokens: int) -> float:
    """A prompt of ``tokens`` tokens through the main stack and the MTP
    layer, the logits at its last position and the first draft's."""
    main, mtp = _per_token(g, _share(g))
    return 2.0 * tokens * (main + mtp) + attention_core_flops(g, tokens) + (1 + _dims(g)["mtp"]) * _head_flops(g)


def step_flops(g: dict, context: int) -> float:
    """One verify step whose first token sees ``context`` keys (itself among
    them): two tokens through the main stack, the MTP layer at both
    positions, two rows of the main head and one of the draft's."""
    main, mtp = _per_token(g, _share(g))
    core = _core_per_pair(g) * _attention_layers(g) * (2 * context + 1)
    return 2.0 * 2 * (main + mtp) + core + (2 + _dims(g)["mtp"]) * _head_flops(g)


def flops(group: dict, useful_tokens) -> float:
    """The requests of a slice: one ``(prompt tokens, decode steps)`` each;
    a decode step is counted as one verify step (each emits one token where
    the draft is not kept, as with seeded weights nearly every draft is)."""
    return sum(prompt_flops(group, prompt) + sum(step_flops(group, prompt + i + 1) for i in range(steps)) for prompt, steps in useful_tokens)


def step_experts(g: dict) -> float:
    """Distinct experts held here that a step's two tokens name in a layer,
    expected under routing spread evenly: the first token's
    ``num_experts_per_tok``, and the second's where they are not the
    first's."""
    d = _dims(g)
    k, E = d["topk"], d["experts"]
    return (k + k * (E - k) / E) * d["experts_held"] / E


def decode_bytes(group: dict, context: int) -> float:
    """Least bytes one verify step moves at ``context`` keys: every matrix
    its two tokens touch (the dense layer, each routed layer's attention,
    router, shared expert and the distinct experts named, the MTP layer's
    likewise with its input projection), the head twice (the main rows, then
    the draft's, which waits on them), and the latent rows of the context in
    every layer that attends, at two bytes a value."""
    d = _dims(group)
    main, mtp = _per_token(group, step_experts(group))
    state = _attention_layers(group) * (context + 1) * (d["kv_rank"] + d["rope"])
    return 2.0 * (main + mtp + (1 + d["mtp"]) * d["vocab"] * d["hidden"] + state)


def built_differs(group: dict, built) -> dict:
    """``built`` is the program's ``DecoderConfig``; returns key -> (built,
    file) for every key on which the two differ."""
    same_name = (
        "hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor", "rms_norm_eps", "rope_theta",
        "vocab_size", "num_nextn_predict_layers", "experts_held", "expert_offset",
    )
    stated = {k: getattr(built, k) for k in same_name}
    stated["rope_scaling"] = None if built.rope_scaling is None else dict(built.rope_scaling)
    stated["index_topk"] = built.index_topk
    stated["vocab_held"] = built.vocab_held
    stated["param_dtype"] = np.dtype(built.dtype).name
    wanted = dict(group, index_topk=0, vocab_held=group.get("vocab_size"))
    return {k: (v, wanted.get(k)) for k, v in stated.items() if wanted.get(k) != v}
