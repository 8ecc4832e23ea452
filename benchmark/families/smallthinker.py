"""Model family ``smallthinker``: a causal decoder of grouped-query attention,
sliding-window with rope in three layers of four and global with no
positional encoding in the fourth, whose router reads the layer's input
before attention and whose routed experts are ReGLU units.

Everything the yardstick knows of this family, in one file, found by the
``family`` a configuration's model group names, as ``deepseek_v32`` is:

- ``make_params(group, seed)`` -- the parameter tree drawn from the seed in
  one jitted call on the device, bfloat16, under the names the program's
  ``models/window_moe_decoder.py`` takes: matrices normal with std
  ``1/sqrt(fan_in)``, embedding rows 0.02, norm scales 1, the head untied.
  An expert's weights depend on its number among the router's, so another
  ``expert_offset`` draws another share of the same model.
- the plain forward (``reference_logits``): the layer equations below in
  float32 ``jax.numpy`` at ``highest`` matmul precision over the same tree,
  the whole sequence at once with no cache, ring, chunks or kernel, sublayer
  by sublayer over all the sequences, queries in blocks so that 16,384
  positions fit.  It imports nothing of the program.  ``precision``:
  ``"f32"`` the reference, ``"fp8"`` the control (every matrix product's
  inputs rounded to float8_e4m3fn under a per-tensor scale).
- ``parameter_counts`` / ``token_flops`` / ``attention_core_flops`` /
  ``prompt_flops`` / ``flops`` / ``decode_bytes`` -- the work a request needs
  and the bytes a decode step touches, from the group's numbers, never from
  what the program dispatches; the attention core counts only the pairs
  inside the causal bound and the window.
- ``built_differs(group, built)``.

**The layer equations** (the published ``config.json`` and, for what it
does not say, the family's description: "router placed before attention",
"sparse ReGLU").  ``RMS(x; w) = x rsqrt(mean(x^2) + eps)
w``; for layer ``l`` and the residual stream ``h``::

    r  = softmax(h W_r)                  # the layer's input, not RMS(h)
    E  = top-k(r);  g_e = r_e / sum over E of r
    a  = h + Attn_l(RMS(h; w_in))
    x  = RMS(a; w_post)
    h' = a + sum over e in E held here of g_e (relu(x W_g,e) * x W_u,e) W_d,e

``Attn_l(y)`` at position t: ``q = y W_q`` (``num_attention_heads`` heads),
``k = y W_k``, ``v = y W_v`` (``num_key_value_heads``), query head ``j``
reading K/V head ``j // (H / G)``; where ``rope_layout[l]`` is 1, q and k
rotated at their positions (``rope_theta``, all ``head_dim`` dimensions,
the first half paired with the second); visible ``s <= t``, and where
``sliding_window_layout[l]`` is 1 also ``s > t - sliding_window_size``;
softmax of ``q . k / sqrt(head_dim)``; ``W_o`` over the heads' results.
Then the final RMS and the head over every row of the vocabulary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# what every decoder family of the yardstick shares, defined where the first of them is: the seeded draws, the hashing
# tokenizer's copy (token_ids: what checks/answer.py asks a family for), products at a precision
from benchmark.families.deepseek_v32 import _head, _mm, _normal, _rms_norm, _rows, _widen, token_ids  # noqa: F401
from benchmark.weights import seed_key


# --------------------------------------------------------------- the group
def _dims(g: dict) -> dict:
    """The sizes the draw and the work functions need, from the group."""
    return {
        "hidden": g["hidden_size"],
        "layers": g["num_hidden_layers"],
        "heads": g["num_attention_heads"],
        "kv_heads": g["num_key_value_heads"],
        "head_dim": g["head_dim"],
        "expert_mlp": g["moe_ffn_hidden_size"],
        "experts": g["moe_num_primary_experts"],
        "experts_held": g["experts_held"],
        "expert_offset": g["expert_offset"],
        "topk": g["moe_num_active_primary_experts"],
        "vocab": g["vocab_size"],
        "window": g["sliding_window_size"],
    }


def windowed(g: dict) -> list[bool]:
    """For each layer held, whether it is a window layer."""
    return [bool(f) for f in g["sliding_window_layout"][: g["num_hidden_layers"]]]


# ---------------------------------------------------------------- the draw
@functools.partial(jax.jit, static_argnames=("dims",))
def _draw(key, *, dims):
    d = dict(dims)
    h, heads, kv, hd, f = d["hidden"], d["heads"], d["kv_heads"], d["head_dim"], d["expert_mlp"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    ones = lambda n: jnp.ones((n,), jnp.float32)

    def expert(key):
        kg, ku, kd = jax.random.split(key, 3)
        return {"gate": _normal(kg, (h, f), h), "up": _normal(ku, (h, f), h), "down": _normal(kd, (f, h), f)}

    layers = []
    for li in range(d["layers"]):
        ks = dict(zip(["q", "k", "v", "o", "router", "experts"], jax.random.split(jax.random.fold_in(k_layers, li), 6)))
        held = d["expert_offset"] + jnp.arange(d["experts_held"], dtype=jnp.uint32)
        layers.append({
            "attn_norm": ones(h),
            "q": _normal(ks["q"], (h, heads * hd), h),
            "k": _normal(ks["k"], (h, kv * hd), h),
            "v": _normal(ks["v"], (h, kv * hd), h),
            "o": _normal(ks["o"], (heads * hd, h), heads * hd),
            "mlp_norm": ones(h),
            "router": _normal(ks["router"], (h, d["experts"]), h),
            "experts": jax.vmap(lambda e: expert(jax.random.fold_in(ks["experts"], e)))(held),
        })
    return {
        "embed": _rows(k_embed, 0, d["vocab"], h, 0.02),
        "head": _rows(k_head, 0, d["vocab"], h, 1.0 / np.sqrt(h)).T,
        "final_norm": ones(h),
        "layers": layers,
    }


def make_params(group: dict, seed: int):
    """The decoder's parameter tree for a configuration file's model group,
    drawn from ``seed`` on the default device."""
    return _draw(seed_key(seed, stream=3), dims=tuple(sorted(_dims(group).items())))


# ------------------------------------------------------------ the forward
def inv_freq(g: dict) -> np.ndarray:
    dim = g["head_dim"]
    return 1.0 / g["rope_theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def _rope(x, angles):
    """Rotate ``x`` [T, heads, dim], dimension i paired with i + dim/2; ``angles`` [T, dim/2]."""
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("gkey", "precision", "q_block", "window", "rope"))
def _attention(h, lp, *, gkey, precision, q_block, window, rope):
    """One layer's attention over a whole sequence: ``h`` [T, hidden] -> what
    it adds.  Keys and values stand for the whole sequence, one per query
    head; queries and their attention are taken ``q_block`` positions at a
    time."""
    g = dict(gkey)
    lp = _widen({k: lp[k] for k in ("attn_norm", "q", "k", "v", "o")})
    T = h.shape[0]
    heads, kv, hd = g["num_attention_heads"], g["num_key_value_heads"], g["head_dim"]
    freq = jnp.asarray(g["inv_freq"], jnp.float32)
    pos = jnp.arange(T)
    x = _rms_norm(h, lp["attn_norm"], g["rms_norm_eps"])
    k = _mm("tc,cd->td", x, lp["k"], precision).reshape(T, kv, hd)
    v = _mm("tc,cd->td", x, lp["v"], precision).reshape(T, kv, hd)
    if rope:
        k = _rope(k, pos.astype(jnp.float32)[:, None] * freq[None, :])
    of_head = jnp.arange(heads) // (heads // kv)
    k, v = k[:, of_head], v[:, of_head]
    pad = (-T) % q_block

    def block(args):
        xb, t = args  # a block of positions: their normed rows and numbers
        q = _mm("tc,cd->td", xb, lp["q"], precision).reshape(-1, heads, hd)
        if rope:
            q = _rope(q, t.astype(jnp.float32)[:, None] * freq[None, :])
        seen = pos[None, :] <= t[:, None]
        if window:
            seen = seen & (pos[None, :] > t[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None], _mm("thd,shd->hts", q, k, precision) * hd**-0.5, -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", probs, v, precision)

    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(-1, q_block, *a.shape[1:]) for a in (x, pos))
    out = jax.lax.map(block, blocks).reshape(T + pad, heads * hd)[:T]
    return _mm("td,dc->tc", out, lp["o"], precision)


@functools.partial(jax.jit, static_argnames=("topk", "precision"))
def _route(h, router, *, topk, precision):
    """Each token's chosen experts and their gates, from the layer's input."""
    s = jax.nn.softmax(_mm("tc,ce->te", h, router.astype(jnp.float32), precision), axis=-1)
    weight, chosen = jax.lax.top_k(s, topk)
    return chosen, weight / jnp.sum(weight, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(x, rows, gates, p, *, precision):
    """One ReGLU expert over the rows that chose it (``gates`` 0 on padding)."""
    p = _widen(p)
    xr = x[rows]
    hidden = jax.nn.relu(_mm("tc,cf->tf", xr, p["gate"], precision)) * _mm("tc,cf->tf", xr, p["up"], precision)
    return _mm("tf,fc->tc", hidden, p["down"], precision) * gates[:, None]


def routed(x, chosen, gates, lp, g: dict, precision: str = "f32"):
    """What the experts held here add to the normed rows ``x``: expert by
    expert, its tokens gathered, run through it, weighted and added back."""
    chosen, gates_np = np.asarray(chosen), np.asarray(gates)
    out = jnp.zeros_like(x)
    for local in range(g["experts_held"]):
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


@functools.partial(jax.jit, static_argnames=("eps",))
def _post_norm(a, norm, *, eps):
    return _rms_norm(a, norm.astype(jnp.float32), eps)


def _group_key(g: dict):
    """The group's numbers as a hashable static argument."""
    static = {k: g[k] for k in ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps")}
    static["inv_freq"] = tuple(float(f) for f in inv_freq(g))
    return tuple(sorted(static.items()))


def reference_logits(params, group: dict, sequences: list, positions: list, *, precision: str = "f32", q_block: int = 128, pad_to: int | None = None):
    """Logits [len(positions[i]), vocab] of each sequence of ids at the
    positions asked for: the full forward, sublayer by sublayer over all the
    sequences.  Every sequence is padded at its end to one length (``pad_to``,
    or the longest): under a causal mask the padding changes no position
    before it, and one length is one compiled program a sublayer."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    gkey, g = _group_key(group), dict(group)
    eps, W = g["rms_norm_eps"], g["sliding_window_size"]
    length = max(pad_to or 0, max(len(s) for s in sequences))
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        hs = [embed[jnp.asarray(np.pad(np.asarray(s, np.int32), (0, length - len(s))))].astype(jnp.float32) for s in sequences]
        for li, (lp, window) in enumerate(zip(params["layers"], windowed(g))):
            for n, h in enumerate(hs):
                chosen, gates = _route(h, lp["router"], topk=g["moe_num_active_primary_experts"], precision=precision)
                a = h + _attention(
                    h, lp, gkey=gkey, precision=precision, q_block=q_block, window=W if window else 0, rope=bool(g["rope_layout"][li])
                )
                x = _post_norm(a, lp["mlp_norm"], eps=eps)
                hs[n] = a + routed(x, chosen, gates, lp, g, precision)
        return [
            np.asarray(_head(h[jnp.asarray(np.asarray(p, np.int32))], params["final_norm"], params["head"], eps=eps, precision=precision))
            for h, p in zip(hs, positions)
        ]


# ----------------------------------------------------------------- the work
def parameter_counts(g: dict) -> dict:
    """Parameters by part, from the group's numbers, norm scales among them."""
    d = _dims(g)
    h, hd = d["hidden"], d["head_dim"]
    attention = h * d["heads"] * hd + 2 * h * d["kv_heads"] * hd + d["heads"] * hd * h
    router = h * d["experts"]
    expert = 3 * h * d["expert_mlp"]
    outside = attention + router + 2 * h
    layer = outside + d["experts_held"] * expert
    return {
        "attention": attention, "router": router, "expert": expert, "layer_outside_experts": outside, "layer": layer,
        "vocabulary": 2 * d["vocab"] * h, "total": d["layers"] * layer + 2 * d["vocab"] * h + h,
    }


def _matrices(g: dict) -> float:
    """Matrix parameters one token passes in the layers held (no norms), the
    routed experts at the share of its choices held here."""
    c, d = parameter_counts(g), _dims(g)
    experts = d["topk"] * d["experts_held"] / d["experts"] * c["expert"]
    return d["layers"] * (c["attention"] + c["router"] + experts)


def linear_flops_per_token(g: dict) -> float:
    """Multiply-adds x 2 of one token through every matrix of the layers
    held; the head is counted where logits are taken."""
    return 2.0 * _matrices(g)


def _core_per_pair(g: dict) -> float:
    d = _dims(g)
    return 2.0 * d["heads"] * 2 * d["head_dim"]


def _keys_seen(g: dict, context: int) -> int:
    """Keys one query at ``context`` keys (itself among them) sees, summed over the layers held."""
    return sum(min(context, g["sliding_window_size"]) if w else context for w in windowed(g))


def token_flops(g: dict, context: int) -> float:
    """One token whose query sees ``context`` keys (itself among them) where
    nothing bounds it, the window's where one does."""
    return linear_flops_per_token(g) + _core_per_pair(g) * _keys_seen(g, context)


def attention_core_flops(g: dict, tokens: int) -> float:
    """The attention core over a prompt of ``tokens`` tokens: scores and
    weighted sum of each query over the keys visible to it (the causal bound
    and, in a window layer, the window), in every layer: the work the fused
    prefill kernel is there to do."""
    W = g["sliding_window_size"]
    causal = tokens * (tokens + 1) / 2.0
    in_window = causal if tokens <= W else W * (W + 1) / 2.0 + (tokens - W) * W
    return _core_per_pair(g) * sum(in_window if w else causal for w in windowed(g))


def prompt_flops(g: dict, tokens: int) -> float:
    """A prompt of ``tokens`` tokens and the logits at its last position."""
    d = _dims(g)
    return tokens * linear_flops_per_token(g) + attention_core_flops(g, tokens) + 2.0 * d["vocab"] * d["hidden"]


def flops(group: dict, useful_tokens) -> float:
    """The requests of a slice: one ``(prompt tokens, decode steps)`` each.
    Every decode step takes one token at its context and gives logits."""
    d = _dims(group)
    total = 0.0
    for prompt, steps in useful_tokens:
        total += prompt_flops(group, prompt)
        total += sum(token_flops(group, prompt + i + 1) for i in range(steps)) + steps * 2.0 * d["vocab"] * d["hidden"]
    return total


def decode_bytes(group: dict, context: int) -> float:
    """Least bytes one decode step of one sequence moves at ``context`` keys:
    every matrix the token touches (attention, the router, its chosen
    experts held here, the head), and the keys and values it sees in every
    layer, at two bytes a value."""
    d = _dims(group)
    state = _keys_seen(group, context) * 2 * d["kv_heads"] * d["head_dim"]
    return 2.0 * (_matrices(group) + d["vocab"] * d["hidden"] + state)


def built_differs(group: dict, built) -> dict:
    """``built`` is the program's ``WindowMoEDecoderConfig``; returns key ->
    (built, file) for every key on which the two differ."""
    same_name = (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
        "moe_num_primary_experts", "moe_num_active_primary_experts", "moe_primary_router_apply_softmax", "norm_topk_prob", "rope_theta",
        "sliding_window_size", "rms_norm_eps", "vocab_size", "max_position_embeddings", "tie_word_embeddings", "experts_held", "expert_offset",
    )
    stated = {k: getattr(built, k) for k in same_name}
    stated["rope_layout"], stated["sliding_window_layout"] = list(built.rope_layout), list(built.sliding_window_layout)
    stated["param_dtype"] = np.dtype(built.dtype).name
    return {k: (v, group.get(k)) for k, v in stated.items() if group.get(k) != v}
