"""Model family ``phi4flash``: a decoder-hybrid-decoder (Phi-4-mini-flash-
reasoning): Mamba, window-attention, full-attention, gated-memory and
cross-attention layers in one stack, differential attention, no positional
encoding, the embedding tied to the head.

Everything the yardstick knows of this family, in one file, found by the
``family`` a configuration's model group names:

- ``make_params(group, seed)`` -- the parameter tree drawn from the seed in
  one jitted call on the device, under the names the program's
  ``models/hybrid_decoder.py`` takes (same-kind layers stacked).
- the plain forward (``reference_logits``): the layer equations below in
  float32 ``jax.numpy`` at ``highest`` matmul precision over the same tree,
  **all the layers over every token**: no cache, no chunks, no ring, no
  linear prefill; the recurrence a token at a time, attention by explicit
  masks in blocks of queries so that it fits, a layer's weights widened to
  float32 while that layer is applied.  It imports nothing of the program.
  ``precision`` selects the arithmetic of every matrix product as in the
  other families: ``"f32"`` the reference, ``"fp8"`` the control.
- ``flops`` / ``prompt_flops`` / ``decode_bytes`` / ``scan_bytes`` -- the work
  the architecture requires, from the group's numbers, never from what the
  program dispatches: a prompt token goes through layers ``0 .. N/2 + 1``
  only, the prompt's last token and every new one through all of them.
- ``built_differs(group, built)``.

**The layer equations** (ISSUE 35; the published ``config.json`` and
modelling code, the Mamba paper's section 3 and the Differential Transformer's
section 2 for what the configuration does not spell out).  ``N`` layers;
every layer is ``x += Mix(LN(x)); x += W_down(silu(W_gate h) * W_up h)``,
``h = LN'(x)``; after the last a LayerNorm and ``logits = h E^T``.

- ``Mix_i``: i even, i <= N/2: Mamba; i odd, i < N/2: window attention
  (``sliding_window`` keys, the query's own among them); i = N/2 + 1: full
  attention, whose K and V are kept; i even, i >= N/2 + 2: gated memory unit;
  i odd, i >= N/2 + 3: cross attention to layer N/2 + 1's K, V.
- Mamba: ``[x; z] = W_in u``; ``x <- silu(conv_causal(x) + b_c)`` (depthwise,
  ``d_conv`` taps); ``[d; B; C] = W_x x``; ``dt = softplus(W_dt d + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} + (dt_t * x_t) B_t^T``;
  ``y_t = h_t C_t + D * x_t``; out ``= W_out (y * silu(z))``.  Layer N/2 also
  publishes ``m_t = y_t``.
- Gated memory unit: out ``= W_out (m_t * silu(W_in u))``.
- Attention: query heads pair up ``(q1, q2)`` = heads ``(2p, 2p + 1)``, key
  heads ``(k1, k2)`` = ``(2g, 2g + 1)``, ``v = [v_2g; v_2g+1]``, ``g = p // 2``;
  ``a = softmax(q1 k1^T / sqrt(d)) v - lambda softmax(q2 k2^T / sqrt(d)) v``,
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init =
  0.8 - 0.6 exp(-0.3 i)``; ``a <- (1 - lambda_init) RMSNorm(a) * g``; the pairs'
  outputs side by side are ``W_o``'s input.  A cross-attention layer has
  ``W_q`` and ``W_o`` only.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the program's one tokenizer (a word an id) and the arithmetic every family's reference shares: a product at
# ``highest`` whose inputs ``precision`` rounds (``"fp8"``: the control), a tree widened to float32
from benchmark.families.deepseek_v32 import _mm, _widen, token_ids  # noqa: F401  (token_ids: what checks/answer.py asks a family for)
from benchmark.weights import seed_key


# --------------------------------------------------------------- the group
def _dims(g: dict) -> dict:
    d = g["hidden_size"]
    return {
        "hidden": d,
        "layers": g["num_hidden_layers"],
        "heads": g["num_attention_heads"],
        "kv_heads": g["num_key_value_heads"],
        "head": d // g["num_attention_heads"],
        "mlp": g["intermediate_size"],
        "window": g["sliding_window"],
        "inner": g["mamba_expand"] * d,
        "states": g["mamba_d_state"],
        "taps": g["mamba_d_conv"],
        "dt_rank": g["mamba_dt_rank"],
        "vocab": g["vocab_size"],
    }


def layer_kinds(layers: int) -> list[str]:
    """Which mixer each layer has."""
    mid = layers // 2
    kinds = []
    for i in range(layers):
        if i % 2 == 0:
            kinds.append("mamba" if i <= mid else "gmu")
        else:
            kinds.append("window" if i < mid else "full" if i == mid + 1 else "cross")
    return kinds


# ---------------------------------------------------------------- the draw
def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(jnp.bfloat16)


def _common(key, d):
    """What every layer has beside its mixer: two norms and the MLP."""
    kg, ku, kd = jax.random.split(key, 3)
    h, f = d["hidden"], d["mlp"]
    norm = lambda: {"scale": jnp.ones((h,), jnp.float32), "bias": jnp.zeros((h,), jnp.float32)}
    return {
        "norm": norm(), "mlp_norm": norm(),
        "mlp": {"gate": _normal(kg, (h, f), h), "up": _normal(ku, (h, f), h), "down": _normal(kd, (f, h), f)},
    }


def _mamba(key, d):
    h, inner, s, r = d["hidden"], d["inner"], d["states"], d["dt_rank"]
    k = jax.random.split(key, 7)
    dt = jnp.exp(jax.random.uniform(k[5], (inner,), jnp.float32) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        **_common(k[6], d),
        "in": _normal(k[0], (h, 2 * inner), h),
        "conv_w": _normal(k[1], (d["taps"], inner), d["taps"]),
        "conv_b": jnp.zeros((inner,), jnp.float32),
        "x": _normal(k[2], (inner, r + 2 * s), inner),
        "dt_w": _normal(k[3], (r, inner), r),
        "dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_b) = dt: the step starts in 1e-3 .. 1e-1
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, s + 1, dtype=jnp.float32)), (inner, s)),
        "D": jnp.ones((inner,), jnp.float32),
        "out": _normal(k[4], (inner, h), inner),
    }


def _attention(key, d, cross: bool):
    h, hd = d["hidden"], d["head"]
    wide = d["heads"] * hd + (0 if cross else 2 * d["kv_heads"] * hd)
    k = jax.random.split(key, 9)
    lam = lambda kk: jax.random.normal(kk, (hd,), jnp.float32) * 0.1
    q_name = "q" if cross else "qkv"
    return {
        **_common(k[8], d),
        q_name: _normal(k[0], (h, wide), h),
        q_name + "_b": jax.random.normal(k[1], (wide,), jnp.float32) * 0.02,
        "o": _normal(k[2], (d["heads"] * hd, h), d["heads"] * hd),
        "o_b": jax.random.normal(k[3], (h,), jnp.float32) * 0.02,
        "lambda_q1": lam(k[4]), "lambda_k1": lam(k[5]), "lambda_q2": lam(k[6]), "lambda_k2": lam(k[7]),
        "subln": jnp.ones((2 * hd,), jnp.float32),
    }


def _gmu(key, d):
    k = jax.random.split(key, 3)
    return {**_common(k[2], d), "in": _normal(k[0], (d["hidden"], d["inner"]), d["hidden"]), "out": _normal(k[1], (d["inner"], d["hidden"]), d["inner"])}


@functools.partial(jax.jit, static_argnames=("dims",))
def _draw(key, *, dims):
    d = dict(dims)
    k_embed, k_layers = jax.random.split(key)
    n, mid = d["layers"], d["layers"] // 2
    of = lambda i: jax.random.fold_in(k_layers, i)
    stack = lambda draw, layers: jax.vmap(lambda i: draw(of(i)))(jnp.asarray(layers, jnp.uint32))
    rows = jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(k_embed, i), (d["hidden"],), jnp.float32) * 0.02)
    return {
        "embed": rows(jnp.arange(d["vocab"], dtype=jnp.uint32)).astype(jnp.bfloat16),
        "final_norm": {"scale": jnp.ones((d["hidden"],), jnp.float32), "bias": jnp.zeros((d["hidden"],), jnp.float32)},
        "self_pairs": {
            "mamba": stack(lambda k: _mamba(k, d), range(0, mid, 2)),
            "window": stack(lambda k: _attention(k, d, False), range(1, mid, 2)),
        },
        "mamba_last": _mamba(of(mid), d),
        "full": _attention(of(mid + 1), d, False),
        "cross_pairs": {
            "gmu": stack(lambda k: _gmu(k, d), range(mid + 2, n, 2)),
            "cross": stack(lambda k: _attention(k, d, True), range(mid + 3, n, 2)),
        },
    }


def make_params(group: dict, seed: int):
    """The decoder's parameter tree for a configuration file's model group,
    drawn from ``seed`` on the default device: matrices bfloat16 (std
    1/sqrt(fan-in); embedding rows std 0.02, tied to the head), vectors
    float32 (norms at rest, attention biases std 0.02, the lambda vectors
    std 0.1, ``A_log`` = log(1..states), ``dt``'s bias so that the step starts
    in 1e-3..1e-1, ``D`` 1: Mamba's own initialisation, without which a random
    state-space layer forgets or explodes)."""
    return _draw(seed_key(seed, stream=3), dims=tuple(sorted(_dims(group).items())))


def layer_params(params, i: int, layers: int) -> dict:
    """Layer ``i``'s parameters out of the stacked tree."""
    mid = layers // 2
    at = lambda stack, j: jax.tree.map(lambda a: a[j], stack)
    if i == mid:
        return params["mamba_last"]
    if i == mid + 1:
        return params["full"]
    if i < mid:
        return at(params["self_pairs"]["mamba" if i % 2 == 0 else "window"], i // 2)
    return at(params["cross_pairs"]["gmu" if i % 2 == 0 else "cross"], (i - mid - 2) // 2)


# ------------------------------------------------------------ the forward
def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _mlp(h, lp, *, eps, precision):
    p = _widen(lp["mlp"])
    x = _layer_norm(h, lp["mlp_norm"], eps)
    hidden = jax.nn.silu(_mm("tc,cf->tf", x, p["gate"], precision)) * _mm("tc,cf->tf", x, p["up"], precision)
    return _mm("tf,fc->tc", hidden, p["down"], precision)


@functools.partial(jax.jit, static_argnames=("gkey", "precision"))
def _mamba_mixer(h, lp, *, gkey, precision):
    """A whole sequence through a Mamba mixer: what it adds, and ``y``."""
    g = dict(gkey)
    lp = _widen({k: v for k, v in lp.items() if k != "mlp"})
    inner, s, r, taps = g["inner"], g["states"], g["dt_rank"], g["taps"]
    T = h.shape[0]
    xz = _mm("tc,cf->tf", _layer_norm(h, lp["norm"], g["eps"]), lp["in"], precision)
    x, z = xz[:, :inner], xz[:, inner:]
    before = jnp.concatenate([jnp.zeros((taps - 1, inner), jnp.float32), x], axis=0)
    x = jax.nn.silu(sum(lp["conv_w"][k] * before[k : k + T] for k in range(taps)) + lp["conv_b"])
    dbc = _mm("tf,fr->tr", x, lp["x"], precision)
    dt = jax.nn.softplus(_mm("tr,rf->tf", dbc[:, :r], lp["dt_w"], precision) + lp["dt_b"])
    a = -jnp.exp(lp["A_log"])

    def token(state, row):
        xt, dtt, bt, ct = row
        state = jnp.exp(dtt[:, None] * a) * state + (dtt * xt)[:, None] * bt[None, :]
        return state, state @ ct + lp["D"] * xt

    _, y = jax.lax.scan(token, jnp.zeros((inner, s), jnp.float32), (x, dt, dbc[:, r : r + s], dbc[:, r + s :]))
    return _mm("tf,fc->tc", y * jax.nn.silu(z), lp["out"], precision), y


def _lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@functools.partial(jax.jit, static_argnames=("gkey", "precision", "layer", "kind", "q_block"))
def _attention_mixer(h, lp, k, v, *, gkey, precision, layer, kind, q_block):
    """A whole sequence through an attention mixer of ``kind`` (``window``,
    ``full``: keys and values of its own, returned; ``cross``: those given).
    Queries in blocks of ``q_block``, each against every key under an
    explicit mask."""
    g = dict(gkey)
    lp = _widen({k_: v_ for k_, v_ in lp.items() if k_ != "mlp"})
    T, heads, kvh, hd = h.shape[0], g["heads"], g["kv_heads"], g["head"]
    x = _layer_norm(h, lp["norm"], g["eps"])
    if kind == "cross":
        q = _mm("tc,cf->tf", x, lp["q"], precision) + lp["q_b"]
    else:
        qkv = _mm("tc,cf->tf", x, lp["qkv"], precision) + lp["qkv_b"]
        q, k, v = qkv[:, : heads * hd], qkv[:, heads * hd : (heads + kvh) * hd], qkv[:, (heads + kvh) * hd :]
    qh, kh, vh = q.reshape(T, heads, hd), k.reshape(T, kvh, hd), v.reshape(T, kvh, hd)
    pairs = heads // 2
    of_pair = np.arange(pairs) // (pairs // (kvh // 2))  # the key pair of each query pair
    k1, k2 = kh[:, 0::2][:, of_pair], kh[:, 1::2][:, of_pair]
    values = jnp.concatenate([vh[:, 0::2], vh[:, 1::2]], axis=-1)[:, of_pair]
    lam_init = _lambda_init(layer)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam_init
    pos = jnp.arange(T)
    pad = (-T) % q_block

    def block(args):
        q1, q2, t = args
        seen = pos[None, :] <= t[:, None]
        if kind == "window":
            seen = seen & (pos[None, :] > t[:, None] - g["window"])
        weights = lambda qs, ks: jax.nn.softmax(jnp.where(seen[None], _mm("tpd,spd->pts", qs, ks, precision) / math.sqrt(hd), -jnp.inf), axis=-1)
        a = _mm("pts,spe->tpe", weights(q1, k1), values, precision) - lam * _mm("pts,spe->tpe", weights(q2, k2), values, precision)
        a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + g["eps"]) * lp["subln"]
        return a * (1.0 - lam_init)

    blocks = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(-1, q_block, *a.shape[1:]) for a in (qh[:, 0::2], qh[:, 1::2], pos))
    a = jax.lax.map(block, blocks).reshape(T + pad, pairs * 2 * hd)[:T]
    return _mm("td,dc->tc", a, lp["o"], precision) + lp["o_b"], k, v


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _gmu_mixer(h, m, lp, *, eps, precision):
    lp = _widen({k: v for k, v in lp.items() if k != "mlp"})
    gate = jax.nn.silu(_mm("tc,cf->tf", _layer_norm(h, lp["norm"], eps), lp["in"], precision))
    return _mm("tf,fc->tc", m * gate, lp["out"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, norm, rows, *, eps, precision):
    return _mm("tc,vc->tv", _layer_norm(h, norm, eps), rows.astype(jnp.float32), precision)


def _group_key(g: dict):
    """The group's numbers as a hashable static argument."""
    d = _dims(g)
    return tuple(sorted({**{k: d[k] for k in ("heads", "kv_heads", "head", "window", "inner", "states", "taps", "dt_rank")}, "eps": g["layer_norm_eps"]}.items()))


def reference_logits(params, group: dict, sequences: list, positions: list, *, precision: str = "f32", q_block: int = 256, pad_to: int | None = None, vocab_block: int = 25008):
    """Logits [len(positions[i]), vocab] of each sequence of ids at the
    positions asked for: every layer over every token, layer by layer over
    all the sequences.  Every sequence is padded at its end to one length
    (``pad_to``, or the longest): every mixer is causal, so the padding
    changes no position before it, and one length is one compiled program a
    kind of layer.  The head in blocks of ``vocab_block`` rows of the (tied)
    embedding, so that its float32 copy stays small."""
    gkey = _group_key(group)
    eps, n = group["layer_norm_eps"], group["num_hidden_layers"]
    kinds = layer_kinds(n)
    length = max(pad_to or 0, max(len(s) for s in sequences))
    with jax.default_matmul_precision("highest"):
        embed = params["embed"]
        hs = [embed[jnp.asarray(np.pad(np.asarray(s, np.int32), (0, length - len(s))))].astype(jnp.float32) for s in sequences]
        memory, keys, values = [None] * len(hs), [None] * len(hs), [None] * len(hs)
        for i, kind in enumerate(kinds):
            lp = layer_params(params, i, n)
            for j, h in enumerate(hs):
                if kind == "mamba":
                    added, y = _mamba_mixer(h, lp, gkey=gkey, precision=precision)
                    if i == n // 2:
                        memory[j] = y
                elif kind == "gmu":
                    added = _gmu_mixer(h, memory[j], lp, eps=eps, precision=precision)
                else:
                    added, k, v = _attention_mixer(h, lp, keys[j], values[j], gkey=gkey, precision=precision, layer=i, kind=kind, q_block=q_block)
                    if kind == "full":
                        keys[j], values[j] = k, v
                h = h + added
                hs[j] = h + _mlp(h, lp, eps=eps, precision=precision)
        out = []
        for h, p in zip(hs, positions):
            rows = h[jnp.asarray(np.asarray(p, np.int32))]
            blocks = [_head(rows, params["final_norm"], embed[v0 : v0 + vocab_block], eps=eps, precision=precision) for v0 in range(0, embed.shape[0], vocab_block)]
            out.append(np.concatenate([np.asarray(b) for b in blocks], axis=1))
        return out


# ----------------------------------------------------------------- the work
def parameter_counts(g: dict) -> dict:
    """Parameters by part, from the group's numbers."""
    d = _dims(g)
    h, inner, s, r, hd = d["hidden"], d["inner"], d["states"], d["dt_rank"], d["head"]
    q, kv = d["heads"] * hd, d["kv_heads"] * hd
    lambdas = 4 * hd + 2 * hd
    counts = {
        "mlp": 3 * h * d["mlp"],
        "norms": 4 * h,
        "mamba": h * 2 * inner + (d["taps"] + 1) * inner + inner * (r + 2 * s) + (r + 1) * inner + inner * s + inner + inner * h,
        "attention": h * (q + 2 * kv) + (q + 2 * kv) + q * h + h + lambdas,
        "cross": h * q + q + q * h + h + lambdas,
        "gmu": 2 * h * inner,
        "embedding": d["vocab"] * h,
    }
    kinds = layer_kinds(d["layers"])
    mixer = {"mamba": "mamba", "window": "attention", "full": "attention", "gmu": "gmu", "cross": "cross"}
    per_layer = [counts[mixer[k]] + counts["mlp"] + counts["norms"] for k in kinds]
    counts["self_decoder"] = sum(per_layer[: d["layers"] // 2 + 2])
    counts["cross_decoder"] = sum(per_layer[d["layers"] // 2 + 2 :])
    counts["total"] = sum(per_layer) + counts["embedding"] + 2 * h
    return counts


def _linear(g: dict) -> dict:
    """Multiply-adds x 2 a token through the matrices of each kind of layer (its MLP among them)."""
    d = _dims(g)
    h, inner, hd = d["hidden"], d["inner"], d["head"]
    q, kv = d["heads"] * hd, d["kv_heads"] * hd
    mlp = 3 * h * d["mlp"]
    return {
        "mamba": 2.0 * (mlp + h * 2 * inner + inner * (d["dt_rank"] + 2 * d["states"]) + d["dt_rank"] * inner + inner * h),
        "window": 2.0 * (mlp + h * (q + 2 * kv) + q * h),
        "full": 2.0 * (mlp + h * (q + 2 * kv) + q * h),
        "gmu": 2.0 * (mlp + 2 * h * inner),
        "cross": 2.0 * (mlp + 2 * h * q),
    }


def _scan_flops_per_token(g: dict) -> float:
    """One token of one Mamba layer outside its matrices: the convolution,
    and a state and channel ``exp(dt A)``, ``h = . h + (dt x) B``, ``y += h C``:
    seven operations."""
    d = _dims(g)
    return 2.0 * d["taps"] * d["inner"] + 7.0 * d["inner"] * d["states"]


def _key_flops(g: dict) -> float:
    """One query of one attention layer against one key: scores and the weighted value, every head."""
    d = _dims(g)
    return 2.0 * d["heads"] * (d["head"] + 2 * d["head"])


def _count(g: dict) -> dict:
    kinds = layer_kinds(g["num_hidden_layers"])
    return {k: kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")}


def self_decoder_flops(g: dict, tokens: int, first: int = 0) -> float:
    """Tokens ``first .. first + tokens`` of a sequence through layers
    ``0 .. N/2 + 1``: the matrices, the recurrence at its arithmetic, window
    attention over the keys inside the window and full attention over the
    keys before each query."""
    lin, n = _linear(g), _count(g)
    contexts = np.arange(first + 1, first + tokens + 1, dtype=np.float64)
    return (
        tokens * (n["mamba"] * (lin["mamba"] + _scan_flops_per_token(g)) + n["window"] * lin["window"] + n["full"] * lin["full"])
        + _key_flops(g) * (n["window"] * np.minimum(contexts, g["sliding_window"]).sum() + n["full"] * contexts.sum())
    )


def cross_decoder_flops(g: dict, context: int) -> float:
    """One token through layers ``N/2 + 2 .. N - 1`` and the head, its cross
    attention over ``context`` keys."""
    lin, n, d = _linear(g), _count(g), _dims(g)
    return n["gmu"] * lin["gmu"] + n["cross"] * (lin["cross"] + _key_flops(g) * context) + 2.0 * d["vocab"] * d["hidden"]


def prompt_flops(g: dict, tokens: int) -> float:
    """A prompt of ``tokens`` tokens and the logits at its last position:
    every token through the self-decoder, the last through the rest."""
    return self_decoder_flops(g, tokens) + cross_decoder_flops(g, tokens)


def flops(group: dict, useful_tokens) -> float:
    """The requests of a slice: one ``(prompt tokens, decode steps)`` each.
    Every decode step takes one token at its context through every layer."""
    total = 0.0
    for prompt, steps in useful_tokens:
        total += prompt_flops(group, prompt)
        total += sum(self_decoder_flops(group, 1, prompt + i) + cross_decoder_flops(group, prompt + i + 1) for i in range(steps))
    return total


def decode_bytes(group: dict, context: int) -> float:
    """Least bytes one decode step of one sequence moves at ``context`` keys:
    every weight once (the tied embedding as the head), the Mamba layers'
    recurrent states (float32) and tails, the window layers' rings as far as
    they are filled, and layer N/2 + 1's keys and values over the context,
    read by itself and by every cross-attention layer."""
    c, d, n = parameter_counts(group), _dims(group), _count(group)
    kv = 2 * d["kv_heads"] * d["head"]
    recurrent = n["mamba"] * (4 * d["inner"] * d["states"] + 2 * (d["taps"] - 1) * d["inner"])
    rings = n["window"] * min(context, d["window"]) * kv * 2
    return 2.0 * c["total"] + recurrent + rings + (n["full"] + n["cross"]) * context * kv * 2


def scan_bytes(group: dict, tokens: int) -> float:
    """Least bytes the recurrences of a prompt of ``tokens`` tokens move,
    every Mamba layer: a token and channel ``x`` in and ``y`` out at the
    parameters' two bytes and ``dt`` in float32, a token ``B`` and ``C`` in
    float32, and a layer's state in and out."""
    d, n = _dims(group), _count(group)
    per_token = d["inner"] * (2 + 2 + 4) + 2 * d["states"] * 4
    return float(n["mamba"] * (tokens * per_token + 2 * 4 * d["inner"] * d["states"]))


def built_differs(group: dict, built) -> dict:
    """``built`` is the program's ``HybridDecoderConfig``; returns key ->
    (built, file) for every key on which the two differ."""
    same_name = (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "intermediate_size", "sliding_window",
        "mb_per_layer", "layer_norm_eps", "vocab_size", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
    )
    stated = {k: getattr(built, k) for k in same_name}
    stated["param_dtype"] = np.dtype(built.dtype).name
    wrong = {k: (v, group.get(k)) for k, v in stated.items() if group.get(k) != v}
    if built.vocab_held != group.get("vocab_size"):  # the whole vocabulary is held: no slice to state
        wrong["vocab_held"] = (built.vocab_held, group.get("vocab_size"))
    return wrong
