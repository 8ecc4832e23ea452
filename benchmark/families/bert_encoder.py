"""Model family ``bert_encoder``: a post-LN BERT sentence encoder.

Everything the yardstick knows of this family, in one file, found by the
``family`` a configuration's model group names:

- ``make_params(group, seed)`` -- the whole parameter tree drawn from the seed
  in one jitted call on the device, in the type the group states (float32
  parameters), laid out under the names the program's flax modules use, so
  that ``TPUEncoderEmbedder(..., params=tree)`` takes it as it would a
  checkpoint.  The plain forward below is given the same tree.
- the plain forward (``stack_layers``, ``embed``): straightforward
  ``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
  batching tricks, no cache, with its own copy of the hashing tokenizer
  (``token_ids``).  It imports nothing of the program and takes nothing the
  program has made.  ``precision`` selects the arithmetic of every matrix
  product: ``"f32"`` -- float32 inputs, ``highest`` precision: the reference;
  ``"fp8"`` -- inputs scaled per tensor to float8_e4m3fn's range, rounded,
  float32 accumulation: the nearest precision below the bfloat16 that the
  configurations state for activations, the control.
- ``flops(group, useful_tokens)`` -- the work the rows need, from the group's
  numbers, never from what the program happens to dispatch: padding, a dtype
  change or another kernel are then read against the same work.
- ``built_differs(group, built)`` -- what the encoder the program built
  states otherwise than the group.

Departures from the published BERT (both are the program's presets, listed
under ``assumed`` in each configuration file): GELU in its tanh form, and
the hashing tokenizer (one token a word) in place of WordPiece.
"""

from __future__ import annotations

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import seed_key


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "layers", "heads", "mlp", "max_len", "types"))
def _draw(key, *, vocab, hidden, layers, heads, mlp, max_len, types):
    head_dim = hidden // heads
    names = ["word", "position", "type", "query", "key", "value", "out", "up", "down", "bias"]
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def normal(k, shape, std):
        return jax.random.normal(k, shape, jnp.float32) * std

    fan_in = 1.0 / np.sqrt(hidden)
    stacked = {
        "query": normal(ks["query"], (layers, hidden, heads, head_dim), fan_in),
        "key": normal(ks["key"], (layers, hidden, heads, head_dim), fan_in),
        "value": normal(ks["value"], (layers, hidden, heads, head_dim), fan_in),
        "out": normal(ks["out"], (layers, heads, head_dim, hidden), fan_in),
        "up": normal(ks["up"], (layers, hidden, mlp), fan_in),
        "down": normal(ks["down"], (layers, mlp, hidden), 1.0 / np.sqrt(mlp)),
    }
    bias_keys = jax.random.split(ks["bias"], 4)
    small = {
        "qkv_bias": normal(bias_keys[0], (layers, 3, heads, head_dim), 0.02),
        "out_bias": normal(bias_keys[1], (layers, hidden), 0.02),
        "up_bias": normal(bias_keys[2], (layers, mlp), 0.02),
        "down_bias": normal(bias_keys[3], (layers, hidden), 0.02),
    }
    ones = jnp.ones((hidden,), jnp.float32)
    zeros = jnp.zeros((hidden,), jnp.float32)
    tree = {
        "embeddings": {
            "word": {"embedding": normal(ks["word"], (vocab, hidden), 1.0)},
            "position": {"embedding": normal(ks["position"], (max_len, hidden), 0.1)},
            "type": {"embedding": normal(ks["type"], (types, hidden), 0.1)},
            "ln": {"scale": ones, "bias": zeros},
        }
    }
    for i in range(layers):
        tree[f"layer_{i}"] = {
            "attention": {
                "query": {"kernel": stacked["query"][i], "bias": small["qkv_bias"][i, 0]},
                "key": {"kernel": stacked["key"][i], "bias": small["qkv_bias"][i, 1]},
                "value": {"kernel": stacked["value"][i], "bias": small["qkv_bias"][i, 2]},
                "out": {"kernel": stacked["out"][i], "bias": small["out_bias"][i]},
            },
            "attention_ln": {"scale": ones, "bias": zeros},
            "mlp_up": {"kernel": stacked["up"][i], "bias": small["up_bias"][i]},
            "mlp_down": {"kernel": stacked["down"][i], "bias": small["down_bias"][i]},
            "mlp_ln": {"scale": ones, "bias": zeros},
        }
    return {"params": tree}


def make_params(group: dict, seed: int):
    """The encoder's parameter tree for a configuration file's model group,
    drawn from ``seed`` on the default device."""
    return _draw(
        seed_key(seed, stream=1),
        vocab=group["vocab_size"],
        hidden=group["hidden_size"],
        layers=group["num_hidden_layers"],
        heads=group["num_attention_heads"],
        mlp=group["intermediate_size"],
        max_len=group["max_position_embeddings"],
        types=group["type_vocab_size"],
    )


_WORD = re.compile(r"[a-z0-9]+")
PAD, CLS, SEP, RESERVED = 0, 101, 102, 1000
_FP8_MAX = 448.0


def token_ids(text: str, vocab_size: int, max_len: int) -> list[int]:
    """[CLS] one id per word [SEP]; id = 1000 + blake2b64(word) mod (V-1000)."""
    ids = []
    for word in _WORD.findall(text.lower())[: max_len - 2]:
        h = int.from_bytes(hashlib.blake2b(word.encode(), digest_size=8).digest(), "little")
        ids.append(RESERVED + h % (vocab_size - RESERVED))
    return [CLS, *ids, SEP]


def _round_inputs(x, precision: str):
    if precision == "f32":
        return x
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec,
        _round_inputs(a, precision),
        _round_inputs(b, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


def stack_layers(params, layers: int):
    """The same parameters with the per-layer groups stacked on a leading
    axis, so that the forward pass is one scanned block (it compiles once,
    not once per layer)."""
    p = params["params"]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[p[f"layer_{i}"] for i in range(layers)])
    return {"embeddings": p["embeddings"], "layers": stacked}


def _block(x, lp, bias, precision: str, eps: float):
    at = lp["attention"]
    q = _mm("blc,chd->blhd", x, at["query"]["kernel"], precision) + at["query"]["bias"]
    k = _mm("blc,chd->blhd", x, at["key"]["kernel"], precision) + at["key"]["bias"]
    v = _mm("blc,chd->blhd", x, at["value"]["kernel"], precision) + at["value"]["bias"]
    logits = _mm("blhd,bmhd->bhlm", q, k, precision) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(logits + bias, axis=-1)
    ctx = _mm("bhlm,bmhd->blhd", probs, v, precision)
    a = _mm("blhd,hdc->blc", ctx, at["out"]["kernel"], precision) + at["out"]["bias"]
    x = _layer_norm(x + a, lp["attention_ln"], eps)
    h = _mm("blc,cf->blf", x, lp["mlp_up"]["kernel"], precision) + lp["mlp_up"]["bias"]
    h = _gelu_tanh(h)
    h = _mm("blf,fc->blc", h, lp["mlp_down"]["kernel"], precision) + lp["mlp_down"]["bias"]
    return _layer_norm(x + h, lp["mlp_ln"], eps)


def forward(stacked, ids, mask, *, pool: str, eps: float, precision: str):
    """ids, mask: int32 [B, L] -> unit-norm float32 [B, hidden]."""
    emb = stacked["embeddings"]
    x = (
        emb["word"]["embedding"][ids]
        + emb["position"]["embedding"][jnp.arange(ids.shape[1])][None]
        + emb["type"]["embedding"][jnp.zeros_like(ids)]
    )
    x = _layer_norm(x, emb["ln"], eps)
    bias = jnp.where(mask.astype(bool)[:, None, None, :], 0.0, -1e30)
    x, _ = jax.lax.scan(
        lambda x, lp: (_block(x, lp, bias, precision, eps), None), x, stacked["layers"]
    )
    if pool == "cls":
        pooled = x[:, 0]
    else:
        m = mask.astype(jnp.float32)[..., None]
        pooled = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    norm = jnp.sqrt(jnp.sum(pooled**2, axis=-1, keepdims=True))
    return pooled / jnp.maximum(norm, 1e-12)


_forward_jit = jax.jit(forward, static_argnames=("pool", "eps", "precision"))


def embed(stacked, texts, model: dict, *, precision: str = "f32", block_tokens: int = 8192):
    """Embed ``texts`` in blocks of rows of one padded length (a row's
    result does not depend on its neighbours: padding is masked exactly).
    Lengths are padded to powers of two from 16 and every block of one
    length has the same number of rows, so at most six shapes compile."""
    max_len = model["max_position_embeddings"]
    rows = [token_ids(t, model["vocab_size"], max_len) for t in texts]
    out = np.zeros((len(rows), model["hidden_size"]), np.float32)
    by_width: dict[int, list[int]] = {}
    for i, r in enumerate(rows):
        width = min(max(16, 1 << (len(r) - 1).bit_length()), max_len)
        by_width.setdefault(width, []).append(i)
    for width, members in sorted(by_width.items()):
        n = max(1, block_tokens // width)
        for start in range(0, len(members), n):
            take = members[start : start + n]
            ids = np.zeros((n, width), np.int32)
            mask = np.zeros((n, width), np.int32)
            mask[len(take) :, 0] = 1
            for r, i in enumerate(take):
                ids[r, : len(rows[i])] = rows[i]
                mask[r, : len(rows[i])] = 1
            emb = _forward_jit(
                stacked,
                jnp.asarray(ids),
                jnp.asarray(mask),
                pool=model["pooling"],
                eps=model["layer_norm_eps"],
                precision=precision,
            )
            out[take] = np.asarray(emb)[: len(take)]
    return out


def row_flops(group: dict, tokens: int) -> float:
    """Multiply-adds x 2 of one row of ``tokens`` useful tokens through the
    encoder: per layer the four attention projections (8 h^2 T), the two MLP
    products (4 h f T) and the two attention products (4 T^2 h)."""
    h = group["hidden_size"]
    f = group["intermediate_size"]
    per_layer = tokens * (8 * h * h + 4 * h * f) + 4 * tokens * tokens * h
    return float(group["num_hidden_layers"] * per_layer)


def flops(group: dict, useful_tokens) -> float:
    """The rows of a slice: one entry of ``useful_tokens`` a row."""
    return sum(row_flops(group, n) for n in useful_tokens)


def built_differs(group: dict, built) -> dict:
    """``built`` is the program's ``EncoderConfig``; returns key -> (built,
    file) for every key on which the two differ."""
    stated = {
        "num_hidden_layers": built.layers,
        "hidden_size": built.hidden,
        "num_attention_heads": built.heads,
        "intermediate_size": built.mlp_dim,
        "max_position_embeddings": built.max_len,
        "vocab_size": built.vocab_size,
        "type_vocab_size": built.type_vocab,
        "pooling": built.pool,
        "layer_norm_eps": built.ln_eps,
        "activation_dtype": np.dtype(built.dtype).name,
        "param_dtype": np.dtype(built.param_dtype).name,
    }
    return {k: (v, group.get(k)) for k, v in stated.items() if group.get(k) != v}
