"""The plain reference: a post-LN BERT sentence encoder and an exact top-k.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
no kernels, no batching tricks, no cache.  It imports nothing of the program
and takes nothing the program has made: the parameters and the filler rows
come from the benchmark's own seeded makers (``weights.py``, ``corpus.py``),
the tokenizer below is a copy of the hashing rule, not a call into it.

Departures from the published BERT (both are the program's presets, listed
under ``assumed`` in each configuration file): GELU in its tanh form, and
the hashing tokenizer (one token a word) in place of WordPiece.

``precision`` selects the arithmetic of every matrix product:

- ``"f32"`` -- float32 inputs, ``highest`` precision: the reference;
- ``"fp8"`` -- inputs scaled per tensor to float8_e4m3fn's range, rounded,
  float32 accumulation: the nearest precision below the bfloat16 that the
  configurations state for activations, the control.
"""

from __future__ import annotations

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np

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


@jax.jit
def _block_scores(q, block):
    return jnp.einsum(
        "qd,nd->qn", q, block, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def exact_topk(queries: np.ndarray, blocks, k: int):
    """Exact top-k of ``queries @ rows.T`` over an iterable of
    ``(first_row_id, rows[n, d])`` blocks, one block on the device at a time.
    Returns (scores [q, k] descending, row ids [q, k])."""
    q = jnp.asarray(queries, jnp.float32)
    best_s = np.full((queries.shape[0], 0), -np.inf, np.float32)
    best_i = np.zeros((queries.shape[0], 0), np.int64)
    for first, rows in blocks:
        s = _block_scores(q, jnp.asarray(rows, jnp.float32))
        kk = min(k, s.shape[1])
        top_s, top_i = jax.lax.top_k(s, kk)
        best_s = np.concatenate([best_s, np.asarray(top_s)], axis=1)
        best_i = np.concatenate([best_i, np.asarray(top_i).astype(np.int64) + first], axis=1)
        keep = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(best_s, keep, axis=1)
        best_i = np.take_along_axis(best_i, keep, axis=1)
    return best_s, best_i
