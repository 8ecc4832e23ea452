"""The ``bert_encoder`` family's plain reference follows the same mathematics
as the program's encoder (float32 on both sides: agreement to rounding) and
tokenizes as the hashing tokenizer does; the ``retrieval`` check's block top-k
is exact."""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import weights
from benchmark.checks import retrieval
from benchmark.families import bert_encoder

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("pool", ["mean", "cls"])
def test_reference_matches_the_programs_encoder_in_float32(pool):
    import jax.numpy as jnp

    from pathway_tpu.models.encoder import MINILM_L6
    from pathway_tpu.parallel import JittedEncoder

    with open(os.path.join(HERE, "..", "rehearsal", "configs", "toy.json")) as f:
        model = dict(json.load(f)["model"], pooling=pool)
    cfg = dataclasses.replace(MINILM_L6, hidden=64, layers=2, heads=4, mlp_dim=128, max_len=128, pool=pool, dtype=jnp.float32)
    params = bert_encoder.make_params(model, 9)
    enc = JittedEncoder(cfg, params=params)
    texts = ["doc0000001 w1 w2 w3", "q7 " + " ".join(f"w{i}" for i in range(40)), "Hello, World! 42"]
    ours = bert_encoder.embed(bert_encoder.stack_layers(params, 2), texts, model)
    theirs = enc.encode(texts)
    assert np.abs(ours - theirs).max() < 2e-5


def test_tokenizer_is_the_hashing_rule():
    from pathway_tpu.models.tokenizer import HashTokenizer

    text = "Doc0000123 w17 w9 mixed-CASE, punctuation! 007"
    ids, mask, _ = HashTokenizer(30522).encode_batch([text], max_len=512)
    ours = bert_encoder.token_ids(text, 30522, 512)
    assert list(ids[0][: mask[0].sum()]) == ours


def test_block_topk_is_exact():
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((1000, 16)).astype(np.float32)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    blocks = [(s, rows[s : s + 256]) for s in range(0, 1000, 256)]
    top_s, top_i = retrieval.exact_topk(q, blocks, 10)
    full = q.astype(np.float64) @ rows.T.astype(np.float64)
    want = np.argsort(-full, axis=1)[:, :10]
    assert (top_i == want).all()
    assert np.allclose(top_s, np.take_along_axis(full, want, axis=1), atol=1e-5)


def test_filler_is_the_same_rows_for_the_same_seed_and_unit_length():
    a = np.asarray(weights.filler_block(2**31 + 5, 3, 64, 32))
    b = np.asarray(weights.filler_block(2**31 + 5, 3, 64, 32))
    c = np.asarray(weights.filler_block(2**31 + 6, 3, 64, 32))
    assert (a == b).all() and not (a == c).all()
    assert np.allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-6)
