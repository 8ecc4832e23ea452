"""The generation stage's second architecture at a toy size on the CPU: the
decoder-hybrid-decoder (``models/hybrid_decoder.py``) and its fused scan
(``ops/selective_scan.py``), held against the plain reference of the
benchmark's ``phi4flash`` family (float32 ``jax.numpy``, every layer over
every token, no cache, no ring, no chunks), on seeded weights.  The executor
over both architectures is ``test_decoder.py``'s, and so is the scan
kernel's compile for a described v5e (one file loads libtpu)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import phi4flash as family
from pathway_tpu.internals import device_counters as devctr
from pathway_tpu.models import hybrid_decoder as decoder
from pathway_tpu.parallel import JittedDecoder
from tests.hybrid_toy import GROUP, POSITIONS, config_of, float32_params


@pytest.fixture(scope="module")
def model():
    params = float32_params(GROUP)
    ids = np.random.default_rng(0).integers(1000, GROUP["vocab_size"], size=44).astype(np.int32)
    return {
        "cfg": config_of(GROUP), "params": params, "ids": ids,
        "reference": family.reference_logits(params, GROUP, [ids], [list(range(ids.size))], q_block=16)[0],
        "prefill": jax.jit(decoder.prefill, static_argnames=("config",)),
        "decode": jax.jit(decoder.decode_step, static_argnames=("config",)),
    }


def _prefill(model, cache, slot, start, tokens, bucket, last=True, cfg=None, params=None):
    ids = np.zeros(bucket, np.int32)
    ids[: len(tokens)] = tokens
    return model["prefill"](params or model["params"], jnp.asarray(ids), cache, slot, start, len(tokens), last, config=cfg or model["cfg"])


def _decode(model, cache, slot, position, token):
    logits, cache, stats = model["decode"](model["params"], jnp.asarray([token]), cache, jnp.asarray([slot]), jnp.asarray([position]), config=model["cfg"])
    return logits[0], cache, stats


def test_the_layer_pattern_and_the_built_configuration_are_the_groups(model):
    assert family.layer_kinds(8) == ["mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross"]
    kinds = family.layer_kinds(32)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7] and kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    assert family.built_differs(GROUP, model["cfg"]) == {}
    assert "sliding_window" in family.built_differs(GROUP, dataclasses.replace(model["cfg"], sliding_window=4, key_block=8))
    with pytest.raises(ValueError, match="multiple of 4"):
        config_of(dict(GROUP, num_hidden_layers=6))


def test_prefill_then_decode_through_the_four_kinds_of_state_is_the_references_full_forward(model):
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    assert {k: v.shape for k, v in cache.items()} == {
        "ssm": (3, 2, 4, 128), "conv": (3, 2, 3, 128), "ring_k": (2, 2, 2, 8, 16), "ring_v": (2, 2, 2, 8, 16), "k": (2, 2, POSITIONS, 16), "v": (2, 2, POSITIONS, 16),
    }
    logits, cache, _ = _prefill(model, cache, 1, 0, ids[:24], 24)
    assert np.abs(np.asarray(logits) - ref[23]).max() < 2e-5
    for t in range(24, ids.size):
        logits, cache, _ = _decode(model, cache, 1, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < 2e-5, t


def test_chunked_prefill_is_the_whole_and_padding_and_a_used_slot_change_nothing(model):
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    # the slot has held another, longer sequence before: it left a recurrent state, a tail, full rings and K/V rows
    _, cache, _ = _prefill(model, cache, 0, 0, np.arange(1000, 1040), 40)
    assert float(jnp.abs(cache["ssm"][:, 0]).max()) > 0
    # three chunks of one bucket, the last padded: the padding advances neither state nor tail nor ring
    for start in (0, 16, 32):
        chunk = ids[start : min(start + 16, 37)]
        logits, cache, _ = _prefill(model, cache, 0, start, chunk, 16)
        assert np.abs(np.asarray(logits) - ref[start + len(chunk) - 1]).max() < 2e-5
    for t in range(37, 41):
        logits, cache, _ = _decode(model, cache, 0, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < 2e-5
    assert float(jnp.abs(cache["ssm"][:, 1]).max()) == 0  # and the other slot was never touched


@pytest.mark.parametrize("prompt", [5, 8, 13, 29])
def test_a_context_past_the_window_is_the_references(model, prompt):
    """The ring wrapped: at prefill across a chunk boundary (29 = 16 + 13: the
    second chunk's first queries see the first's last keys), and at decode (a
    prompt shorter than the window, one that fills it, one past it)."""
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    for start in range(0, prompt, 16):
        chunk = ids[start : min(start + 16, prompt)]
        logits, cache, _ = _prefill(model, cache, 0, start, chunk, 16 if len(chunk) > 8 else 8, last=start + len(chunk) == prompt)
    assert np.abs(np.asarray(logits) - ref[prompt - 1]).max() < 2e-5
    for t in range(prompt, prompt + 12):
        logits, cache, _ = _decode(model, cache, 0, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < 2e-5, t


def test_the_window_and_the_memory_matter(model):
    """What the comparison would miss if the reference were blind to them: a
    wider window, and a gated memory unit fed another layer's scan."""
    ids = model["ids"]
    wide = family.reference_logits(model["params"], dict(GROUP, sliding_window=64), [ids], [[43]], q_block=16)[0]
    assert np.abs(wide - model["reference"][43]).max() > 1e-3
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    params = dict(model["params"], cross_pairs=jax.tree.map(jnp.zeros_like, model["params"]["cross_pairs"]))
    logits, _, _ = _prefill(model, cache, 0, 0, ids[:24], 24, params=params)
    assert np.abs(np.asarray(logits) - model["reference"][23]).max() > 1e-3


def test_a_chunk_that_is_not_the_prompts_last_runs_no_cross_decoder_and_counts_what_it_did(model):
    ids = model["ids"]
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    logits, cache, stats = _prefill(model, cache, 0, 0, ids[:16], 16, last=False)
    assert float(jnp.abs(logits).max()) == 0.0
    # 2 window layers; 16 tokens, each seeing min(t + 1, 8) keys of the 16 its block is multiplied with
    assert list(np.asarray(stats)) == [0, 16, 2 * (36 + 8 * 8), 2 * 16 * 16]
    logits, cache, stats = _prefill(model, cache, 0, 16, ids[16:29], 16)
    assert list(np.asarray(stats)) == [1, 13, 2 * 13 * 8, 2 * 16 * 16]
    assert np.abs(np.asarray(logits) - model["reference"][28]).max() < 2e-5
    _, _, stats = _decode(model, cache, 0, 29, ids[29])
    assert list(np.asarray(stats)) == [1, 1, 2 * 8, 2 * 8]


@pytest.mark.parametrize("steps", [1, 5])
def test_a_generation_sends_one_prompt_row_and_every_new_token_through_the_cross_decoder(model, steps):
    executor = JittedDecoder(model["cfg"], params=model["params"], slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    before = devctr.snapshot()
    out = executor.generate(model["ids"][:29], steps + 1)
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert moved["gen_prefill_dispatches"] == 2 and moved["xdec_tokens_run"] == 1 + steps and moved["xdec_tokens_seen"] == 29 + steps
    assert not [k for k in moved if k.startswith("ssm_")]  # the scanned-token pair was prompt_useful_token_pct times nine (PR 39)
    assert moved["moe_rows_routed"] == moved["dsa_keys_scored"] == 0  # the other architecture's counters stay where they were
    whole = np.concatenate([model["ids"][:29], out["ids"]])
    ref = family.reference_logits(model["params"], GROUP, [whole], [list(range(28, 29 + steps))], q_block=16)[0]
    assert np.abs(out["logits"] - ref).max() < 2e-5


def test_bfloat16_stays_near_the_reference(model):
    """The serving type: bfloat16 weights, rings, K/V and tails; float32 state and accumulation."""
    cfg = dataclasses.replace(model["cfg"], dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 2 or a.shape[-1] * a.shape[0] > 4096 else a, model["params"])
    ids = model["ids"]
    ref = family.reference_logits(params, GROUP, [ids], [[23, 24]], q_block=16)[0]
    cache = decoder.init_cache(cfg, 1, POSITIONS)
    assert cache["ssm"].dtype == jnp.float32 and cache["ring_k"].dtype == jnp.bfloat16
    logits, cache, _ = model["prefill"](params, jnp.asarray(ids[:24]), cache, 0, 0, 24, True, config=cfg)
    assert np.abs(np.asarray(logits) - ref[0]).max() < 0.25 * ref.std()
    logits, _, _ = model["decode"](params, jnp.asarray(ids[24:25]), cache, jnp.asarray([0]), jnp.asarray([24]), config=cfg)
    assert np.abs(np.asarray(logits[0]) - ref[1]).max() < 0.25 * ref.std()


# --------------------------------------------------------------- the kernel
@pytest.mark.parametrize("block_t", [16, 64])
def test_the_fused_scan_is_the_per_token_recurrence_and_returns_its_final_state(block_t):
    """``ops/selective_scan.py`` interpreted (the TPU's prefill path; on the
    CPU the decoder runs the recurrence in ``jax.numpy``): two channel
    blocks, token blocks that carry the state between them, a padded tail
    (``dt`` 0) that leaves the state where the last real token put it."""
    from pathway_tpu.ops.selective_scan import selective_scan, selective_scan_reference

    rng = np.random.default_rng(0)
    T, channels, states, real = 64, 2048, 16, 41
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    x, b, c, d, h0 = draw(T, channels), draw(T, states), draw(T, states), draw(channels), draw(states, channels)
    dt = jnp.abs(draw(T, channels)) * 0.05
    a = -jnp.exp(draw(states, channels))
    padded = dt.at[real:].set(0.0)
    y, hT = selective_scan(x, padded, a, b, c, d, h0, block_t=block_t, interpret=True)
    want_y, want_h = selective_scan_reference(x, padded, a, b, c, d, h0)
    assert y.shape == (T, channels) and float(jnp.abs(y - want_y).max()) < 1e-4 and float(jnp.abs(hT - want_h).max()) < 1e-5
    _, stopped = selective_scan_reference(x[:real], dt[:real], a, b[:real], c[:real], d, h0)
    assert float(jnp.abs(hT - stopped).max()) < 1e-5
    h = np.asarray(h0)  # and both are the recurrence written out
    for t in range(3):
        h = np.exp(np.asarray(dt[t])[None, :] * np.asarray(a)) * h + (np.asarray(dt[t]) * np.asarray(x[t]))[None, :] * np.asarray(b[t])[:, None]
        assert np.abs((h * np.asarray(c[t])[:, None]).sum(0) + np.asarray(d) * np.asarray(x[t]) - np.asarray(y[t])).max() < 1e-4
    with pytest.raises(ValueError, match="multiple"):
        selective_scan(x[:, :1000], dt[:, :1000], a[:, :1000], b, c, d[:1000], h0[:, :1000], interpret=True)
