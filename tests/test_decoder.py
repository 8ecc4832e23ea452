"""The generation stage at a tiny size on the CPU: the causal decoder
(``models/decoder.py``), its executor (``parallel/generation.py``) and
``TPUDecoderChat``, each held against the plain reference of the benchmark's
``deepseek_v32`` family (float32 ``jax.numpy``, no cache, no chunks, no
absorbed form), on seeded weights.  The executor, the chat and the answer
route are one code for all four architectures and run here over each
(``served``): the second, ``models/hybrid_decoder.py``, against the
``phi4flash`` family's reference, the third, ``models/shortcut_moe_decoder.py``,
against the ``longcat_flash`` family's, the fourth,
``models/window_moe_decoder.py``, against the ``smallthinker`` family's (their
own tests are ``test_hybrid_decoder.py``'s, ``test_shortcut_moe_decoder.py``'s
and ``test_window_moe_decoder.py``'s)."""

from __future__ import annotations

import dataclasses
import json
import re
import socket
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pathway_tpu as pw
from benchmark.families import deepseek_v32 as family
from benchmark.families import longcat_flash as shortcut_family
from benchmark.families import phi4flash as hybrid_family
from benchmark.families import smallthinker as window_family
from pathway_tpu.internals import device_counters as devctr
from pathway_tpu.models import MINILM_L6, decoder
from pathway_tpu.parallel import JittedDecoder
from tests import hybrid_toy, shortcut_toy, window_moe_toy
from tests.utils import T

ROPE_SCALING = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 16, "type": "yarn"}
#: hidden 64, 4 heads, 1 dense + 2 routed layers, 16 experts in 4 groups with 4 a token, index_topk 8
GROUP = {
    "family": "deepseek_v32", "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16, "n_routed_experts_published": 16, "expert_offset": 0, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 8, "rope_theta": 10000.0, "rope_scaling": ROPE_SCALING, "rms_norm_eps": 1e-6, "vocab_size": 1280,
    "vocab_size_published": 1280, "param_dtype": "float32",
}
POSITIONS = 48


def config_of(group: dict, **over) -> decoder.DecoderConfig:
    same = {f.name for f in dataclasses.fields(decoder.DecoderConfig)} & set(group) - {"n_routed_experts", "vocab_size", "rope_scaling"}
    return decoder.DecoderConfig(
        **{k: group[k] for k in same}, rope_scaling=tuple(sorted(group["rope_scaling"].items())),
        n_routed_experts=group["n_routed_experts_published"], experts_held=group["n_routed_experts"],
        vocab_size=group["vocab_size_published"], vocab_held=group["vocab_size"],
        **{"dtype": jnp.float32, "key_block": 8, "expert_block": 4, **over},
    )


def float32_params(group: dict, seed: int = 7):
    """The family's draw in float32, norms and the router's bias moved off
    their resting values so that leaving one out shows."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), family.make_params(group, seed))
    rng = np.random.default_rng(seed)
    for lp in params["layers"]:
        for name in ("attn_norm", "q_norm", "kv_norm", "mlp_norm"):
            lp[name] = lp[name] + jnp.asarray(rng.normal(0, 0.1, lp[name].shape), jnp.float32)
        lp["idx_k_norm"] = {k: v + jnp.asarray(rng.normal(0, 0.1, v.shape), jnp.float32) for k, v in lp["idx_k_norm"].items()}
        if "router_bias" in lp:
            lp["router_bias"] = jnp.asarray(rng.normal(0, 0.05, lp["router_bias"].shape), jnp.float32)
    params["final_norm"] = params["final_norm"] + jnp.asarray(rng.normal(0, 0.1, params["final_norm"].shape), jnp.float32)
    return params


@pytest.fixture(scope="module")
def model():
    cfg = config_of(GROUP)
    params = float32_params(GROUP)
    ids = np.random.default_rng(0).integers(1000, GROUP["vocab_size"], size=44).astype(np.int32)
    reference = family.reference_logits(params, GROUP, [ids], [list(range(ids.size))], q_block=16)[0]
    return {
        "cfg": cfg, "params": params, "ids": ids, "reference": reference,
        "prefill": jax.jit(decoder.prefill, static_argnames=("config",)),
        "decode": jax.jit(decoder.decode_step, static_argnames=("config",)),
    }


def _prefill(model, cache, slot, start, tokens, bucket, cfg=None, params=None):
    ids = np.zeros(bucket, np.int32)
    ids[: len(tokens)] = tokens
    return model["prefill"](params or model["params"], jnp.asarray(ids), cache, slot, start, len(tokens), config=cfg or model["cfg"])


def _decode(model, cache, slot, position, token, cfg=None):
    logits, cache, stats = model["decode"](
        model["params"], jnp.asarray([token]), cache, jnp.asarray([slot]), jnp.asarray([position]), config=cfg or model["cfg"]
    )
    return logits[0], cache, stats


def test_the_built_configuration_is_the_groups(model):
    assert family.built_differs(GROUP, model["cfg"]) == {}
    assert "hidden_size" in family.built_differs(GROUP, dataclasses.replace(model["cfg"], hidden_size=128))


def test_prefill_then_decode_through_the_caches_is_the_references_full_forward(model):
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    logits, cache, _ = _prefill(model, cache, 1, 0, ids[:24], 24)
    assert np.abs(np.asarray(logits) - ref[23]).max() < 2e-5
    for t in range(24, ids.size):
        logits, cache, _ = _decode(model, cache, 1, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < 2e-5, t


def test_chunked_prefill_is_the_whole_and_padding_and_a_used_slot_change_nothing(model):
    ids, ref = model["ids"], model["reference"]
    cache = decoder.init_cache(model["cfg"], 2, POSITIONS)
    # the slot has held another, longer sequence before
    _, cache, _ = _prefill(model, cache, 0, 0, np.arange(1000, 1040), 40)
    # three chunks of one bucket, the last padded; the padding's rows are written past the prompt and never read
    for start in (0, 16, 32):
        chunk = ids[start : min(start + 16, 37)]
        logits, cache, _ = _prefill(model, cache, 0, start, chunk, 16)
        assert np.abs(np.asarray(logits) - ref[start + len(chunk) - 1]).max() < 2e-5
    for t in range(37, 41):  # decode overwrites the padding's rows one by one
        logits, cache, _ = _decode(model, cache, 0, t, ids[t])
        assert np.abs(np.asarray(logits) - ref[t]).max() < 2e-5


def test_the_absorbed_form_is_the_expanded_form(model):
    """Token t through the decode program (the query carried into the latent
    space) and through the prefill program as a chunk of one (keys and values
    expanded per head) give the same logits."""
    ids = model["ids"]
    cache = decoder.init_cache(model["cfg"], 1, POSITIONS)
    _, cache, _ = _prefill(model, cache, 0, 0, ids[:32], 32)
    absorbed, _, _ = _decode(model, jax.tree.map(jnp.copy, cache), 0, 32, ids[32])
    chunk = np.zeros(8, np.int32)
    chunk[0] = ids[32]
    expanded, _, _ = model["prefill"](model["params"], jnp.asarray(chunk), cache, 0, 32, 1, config=model["cfg"])
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() < 2e-5


@pytest.mark.parametrize("topk", [4, 64])
def test_the_selection_is_the_references_and_dense_where_topk_covers_the_context(model, topk):
    ids = model["ids"]
    group = dict(GROUP, index_topk=topk)
    cfg = config_of(group)
    ref = family.reference_logits(model["params"], group, [ids], [list(range(ids.size))], q_block=16)[0]
    cache = decoder.init_cache(cfg, 1, POSITIONS)
    logits, cache, stats = _prefill(model, cache, 0, 0, ids[:40], 40, cfg=cfg)
    assert np.abs(np.asarray(logits) - ref[39]).max() < 2e-5
    logits, cache, step_stats = _decode(model, cache, 0, 40, ids[40], cfg=cfg)
    assert np.abs(np.asarray(logits) - ref[40]).max() < 2e-5
    scored = 3 * 40 * 41 // 2
    selected, step_selected = int(stats[2]), int(step_stats[2])
    if topk >= POSITIONS:  # every visible key is attended to: dense MLA
        assert (selected, int(stats[3])) == (scored, scored) and step_selected == 3 * 41
        assert np.abs(ref - model["reference"]).max() > 1e-3  # and it is not what top-8 gives
    else:  # keys that tie with the k-th (indexer heads all at ReLU's zero) are selected with it, here as in the reference
        exact = 3 * sum(min(t + 1, topk) for t in range(40))
        assert exact <= selected <= exact + 12 and 3 * topk <= step_selected <= 3 * topk + 2


def test_the_shares_add_up(model):
    """The parts of a routed layer's result that all four shares of its
    experts give, the shared expert counted once, are the uncut layer."""
    params, cfg = model["params"], model["cfg"]
    lp = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(3).normal(0, 1, (24, GROUP["hidden_size"])), jnp.float32)
    live = jnp.ones((24,), bool)
    gkey = family._group_key(GROUP)
    x, shared, chosen, gates = family._route(h, lp, gkey=gkey, precision="f32")
    uncut = np.asarray(shared + family._routed(x, chosen, gates, lp, GROUP, "f32"))
    whole, here, routed, grouped = decoder._mlp(h, lp, live, cfg)
    assert np.abs(np.asarray(whole) - uncut).max() < 2e-5 and int(here) == int(routed) == 24 * 4 and grouped == 1
    total, pairs = np.zeros_like(uncut), 0
    for share in range(4):
        group = dict(GROUP, n_routed_experts=4, expert_offset=4 * share)
        drawn = family.make_params(group, 7)["layers"][1]["experts"]
        mine = jax.tree.map(lambda w: w[4 * share : 4 * share + 4], lp["experts"])
        # another offset draws another share of the same experts
        assert all(np.array_equal(np.asarray(a, np.float32), np.asarray(b)) for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(mine)))
        part, here, routed, _ = decoder._mlp(h, dict(lp, experts=mine), live, config_of(group))
        reference_part = shared + family._routed(x, chosen, gates, dict(lp, experts=mine), group, "f32")
        assert np.abs(np.asarray(part) - np.asarray(reference_part)).max() < 2e-5
        total += np.asarray(part)
        pairs += int(here)
    assert pairs == 24 * 4
    assert np.abs(total - 3 * np.asarray(shared) - uncut).max() < 5e-5


def test_the_vocabulary_slice(model):
    """A model that holds the first rows of the vocabulary gives the uncut
    model's logits over those rows."""
    held = 1024
    group = dict(GROUP, vocab_size=held)
    drawn = family.make_params(group, 7)
    assert np.array_equal(np.asarray(drawn["embed"]), np.asarray(family.make_params(GROUP, 7)["embed"][:held]))
    params = dict(model["params"], embed=model["params"]["embed"][:held], head=model["params"]["head"][:, :held])
    ids = np.arange(1000, 1020).astype(np.int32)
    cfg = config_of(group)
    sliced, _, _ = _prefill(model, decoder.init_cache(cfg, 1, POSITIONS), 0, 0, ids, 24, cfg=cfg, params=params)
    whole, _, _ = _prefill(model, decoder.init_cache(model["cfg"], 1, POSITIONS), 0, 0, ids, 24)
    assert sliced.shape == (held,) and np.abs(np.asarray(sliced) - np.asarray(whole)[:held]).max() < 1e-6


def test_bfloat16_stays_near_the_reference(model):
    """The serving type: bfloat16 weights and caches, float32 accumulation."""
    cfg = dataclasses.replace(model["cfg"], dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, model["params"])
    ids = model["ids"]
    ref = family.reference_logits(params, GROUP, [ids], [[23]], q_block=16)[0]
    logits, _, _ = model["prefill"](params, jnp.asarray(ids[:24]), decoder.init_cache(cfg, 1, POSITIONS), 0, 0, 24, config=cfg)
    assert np.abs(np.asarray(logits) - ref[0]).max() < 0.25 * ref.std()


# ------------------------------------------------------------ the executor
@pytest.fixture(scope="module", params=["deepseek_v32", "phi4flash", "longcat_flash", "smallthinker"])
def served(request, model):
    """Each architecture the executor serves, at its toy size: the
    configuration, seeded float32 parameters, the family whose reference they
    are held against, and what a generation of ``prompt`` tokens in chunks of
    ``padded`` and ``steps`` decode steps makes the architecture's own
    counters read."""
    if request.param == "deepseek_v32":
        def counted(prompt, padded, steps):
            tokens = prompt + steps
            return {"moe_rows_routed": 2 * 4 * tokens, "moe_rows_here": 2 * 4 * tokens, "dsa_keys_scored": 3 * sum(range(1, tokens + 1))}

        return {"cfg": model["cfg"], "params": model["params"], "family": family, "group": GROUP, "counted": counted, "silent": "xdec_tokens_seen"}

    if request.param == "longcat_flash":
        def counted(prompt, padded, steps):
            # 2 layers = 4 attention sublayers; every live token routes 4 pairs a layer; a query sees the keys up to its own; a
            # prompt chunk multiplies its query tiles of 8 rows (a key block's), each with every key block of 8 its last row can
            # see (the request of the test: a chunk of 16 at 0, tiles of one and two blocks, and one of 8 at 16, a tile of three),
            # a decode step all 48 positions
            tokens = prompt + steps
            assert (prompt, padded) == (21, 24)
            return {
                "moe_rows_routed": 2 * 4 * tokens, "mla_keys_visible": 4 * sum(range(1, tokens + 1)),
                "mla_keys_multiplied": 4 * (8 * 8 * (1 + 2 + 3) + steps * POSITIONS),
            }

        return {
            "cfg": shortcut_toy.config_of(shortcut_toy.GROUP), "params": shortcut_toy.float32_params(shortcut_toy.GROUP), "family": shortcut_family,
            "group": shortcut_toy.GROUP, "counted": counted, "silent": "dsa_keys_scored",
        }

    if request.param == "smallthinker":
        def counted(prompt, padded, steps):
            # 1 global + 3 window layers; every live token routes 2 pairs a layer, all 8 experts held here; a window query sees up
            # to 16 keys; a prompt chunk's query tiles of 8 rows visit key blocks of 8 from the one their window reaches (the
            # request of the test: a chunk of 16 at 0, tiles of one and two blocks past the empty ring, and one of 8 at 16, three),
            # a decode step the ring's 16
            tokens = prompt + steps
            assert (prompt, padded) == (21, 24)
            return {
                "moe_rows_routed": 4 * 2 * tokens, "moe_rows_here": 4 * 2 * tokens,
                "swa_keys_in_window": 3 * sum(min(t + 1, 16) for t in range(tokens)), "swa_keys_multiplied": 3 * (8 * 8 * (3 + 3) + steps * 16),
            }

        return {
            "cfg": window_moe_toy.config_of(window_moe_toy.GROUP), "params": window_moe_toy.float32_params(window_moe_toy.GROUP),
            "family": window_family, "group": window_moe_toy.GROUP, "counted": counted, "silent": "mla_keys_multiplied",
        }

    def counted(prompt, padded, steps):
        tokens = prompt + steps
        return {
            "xdec_tokens_run": 1 + steps, "xdec_tokens_seen": tokens,
            "swa_keys_in_window": 2 * sum(min(t + 1, 8) for t in range(tokens)), "swa_keys_multiplied": 2 * (padded * 16 + steps * 8),
        }

    return {
        "cfg": hybrid_toy.config_of(hybrid_toy.GROUP), "params": hybrid_toy.float32_params(hybrid_toy.GROUP), "family": hybrid_family,
        "group": hybrid_toy.GROUP, "counted": counted, "silent": "dsa_keys_scored",
    }


@pytest.fixture(scope="module")
def executor(served):
    return JittedDecoder(served["cfg"], params=served["params"], slots=2, positions=POSITIONS, chunk_buckets=(8, 16))


def _reference(served, ids, positions):
    return served["family"].reference_logits(served["params"], served["group"], [ids], [positions], q_block=16)[0]


def test_the_plan_cuts_a_prompt_into_buckets(executor):
    assert executor.plan(16) == [(0, 16, 16)]
    assert executor.plan(13) == [(0, 13, 16)]
    assert executor.plan(21) == [(0, 16, 16), (16, 5, 8)]
    assert executor.plan(41) == [(0, 16, 16), (16, 16, 16), (32, 9, 16)]
    assert executor.plan(45) == [(0, 16, 16), (16, 16, 16), (32, 13, 16)]
    with pytest.raises(ValueError, match="do not fit"):
        executor.generate(np.ones(45, np.int32), 4)
    with pytest.raises(ValueError, match="multiples"):
        JittedDecoder(executor.config, params=executor.params, positions=POSITIONS, chunk_buckets=(12, 16))


def test_what_a_dispatch_costs_is_the_architectures(executor):
    """The seam: the executor takes ``init_cache``, ``prefill``, ``decode_step``,
    ``STATS`` and ``DISPATCH_TOKENS`` from the module of the configuration's
    class; a dearer dispatch makes the plan prefer fewer, larger chunks."""
    arch = executor.architecture
    assert arch.__name__ == type(executor.config).__module__ and set(executor.cache) == set(arch.init_cache(executor.config, 1, 8))
    wide = JittedDecoder(executor.config, params=executor.params, slots=1, positions=4096, chunk_buckets=(512, 2048, 2560))
    dear = [(0, 1100, 2048)]  # a dispatch at a chunk of 512's price: one chunk of 2,048, 948 of them padding
    cheap = [(0, 512, 512), (512, 512, 512), (1024, 76, 512)]
    assert wide.plan(1100) == (dear if arch.DISPATCH_TOKENS >= 512 else cheap)
    assert wide.plan(2561) == [(0, 2560, 2560), (2560, 1, 512)]


def test_generate_is_greedy_over_the_references_logits_and_moves_the_counters(model, served, executor):
    prompt = model["ids"][:21]
    before = devctr.snapshot()
    out = executor.generate(prompt, 6)
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert out["ids"].shape == (6,) and out["logits"].shape == (6, served["group"]["vocab_size"])
    assert np.array_equal(out["ids"], out["logits"].argmax(axis=1))
    whole = np.concatenate([prompt, out["ids"]])
    assert np.abs(out["logits"] - _reference(served, whole, list(range(20, 26)))).max() < 2e-5
    # what a request of 21 tokens (a chunk of 16 and one of 8) and 6 new ones implies
    want = {
        "gen_requests": 1, "gen_prompt_tokens": 21, "gen_prompt_tokens_padded": 24, "gen_prefill_dispatches": 2,
        "gen_new_tokens": 6, "gen_decode_steps": 5, "span_count.generate_prefill": 1, "span_count.generate_decode": 1,
        "gen_logit_rows": 6, "span_count.generate_keep": 1,
        **served["counted"](21, 24, 5), served["silent"]: 0,  # the other architecture's counters stay where they were
    }
    assert {k: moved[k] for k in want} == want
    assert 0 <= moved["gen_logit_rows_early"] <= 5  # the last row lands once the last step has run
    if served["family"] is family:
        exact = 3 * sum(min(t, 8) for t in range(1, 27))
        assert exact <= moved["dsa_keys_selected"] <= exact + 12  # ties with the k-th score are selected with it
    if served["family"] is shortcut_family:  # every routed pair is computed here (all 16 experts held) or costs nothing
        assert moved["moe_rows_here"] + moved["moe_rows_zero"] == moved["moe_rows_routed"] and 0 < moved["moe_rows_zero"] < moved["moe_rows_routed"]
    again = executor.generate(prompt, 6)  # the next slot, and then the first again
    third = executor.generate(prompt, 6)
    assert np.array_equal(again["logits"], out["logits"]) and np.array_equal(third["logits"], out["logits"])


def _stepwise(executor, prompt, new):
    """One request through the executor's two programs with nothing kept on
    the way: every row and id fetched after the last step and stacked."""
    slot = executor._turn % executor.slots
    executor._turn += 1
    for start, real, bucket in executor.plan(prompt.size):
        ids = np.zeros(bucket, np.int32)
        ids[:real] = prompt[start : start + real]
        token, logits, executor.cache, _ = executor._prefill(
            executor.params, ids, executor.cache, np.int32(slot), np.int32(start), np.int32(real), np.bool_(start + real == prompt.size)
        )
    rows, tokens = [logits], [token]
    for i in range(new - 1):
        token, logits, executor.cache, _ = executor._decode(
            executor.params, token, executor.cache, np.asarray([slot], np.int32), np.asarray([prompt.size + i], np.int32)
        )
        rows.append(logits)
        tokens.append(token)
    return np.stack(jax.device_get(rows)), np.concatenate(jax.device_get(tokens))


@pytest.mark.parametrize("new", [1, 6])
def test_generate_lands_each_row_in_one_host_array_bit_for_bit(model, served, executor, new):
    """The kept logits are written row by row into their final array while
    later steps run: what comes back is what stacking the programs' rows
    gives, and the counters say how many rows landed before the last step
    had run (never the last row itself)."""
    prompt = model["ids"][:21]
    before = devctr.snapshot()
    out = executor.generate(prompt, new)
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    rows, tokens = _stepwise(executor, prompt, new)
    assert out["logits"].dtype == np.float32 and out["logits"].flags.c_contiguous and out["logits"].shape == rows.shape
    assert np.array_equal(out["logits"], rows)
    assert out["ids"].dtype == np.int32 and np.array_equal(out["ids"], rows.argmax(axis=1)) and np.array_equal(out["ids"], tokens)
    assert moved["gen_logit_rows"] == new and 0 <= moved["gen_logit_rows_early"] <= new - 1
    assert moved["span_count.generate_keep"] == 1 and moved["d2h_bytes"] == rows.nbytes


@pytest.mark.parametrize("last_step_done, early", [(False, 5), (True, 0)])
def test_a_row_counts_early_while_the_last_step_has_not_finished(model, executor, monkeypatch, last_step_done, early):
    """Whether the last step had finished is asked of its row after each
    copy; on the CPU the answer is a race, so the test gives it."""
    monkeypatch.setattr(type(jnp.zeros(0)), "is_ready", lambda self: last_step_done)
    before = devctr.snapshot()
    executor.generate(model["ids"][:21], 6)
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert (moved["gen_logit_rows"], moved["gen_logit_rows_early"]) == (6, early)


def test_a_share_of_the_experts_counts_the_rows_it_computed(model):
    group = dict(GROUP, n_routed_experts=4, expert_offset=4)
    params = dict(model["params"], layers=[
        dict(lp, experts=jax.tree.map(lambda w: w[4:8], lp["experts"])) if "experts" in lp else lp for lp in model["params"]["layers"]
    ])
    share = JittedDecoder(config_of(group), params=params, slots=1, positions=POSITIONS, chunk_buckets=(8, 16))
    before = devctr.snapshot()
    out = share.generate(model["ids"][:30], 2)
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert moved["moe_rows_routed"] == 2 * 4 * 31 and 0 < moved["moe_rows_here"] < moved["moe_rows_routed"]
    whole = np.concatenate([model["ids"][:30], out["ids"]])
    ref = family.reference_logits(params, group, [whole], [[29, 30]], q_block=16)[0]
    assert np.abs(out["logits"] - ref).max() < 2e-5


@pytest.mark.parametrize("program", ["jit__prefill_chunk", "jit__decode_token"])
def test_the_generation_programs_keep_the_module_names_the_benchmark_reads(program, executor):
    """``benchmark/metrics/*.json`` find the prefill and the decode program
    in a device trace by these XLA module names."""
    one = np.zeros(1, np.int32)
    if program == "jit__prefill_chunk":  # whatever a prompt runs, its last chunk's further layers included, lowers under this name
        lowered = executor._prefill.lower(executor.params, np.zeros(8, np.int32), executor.cache, np.int32(0), np.int32(0), np.int32(8), np.bool_(True))
    else:
        lowered = executor._decode.lower(executor.params, one, executor.cache, one, one)
    assert re.search(r"module @(\w+)", lowered.as_text()).group(1) == program


def test_warm_runs_every_program_and_a_generation_then_compiles_nothing(model, served):
    fresh = JittedDecoder(served["cfg"], params=served["params"], slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    fresh.warm()
    before = devctr.compile_count()
    fresh.generate(model["ids"][:29], 5)
    assert devctr.compile_count() == before


# ----------------------------------------------------------------- the chat
def test_the_chat_tokenizes_generates_and_keeps_what_it_produced(served):
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat, decoder_preset

    model, GROUP = served, served["group"]
    presets = {
        family: "deepseek-ai/DeepSeek-V3.2-Exp", hybrid_family: "microsoft/Phi-4-mini-flash-reasoning", shortcut_family: "meituan-longcat/LongCat-Flash-Chat",
        window_family: "PowerInfer/SmallThinker-21BA3B-Instruct",
    }
    assert type(decoder_preset(presets[served["family"]])) is type(served["cfg"])
    chat = TPUDecoderChat(config=model["cfg"], params=model["params"], max_new_tokens=4, slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    before = devctr.snapshot()
    text = chat.__wrapped__([{"role": "user", "content": "What colour are bananas, then?"}])
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert re.fullmatch(r"t\d+( t\d+){3}", text)
    (kept,) = chat.recent_generations()
    assert kept["prompt_ids"] == family.token_ids("What colour are bananas, then?", GROUP["vocab_size"])
    assert [f"t{i}" for i in kept["ids"]] == text.split() and kept["logits"].shape == (4, GROUP["vocab_size"])
    assert moved["span_count.generate_tokenize"] == moved["span_count.generate_detokenize"] == 1 and moved["gen_prompt_tokens"] == 5
    with pytest.raises(ValueError, match="params"):
        TPUDecoderChat(config=model["cfg"])
    with pytest.raises(ValueError, match="unknown decoder model"):
        TPUDecoderChat("no-such-decoder", params=model["params"])


def test_the_answer_route_end_to_end(served):
    """REST -> retrieve -> prompt -> TPUDecoderChat -> response, as
    ``BaseRAGQuestionAnswerer`` and ``QARestServer`` stand."""
    model, GROUP = served, served["group"]
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm import prompts
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer
    from pathway_tpu.xpacks.llm.servers import QARestServer

    tiny = dataclasses.replace(MINILM_L6, layers=2, hidden=64, heads=4, mlp_dim=128, dtype=jnp.float32)
    docs = T(
        """
    d | data
    1 | apples grow on trees
    2 | bananas are yellow
    3 | the tpu multiplies matrices
    """
    ).select(data=pw.this.data, _metadata=pw.apply(lambda d: {"path": f"/docs/{d}.txt"}, pw.this.d))
    store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(embedder=TPUEncoderEmbedder(config=tiny), reserved_space=32))
    chat = TPUDecoderChat(config=model["cfg"], params=model["params"], max_new_tokens=3, slots=2, positions=POSITIONS, chunk_buckets=(8, 16))
    rag = BaseRAGQuestionAnswerer(chat, store, search_topk=2, prompt_template=lambda query, docs: prompts._docs_text(docs) + "\n" + query)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    server = QARestServer("127.0.0.1", port, rag)
    before = devctr.snapshot()
    thread = server.run(threaded=True)
    try:
        body = json.dumps({"prompt": "which fruit is yellow", "return_context_docs": True}).encode()
        answer, deadline = None, time.monotonic() + 120
        while answer is None and time.monotonic() < deadline:
            try:
                request = urllib.request.Request(f"http://127.0.0.1:{port}/v1/pw_ai_answer", data=body, headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(request, timeout=60) as response:
                    answer = json.loads(response.read())
            except OSError:
                time.sleep(0.2)
        assert answer is not None, "the server did not come up"
    finally:
        G.active_scheduler.stop()
        thread.join(timeout=30)
    assert re.fullmatch(r"t\d+ t\d+ t\d+", answer["response"]) and len(answer["context_docs"]) == 2
    (kept,) = chat.recent_generations()
    prompt = prompts._docs_text(answer["context_docs"]) + "\nwhich fruit is yellow"
    assert kept["prompt_ids"] == family.token_ids(prompt, GROUP["vocab_size"]) and kept["text"] == answer["response"]
    moved = {k: v - before.get(k, 0) for k, v in devctr.snapshot().items()}
    assert moved["gen_requests"] == 1 and moved["span_count.answer_prompt"] == 1


# --------------------------------------------------------------- the kernel
def _plain_selected_attention(qn, qr, kn, kr, v, sel, scale):
    s = (jnp.einsum("hqd,hkd->hqk", qn, kn, preferred_element_type=jnp.float32) + jnp.einsum("hqd,kd->hqk", qr, kr, preferred_element_type=jnp.float32)) * scale
    p = jax.nn.softmax(jnp.where(sel[None] != 0, s, -1e30), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def test_the_fused_attention_kernel_is_the_plain_softmax_over_the_selected_keys():
    """``ops/selected_attention.py`` in interpret mode (the TPU's prefill
    path; on the CPU the decoder runs the same loop in ``jax.numpy``): a
    chunk that starts inside its sequence, so that the last key blocks are
    past every query and skipped, in two query tiles of a key block's rows."""
    from pathway_tpu.ops.selected_attention import selected_attention

    rng = np.random.default_rng(0)
    H, C, L, bk, start = 2, 256, 512, 128, 100
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
    qn, qr, kn, kr, v = 0.12 * draw(H, C, 128), 0.12 * draw(H, C, 64), draw(H, L, 128), draw(L, 64), draw(H, L, 128)
    sel = (np.arange(L)[None, :] <= start + np.arange(C)[:, None]) & (rng.random((C, L)) < 0.3)
    sel[:, 0] = True  # every query selects a key
    blocks = (start + C + bk - 1) // bk
    assert blocks < L // bk
    kn, v = kn.at[:, blocks * bk :].set(jnp.nan), v.at[:, blocks * bk :].set(jnp.nan)  # never fetched
    sel[200:210, : 2 * bk] = False  # rows whose first tiles hold no selected key
    sel[200:210, 2 * bk] = True
    got = selected_attention(qn, qr, kn, kr, v, jnp.asarray(sel), jnp.int32(start), jnp.int32(C), block_k=bk, interpret=True)
    want = _plain_selected_attention(qn, qr, kn[:, : blocks * bk], kr[: blocks * bk], v[:, : blocks * bk], sel[:, : blocks * bk], 1.0)
    assert got.shape == (H, C, 128) and float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.03
    with pytest.raises(ValueError, match="multiple of the key block"):
        selected_attention(qn, qr, kn, kr, v, jnp.asarray(sel), jnp.int32(0), jnp.int32(C), block_k=384, interpret=True)
    with pytest.raises(ValueError, match="multiple of the query tile"):
        selected_attention(qn, qr, kn, kr, v, jnp.asarray(sel), jnp.int32(0), jnp.int32(C), block_q=96, block_k=bk, interpret=True)


@pytest.mark.parametrize(
    "heads, chunk, keys, block_q, block_k, start, length",
    [(2, 256, 512, 64, 128, 0, 256), (2, 256, 640, 64, 128, 100, 256), (4, 256, 640, 64, 128, 128, 100), (3, 128, 256, 32, 64, 40, 1)],
    ids=["at_0", "mid_block", "padding_tiles", "one_live_row"],
)
def test_the_fused_kernels_query_tiles_are_the_plain_softmax_and_a_tile_of_padding_is_zero(heads, chunk, keys, block_q, block_k, start, length):
    """The causal schedule in interpret mode: query tiles of ``block_q`` rows,
    each over the key blocks its last row can see, a chunk at the sequence's
    start or inside a block, every row real or the last tiles padding (a
    tile with a real row keeps its rows of padding; three heads are a step
    each, four one step).  Real rows are the plain softmax's; a tile of
    padding is exactly zero; nothing is non-finite; keys past the chunk's
    last are never fetched."""
    from pathway_tpu.ops.selected_attention import query_tiles, selected_attention

    rng = np.random.default_rng(start + length)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
    H, C, L, bk = heads, chunk, keys, block_k
    qn, qr, kn, kr, v = 0.12 * draw(H, C, 128), 0.12 * draw(H, C, 64), draw(H, L, 128), draw(L, 64), draw(H, L, 128)
    sel = (np.arange(L)[None, :] <= start + np.arange(C)[:, None]) & (rng.random((C, L)) < 0.3)
    sel[:, 0] = True
    seen = (start + C + bk - 1) // bk * bk
    kn, v = kn.at[:, seen:].set(jnp.nan), v.at[:, seen:].set(jnp.nan)
    got = selected_attention(qn, qr, kn, kr, v, jnp.asarray(sel), jnp.int32(start), jnp.int32(length), block_q=block_q, block_k=bk, interpret=True)
    want = _plain_selected_attention(qn, qr, kn[:, :seen], kr[:seen], v[:, :seen], sel[:, :seen], 1.0)
    rows, visits = query_tiles(start, length, C, block_q, bk)
    padding = np.repeat(np.asarray(visits) == 0, rows)
    got = np.asarray(got, np.float32)
    assert rows == block_q and padding.sum() == C - -(-length // rows) * rows
    assert np.isfinite(got).all() and (got[:, padding] == 0).all()
    assert float(np.abs(got[:, ~padding] - np.asarray(want)[:, ~padding]).max()) < 0.03


def test_the_query_tiles_of_the_cells_plan():
    """The schedule at the answer cells' chunk plan (a 6,335-token prompt:
    2,560 at 0, 2,048 at 2,560, 2,048 at 4,608 of which 1,727 are real):
    tiles of 512 visit 15, 30 and 46 key blocks of 512, tiles of 256 30, 60
    and 79; of the pairs they multiply, the causally visible pairs of real
    rows are 84 % and 90.6 %, where one tile a chunk multiplied 29.6 M, 68 %
    of them visible.  A tile is no larger than the key block or the chunk."""
    from pathway_tpu.ops.selected_attention import query_tiles

    plan = [(2560, 0, 2560), (2048, 2560, 2048), (2048, 4608, 1727)]
    visible = sum((start + 1 + start + length) * length // 2 for _, start, length in plan)
    assert visible == 20_069_280
    at_512 = [query_tiles(start, length, C, 512, 512) for C, start, length in plan]
    assert [np.asarray(v).tolist() for _, v in at_512] == [[1, 2, 3, 4, 5], [6, 7, 8, 9], [10, 11, 12, 13]]
    at_256 = [query_tiles(start, length, C, 256, 512) for C, start, length in plan]
    assert [int(np.sum(v)) for _, v in at_256] == [30, 60, 79] and np.asarray(at_256[2][1]).tolist()[-1] == 0
    pairs = lambda tiles: sum(rows * 512 * int(np.sum(v)) for rows, v in tiles)
    assert (pairs(at_512), pairs(at_256)) == (23_855_104, 22_151_168)
    assert round(100 * visible / pairs(at_512), 1) == 84.1 and round(100 * visible / pairs(at_256), 1) == 90.6
    assert sum(C * 512 * -(-(start + C) // 512) for C, start, _ in plan) == 29_622_272
    assert query_tiles(0, 16, 16, 256, 8)[0] == 8 and query_tiles(0, 4, 4, 256, 8)[0] == 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads,chunk", [(128, 512), (128, 2048), (64, 512), (64, 2048), (64, 2560)])
def test_the_fused_attention_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, heads, chunk):
    """128 heads of 128 + 64 (``models/decoder.py``'s) and 64
    (``models/shortcut_moe_decoder.py``'s, which calls the same kernel with
    the causal mask), 8,704 keys in blocks of 512, a prompt chunk of queries
    in the query tiles and heads a step chosen on the chip (PR 38), the
    chunk's start and its real rows as scalars: what the chip's compiler
    refuses (tiling, VMEM, the grid the schedule sets) shows here."""
    from pathway_tpu.ops.selected_attention import BLOCK_Q, selected_attention

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    H, L, bf16 = heads, 8704, jnp.bfloat16
    compiled = selected_attention.lower(
        shape((H, chunk, 128), bf16), shape((H, chunk, 64), bf16), shape((H, L, 128), bf16), shape((L, 64), bf16), shape((H, L, 128), bf16),
        shape((chunk, L), jnp.bool_), shape((), jnp.int32), shape((), jnp.int32), block_q=BLOCK_Q, block_k=512,
    ).compile()
    assert "selected_attention" in compiled.as_text()


@pytest.mark.parametrize("chunk", [512, 2048, 2560])
def test_the_fused_attention_kernel_compiles_for_a_v5e_at_glm_widths(one_chip, chunk):
    """GLM-4.7-Flash's latent attention through the same kernel with the
    causal mask: 20 heads (four a grid step) of 192 + 64, values of 256, 8,704
    keys, a prompt chunk of each bucket."""
    from pathway_tpu.ops.selected_attention import BLOCK_Q, selected_attention

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    H, L, bf16 = 20, 8704, jnp.bfloat16
    compiled = selected_attention.lower(
        shape((H, chunk, 192), bf16), shape((H, chunk, 64), bf16), shape((H, L, 192), bf16), shape((L, 64), bf16), shape((H, L, 256), bf16),
        shape((chunk, L), jnp.bool_), shape((), jnp.int32), shape((), jnp.int32), block_q=BLOCK_Q, block_k=512,
    ).compile()
    assert "selected_attention" in compiled.as_text()


@pytest.mark.parametrize("chunk,window", [(512, 4096), (2048, 4096), (2560, 4096), (2048, None), (2560, None)])
def test_the_grouped_attention_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, chunk, window):
    """``models/window_moe_decoder.py``'s form of the kernel: 28 query heads
    over 4 K/V heads of 128, seven query heads a grid step over one K/V
    head's block; a window layer's 4,096 keys of the ring and the chunk's own,
    a global layer's 16,384 positions; the chunk's start, its first key and
    its real rows as scalars."""
    from pathway_tpu.ops.selected_attention import BLOCK_Q, grouped_attention

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    L, bf16 = (window + chunk) if window else 16384, jnp.bfloat16
    scalar = shape((), jnp.int32)
    compiled = grouped_attention.lower(
        shape((28, chunk, 128), bf16), shape((4, L, 128), bf16), shape((4, L, 128), bf16), shape((chunk, L), jnp.bool_), scalar, scalar, scalar,
        window=window, block_q=BLOCK_Q, block_k=512,
    ).compile()
    assert "selected_attention" in compiled.as_text()


@pytest.mark.parametrize("chunk", [512, 2048, 2560])
def test_the_grouped_experts_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, chunk):
    """``ops/grouped_experts.py`` as ``models/window_moe_decoder.py`` calls it
    for a prompt chunk: 64 ReGLU experts of 2,560 -> 768 -> 2,560, one
    expert's three matrices a block (11.8 MB, twice, in the kernel's VMEM),
    the chunk's 6 pairs a token in as many tiles of 128 rows as they can
    fill, the schedule as scalars."""
    from pathway_tpu.models import decoder as mla_decoder
    from pathway_tpu.models.window_moe_decoder import SMALLTHINKER_21BA3B, _reglu
    from pathway_tpu.ops.grouped_experts import grouped_experts

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    tiles, bf16 = mla_decoder._tiles(chunk * 6, SMALLTHINKER_21BA3B), jnp.bfloat16
    experts = {"gate": shape((64, 2560, 768), bf16), "up": shape((64, 2560, 768), bf16), "down": shape((64, 768, 2560), bf16)}
    steps = shape((tiles,), jnp.int32)
    compiled = grouped_experts.lower(
        shape((tiles * 128, 2560), bf16), shape((tiles * 128, 1), jnp.float32), steps, steps, shape((1,), jnp.int32), experts,
        activation=_reglu, dtype=bf16, block=128, vmem_bytes=mla_decoder._GROUPED_VMEM,
    ).compile()
    assert "grouped_experts" in compiled.as_text()


@pytest.mark.parametrize("chunk", [512, 2560])
def test_the_fused_scan_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, chunk):
    """``ops/selective_scan.py`` (the second architecture's prefill; its own
    tests are ``test_hybrid_decoder.py``'s, this one stands here because one
    file loads libtpu): 5,120 channels of 16 states over a prompt chunk, the
    state of 1,024 channels on chip, ``B`` and ``C`` as scalars in SMEM."""
    from pathway_tpu.ops.selective_scan import selective_scan

    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
    compiled = selective_scan.lower(
        shape(chunk, 5120), shape(chunk, 5120), shape(16, 5120), shape(chunk, 16), shape(chunk, 16), shape(5120), shape(16, 5120)
    ).compile()
    assert "selective_scan" in compiled.as_text()
