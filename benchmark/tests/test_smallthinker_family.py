"""The ``smallthinker`` family's work functions against a hand count at the
published numbers, its configuration file against the catalog's row, its
reference against its fp8 control, and its toy cell of ``rag_generator`` end
to end on the CPU (``tests/test_window_moe_decoder.py`` holds the program
against this family's reference, in tier-1)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark.families import smallthinker as family
from benchmark.tests.test_phi4flash_family import _rehearse  # a further toy cell of one traffic kind: PERF.md section 7 ii

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = "smallthinker-toy.answer"
LAYOUT = [0, 1, 1, 1] * 13
#: the catalog's row (model-configs guide, architectures.jsonl, SmallThinker-21BA3B-Instruct), its ``config`` whole
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384, "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": LAYOUT, "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 8}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "smallthinker-21ba3b-ep1.json")) as f:
        return json.load(f)


def test_parameters_by_hand(config):
    c = family.parameter_counts(config["generator"])
    h = 2560
    assert c["attention"] == h * 3584 + 2 * h * 512 + 3584 * h == 20_971_520
    assert (c["router"], c["expert"]) == (h * 64, 3 * h * 768) == (163_840, 5_898_240)
    assert c["layer_outside_experts"] == 20_971_520 + 163_840 + 2 * h == 21_140_480  # the catalog's "about 21M"
    assert c["layer"] == 21_140_480 + 64 * 5_898_240 == 398_627_840  # 0.797 GB in bfloat16
    assert c["vocabulary"] + h == 2 * 151_936 * h + h == 777_914_880
    assert c["total"] == 8 * 398_627_840 + 777_914_880 == 3_966_937_600  # 7.93 GB


def test_flops_and_decode_bytes_by_hand(config):
    g = config["generator"]
    h = 2560
    per_token = 2 * 8 * (20_971_520 + 163_840 + 6 * 5_898_240)
    assert family.linear_flops_per_token(g) == per_token
    pair = 2 * 28 * 256
    assert family.token_flops(g, 1000) == per_token + pair * 8 * 1000
    assert family.token_flops(g, 13000) == per_token + pair * (2 * 13000 + 6 * 4096)  # the window bounds six layers of eight
    n = 13000
    causal, in_window = n * (n + 1) / 2, 4096 * 4097 / 2 + (n - 4096) * 4096
    assert (causal, in_window) == (84_506_500, 44_861_440)  # 84.5M and 44.9M pairs
    core = pair * (2 * causal + 6 * in_window)
    assert family.attention_core_flops(g, n) == core and round(core / 1e12, 2) == 6.28
    assert round(n * per_token / 1e12, 2) == 11.76  # experts 7.36, projections 4.36, the router 0.03
    head = 2 * 151_936 * h
    assert family.prompt_flops(g, n) == n * per_token + core + head and round(family.prompt_flops(g, n) / 1e12, 1) == 18.0
    assert family.prompt_flops(g, 3000) == pytest.approx(sum(family.token_flops(g, t) for t in range(1, 3001)) + head, rel=1e-12)
    assert family.prompt_flops(g, 5000) == pytest.approx(sum(family.token_flops(g, t) for t in range(1, 5001)) + head, rel=1e-12)
    steps = sum(family.token_flops(g, 3000 + i) + head for i in range(1, 32))
    assert family.flops(g, [(3000, 31)]) == pytest.approx(family.prompt_flops(g, 3000) + steps, rel=1e-12)
    weights = 2 * (per_token / 2 + 151_936 * h)  # every matrix the token touches, the head among them, two bytes each
    assert family.decode_bytes(g, n) == weights + 2 * (2 * n + 6 * 4096) * 2 * 4 * 128
    assert round(family.decode_bytes(g, n) / 1e9, 2) == 1.79  # 2.18 ms a step at 819 GB/s


def test_the_file_holds_every_published_key_but_the_reduced(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == list(config["reduced"]) == [*REDUCED, "filler_rows"]
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    assert entry["source"] == config["source"]
    # the generator's group says what the top level says; every expert and the whole vocabulary are held here
    g = config["generator"]
    assert all(g[k] == config[k] for k in g if k in config)
    assert (g["experts_held"], g["expert_offset"], g["vocab_size"]) == (64, 0, 151936)
    gen = config["program"]["generator"]
    assert gen["share"] == REDUCED and (gen["slots"], gen["positions"], gen["max_new_tokens"]) == (4, 16384, 32)
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v32-exp-ep16.json")) as f:
        other = json.load(f)
    for shared in ("embedder", "slab", "filler"):  # the retrieve half is the other answer cells', verbatim
        assert config[shared] == other[shared]
    assert config["program"]["embedder"] == other["program"]["embedder"] and config["program"]["splitter"] == other["program"]["splitter"]
    assert config["program"]["search_topk"] == 32 and len(config["assumed"]) >= 10
    # the longest prompt a draw allows (32 chunks of 500 words, a question of 60, the template) fits with the answer's 32 tokens
    with open(os.path.join(ROOT, "benchmark", "workloads", "smallthinker-21ba3b-ep1.answer.json")) as f:
        wl = json.load(f)
    assert 32 * wl["docs"]["words"]["max"] + wl["questions"]["words"]["max"] + 30 <= gen["positions"] - gen["max_new_tokens"]


def test_built_differs_is_empty_for_the_presets_cut_and_names_what_differs(config):
    import dataclasses

    from pathway_tpu.xpacks.llm.llms import decoder_preset

    preset = decoder_preset(config["program"]["generator"]["preset"])
    built = dataclasses.replace(preset, **config["program"]["generator"]["share"])
    assert family.built_differs(config["generator"], built) == {}
    assert family.built_differs(config["generator"], dataclasses.replace(built, sliding_window_size=2048)) == {"sliding_window_size": (2048, 4096)}
    assert set(family.built_differs(config["generator"], preset)) == {"num_hidden_layers"}


def _toy_group():
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "smallthinker-toy.json")) as f:
        return json.load(f)["generator"]


def test_the_draw_is_the_seeds_and_a_share_is_the_uncut_draws_experts():
    import jax

    g = _toy_group()
    a, b, c = family.make_params(g, 5), family.make_params(g, 5), family.make_params(g, 6)
    same = lambda x, y: all(np.array_equal(np.asarray(p, np.float32), np.asarray(q, np.float32)) for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y)))
    assert same(a, b) and not same(a, c)
    uncut = family.make_params(dict(g, experts_held=8, expert_offset=0), 5)
    assert a["layers"][1]["router"].shape == (64, 8) and a["layers"][1]["experts"]["gate"].shape == (4, 64, 32)
    assert same(a["layers"][1]["experts"], jax.tree.map(lambda w: w[4:8], uncut["layers"][1]["experts"]))
    assert a["layers"][0]["k"].shape == (64, 2 * 16) and a["head"].shape == (64, 2048)
    assert 0.015 < float(np.asarray(a["embed"], np.float32).std()) < 0.025


def test_the_fp8_control_is_farther_from_the_reference_than_padding():
    g = _toy_group()
    params = family.make_params(g, 3)
    ids = np.random.default_rng(1).integers(1000, g["vocab_size"], size=96).astype(np.int32)
    at = [[63, 95]]
    truth = family.reference_logits(params, g, [ids], at, q_block=32)[0]
    control = family.reference_logits(params, g, [ids], at, precision="fp8", q_block=32)[0]
    again = family.reference_logits(params, g, [ids, ids[:80]], [at[0], [63]], q_block=32, pad_to=128)
    assert np.abs(again[0] - truth).max() < 1e-4 and np.abs(again[1][0] - truth[0]).max() < 1e-4  # padding changes nothing
    assert truth.shape == (2, g["vocab_size"]) and np.abs(control - truth).max() / truth.std() > 0.3
    with pytest.raises(ValueError, match="unknown precision"):
        family.reference_logits(params, g, [ids], at, precision="int4")


def test_the_window_bounds_the_references_attention():
    """A sequence longer than the window: the reference's logits at its end
    move when the window is widened past it (so the window is applied), and
    not when a token the window has left behind is changed in a window
    layer's only reach (a model of window layers alone)."""
    g = dict(_toy_group(), rope_layout=[1, 1, 1, 1], sliding_window_layout=[1, 1, 1, 1])
    params = family.make_params(g, 3)
    ids = np.random.default_rng(2).integers(1000, g["vocab_size"], size=140).astype(np.int32)
    windowed = family.reference_logits(params, g, [ids], [[139]], q_block=32)[0]
    wide = family.reference_logits(params, dict(g, sliding_window_size=256), [ids], [[139]], q_block=32)[0]
    assert np.abs(windowed - wide).max() > 1e-3
    changed = ids.copy()
    changed[0] = 1001 if ids[0] != 1001 else 1002  # 4 layers of a 32-key window reach back 4 x 31 = 124 < 139 positions
    moved = family.reference_logits(params, g, [changed], [[139]], q_block=32)[0]
    assert np.abs(moved - windowed).max() == 0


def test_the_toy_cell_plays_and_reports_its_metrics():
    line = _rehearse(TOY, 2**31 + 5, trace=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8, line
    assert set(line["compared"]) == {"logit_gap", "context_gap", "wrong"}
    assert {
        "compiles_in_window.answer", "generate_prefill_ms", "generate_decode_ms_per_token", "prompt_useful_token_pct", "moe_rows_here_pct",
        "window_keys_useful_pct", "expert_rows_useful_pct",
    } <= set(line["metrics"])
    silent = {"dsa_selected_pct", "cross_decoder_tokens_pct", "mla_keys_useful_pct"}
    assert not silent & set(line["metrics"])  # the other architectures' counters did not move
    assert line["metrics"]["moe_zero_rows_pct"]["value"] == 0  # this router has no zero-computation experts
    assert line["metrics"]["compiles_in_window.answer"]["value"] == 0
    # 4 of the router's 8 experts are held here; the loop's blocks of 8 rows are partly padding
    assert 25 < line["metrics"]["moe_rows_here_pct"]["value"] < 75 and 0 < line["metrics"]["expert_rows_useful_pct"]["value"] <= 100
    assert 20 < line["metrics"]["window_keys_useful_pct"]["value"] < 100
    untraced = _rehearse(TOY, 2**31 + 6, trace=False, control="fp8")
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"setup_s", "retrieve_p50_ms"}
    limits = untraced["compared"]
    assert untraced["control"]["logit_gap"] > limits["logit_gap"]["limit"] > limits["logit_gap"]["value"]


def test_the_new_declaration_reads_nothing_from_a_program_without_the_counter():
    """On the parent of the PR that added it (``device_counters.snapshot()``
    has no ``moe_rows_multiplied``) the ratio returns nothing and does not
    raise."""
    from benchmark.readers import counter_ratio

    with open(os.path.join(ROOT, "benchmark", "metrics", "expert_rows_useful_pct.json")) as f:
        decl = json.load(f)
    old = {"open": {"moe_rows_here": 10}, "close": {"moe_rows_here": 250}}
    assert counter_ratio.read(decl, {"counters": old, "window": {}}) is None
    new = {"open": {}, "close": {"moe_rows_here": 240, "moe_rows_multiplied": 320}}
    assert counter_ratio.read(decl, {"counters": new, "window": {}}) == pytest.approx(75.0)
