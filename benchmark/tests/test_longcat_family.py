"""The ``longcat_flash`` family's work functions against a hand count at the
published numbers, its configuration file against the catalog's row, its
reference against its fp8 control, and its toy cell of ``rag_generator`` end
to end on the CPU (``tests/test_shortcut_moe_decoder.py`` holds the program
against this family's reference, in tier-1)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark.families import longcat_flash as family
from benchmark.tests.test_phi4flash_family import _rehearse  # a further toy cell of one traffic kind: PERF.md section 7 ii

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = "longcat-toy.answer"
#: the catalog's row (model-configs guide, architectures.jsonl, LongCat-Flash-Chat), its ``config`` whole
CATALOG = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "longcat-flash-chat-ep32.json")) as f:
        return json.load(f)


def test_parameters_by_hand(config):
    c = family.parameter_counts(config["generator"])
    h = 6144
    attention = h * 1536 + 1536 * 64 * 192 + h * 576 + 512 * 64 * 256 + 64 * 128 * h + 1536 + 512
    assert (c["attention"], c["dense_mlp"], c["router"], c["expert"]) == (attention, 3 * h * 12288, h * 768 + 768, 3 * h * 2048)
    assert (c["attention"], c["dense_mlp"], c["router"], c["expert"]) == (90_572_800, 226_492_416, 4_719_360, 37_748_736)
    assert c["layer_outside_experts"] == 2 * 90_572_800 + 2 * 226_492_416 + 4_719_360 + 4 * h == 638_874_368  # the catalog's "about 637M"
    assert c["layer"] == 638_874_368 + 16 * 37_748_736 == 1_242_854_144  # 2.486 GB in bfloat16
    assert c["total"] == 4 * 1_242_854_144 + 2 * 16384 * h + h == 5_172_749_312  # 10.35 GB


def test_flops_and_decode_bytes_by_hand(config):
    g = config["generator"]
    h = 6144
    attention, dense, expert = 90_572_800 - 2048, 226_492_416, 37_748_736
    # 12 choices over 768 outputs, 16 of them held here: a quarter of an expert a token
    per_token = 2 * 4 * (2 * attention + 2 * dense + h * 768 + 0.25 * expert)
    assert family.linear_flops_per_token(g) == per_token and round(per_token / 1e9, 2) == 5.19
    pair = 2 * 64 * (192 + 128)
    assert family.token_flops(g, 1000) == per_token + 8 * pair * 1000
    n = 6200
    core = 8 * pair * n * (n + 1) / 2
    assert family.attention_core_flops(g, n) == core and round(core / 1e12, 2) == 6.3  # 19.2M visible pairs x 64 heads x 640 x 8 sublayers
    head = 2 * 16384 * h
    assert family.prompt_flops(g, n) == n * per_token + core + head
    assert family.prompt_flops(g, 3000) == pytest.approx(sum(family.token_flops(g, t) for t in range(1, 3001)) + head, rel=1e-12)
    assert round(family.prompt_flops(g, n) / 1e12, 1) == 38.5  # ISSUE 37's "some 41" counts the keys and values expanded again every chunk
    steps = sum(family.token_flops(g, 3000 + i) + head for i in range(1, 32))
    assert family.flops(g, [(3000, 31)]) == pytest.approx(family.prompt_flops(g, 3000) + steps, rel=1e-12)
    assert family.flops(g, [(3000, 31), (3000, 31)]) == pytest.approx(2 * family.flops(g, [(3000, 31)]), rel=1e-12)
    weights = 2 * (per_token / 2 + 16384 * h)  # every matrix the token touches, the head's slice among them, two bytes each
    assert family.decode_bytes(g, n) == weights + 2 * 8 * n * 576
    assert round(family.decode_bytes(g, n) / 1e9, 2) == 5.44  # 6.65 ms a step at 819 GB/s


def test_the_file_holds_every_published_key_but_the_reduced(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == list(config["reduced"]) == [*REDUCED, "filler_rows"]
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the copy above is the row's
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        assert next(r for r in rows if r["source_url"] == entry["source"] == config["source"])["config"] == CATALOG
    # the generator's group says what the top level says, and what it holds of the published counts
    g = config["generator"]
    assert all(g[k] == config[k] for k in g if k in config)
    assert (g["n_routed_experts_published"], g["vocab_size_published"], g["expert_offset"]) == (512, 131072, 0)
    share = config["program"]["generator"]["share"]
    assert share == {"num_layers": 4, "experts_held": 16, "expert_offset": 0, "vocab_held": 16384}
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v32-exp-ep16.json")) as f:
        other = json.load(f)
    for shared in ("embedder", "slab", "filler"):  # the retrieve half is the other answer cells', verbatim
        assert config[shared] == other[shared]
    assert config["program"]["embedder"] == other["program"]["embedder"] and config["program"]["splitter"] == other["program"]["splitter"]
    assert config["program"]["search_topk"] == other["program"]["search_topk"] == 16 and len(config["assumed"]) >= 10


def test_built_differs_is_empty_for_the_presets_share_and_names_what_differs(config):
    import dataclasses

    from pathway_tpu.xpacks.llm.llms import decoder_preset

    preset = decoder_preset(config["program"]["generator"]["preset"])
    built = dataclasses.replace(preset, **config["program"]["generator"]["share"])
    assert family.built_differs(config["generator"], built) == {}
    assert family.built_differs(config["generator"], dataclasses.replace(built, moe_topk=8)) == {"moe_topk": (8, 12)}
    assert set(family.built_differs(config["generator"], preset)) == {"num_layers", "n_routed_experts", "vocab_size"}


def _toy_group():
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "longcat-toy.json")) as f:
        return json.load(f)["generator"]


def test_the_draw_is_the_seeds_and_a_share_is_the_uncut_draws_rows():
    import jax

    g = _toy_group()
    a, b, c = family.make_params(g, 5), family.make_params(g, 5), family.make_params(g, 6)
    same = lambda x, y: all(np.array_equal(np.asarray(p, np.float32), np.asarray(q, np.float32)) for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y)))
    assert same(a, b) and not same(a, c)
    uncut = family.make_params(dict(g, n_routed_experts=16, expert_offset=0, vocab_size=4096), 5)
    assert a["layers"][1]["router"].shape == (64, 24) and a["layers"][1]["experts"]["gate"].shape == (4, 64, 32)
    assert same(a["layers"][1]["experts"], jax.tree.map(lambda w: w[4:8], uncut["layers"][1]["experts"]))
    assert np.array_equal(np.asarray(a["embed"], np.float32), np.asarray(uncut["embed"][:2048], np.float32))
    assert float(np.abs(np.asarray(a["layers"][0]["router_bias"])).max()) == 0 and 0.015 < float(np.asarray(a["embed"], np.float32).std()) < 0.025


def test_the_fp8_control_is_farther_from_the_reference_than_bfloat16():
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


def test_the_tokenizer_is_the_programs():
    from pathway_tpu.models.tokenizer import HashTokenizer

    text = "q0000001 what of w9? Doc0000123 w17 mixed-CASE, punctuation! 007"
    assert HashTokenizer(16384).word_ids(text) == family.token_ids(text, 16384)
    assert min(family.token_ids(text, 16384)) >= 1000 and max(family.token_ids(text, 16384)) < 16384


def test_the_toy_cell_plays_and_reports_its_metrics():
    line = _rehearse(TOY, 2**31 + 5, trace=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8, line
    assert set(line["compared"]) == {"logit_gap", "context_gap", "wrong"}
    assert {
        "compiles_in_window.answer", "generate_prefill_ms", "generate_decode_ms_per_token", "prompt_useful_token_pct", "moe_rows_here_pct",
        "moe_zero_rows_pct", "mla_keys_useful_pct",
    } <= set(line["metrics"])
    assert "dsa_selected_pct" not in line["metrics"] and "cross_decoder_tokens_pct" not in line["metrics"]  # the other architectures' counters did not move
    assert line["metrics"]["compiles_in_window.answer"]["value"] == 0
    # 4 of the router's 24 outputs are held here and 8 compute nothing
    assert 5 < line["metrics"]["moe_rows_here_pct"]["value"] < 35 and 15 < line["metrics"]["moe_zero_rows_pct"]["value"] < 55
    assert 20 < line["metrics"]["mla_keys_useful_pct"]["value"] < 100
    untraced = _rehearse(TOY, 2**31 + 6, trace=False, control="fp8")
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"setup_s", "retrieve_p50_ms"}
    limits = untraced["compared"]
    assert untraced["control"]["logit_gap"] > limits["logit_gap"]["limit"] > limits["logit_gap"]["value"]


def test_the_new_declarations_read_nothing_from_a_program_without_the_counters():
    """On the parent of the PR that added them (``device_counters.snapshot()``
    has no such key) the two ratios return nothing and do not raise."""
    from benchmark.readers import counter_ratio

    for name in ("moe_zero_rows_pct", "mla_keys_useful_pct"):
        with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
            decl = json.load(f)
        old = {"open": {"moe_rows_routed": 10}, "close": {"moe_rows_routed": 250}}
        assert counter_ratio.read(decl, {"counters": old, "window": {}}) is None
        new = {"open": {}, "close": {"moe_rows_routed": 240, "moe_rows_zero": 80, "mla_keys_visible": 30, "mla_keys_multiplied": 40}}
        assert counter_ratio.read(decl, {"counters": new, "window": {}}) == pytest.approx({"moe_zero_rows_pct": 100 / 3, "mla_keys_useful_pct": 75.0}[name])
