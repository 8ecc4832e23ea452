"""The ``glm4_moe_lite`` family's counts against the configuration file's
arithmetic and the catalog's row, its work functions by hand, check kind
``answer_drafted`` against a skipped MTP layer, and the toy cell of
``rag_generator`` end to end on the CPU (``tests/test_glm_mtp_decoder.py``
holds the program against this family's reference, in tier-1)."""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark.families import glm4_moe_lite as family
from benchmark.tests.test_phi4flash_family import _rehearse  # a further toy cell of one traffic kind: PERF.md section 7 ii

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = "glm-toy.answer"
CELL = "glm-4.7-flash-ep1"
#: the catalog's row (model-configs guide, architectures.jsonl, GLM-4.7-Flash), its ``config`` whole
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20,
    "n_group": 1, "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47, "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}
REDUCED = {"num_hidden_layers": 6}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CELL}.json")) as f:
        return json.load(f)


def test_parameters_are_the_bytes_on_chip_arithmetic(config):
    c = family.parameter_counts(config["generator"])
    h = 2048
    attention = h * 768 + 768 + 768 * 20 * 256 + h * 576 + 512 + 512 * 20 * 448 + 20 * 256 * h
    assert c["mla"] == attention == 21_759_232 and (c["router"], c["expert"]) == (131_072 + 64, 9_437_184)
    assert c["routed_layer"] == 21_759_232 + 4_096 + 131_136 + 9_437_184 + 64 * 9_437_184 == 635_311_424
    assert c["dense_layer"] == 21_759_232 + 4_096 + 3 * h * 10240 == 84_677_888
    assert c["mtp_layer"] == 635_311_424 + 3 * h + 2 * h * h == 643_706_176
    assert c["vocabulary"] == 2 * 154_880 * h + h == 634_390_528
    assert c["total"] == 84_677_888 + 5 * 635_311_424 + 643_706_176 + 634_390_528 == 4_539_331_712
    for number in ("635,311,424", "84,677,888", "643,706,176", "634,390,528", "4,539,331,712"):
        assert number in config["bytes_on_chip"]


def test_the_work_by_hand(config):
    g = config["generator"]
    h, E = 2048, 64
    mla = h * 768 + 768 * 20 * 256 + h * 576 + 512 * 20 * 448 + 20 * 256 * h
    routed = mla + h * E + (1 + 4) * 3 * h * 1536
    main = mla + 3 * h * 10240 + 5 * routed
    mtp = 2 * h * h + routed
    pair, head = 2 * 20 * (192 + 64 + 256), 2 * 154_880 * h
    n = 3000
    assert family.attention_core_flops(g, n) == 7 * pair * n * (n + 1) / 2
    assert family.prompt_flops(g, n) == 2 * n * (main + mtp) + family.attention_core_flops(g, n) + 2 * head
    assert family.step_flops(g, n) == 2 * 2 * (main + mtp) + pair * 7 * (2 * n + 1) + 3 * head
    assert family.flops(g, [(n, 2)]) == family.prompt_flops(g, n) + family.step_flops(g, n + 1) + family.step_flops(g, n + 2)
    distinct = 4 + 4 * 60 / 64
    assert family.step_experts(g) == distinct == 7.75
    step_routed = mla + h * E + (1 + distinct) * 3 * h * 1536
    weights = mla + 3 * h * 10240 + 5 * step_routed + 2 * h * h + step_routed + 2 * 154_880 * h
    assert family.decode_bytes(g, n) == 2 * (weights + 7 * (n + 1) * 576)
    assert round(family.decode_bytes(g, 4000) / 1e9, 2) == 2.74  # 7.75 distinct experts a layer and the latent rows of 4,000 keys
    # the MTP layer and the draft's head are about 31 % of a step's bytes
    assert 0.29 < 2 * (2 * h * h + step_routed + 154_880 * h) / family.decode_bytes(g, 4000) < 0.33


def test_the_file_holds_every_published_key_but_the_reduced(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == CELL)
    assert entry["reduced"] == list(config["reduced"]) == [*REDUCED, "filler_rows"] and entry["source"] == config["source"]
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert config["published"] == {k: CATALOG[k] for k in REDUCED}
    g = config["generator"]
    assert all(g[k] == config[k] for k in g if k in config)
    assert (g["experts_held"], g["expert_offset"], g["vocab_size"], g["num_nextn_predict_layers"]) == (64, 0, 154880, 1)
    gen = config["program"]["generator"]
    assert gen["share"] == REDUCED and (gen["slots"], gen["positions"], gen["max_new_tokens"]) == (8, 8704, 128)
    assert config["program"]["search_topk"] == 8 and len(config["assumed"]) >= 10
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v32-exp-ep16.json")) as f:
        other = json.load(f)
    for shared in ("embedder", "slab", "filler"):  # the retrieve half is the other answer cells', verbatim
        assert config[shared] == other[shared]
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{CELL}.answer.json")) as f:
        wl = json.load(f)
    assert wl["check"]["kind"] == "answer_drafted" and set(wl["limits"]) == {"logit_gap", "draft_gap", "context_gap", "wrong"}
    # the longest prompt a draw allows (8 chunks of 500 words, a question of 60, the template) fits with the answer's 128 tokens
    assert 8 * wl["docs"]["words"]["max"] + wl["questions"]["words"]["max"] + 30 <= gen["positions"] - gen["max_new_tokens"]


def test_built_differs_is_empty_for_the_presets_cut_and_names_what_differs(config):
    from pathway_tpu.xpacks.llm.llms import decoder_preset

    preset = decoder_preset(config["program"]["generator"]["preset"])
    built = dataclasses.replace(preset, **config["program"]["generator"]["share"])
    assert family.built_differs(config["generator"], built) == {}
    assert family.built_differs(config["generator"], dataclasses.replace(built, index_topk=2048)) == {"index_topk": (2048, 0)}
    assert set(family.built_differs(config["generator"], preset)) == {"num_hidden_layers"}


def _toy():
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "glm-toy.json")) as f:
        return json.load(f)


def test_answer_drafted_catches_a_skipped_mtp_layer_at_toy_size(monkeypatch):
    """The toy cell with the program's MTP layer left out (the draft taken
    from the main model's own row): the emitted ids and their rows are what
    they were, so ``answer``'s numbers pass; ``draft_gap`` does not."""
    from tests.test_glm_mtp_decoder import skip_mtp_layer

    skip_mtp_layer(monkeypatch)
    line = _rehearse(TOY, 2**31 + 7, trace=False)
    compared = line["compared"]
    assert line["correct"] is False and compared["draft_gap"]["value"] > compared["draft_gap"]["limit"]
    assert all(compared[n]["value"] <= compared[n]["limit"] for n in ("logit_gap", "context_gap", "wrong"))


def test_the_toy_cell_plays_and_reports_its_metrics():
    line = _rehearse(TOY, 2**31 + 5, trace=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8, line
    assert set(line["compared"]) == {"logit_gap", "draft_gap", "context_gap", "wrong"}
    assert {"compiles_in_window.answer", "generate_prefill_ms", "generate_decode_ms_per_token", "moe_rows_here_pct", "tokens_per_decode_step"} <= set(line["metrics"])
    assert not {"dsa_selected_pct", "window_keys_useful_pct", "mla_keys_useful_pct", "expert_rows_useful_pct"} & set(line["metrics"])
    assert line["metrics"]["compiles_in_window.answer"]["value"] == 0 and 1.0 <= line["metrics"]["tokens_per_decode_step"]["value"] < 1.1
    assert 25 < line["metrics"]["moe_rows_here_pct"]["value"] < 75  # 8 of the router's 16 experts are held here
    untraced = _rehearse(TOY, 2**31 + 6, trace=False, control="fp8")
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"setup_s", "retrieve_p50_ms"}
    limits = untraced["compared"]
    for name in ("logit_gap", "draft_gap"):
        assert untraced["control"][name] > limits[name]["limit"] > limits[name]["value"], name


def test_the_new_declaration_reads_nothing_from_a_program_without_the_counter():
    """On the parent (``device_counters.snapshot()`` has no
    ``mtp_drafts_accepted``) the ratio returns nothing and does not raise;
    on this program it is one plus the share of drafts kept."""
    from benchmark.readers import counter_ratio

    with open(os.path.join(ROOT, "benchmark", "metrics", "tokens_per_decode_step.json")) as f:
        decl = json.load(f)
    old = {"open": {"gen_decode_steps": 10}, "close": {"gen_decode_steps": 137}}
    assert counter_ratio.read(decl, {"counters": old, "window": {}}) is None
    new = {"open": {"gen_decode_steps": 10, "mtp_drafts_accepted": 1}, "close": {"gen_decode_steps": 110, "mtp_drafts_accepted": 26}}
    assert counter_ratio.read(decl, {"counters": new, "window": {}}) == pytest.approx(1.25)


def test_the_draw_is_the_seeds_and_the_reference_is_padding_blind():
    import jax

    g = _toy()["generator"]
    a, b = family.make_params(g, 5), family.make_params(g, 5)
    assert all(np.array_equal(np.asarray(p, np.float32), np.asarray(q, np.float32)) for p, q in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert a["mtp"]["eh_proj"].shape == (128, 64) and a["layers"][1]["experts"]["gate"].shape == (8, 64, 32) and "idx_q" not in a["layers"][0]
    ids = np.random.default_rng(1).integers(1000, g["vocab_size"], size=40).astype(np.int32)
    main, drafts = family.reference_draft_logits(a, g, [ids], [[30, 39]], [[30, 38]], q_block=16)
    again, again_drafts = family.reference_draft_logits(a, g, [ids], [[30, 39]], [[30, 38]], q_block=16, pad_to=64)
    assert np.abs(again[0] - main[0]).max() < 1e-4 and np.abs(again_drafts[0] - drafts[0]).max() < 1e-4
    control = family.reference_logits(a, g, [ids], [[30, 39]], precision="fp8", q_block=16)[0]
    assert np.abs(control - main[0]).max() / main[0].std() > 0.1
    with pytest.raises(ValueError, match="unknown precision"):
        family.reference_logits(a, g, [ids], [[30]], precision="int4")
