"""The ``deepseek_v32`` family's work functions against a hand count at the
published numbers, its tokenizer and template against the program's, and its
plain reference against the program's decoder in float32 (``tests/
test_decoder.py`` holds the rest of that comparison, in tier-1)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark.families import deepseek_v32 as family
from benchmark.traffic.answer_open import prompt_text

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v32-exp-ep16.json")) as f:
        return json.load(f)


def test_parameters_by_hand(config):
    c = family.parameter_counts(config["generator"])
    h = 7168
    mla = h * 1536 + 1536 * 128 * 192 + h * 576 + 512 * 128 * 256 + 128 * 128 * h
    indexer = 1536 * 64 * 128 + h * 128 + h * 64
    expert = 3 * h * 2048
    assert (c["mla"], c["indexer"], c["expert"]) == (mla, indexer, expert) == (187_105_280, 13_959_168, 44_040_192)
    routed = mla + indexer + h * 256 + 17 * expert
    dense = mla + indexer + 3 * h * 18432
    assert c["total"] == dense + 4 * routed + 2 * 16160 * h == 4_635_426_816  # 4,635M: 9.27 GB in bfloat16


def test_flops_and_decode_bytes_by_hand(config):
    g = config["generator"]
    h = 7168
    attention = 187_105_280 + 13_959_168
    per_token = 2 * (5 * attention + 3 * h * 18432 + 4 * (h * 256 + 1.5 * 44_040_192))  # 8 x 16 / 256 = half an expert a token
    assert family.linear_flops_per_token(g) == per_token and round(per_token / 1e9, 2) == 3.35
    assert family.token_flops(g, 1000) == per_token + 5 * (16_384 * 1000 + 81_920 * 1000)
    assert family.token_flops(g, 6500) == per_token + 5 * (16_384 * 6500 + 81_920 * 2048)
    head = 2 * 16160 * h
    prompt = sum(family.token_flops(g, t) for t in range(1, 3001)) + head
    assert family.prompt_flops(g, 3000) == pytest.approx(prompt, rel=1e-12)
    steps = sum(family.token_flops(g, 3000 + i) + head for i in range(1, 32))
    assert family.flops(g, [(3000, 31)]) == pytest.approx(prompt + steps, rel=1e-12)
    assert family.flops(g, [(3000, 31), (3000, 31)]) == pytest.approx(2 * (prompt + steps), rel=1e-12)
    assert round(family.prompt_flops(g, 6500) / 1e12, 1) == 28.1
    weights = 2 * (per_token / 2 + 16160 * h)  # every weight the token touches, the head's slice among them, two bytes each
    assert round(weights / 1e9, 2) == 3.58
    assert family.decode_bytes(g, 6500) == weights + 2 * 5 * (6500 * 128 + 2048 * 576)
    assert round(family.decode_bytes(g, 6500) / 1e9, 2) == 3.60  # 4.4 ms a step at 819 GB/s


def test_the_file_holds_the_published_keys_but_the_reduced(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row["source_url"] == entry["source"]:
                published = row["config"]
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(entry["reduced"]) - {"filler_rows"} == set(config["published"])
    assert {k: config["published"][k] for k in differs} == {k: published[k] for k in differs}
    # the generator's group says what the top level says, and what it holds of the published counts
    g = config["generator"]
    assert all(g[k] == config[k] for k in g if k in config)
    assert (g["n_routed_experts_published"], g["vocab_size_published"]) == (published["n_routed_experts"], published["vocab_size"])
    assert config["embedder"] == json.load(open(os.path.join(ROOT, "benchmark", "configs", "bge-large-1m.json")))["model"]


def test_the_tokenizer_and_the_template_are_the_programs():
    from pathway_tpu.models.tokenizer import HashTokenizer
    from pathway_tpu.xpacks.llm import prompts

    docs = [{"text": "Doc0000123 w17 w9 mixed-CASE, punctuation! 007"}, {"text": "doc0000007 w1"}]
    text = prompts.prompt_qa_geometric_rag("q0000001 what of w9?", docs)
    assert text == prompt_text("q0000001 what of w9?", [d["text"] for d in docs])
    assert HashTokenizer(16160).word_ids(text) == family.token_ids(text, 16160)
    assert min(family.token_ids(text, 16160)) >= 1000 and max(family.token_ids(text, 16160)) < 16160


def test_the_fp8_control_is_farther_from_the_reference_than_bfloat16():
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "toy-answer.json")) as f:
        group = json.load(f)["generator"]
    params = family.make_params(group, 5)
    ids = np.random.default_rng(0).integers(1000, group["vocab_size"], size=96).astype(np.int32)
    at = [[63, 95]]
    truth = family.reference_logits(params, group, [ids], at, q_block=32)[0]
    control = family.reference_logits(params, group, [ids], at, precision="fp8", q_block=32)[0]
    again = family.reference_logits(params, group, [ids, ids[:80]], [at[0], [63]], q_block=32, pad_to=128)
    assert np.abs(again[0] - truth).max() < 1e-4 and np.abs(again[1][0] - truth[0]).max() < 1e-4  # padding changes nothing
    assert np.abs(control - truth).max() / truth.std() > 0.3
    with pytest.raises(ValueError, match="unknown precision"):
        family.reference_logits(params, group, [ids], at, precision="int4")


def test_the_model_module_reader_over_hand_made_readings(config):
    from benchmark.readers import model_module

    g = config["generator"]
    work = [(6000, 31), (6400, 31)]
    r = {
        "trace": {"modules": {"jit__prefill_chunk": {"count": 6, "total_s": 0.9, "busy_s": 0.9}, "jit__decode_token": {"count": 62, "total_s": 0.31, "busy_s": 0.31}},
                  "ops": [["fusion", 0.5], ["selected_attention", 0.3]]},
        "slice": {"useful_tokens": {"embedder": [14, 9], "generator": work}},
        "config": config,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    decl = {"group": "generator", "modules": ["jit__prefill_chunk"], "quantity": "prefill_mfu_pct"}
    assert model_module.read(decl, r) == pytest.approx(100 * (family.prompt_flops(g, 6000) + family.prompt_flops(g, 6400)) / (0.9 * 197e12))
    decl = {"group": "generator", "modules": ["jit__decode_token"], "quantity": "decode_roofline_pct"}
    assert model_module.read(decl, r) == pytest.approx(100 * (family.decode_bytes(g, 6216) / 819e9) / 0.005)
    decl = {"group": "generator", "ops": ["selected_attention"], "work": "attention_core_flops", "quantity": "kernel_roofline_pct"}
    core = 5 * 81_920 * sum(sum(min(t, 2048) for t in range(1, p + 1)) for p, _s in work)
    assert model_module.read(decl, r) == pytest.approx(100 * core / (0.3 * 197e12))
    # nothing to read: another program, another traffic kind's slice, a kernel past the ten kinds kept
    assert model_module.read(dict(decl, ops=["no_such_kernel"]), r) is None
    assert model_module.read({"group": "generator", "modules": ["jit_run"], "quantity": "prefill_mfu_pct"}, r) is None
    assert model_module.read(decl, dict(r, slice={"useful_tokens": [16, 32]})) is None
