"""The ``phi4flash`` family's work functions against a hand count at the
published numbers, its configuration file against the catalog's row, its
reference against its fp8 control, and the toy cell of ``rag_generator`` end
to end on the CPU (``tests/test_hybrid_decoder.py`` holds the program against
this family's reference, in tier-1)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark import rehearse_cpu, run
from benchmark.families import phi4flash as family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = "phi4flash-toy.answer"
#: the catalog's row (model-configs guide, architectures.jsonl, Phi-4-mini-flash-reasoning), its ``config`` whole
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 200064,
}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "phi4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_parameters_by_hand(config):
    c = family.parameter_counts(config["generator"])
    h, inner = 2560, 5120
    mamba = h * 2 * inner + 4 * inner + inner + inner * (160 + 32) + 160 * inner + inner + inner * 16 + inner + inner * h
    attention = h * 5120 + 5120 + h * h + h + 4 * 64 + 128
    cross = 2 * (h * h + h) + 4 * 64 + 128
    assert (c["mlp"], c["mamba"], c["attention"], c["cross"], c["gmu"]) == (3 * h * 10240, mamba, attention, cross, 2 * h * inner)
    assert (c["mlp"], c["mamba"], c["attention"], c["cross"], c["gmu"], c["embedding"]) == (78_643_200, 41_241_600, 19_668_864, 13_112_704, 26_214_400, 512_163_840)
    layers = 32 * (78_643_200 + 4 * h) + 9 * mamba + 9 * attention + 7 * cross + 7 * 26_214_400
    assert c["total"] == layers + 512_163_840 + 2 * h == 3_852_562_944  # 3,852.6M: 7.705 GB in bfloat16
    assert c["self_decoder"] == 18 * (78_643_200 + 4 * h) + 9 * mamba + 9 * attention == 1_963_956_096  # layers 0-17
    assert c["self_decoder"] + c["cross_decoder"] == layers


def test_the_file_holds_every_published_key_and_cuts_nothing(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "phi4-mini-flash-reasoning")
    assert entry["reduced"] == ["filler_rows"] == list(config["reduced"])
    assert {k: config[k] for k in CATALOG} == CATALOG
    g = config["generator"]
    assert {k: g[k] for k in CATALOG if k in g} == {k: v for k, v in CATALOG.items() if k in g} and g["vocab_size"] == 200064
    assert (g["mamba_d_state"], g["mamba_d_conv"], g["mamba_expand"], g["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert config["program"]["generator"]["share"] == {} and len(config["assumed"]) >= 8


def test_flops_decode_bytes_and_scan_bytes_by_hand(config):
    g = config["generator"]
    h, inner, mlp = 2560, 5120, 78_643_200
    mamba = 2 * (mlp + h * 2 * inner + inner * 192 + 160 * inner + inner * h)
    attention = 2 * (mlp + h * 5120 + h * h)
    scan = 2 * 4 * inner + 7 * inner * 16
    key = 2 * 40 * (64 + 128)
    n = 6200
    contexts = np.arange(1, n + 1, dtype=np.float64)
    self_decoder = n * (9 * (mamba + scan) + 9 * attention) + key * (8 * np.minimum(contexts, 512).sum() + contexts.sum())
    assert family.self_decoder_flops(g, n) == pytest.approx(self_decoder, rel=1e-12)
    assert round(n * (9 * mamba + 9 * attention) / 1e12, 2) == 24.34  # the products of layers 0-17: ISSUE 35's 24.4 TFLOP
    last = 7 * 2 * (mlp + 2 * h * inner) + 7 * (2 * (mlp + 2 * h * h) + key * n) + 2 * 200064 * h
    assert family.cross_decoder_flops(g, n) == last
    assert family.prompt_flops(g, n) == pytest.approx(self_decoder + last, rel=1e-12)
    assert round(family.prompt_flops(g, n) / 1e12, 2) == 25.04
    # a program that ran every layer for every token would not read higher: the cross-decoder is counted for one row
    assert family.prompt_flops(g, n) < self_decoder + 0.001 * n * last
    step = 9 * (mamba + scan) + 9 * attention + key * (8 * 512 + (n + 1)) + 7 * 2 * (mlp + 2 * h * inner) + 7 * (2 * (mlp + 2 * h * h) + key * (n + 1)) + 2 * 200064 * h
    assert family.flops(g, [(n, 1)]) == pytest.approx(self_decoder + last + step, rel=1e-12)
    assert family.flops(g, [(n, 63), (n, 63)]) == pytest.approx(2 * family.flops(g, [(n, 63)]), rel=1e-12)
    weights = 2 * 3_852_562_944
    state = 9 * (4 * inner * 16 + 2 * 3 * inner) + 8 * 512 * 2560 * 2 + 8 * n * 2560 * 2
    assert family.decode_bytes(g, n) == weights + state == 7_983_275_008  # 9.75 ms a step at 819 GB/s
    assert family.decode_bytes(g, 100) == weights + 9 * (4 * inner * 16 + 2 * 3 * inner) + 8 * 100 * 2560 * 2 + 8 * 100 * 2560 * 2
    assert family.scan_bytes(g, n) == 9 * (n * (inner * 8 + 2 * 16 * 4) + 2 * 4 * inner * 16) == 2_298_608_640  # 2.8 ms a prompt at 819 GB/s


def test_built_differs_is_empty_for_the_preset_and_names_what_differs(config):
    import dataclasses

    from pathway_tpu.xpacks.llm.llms import decoder_preset

    preset = decoder_preset(config["program"]["generator"]["preset"])
    assert family.built_differs(config["generator"], preset) == {}
    assert family.built_differs(config["generator"], dataclasses.replace(preset, mamba_d_state=8)) == {"mamba_d_state": (8, 16)}
    assert "vocab_held" in family.built_differs(config["generator"], dataclasses.replace(preset, vocab_held=1024))


def _toy_group():
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "phi4flash-toy.json")) as f:
        return json.load(f)["generator"]


def test_the_draw_is_the_seeds_and_mambas_own_initialisation():
    g = _toy_group()
    a, b, c = family.make_params(g, 5), family.make_params(g, 5), family.make_params(g, 6)
    import jax

    same = lambda x, y: all(np.array_equal(np.asarray(p, np.float32), np.asarray(q, np.float32)) for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y)))
    assert same(a, b) and not same(a, c)
    mamba = a["self_pairs"]["mamba"]
    assert mamba["A_log"].shape == (2, 128, 4) and np.allclose(np.exp(np.asarray(mamba["A_log"][1, 17])), [1, 2, 3, 4])
    step = np.log1p(np.exp(np.asarray(mamba["dt_b"])))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    assert family.layer_params(a, 4, 8)["in"].shape == (64, 256) and family.layer_params(a, 7, 8)["q"].shape == (64, 64)


def test_the_fp8_control_is_farther_from_the_reference_than_bfloat16():
    g = _toy_group()
    params = family.make_params(g, 3)
    ids = np.random.default_rng(1).integers(1000, g["vocab_size"], size=96).astype(np.int32)
    positions = [list(range(80, 96))]
    truth = family.reference_logits(params, g, [ids], positions, q_block=32)[0]
    control = family.reference_logits(params, g, [ids], positions, precision="fp8", q_block=32, pad_to=128)[0]
    assert truth.shape == (16, g["vocab_size"]) and np.abs(control - truth).max() / truth.std() > 0.3
    padded = family.reference_logits(params, g, [ids], positions, q_block=32, pad_to=128, vocab_block=500)[0]
    assert np.abs(padded - truth).max() < 1e-5  # padding at the end and the head's blocks change nothing


def _rehearse(cell: str, seed: int, trace: bool, control=None) -> dict:
    """``rehearse_cpu.rehearse`` for a second toy cell of one traffic kind:
    ``toy_manifest`` gives each kind's metrics to one toy cell (the last by
    name), so the lists that name the kind's first toy cell are given this
    one too (PERF.md section 7 asks the next ``benchmark`` PR for that in
    ``rehearse_cpu.py`` itself)."""
    manifest = rehearse_cpu.toy_manifest()
    kind = {w["name"]: w["traffic"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if any(kind[w] == kind[cell] for w in m.get("workloads", [])):
            m["workloads"] = sorted({*m["workloads"], cell})
    line = run.run_cell(manifest, rehearse_cpu.ROOT, cell, seed, 3.0, trace, control=control)
    assert all(k in line for k in rehearse_cpu.REQUIRED)
    json.dumps(line, allow_nan=False)
    return line


def test_the_toy_cell_of_rag_generator_plays_and_reports_its_metrics():
    line = _rehearse(TOY, 2**31 + 5, trace=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8, line
    assert set(line["compared"]) == {"logit_gap", "context_gap", "wrong"}
    assert {"compiles_in_window.answer", "generate_prefill_ms", "generate_decode_ms_per_token", "prompt_useful_token_pct", "cross_decoder_tokens_pct", "window_keys_useful_pct"} <= set(line["metrics"])
    assert "dsa_selected_pct" not in line["metrics"] and "moe_rows_here_pct" not in line["metrics"]  # the other architecture's counters did not move
    assert line["metrics"]["compiles_in_window.answer"]["value"] == 0
    assert 0 < line["metrics"]["cross_decoder_tokens_pct"]["value"] < 10 and 0 < line["metrics"]["window_keys_useful_pct"]["value"] < 100
    untraced = _rehearse(TOY, 2**31 + 6, trace=False, control="fp8")
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"setup_s", "retrieve_p50_ms"}
    limits = untraced["compared"]
    assert untraced["control"]["logit_gap"] > limits["logit_gap"]["limit"] > limits["logit_gap"]["value"]


def test_an_unknown_preset_ends_in_its_line_before_a_parameter_is_drawn(monkeypatch):
    drawn = []
    monkeypatch.setattr(family, "make_params", lambda *a, **k: drawn.append(a) or (_ for _ in ()).throw(AssertionError("drawn")))
    from benchmark.systems import rag_generator
    from benchmark.system import SystemFault

    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "phi4flash-toy.json")) as f:
        config = json.load(f)
    config["program"]["generator"]["preset"] = "no-such/decoder"
    with pytest.raises(SystemFault, match="no-such/decoder"):
        rag_generator.System(config, 1, str(os.path.join(ROOT, "benchmark", ".scratch", "never")))
    assert not drawn and not os.path.exists(os.path.join(ROOT, "benchmark", ".scratch", "never"))

