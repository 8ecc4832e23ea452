"""The toy answer cell end to end on the CPU (``rehearse_cpu.rehearse``:
REST -> retrieve -> prompt -> generate -> response -> the ``answer`` check),
and the check against a decoder broken underneath it: both have to end in a
well-formed line, the broken ones with ``correct`` false."""

from __future__ import annotations

import dataclasses

import pytest

from benchmark import rehearse_cpu

CELL = "toy-answer.answer"


def widen_the_selection(system) -> None:
    """Every visible key attended to, where the indexer should select."""
    from pathway_tpu.parallel import JittedDecoder

    old = system.chat.decoder
    new = JittedDecoder(
        dataclasses.replace(old.config, index_topk=10**6), params=old.params, slots=old.slots, positions=old.positions, chunk_buckets=old.chunk_buckets
    )
    new.warm()
    system.chat.decoder = new


def drop_an_expert(system) -> None:
    """One routed expert of one layer gives nothing."""
    decoder = system.chat.decoder
    layers = list(decoder.params["layers"])
    experts = dict(layers[1]["experts"])
    experts["down"] = experts["down"].at[0].set(0)
    layers[1] = dict(layers[1], experts=experts)
    decoder.params = dict(decoder.params, layers=layers)


def cut_the_answer_short(system) -> None:
    system.chat.max_new_tokens -= 1


def test_the_toy_cell_plays_and_reports_its_metrics():
    line = rehearse_cpu.rehearse(CELL, seed=2**31 + 5, seconds=3.0, trace=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8, line
    assert set(line["compared"]) == {"logit_gap", "context_gap", "wrong"}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    # the per-layer metrics that need no device
    assert {"compiles_in_window.answer", "generate_prefill_ms", "generate_decode_ms_per_token", "prompt_useful_token_pct", "dsa_selected_pct", "moe_rows_here_pct"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window.answer"]["value"] == 0
    assert 0 < line["metrics"]["moe_rows_here_pct"]["value"] < 100 and 0 < line["metrics"]["dsa_selected_pct"]["value"] < 100
    untraced = rehearse_cpu.rehearse(CELL, seed=2**31 + 6, seconds=3.0, trace=False)
    assert untraced["correct"] is True and set(untraced["metrics"]) == {"setup_s", "retrieve_p50_ms"}


@pytest.mark.parametrize("sabotage, fails", [(widen_the_selection, "logit_gap"), (drop_an_expert, "logit_gap"), (cut_the_answer_short, "wrong")])
def test_a_decoder_broken_underneath_reads_not_correct(sabotage, fails):
    line = rehearse_cpu.rehearse(CELL, seed=2**31 + 7, seconds=3.0, trace=False, sabotage=sabotage)
    assert line["correct"] is False and line["failed"] == 0, line
    assert line["compared"][fails]["value"] > line["compared"][fails]["limit"]
    assert line["compared"]["context_gap"]["value"] <= line["compared"]["context_gap"]["limit"]  # the retrieve half is whole
