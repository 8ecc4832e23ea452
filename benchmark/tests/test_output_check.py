"""The output check has to pass the program and fail the control and every
fault the cells can have, through the whole of a run (``run.run_cell``, past
the device gate only), at a toy size.

Faults planted underneath the timed path:

- an answer altered where it is produced (the index's ``collect`` swaps the
  two best hits' keys, or shifts a score);
- half of a batch left out (the slab's ``add_batch`` drops every second row);
- the engine stopped mid-window; an offered rate far above what the CPU
  sustains.  These two must end in a well-formed line with ``correct`` false.

The other two faults of the contract's list do not exist here: nothing is a
training step, and the cells run on one chip.
"""

from __future__ import annotations

import json

import pytest

from benchmark import rehearse_cpu

SEED = 2**31 + 77


def _line(cell, **kw):
    line = rehearse_cpu.rehearse(cell, seed=SEED, seconds=2.0, trace=False, **kw)
    json.dumps(line, allow_nan=False)
    return line


@pytest.mark.parametrize("cell", ["toy.retrieve", "toy-cls.ingest"])
def test_program_passes_and_fp8_control_fails(cell):
    from benchmark import run

    line = run.run_cell(rehearse_cpu.toy_manifest(), rehearse_cpu.ROOT, cell, SEED, 2.0, False, control="fp8")
    assert line["correct"] is True, line
    limits = {k: v["limit"] for k, v in line["compared"].items()}
    over = {k: v for k, v in line["control"].items() if v > limits[k]}
    assert over, f"the fp8 control passed every limit: {line['control']} against {limits}"
    # and with room: the control reads three times the program's number or more
    assert line["control"]["emb_gap"] >= 3 * line["compared"]["emb_gap"]["value"]


def _swap_keys(system):
    slab = system.seg  # the segment layer: where an answer's keys and scores are produced
    collect = slab.collect

    def altered(handle):
        rows = collect(handle)
        return [[(row[1][0], row[0][1]), (row[0][0], row[1][1]), *row[2:]] if len(row) > 2 else row for row in rows]

    slab.collect = altered


def _shift_score(system):
    slab = system.seg
    collect = slab.collect
    slab.collect = lambda handle: [[(k, s - 0.05) for k, s in row] for row in collect(handle)]


def _drop_half(system):
    slab = system.slab
    add_batch = slab.add_batch
    slab.add_batch = lambda keys, vectors: add_batch(list(keys)[::2], vectors[::2])
    seg = system.seg
    seg_add = seg.add
    seg.add = lambda items: seg_add(list(items)[::2])


@pytest.mark.parametrize(
    "cell,fault",
    [
        ("toy.retrieve", _swap_keys),
        ("toy.retrieve", _shift_score),
        ("toy-cls.ingest", _drop_half),
    ],
    ids=["answer_keys_swapped", "answer_score_shifted", "half_of_each_batch_left_out"],
)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    line = _line(cell, sabotage=fault)
    assert line["correct"] is False, line
    bad = {k: v for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert bad or line["failed"], line


@pytest.mark.parametrize("cell", ["toy.retrieve", "toy-cls.ingest"])
def test_engine_killed_mid_window_still_ends_in_a_line(cell):
    line = _line(cell, sabotage=rehearse_cpu.kill_engine_after(0.7))
    assert line["correct"] is False and line["failed"] > 0, line


def test_overload_still_ends_in_a_line():
    line = _line("toy.retrieve", edit=lambda w: w.update(rate_per_s=3000, max_in_flight=4, timeout_s=2))
    assert line["correct"] is False
    assert line["failed"] > 0 and line["attempted"] == 6000
    assert line["metrics"]["retrieve_p50_ms"]["value"] >= 2000.0
