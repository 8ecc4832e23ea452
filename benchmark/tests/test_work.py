"""The work functions against numbers worked by hand."""

import pytest

from benchmark import doors, work
from benchmark.families import bert_encoder
from benchmark.peaks import peaks_for

BGE = {"hidden_size": 1024, "intermediate_size": 4096, "num_hidden_layers": 24}


def test_encoder_flops_of_one_padded_bge_row():
    # per layer 512 * (8*1024^2 + 4*1024*4096) + 4*512^2*1024 = 12.88e9 + 1.07e9
    assert bert_encoder.row_flops(BGE, 512) == 24 * (512 * (8 * 1024**2 + 4 * 1024 * 4096) + 4 * 512**2 * 1024)
    assert 330e9 < bert_encoder.row_flops(BGE, 512) < 340e9
    assert bert_encoder.flops(BGE, [512, 512, 16]) == 2 * bert_encoder.row_flops(BGE, 512) + bert_encoder.row_flops(BGE, 16)


def test_a_slices_model_work_is_summed_over_the_groups_it_went_through():
    group = dict(BGE, family="bert_encoder")
    one = {"name": "c", "model": group, "slab": {"dim": 4}}
    assert doors.model_flops(one, [512, 16]) == bert_encoder.flops(BGE, [512, 16])
    assert doors.model_flops(one, {"model": [512]}) == bert_encoder.row_flops(BGE, 512)
    two = dict(one, generator=dict(group, num_hidden_layers=2))
    want = bert_encoder.row_flops(BGE, 512) + bert_encoder.row_flops(two["generator"], 16)
    assert doors.model_flops(two, {"model": [512], "generator": [16]}) == want
    assert doors.model_flops(two, {"generator": [16]}) == bert_encoder.row_flops(two["generator"], 16)
    with pytest.raises(doors.MissingKind):  # two groups, and the slice does not say which
        doors.model_flops(two, [512])


def test_scan_work_of_the_minilm_slab():
    slab = {"capacity_rows": 4420992, "dim": 384, "itemsize": 4}
    assert work.scan_flops(slab) == 2 * 4420992 * 384
    assert work.scan_bytes(slab) == 4420992 * 384 * 4 + 384 * 4
    # bandwidth-bound: 8.3 ms against 0.017 ms
    p = peaks_for("TPU v5 lite")
    assert work.scan_bytes(slab) / p["hbm_bytes_per_s"] > 100 * work.scan_flops(slab) / p["bf16_flops_per_s"]


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9")
