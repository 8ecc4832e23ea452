"""Runtime half of the device-safety story (ISSUE 20): the jit-compile
and transfer counters (``internals/device_counters.py``) cross-validated
against the static PW-J prediction.

The zero-recompile invariant: with no PW-J001 sites on the device
surface, a warmed serving loop must record exactly 0 new XLA compiles —
the counter sees ``jax.monitoring`` backend_compile events, which fire
once per real compile and never on an executable-cache hit.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pathway_tpu.internals import device_counters as devctr  # noqa: E402


@pytest.fixture(autouse=True)
def _installed():
    devctr.install()
    yield


def test_counter_sees_real_compiles_and_ignores_cache_hits():
    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    base = devctr.compile_count()
    f(jnp.ones((3,), jnp.float32)).block_until_ready()
    first = devctr.compile_count() - base
    assert first >= 1  # a fresh trace really compiled

    base = devctr.compile_count()
    for _ in range(5):
        f(jnp.ones((3,), jnp.float32)).block_until_ready()
    assert devctr.compile_count() - base == 0  # cache hits emit nothing


def test_shape_unstable_jit_records_a_compile_per_shape():
    """The storm PW-J001 predicts: every distinct length is a fresh
    trace+compile."""

    @jax.jit
    def f(x):
        return (x * x).sum()

    base = devctr.compile_count()
    for n in range(1, 5):
        f(jnp.ones((n,), jnp.float32)).block_until_ready()
    assert devctr.compile_count() - base >= 4


def test_warmed_ivf_serving_loop_records_zero_compiles():
    """Live cross-validation of the static sweep: the bucketed IVF
    search path, once warmed over a batch-size range, must hold the
    compile counter flat through arbitrary sizes in that range."""
    from pathway_tpu.parallel.ivf_knn import IvfKnnIndex

    dim = 16
    rng = np.random.default_rng(7)
    idx = IvfKnnIndex(dim, capacity=64, query_block=4)
    idx.add_batch(
        [f"d{i}" for i in range(96)],
        rng.standard_normal((96, dim)).astype(np.float32),
    )
    if not idx.trained:
        idx.train()

    sizes = list(range(1, 10))
    base = devctr.compile_count()
    for nq in sizes:  # warmup: compiles land here, bounded by buckets
        idx.search(rng.standard_normal((nq, dim)).astype(np.float32), k=3)
    # a program a bucket, not a program a size (the unbucketed control is
    # test_shape_unstable_jit_records_a_compile_per_shape)
    assert devctr.compile_count() - base < len(sizes)

    base = devctr.compile_count()
    for nq in sizes:
        rows = idx.search(
            rng.standard_normal((nq, dim)).astype(np.float32), k=3
        )
        assert len(rows) == nq
    assert devctr.compile_count() - base == 0


def test_transfer_counters_accumulate():
    snap0 = devctr.snapshot()
    devctr.record_h2d(4096)
    devctr.record_d2h(128)
    snap1 = devctr.snapshot()
    assert snap1["h2d_bytes"] - snap0["h2d_bytes"] == 4096
    assert snap1["h2d_transfers"] - snap0["h2d_transfers"] == 1
    assert snap1["d2h_bytes"] - snap0["d2h_bytes"] == 128
    assert snap1["d2h_transfers"] - snap0["d2h_transfers"] == 1


def test_ivf_search_accounts_its_transfers():
    from pathway_tpu.parallel.ivf_knn import IvfKnnIndex

    dim = 16
    rng = np.random.default_rng(11)
    idx = IvfKnnIndex(dim, capacity=64, query_block=4)
    idx.add_batch(
        [f"d{i}" for i in range(64)],
        rng.standard_normal((64, dim)).astype(np.float32),
    )
    if not idx.trained:
        idx.train()
    snap0 = devctr.snapshot()
    idx.search(rng.standard_normal((5, dim)).astype(np.float32), k=3)
    snap1 = devctr.snapshot()
    assert snap1["h2d_bytes"] > snap0["h2d_bytes"]
    assert snap1["d2h_bytes"] > snap0["d2h_bytes"]


def test_monitoring_joins_counters_with_static_prediction():
    """/status payload shape: live counters + the static sweep, so an
    operator can eyeball predicted-vs-observed in one place."""
    from pathway_tpu.internals import monitoring

    stats = monitoring.device_stats()
    assert "counters" in stats and "static" in stats
    assert "jit_compiles" in stats["counters"]
    assert stats["static"]["predicted_recompile_sites"] == 0


def test_metrics_expose_device_counters():
    import re

    import pathway_tpu as pw
    from pathway_tpu.engine.scheduler import Scheduler
    from pathway_tpu.internals.monitoring_server import _metrics_text
    from pathway_tpu.internals.parse_graph import G

    pw.G.clear()
    t = pw.debug.table_from_markdown(
        """
        a
        1
        """
    )
    t.select(b=pw.this.a)._capture_node()
    sched = Scheduler(G.engine_graph, autocommit_ms=20)
    devctr.record_h2d(64)  # ensure the counter block is non-empty
    body = _metrics_text(sched)
    m = re.search(r"pathway_tpu_jit_compiles_total (\d+)", body)
    assert m, body
    assert "pathway_tpu_h2d_bytes_total" in body
    assert "pathway_tpu_d2h_bytes_total" in body
    assert re.search(
        r"pathway_tpu_device_predicted_recompile_sites 0\b", body
    ), body
    pw.G.clear()


def test_snapshot_reports_listener_state():
    snap = devctr.snapshot()
    assert snap["listener_installed"] == 1  # numeric: metrics-friendly


# ---------------------------------------------------------------------------
# dispatch counters (PR 25): bumped once per dispatch where the work happens;
# the benchmark reads them through snapshot() as differences over its window


def _moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}


@pytest.fixture(scope="module")
def toy_encoder():
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.models.tokenizer import HashTokenizer
    from pathway_tpu.parallel.executor import JittedEncoder

    cfg = EncoderConfig(hidden=32, layers=1, heads=2, mlp_dim=64, vocab_size=512, max_len=64)
    return JittedEncoder(cfg, tokenizer=HashTokenizer(512), max_batch=16)


def test_encode_moves_the_encoder_counters_by_the_reckoned_amounts(toy_encoder):
    # words + [CLS] + [SEP] tokens a text: 4, 7, 19 -> one batch of 3 rows,
    # padded to 8 rows (the smallest row bucket) x 32 tokens (19 -> bucket 32).
    # Packed they would lie in one row, which is padded to the same 8 rows: no
    # gain, so the packer is never entered and a row carries one text
    texts = ["a b", "a b c d e", " ".join("w%d" % i for i in range(17))]
    before = devctr.snapshot()
    out = toy_encoder.encode(texts)
    moved = _moved(before, devctr.snapshot())
    assert out.shape == (3, 32)
    assert moved["encoder_dispatches"] == 1
    assert moved["encoder_segments"] == 3
    assert moved["encoder_rows"] == 3 and moved["encoder_rows_padded"] == 8
    assert moved["encoder_tokens"] == 4 + 7 + 19
    assert moved["encoder_tokens_padded"] == 8 * 32
    for stage in ("encoder_tokenize", "encoder_dispatch", "encoder_readback"):
        assert moved[f"span_count.{stage}"] == 1 and moved[f"span_ns.{stage}"] > 0
    assert "span_count.encoder_pack" not in moved


def test_encode_counts_one_dispatch_per_chunk_never_per_row(toy_encoder):
    # 20 texts of 5 tokens at max_batch 16: two tokenizer batches.  The 16 lie
    # three to a 16-token row in 6 rows -> 8 where they took 16; the 4 would
    # still cost the smallest bucket, 8, so they stay a text a row
    texts = ["x y z"] * 20
    before = devctr.snapshot()
    toy_encoder.encode(texts)
    moved = _moved(before, devctr.snapshot())
    assert moved["encoder_dispatches"] == 2
    assert moved["encoder_segments"] == 20
    assert moved["encoder_rows"] == 6 + 4 and moved["encoder_rows_padded"] == 8 + 8
    assert moved["encoder_tokens"] == 20 * 5
    assert moved["encoder_tokens_padded"] == (8 + 8) * 16
    assert moved["span_count.encoder_pack"] == 1


# ---------------------------------------------------------------------------
# sequence packing (PR 26): short texts share a row where that saves rows


def _fake_batch(lens, length):
    """A tokenized batch of the given lengths: every token its own id."""
    lens = np.asarray(lens)
    mask = (np.arange(length) < lens[:, None]).astype(np.int32)
    ids = (1 + np.arange(mask.size).reshape(mask.shape)) * mask
    return ids.astype(np.int32), mask, (ids % 2).astype(np.int32)


@pytest.mark.parametrize("rows", [8, 64])
@pytest.mark.parametrize("seed", range(5))
def test_packer_places_every_text_once_and_whole(toy_encoder, seed, rows):
    from pathway_tpu.models.encoder import _packed_positions

    rng = np.random.default_rng(seed)
    length = 64
    lens = np.append(rng.integers(1, 40, size=int(rng.integers(30, 64))), length)
    ids, mask, tps = _fake_batch(lens, length)
    units = toy_encoder._pack(ids, mask, tps, rows)
    placed = np.concatenate([at for _arrays, at in units])
    assert sorted(placed) == list(range(len(lens)))  # every text, once
    assert all(arrays[0].shape[0] <= rows for arrays, _at in units)
    assert sum(a[0].shape[0] for a, _at in units) < len(lens)
    for (p_ids, seg, p_tps, first), at in units:
        assert p_ids.shape == seg.shape == p_tps.shape and p_ids.shape[1] == length
        assert np.count_nonzero(seg) == lens[at].sum()
        positions = np.asarray(_packed_positions(jnp.asarray(seg))).reshape(-1)
        for t, f in zip(at, first):
            r, o = divmod(int(f), length)
            whole = slice(o, o + lens[t])
            assert o + lens[t] <= length  # no text runs over its row
            assert (p_ids[r, whole] == ids[t, : lens[t]]).all()
            assert (p_tps[r, whole] == tps[t, : lens[t]]).all()
            assert len(set(seg[r, whole])) == 1 and seg[r, o] > 0  # one segment, contiguous
            assert (seg[r] == seg[r, o]).sum() == lens[t]  # and nothing else in it
            assert (positions[f : f + lens[t]] == np.arange(lens[t])).all()
    # the same multiset in another order: the same shapes, so the same programs
    again = rng.permutation(len(lens))
    other = toy_encoder._pack(ids[again], mask[again], tps[again], rows)
    assert [[a.shape for a in arrays] for arrays, _ in other] == [[a.shape for a in arrays] for arrays, _ in units]


@pytest.mark.parametrize(
    "lens, length",
    [([5], 16), ([16] * 16, 16), ([64] * 5 + [60] * 4, 64), ([3, 4, 5], 16)],
    ids=["one-text", "full-rows", "nearly-full-rows", "under-the-smallest-bucket"],
)
def test_packer_leaves_alone_what_it_cannot_shrink(toy_encoder, lens, length):
    before = devctr.snapshot()
    assert toy_encoder._pack(*_fake_batch(lens, length), 64) is None
    assert "span_count.encoder_pack" not in _moved(before, devctr.snapshot())


@pytest.mark.parametrize("texts", [["a b c"], [" ".join(["w"] * 14)] * 16], ids=["one-text", "full-rows"])
def test_unpackable_batches_take_the_program_of_a_text_a_row(toy_encoder, texts):
    (arrays, at), = toy_encoder._chunks(texts, None)
    assert len(arrays) == 3 and list(at) == list(range(len(texts)))  # ids, mask, type ids: no ``first``
    assert arrays[0].shape == (len(texts), 16)
    before = devctr.snapshot()
    assert toy_encoder.encode(texts).shape == (len(texts), 32)
    moved = _moved(before, devctr.snapshot())
    assert moved["encoder_segments"] == moved["encoder_rows"] == len(texts)
    assert "span_count.encoder_pack" not in moved


def test_one_multiset_of_lengths_is_one_packed_program(toy_encoder):
    rng = np.random.default_rng(11)
    lens = np.append(rng.integers(1, 12, size=15), 30)  # 16 texts, bucket 32
    moved = []
    for _ in range(3):
        texts = [" ".join(f"w{rng.integers(99)}" for _ in range(n)) for n in rng.permutation(lens)]
        before = devctr.snapshot()
        toy_encoder.encode(texts)
        moved.append(_moved(before, devctr.snapshot()))
    assert all(m["span_count.encoder_pack"] == 1 and m["encoder_rows"] < 16 for m in moved)
    assert moved[0]["jit_compiles"] >= 1
    assert "jit_compiles" not in moved[1] and "jit_compiles" not in moved[2]


@pytest.mark.parametrize("door", ["add_batch", "add_batch_device"])
def test_slab_upsert_moves_the_scatter_counters(door):
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    idx = ShardedKnnIndex(8, capacity=256)
    vecs = np.random.default_rng(3).standard_normal((16, 8)).astype(np.float32)
    before = devctr.snapshot()
    if door == "add_batch":
        idx.add_batch([f"k{i}" for i in range(5)], vecs[:5])  # 5 rows -> bucket 8
        padded = 8
    else:
        idx.add_batch_device([f"k{i}" for i in range(5)], jnp.asarray(vecs), n_valid=5)
        padded = 16  # the device array's rows, as the encoder padded them
    moved = _moved(before, devctr.snapshot())
    assert len(idx) == 5
    assert moved["scatter_dispatches"] == 1
    assert moved["scatter_rows"] == 5 and moved["scatter_rows_padded"] == padded
    assert moved["span_count.slab_assign_slots"] == 1 and moved["span_count.slab_scatter"] == 1


def test_slab_search_moves_the_search_counters():
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    rng = np.random.default_rng(5)
    idx = ShardedKnnIndex(8, capacity=256)
    idx.add_batch([f"k{i}" for i in range(32)], rng.standard_normal((32, 8)).astype(np.float32))
    before = devctr.snapshot()
    rows = idx.search(rng.standard_normal((3, 8)).astype(np.float32), k=4)
    moved = _moved(before, devctr.snapshot())
    assert [len(r) for r in rows] == [4, 4, 4]
    assert moved["search_dispatches"] == 1
    assert moved["search_queries"] == 3 and moved["search_queries_padded"] == 4
    assert moved["span_count.search_readback"] == 1
    assert "scatter_dispatches" not in moved and "encoder_dispatches" not in moved


@pytest.mark.parametrize("main_loaded_directly", [False, True], ids=["in_step", "main_loaded_directly"])
def test_segment_bulk_load_times_the_index_add_and_rebuilds_the_keyset_only_out_of_step(main_loaded_directly):
    """A bulk load through the index operator's door is one ``index_add``
    span; ``index_keyset_rebuild`` is inside it only where main was loaded
    behind the segment layer (``benchmark/metrics/index_keyset_rebuild_ms.json``
    reads the one over the other)."""
    from pathway_tpu.engine import graph as eg
    from pathway_tpu.engine.external_index import ExternalIndexNode
    from pathway_tpu.engine.stream import Update
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.stdlib.indexing.adapters import KnnAdapter

    rng = np.random.default_rng(9)
    adapter = KnnAdapter(8, capacity=256, delta_cap=8, auto_merge=False)
    g = eg.EngineGraph()
    node = ExternalIndexNode(
        g, eg.InputNode(g, 1), eg.InputNode(g, 1), adapter,
        index_payload_fn=lambda key, values: values[0],
        query_payload_fn=lambda key, values: values[0],
        query_k_fn=lambda key, values: 1,
    )
    seg, st = adapter.index, node.make_state()

    def epoch(first, n):
        vecs = rng.standard_normal((n, 8)).astype(np.float32)
        return [Update(Pointer(first + i), (vecs[i],), 1) for i in range(n)]

    if main_loaded_directly:
        seg.main.add_batch([f"filler{i}" for i in range(24)], rng.standard_normal((24, 8)).astype(np.float32))
    before = devctr.snapshot()
    assert node._apply_index_batch(st, epoch(0, 8))
    moved = _moved(before, devctr.snapshot())
    assert moved["span_count.index_add"] == 1 and moved["scatter_rows"] == 8
    assert moved.get("span_count.index_keyset_rebuild", 0) == int(main_loaded_directly)
    assert len(seg) == 8 + 24 * main_loaded_directly == len(seg.main)
    before = devctr.snapshot()
    assert node._apply_index_batch(st, epoch(8, 8))  # in step by now, however it began
    assert node._apply_index_batch(st, epoch(16, 1))  # the delta
    moved = _moved(before, devctr.snapshot())
    assert moved["span_count.index_add"] == 2 and "span_count.index_keyset_rebuild" not in moved
    assert len(seg) == 17 + 24 * main_loaded_directly == len(seg.main) + 1
    seg.close()


def _module_name(lowered) -> str:
    import re

    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


@pytest.mark.parametrize("program", ["jit__apply_cast", "jit__apply_cast packed", "jit_run", "jit__scatter_set"])
def test_the_programs_keep_the_module_names_the_benchmark_reads(program, toy_encoder):
    """``benchmark/metrics/*.json`` find the encoder, the slab search and the
    bulk scatter in a device trace by these XLA module names; a rename here
    turns four accepted per-layer metrics to null."""
    from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

    idx = ShardedKnnIndex(8, capacity=256)
    if program.startswith("jit__apply_cast"):
        z = jnp.zeros((8, 16), jnp.int16)
        first = (jnp.zeros((16,), jnp.int32),) if program.endswith("packed") else ()
        lowered = toy_encoder._apply.lower(toy_encoder.params, z, z.astype(jnp.uint8), z.astype(jnp.uint8), *first)
    elif program == "jit_run":
        lowered = idx._search_jit(16).lower(jnp.zeros((1, 8), jnp.float32), idx._vectors, idx._valid)
    else:
        lowered = ShardedKnnIndex._scatter_set.lower(
            idx._vectors, idx._valid, jnp.zeros((8,), jnp.int32), jnp.zeros((8, 8), jnp.float32)
        )
    assert _module_name(lowered) == program.split()[0]


def test_bump_refuses_a_counter_it_does_not_know():
    with pytest.raises(KeyError):
        devctr.bump(no_such_counter=1)
