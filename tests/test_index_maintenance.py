"""Online index maintenance (ISSUE 9): the delta-segment + tombstone +
background-merge layer (``stdlib/indexing/segments.py``) under churn.

The core property drill interleaves upserts, deletions and queries over
every backing index type (host HNSW graph, device sharded slab, device
IVF) and holds recall >= 0.95 against brute force over the reference
corpus at every step — including immediately after explicit merges and
after a ``state_dict``/``load_state_dict`` round-trip into a fresh
index.  The remaining tests pin the sharp edges individually: snapshot
consistency of a checkpoint racing a merge, full rollback of a failed
merge, HNSW tombstone compaction, absent-key deletes, and sharded-slab
dispatch handles surviving a capacity grow."""

from __future__ import annotations

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.internals import tracing
from pathway_tpu.parallel import IvfKnnIndex, ShardedKnnIndex
from pathway_tpu.stdlib.indexing.hnsw import HnswIndex
from pathway_tpu.stdlib.indexing.segments import SegmentedIndex

D = 16  # vector dimensionality for every test in this file
K = 5


def _unit(rng, n=1):
    x = rng.standard_normal((n, D)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _factory(kind):
    if kind == "hnsw":
        return HnswIndex(D, metric="cos")
    if kind == "sharded":
        return ShardedKnnIndex(D, metric="cos", capacity=256)
    # nprobe == nlist: the scan is exhaustive, so any recall loss is the
    # maintenance layer's fault, not the ANN approximation's
    return IvfKnnIndex(D, metric="cos", capacity=1024, nlist=8, nprobe=8)


def _recall(seg, ref, queries, k=K):
    """Recall of ``seg.search`` vs brute force over the reference dict."""
    got = seg.search(queries, k)
    keys = list(ref)
    mat = np.stack([ref[key] for key in keys])
    mat = mat / np.linalg.norm(mat, axis=1, keepdims=True)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    scores = qn @ mat.T
    hits = total = 0
    for qi, reply in enumerate(got):
        kk = min(k, len(keys))
        truth = {keys[i] for i in np.argsort(-scores[qi])[:kk]}
        hits += len({key for key, _ in reply[:kk]} & truth)
        total += kk
    return hits / max(total, 1)


# ---------------------------------------------------------------------------
# the seeded churn property


@pytest.mark.parametrize("kind", ["hnsw", "sharded", "ivf"])
def test_segmented_churn_recall_property(kind):
    """Seeded interleaving of upserts (new + re-keyed), deletions
    (live + absent), explicit merges and queries: recall vs brute force
    must hold at EVERY step, the live key set must track the reference
    exactly, and a checkpoint round-trip must preserve both."""
    rng = np.random.default_rng(42)
    ref: dict[str, np.ndarray] = {}
    seg = SegmentedIndex(_factory(kind), delta_cap=32, auto_merge=False)
    next_id = 0
    try:
        for step in range(12):
            # upserts: ~30% overwrite a live key, the rest are new
            items = []
            for _ in range(int(rng.integers(8, 24))):
                if ref and rng.random() < 0.3:
                    key = str(rng.choice(sorted(ref)))
                else:
                    key = f"k{next_id}"
                    next_id += 1
                vec = _unit(rng)[0]
                items.append((key, vec))
                ref[key] = vec
            seg.add(items)
            # deletions on odd steps: live victims plus an absent key
            # (replay can send deletes for rows that never landed)
            if ref and step % 2:
                victims = [
                    str(v)
                    for v in rng.choice(
                        sorted(ref), size=min(5, len(ref)), replace=False
                    )
                ]
                seg.remove(victims + [f"absent-{step}"])
                for v in victims:
                    del ref[v]
            if step in (4, 8, 10):
                seg.merge(wait=True)
            assert set(seg.keys()) == set(ref), f"step {step} key drift"
            assert len(seg) == len(ref)
            # queries: perturbed live vectors + fresh randoms
            probes = [str(v) for v in rng.choice(sorted(ref), size=4)]
            q = np.concatenate(
                [
                    np.stack([ref[p] for p in probes])
                    + 0.1 * rng.standard_normal((4, D)).astype(np.float32),
                    _unit(rng, 4),
                ]
            )
            r = _recall(seg, ref, q)
            assert r >= 0.95, f"step {step}: recall {r:.3f} < 0.95"
        assert seg.merges_total == 3

        # checkpoint round-trip into a completely fresh index
        state = seg.state_dict()
        seg2 = SegmentedIndex(_factory(kind), delta_cap=32, auto_merge=False)
        seg2.load_state_dict(state)
        assert set(seg2.keys()) == set(ref)
        q = _unit(rng, 8)
        r = _recall(seg2, ref, q)
        assert r >= 0.95, f"post-restore recall {r:.3f} < 0.95"
        # and the restored index keeps absorbing churn
        seg2.add([("fresh", _unit(rng)[0])])
        ref["fresh"] = seg2._delta["fresh"]
        assert "fresh" in seg2
        seg2.merge(wait=True)
        assert set(seg2.keys()) == set(ref)
    finally:
        seg.close()


# ---------------------------------------------------------------------------
# delta visibility and the bulk-load fast path


def test_segmented_upsert_visible_before_merge():
    rng = np.random.default_rng(0)
    seg = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=64, auto_merge=False)
    x = _unit(rng, 8)
    seg.add([(f"k{i}", x[i]) for i in range(8)])
    assert len(seg.main) == 0, "small batch must buffer in the delta"
    (res,) = seg.search(x[:1], 1)
    assert res[0][0] == "k0", "fresh upsert invisible to the next query"
    seg.remove(["k3"])
    (res,) = seg.search(x[3:4], 8)
    assert "k3" not in {k for k, _ in res}


def test_segmented_bulk_load_goes_straight_to_main():
    rng = np.random.default_rng(1)
    seg = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=16, auto_merge=False)
    x = _unit(rng, 32)
    seg.add([(f"k{i}", x[i]) for i in range(32)])  # batch >= delta_cap
    assert len(seg.main) == 32
    assert not seg._delta, "bulk load must not crawl through the delta"
    assert len(seg) == 32


# ---------------------------------------------------------------------------
# the membership set is kept by the batch (ISSUE 33): a bulk load adds its
# own keys and walks main's only when the two sizes are out of step


def _rebuilds():
    return tracing.stage_totals().get("index_keyset_rebuild", (0, 0))[0]


def _assert_membership(seg, expect):
    """Every public reading of the membership against ``expect`` (what the
    calls so far should have left) and against a recount from scratch."""
    recount = (set(seg._main_keys()) | set(seg._delta) | set(seg._frozen)) - seg._tombs - seg._frozen_tombs
    assert recount == expect
    assert set(seg.keys()) == expect
    assert len(seg) == len(seg.keys()) == seg.stats()["size"] == len(expect)
    assert all(key in seg for key in expect) and "absent" not in seg


def _items(rng, keys):
    return list(zip(keys, _unit(rng, len(keys))))


def _load_directly(kind, main, keys, vecs):
    """Rows put into ``main`` behind the segment layer, as the benchmark's
    ``System.fill`` loads its filler (``add_batch_device`` on the slab)."""
    if kind == "sharded":
        main.add_batch_device(keys, jnp.asarray(vecs))
    elif kind == "ivf":
        main.add_batch(keys, vecs)
    else:
        main.add(list(zip(keys, vecs)))


@pytest.mark.parametrize("kind", ["hnsw", "sharded", "ivf"])
def test_segmented_membership_after_public_calls_matches_a_recount(kind):
    """bulk load, bulk load, delta add, remove, merge, bulk load: after each
    call the membership reads what a recount gives, and no bulk load onto an
    index in step walks main's keys."""
    rng = np.random.default_rng(33)
    seg = SegmentedIndex(_factory(kind), delta_cap=16, auto_merge=False)
    try:
        before = _rebuilds()
        expect = {f"a{i}" for i in range(16)}
        seg.add(_items(rng, sorted(expect)))
        _assert_membership(seg, expect)
        if kind == "ivf":
            # untrained it buffers rows and its len() counts a key buffered
            # twice twice: that main is the miscounting case further down
            seg.main.train()
        more = [f"b{i}" for i in range(24)]
        seg.add(_items(rng, more))
        expect |= set(more)
        _assert_membership(seg, expect)
        assert not seg._delta and len(seg.main) == 40
        seg.add(_items(rng, ["d0", "d1", "a3"]))  # under delta_cap: the delta; a3 shadows main's
        expect |= {"d0", "d1"}
        _assert_membership(seg, expect)
        seg.remove(["a0", "d1", "b7", "absent"])  # main, delta-only, main, nowhere
        expect -= {"a0", "d1", "b7"}
        _assert_membership(seg, expect)
        seg.add(_items(rng, [f"c{i}" for i in range(16)]))  # bulk-sized, but tombstones wait: the delta
        expect |= {f"c{i}" for i in range(16)}
        _assert_membership(seg, expect)
        assert len(seg._delta) == 18
        seg.merge()
        _assert_membership(seg, expect)
        assert not seg._delta and not seg._tombs and len(seg.main) == len(expect)
        last = [f"e{i}" for i in range(20)] + ["a3", "c2"]  # new keys and two upserts of main's
        seg.add(_items(rng, last))
        expect |= set(last)
        _assert_membership(seg, expect)
        assert not seg._delta and len(seg.main) == len(expect)
        assert _rebuilds() == before, "an index in step was resynchronised"
    finally:
        seg.close()


@pytest.mark.parametrize("kind", ["hnsw", "sharded", "ivf"])
def test_segmented_direct_load_then_bulk_resynchronises_once(kind, monkeypatch):
    """The harness's sequence: N rows into ``main`` behind the segment
    layer, then two bulk loads.  The first finds the sizes out of step and
    walks main's keys once; the second, and a bulk load onto any index in
    step, never reads ``main.keys``."""
    rng = np.random.default_rng(34)
    main = _factory(kind)
    seg = SegmentedIndex(main, delta_cap=16, auto_merge=False)
    walks = []
    keys_attr = type(main).__dict__["keys"]
    if isinstance(keys_attr, property):  # the slab's
        spy = property(lambda self: walks.append(1) or keys_attr.fget(self))
    else:
        spy = lambda self: walks.append(1) or keys_attr(self)  # noqa: E731
    monkeypatch.setattr(type(main), "keys", spy)
    try:
        direct = [-(i + 1) for i in range(48)]
        _load_directly(kind, main, direct, _unit(rng, 48))
        assert len(seg) == 0 and len(main) == 48, "nothing told the segment layer yet"
        before = _rebuilds()
        first = [-(i + 1) for i in range(48, 64)]
        seg.add(_items(rng, first))
        assert (len(walks), _rebuilds() - before) == (1, 1)
        second = [-(i + 1) for i in range(64, 96)]
        seg.add(_items(rng, second))
        seg.add(_items(rng, [f"live{i}" for i in range(16)]))
        assert (len(walks), _rebuilds() - before) == (1, 1), "a bulk load onto an index in step walked main's keys"
        monkeypatch.undo()
        _assert_membership(seg, set(direct + first + second) | {f"live{i}" for i in range(16)})
        assert seg.stats()["main_size"] == 112 and not seg._delta
    finally:
        seg.close()


@pytest.mark.parametrize("kind", ["hnsw", "sharded", "ivf"])
def test_segmented_bulk_upserts_and_duplicates_keep_the_length_right(kind):
    """A bulk load whose keys main already holds, and one that names a key
    twice, add no member twice."""
    rng = np.random.default_rng(35)
    seg = SegmentedIndex(_factory(kind), delta_cap=16, auto_merge=False)
    try:
        held = [f"k{i}" for i in range(20)]
        seg.add(_items(rng, held))
        seg.add(_items(rng, held[:16]))  # every key an upsert
        _assert_membership(seg, set(held))
        seg.add(_items(rng, [f"n{i}" for i in range(15)] + ["n0", "k1"]))  # n0 twice, k1 again
        _assert_membership(seg, set(held) | {f"n{i}" for i in range(15)})
        (hit,) = seg.search(_unit(rng, 1), 40)
        assert len({key for key, _ in hit}) == len(hit) == 35, "a key surfaced twice"
        _assert_membership(seg, set(held) | {f"n{i}" for i in range(15)})
    finally:
        seg.close()


@pytest.mark.parametrize("main", ["len_counts_one_more", "ivf_untrained"])
def test_segmented_main_that_miscounts_falls_to_the_rebuild(main):
    """A ``main`` whose ``len()`` does not count what its ``keys`` lists
    cannot be shown in step, so its bulk loads take the safe side: an IVF
    index before training buffers rows and counts a key buffered twice
    twice."""

    class Miscounting(HnswIndex):
        def __len__(self):
            return super().__len__() + 1

    rng = np.random.default_rng(36)
    seg = SegmentedIndex(
        Miscounting(D, metric="cos") if main == "len_counts_one_more" else _factory("ivf"),
        delta_cap=16,
        auto_merge=False,
    )
    try:
        before = _rebuilds()
        seg.add(_items(rng, [f"a{i}" for i in range(16)]))
        seg.add(_items(rng, [f"b{i}" for i in range(15)] + ["a0"]))  # a0 again
        seg.add(_items(rng, [f"c{i}" for i in range(16)]))
        assert _rebuilds() - before == (3 if main == "len_counts_one_more" else 2)
        assert set(seg.keys()) == set(seg._main_keys()) and len(seg) == 47 and "b9" in seg and "a0" in seg
    finally:
        seg.close()


def test_segmented_auto_merge_triggers():
    """Both merge triggers fire through the background maintenance
    thread: delta at capacity, then tombstones past the fraction."""
    rng = np.random.default_rng(2)
    seg = SegmentedIndex(
        HnswIndex(D, metric="cos"),
        delta_cap=8,
        tombstone_fraction=0.25,
        auto_merge=True,
    )
    try:
        x = _unit(rng, 64)
        for i in range(8):  # one-by-one: crosses delta_cap on the last add
            seg.add([(f"k{i}", x[i])])
        seg._maintenance.drain()
        assert seg.merges_total == 1
        assert not seg._delta and len(seg.main) == 8
        # grow main past the 16-tombstone floor (bulk path), delete a third
        seg.add([(f"k{i}", x[i]) for i in range(8, 64)])
        seg.remove([f"k{i}" for i in range(20)])
        seg._maintenance.drain()
        assert seg.merges_total == 2, seg.stats()
        assert len(seg.main) == 44 and not seg._tombs
        assert len(seg) == 44
    finally:
        seg.close()


# ---------------------------------------------------------------------------
# snapshot consistency and crash/rollback behavior


@pytest.mark.parametrize("kind", ["hnsw", "sharded"])
def test_segmented_state_dict_racing_merge_is_pre_merge_view(kind):
    """A checkpoint taken in the instant before a merge commits (the
    same window the chaos drill kills in) must serialize the pre-merge
    segmentation — frozen delta folded back — and restore cleanly."""
    rng = np.random.default_rng(3)
    seg = SegmentedIndex(_factory(kind), delta_cap=8, auto_merge=False)
    x = _unit(rng, 48)
    seg.add([(f"m{i}", x[i]) for i in range(32)])  # bulk -> main
    seg.add([(f"d{i}", x[32 + i]) for i in range(6)])  # delta
    seg.remove(["m0", "m1"])  # tombstones
    pre = seg.state_dict()
    pre_keys = set(seg.keys())

    captured = {}
    seg._pre_commit = lambda: captured.update(mid=seg.state_dict())
    seg.merge(wait=True)

    mid = captured["mid"]
    assert set(mid["delta_keys"]) == set(pre["delta_keys"])
    assert set(mid["tombstones"]) == set(pre["tombstones"])
    restored = SegmentedIndex(_factory(kind), delta_cap=8, auto_merge=False)
    restored.load_state_dict(mid)
    assert set(restored.keys()) == pre_keys
    # after the commit the same snapshot API returns the merged view
    post = seg.state_dict()
    assert not post["delta_keys"] and not post["tombstones"]
    assert len(seg.main) == len(pre_keys)
    assert set(seg.keys()) == pre_keys


def test_segmented_failed_merge_rolls_back_fully():
    """A merge that dies mid-flight must leave the index exactly as if
    it never started: delta + tombstones restored, not merging, and the
    next merge succeeds."""
    rng = np.random.default_rng(4)
    seg = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=8, auto_merge=False)
    x = _unit(rng, 40)
    seg.add([(f"m{i}", x[i]) for i in range(32)])
    seg.add([(f"d{i}", x[32 + i]) for i in range(5)])
    seg.remove(["m2"])
    before_keys = set(seg.keys())
    before_hits = seg.search(x[:4], 3)

    def boom():
        raise RuntimeError("rebuild died")

    seg.main.fresh = boom
    with pytest.raises(RuntimeError, match="rebuild died"):
        seg.merge(wait=True)
    assert seg.merge_failures == 1 and not seg._merging
    assert set(seg.keys()) == before_keys
    assert len(seg._delta) == 5 and seg._tombs == {"m2"}
    assert seg.search(x[:4], 3) == before_hits

    del seg.main.fresh  # restore the real rebuild path
    seg.merge(wait=True)
    assert seg.merges_total == 1 and not seg._delta and not seg._tombs
    assert set(seg.keys()) == before_keys


def test_segmented_upsert_during_merge_wins_over_frozen():
    """An upsert landing between a merge's freeze and its commit goes to
    the LIVE delta and must shadow the frozen (about-to-be-merged) value
    for every query — before the commit, after it, and after the next
    merge folds it into main."""
    rng = np.random.default_rng(5)
    seg = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=8, auto_merge=False)
    old = _unit(rng)[0]
    new = -old  # opposite direction: shadowing failures are unmissable
    seg.add([("k", old)])
    # the hook runs in the pre-commit window; the lock is re-entrant
    seg._pre_commit = lambda: seg.add([("k", new)])
    seg.merge(wait=True)
    del seg._pre_commit
    assert len(seg) == 1
    (res,) = seg.search(new[None, :], 1)
    assert res[0][0] == "k" and res[0][1] > 0.99, res
    seg.merge(wait=True)  # folds the winning value into main
    assert not seg._delta
    (res,) = seg.search(new[None, :], 1)
    assert res[0][0] == "k" and res[0][1] > 0.99, res


# ---------------------------------------------------------------------------
# deletes racing a merge: the frozen delta must never resurrect them


@pytest.mark.parametrize("kind", ["hnsw", "sharded"])
def test_segmented_remove_frozen_key_mid_merge(kind):
    """A key whose latest value lives in the FROZEN delta, deleted while
    the merge is in flight, must be invisible for the whole merge window
    (search), must serialize as deleted (a checkpoint taken in the
    window restores without it), and must stay gone after the commit and
    after every later merge — the exactly-once guarantee."""
    rng = np.random.default_rng(11)
    seg = SegmentedIndex(_factory(kind), delta_cap=8, auto_merge=False)
    x = _unit(rng, 48)
    seg.add([(f"m{i}", x[i]) for i in range(32)])  # bulk -> main
    seg.add([("victim", x[40]), ("d0", x[41]), ("d1", x[42])])  # delta

    captured = {}

    def in_window():
        # the merge has frozen the delta but not committed: delete the
        # frozen-delta key NOW (the re-entrant lock admits us)
        seg.remove(["victim"])
        (hits,) = seg.search(x[40][None, :], 8)
        captured["mid_hits"] = {key for key, _ in hits}
        captured["mid_state"] = seg.state_dict()

    seg._pre_commit = in_window
    seg.merge(wait=True)
    del seg._pre_commit

    # invisible inside the merge window, in search AND in the snapshot
    assert "victim" not in captured["mid_hits"]
    mid = captured["mid_state"]
    assert "victim" not in set(mid["delta_keys"]), (
        "mid-merge checkpoint serialized the deleted key's frozen copy"
    )
    # gone after the commit
    assert "victim" not in seg
    (hits,) = seg.search(x[40][None, :], 8)
    assert "victim" not in {key for key, _ in hits}
    # the NEXT merge (which retires the tombstone) must not fold the
    # frozen vector back into main — the review's resurrection path
    seg.merge(wait=True)
    assert "victim" not in seg and "victim" not in set(seg.keys())
    (hits,) = seg.search(x[40][None, :], 8)
    assert "victim" not in {key for key, _ in hits}
    assert {"d0", "d1"} <= set(seg.keys())

    # a checkpoint taken in the window restores WITHOUT the key, and
    # merging the restored index does not resurrect it either
    restored = SegmentedIndex(_factory(kind), delta_cap=8, auto_merge=False)
    restored.load_state_dict(mid)
    assert "victim" not in restored
    (hits,) = restored.search(x[40][None, :], 8)
    assert "victim" not in {key for key, _ in hits}
    restored.merge(wait=True)
    assert "victim" not in restored and "victim" not in set(restored.keys())
    (hits,) = restored.search(x[40][None, :], 8)
    assert "victim" not in {key for key, _ in hits}
    assert set(restored.keys()) == set(seg.keys())


def test_segmented_remove_between_freeze_and_rebuild_fold():
    """A delete landing in the instant between the freeze and the
    rebuild reading the frozen delta: the rebuild must not fold the
    deleted key into the new main."""
    holder: dict = {}

    class Sneaky(HnswIndex):
        @property
        def merge_strategy(self):  # read by _run_merge right after freeze
            seg = holder.get("seg")
            if (
                seg is not None
                and "victim" in seg._frozen
                and "victim" in seg._keys
            ):
                seg.remove(["victim"])
            return "rebuild"

    rng = np.random.default_rng(12)
    seg = SegmentedIndex(Sneaky(D, metric="cos"), delta_cap=4, auto_merge=False)
    holder["seg"] = seg
    x = _unit(rng, 8)
    # bulk load keeps the Sneaky instance as main (a rebuild would swap
    # in a plain HnswIndex via fresh() and disarm the trigger)
    seg.add([(f"m{i}", x[i]) for i in range(4)])
    assert len(seg.main) == 4 and isinstance(seg.main, Sneaky)
    seg.add([("victim", x[6]), ("d9", x[7])])
    seg.merge(wait=True)  # property deletes victim post-freeze
    assert "victim" not in seg
    assert "victim" not in {k for k in seg.main.keys()}, (
        "rebuild folded a post-freeze-deleted frozen key into main"
    )
    (hits,) = seg.search(x[6][None, :], 8)
    assert "victim" not in {key for key, _ in hits}
    seg.merge(wait=True)
    assert "victim" not in seg


def test_segmented_failed_merge_rollback_preserves_deletes():
    """A delete issued while a merge is in flight must survive that
    merge FAILING: the rollback folds the frozen delta back into the
    live segment but must not revive the deleted keys."""
    rng = np.random.default_rng(13)
    seg = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=8, auto_merge=False)
    x = _unit(rng, 40)
    seg.add([(f"m{i}", x[i]) for i in range(16)])  # bulk -> main
    seg.add([("victim", x[20]), ("d0", x[21]), ("d1", x[22])])

    def boom():
        # between freeze and commit: delete a frozen-delta key and a
        # main key, then die
        seg.remove(["victim", "m1"])
        raise RuntimeError("rebuild died")

    seg.main.fresh = boom
    with pytest.raises(RuntimeError, match="rebuild died"):
        seg.merge(wait=True)
    assert seg.merge_failures == 1 and not seg._merging
    assert "victim" not in seg and "m1" not in seg
    assert "victim" not in seg._delta, "rollback revived a deleted key"
    assert {"d0", "d1"} <= set(seg._delta)
    (hits,) = seg.search(x[20][None, :], 16)
    found = {key for key, _ in hits}
    assert "victim" not in found and "m1" not in found

    del seg.main.fresh  # the next merge succeeds and retires tombstones
    seg.merge(wait=True)
    assert not seg._tombs and not seg._delta
    assert "victim" not in seg and "m1" not in seg
    (hits,) = seg.search(x[20][None, :], 16)
    found = {key for key, _ in hits}
    assert "victim" not in found and "m1" not in found
    assert {"d0", "d1"} <= found


def test_segmented_load_state_dict_delete_wins_on_conflict():
    """Checkpoints written before the delta-view fix can carry a key in
    both delta_keys and tombstones; loading one must treat the key as
    deleted."""
    rng = np.random.default_rng(14)
    seg = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=8, auto_merge=False)
    x = _unit(rng, 4)
    seg.add([("a", x[0]), ("b", x[1])])
    state = seg.state_dict()
    state["tombstones"] = list(state["tombstones"]) + ["b"]  # conflict
    fresh = SegmentedIndex(HnswIndex(D, metric="cos"), delta_cap=8, auto_merge=False)
    fresh.load_state_dict(state)
    assert "a" in fresh and "b" not in fresh
    (hits,) = fresh.search(x[1][None, :], 4)
    assert "b" not in {key for key, _ in hits}
    fresh.merge(wait=True)
    assert "b" not in fresh


# ---------------------------------------------------------------------------
# concurrency: queries off the segment lock vs live updates and merges


@pytest.mark.parametrize("kind", ["hnsw", "sharded"])
def test_segmented_concurrent_queries_and_updates(kind):
    """Searcher threads hammer the index while the main thread upserts,
    deletes and auto-merges (background maintenance thread): no
    exception may escape, and the final membership must track the
    reference exactly.  Exercises the off-lock main search, _main_mutex
    exclusion around in-place merges, and the defensive slot decode."""
    seg = SegmentedIndex(_factory(kind), delta_cap=16, auto_merge=True)
    rng = np.random.default_rng(15)
    ref: dict[str, np.ndarray] = {}
    errors: list[BaseException] = []
    stop = threading.Event()

    def searcher(seed):
        srng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                seg.search(_unit(srng, 2), 3)
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=searcher, args=(100 + i,)) for i in range(2)]
    for t in threads:
        t.start()
    try:
        next_id = 0
        for step in range(30):
            items = []
            for _ in range(6):
                key = f"k{next_id}"
                next_id += 1
                vec = _unit(rng)[0]
                items.append((key, vec))
                ref[key] = vec
            seg.add(items)
            if ref and step % 3 == 2:
                victims = [
                    str(v)
                    for v in rng.choice(
                        sorted(ref), size=min(4, len(ref)), replace=False
                    )
                ]
                seg.remove(victims + ["absent"])
                for v in victims:
                    del ref[v]
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        seg.close()
    assert not errors, f"concurrent search raised: {errors[:3]}"
    assert set(seg.keys()) == set(ref)
    q = _unit(rng, 8)
    r = _recall(seg, ref, q)
    assert r >= 0.95, f"post-churn recall {r:.3f} < 0.95"


def test_sharded_handle_across_load_state_dict_raises():
    """load_state_dict replaces the slot->key map wholesale, so a
    dispatch handle from before the restore must be rejected (its
    generation gates the decode) instead of resolving to wrong keys."""
    rng = np.random.default_rng(16)
    idx = ShardedKnnIndex(D, metric="cos", capacity=128)
    x = _unit(rng, 8)
    idx.add_batch([f"a{i}" for i in range(8)], x)
    state = idx.state_dict()
    handle = idx.dispatch(x[:1], 1)
    idx.load_state_dict(state)
    assert idx._inflight == 0 and not idx._quarantine
    with pytest.raises(RuntimeError, match="stale dispatch handle"):
        idx.collect(handle)
    # post-restore dispatches decode against the fresh map
    rows = idx.collect(idx.dispatch(x[:1], 1))
    assert rows[0][0][0] == "a0"
    assert idx._inflight == 0


# ---------------------------------------------------------------------------
# HNSW satellites: absent-key delete, tombstone compaction


def test_hnsw_remove_absent_key_is_noop():
    idx = HnswIndex(D, metric="cos")
    idx.remove(["ghost"])  # empty index
    assert len(idx) == 0
    rng = np.random.default_rng(6)
    x = _unit(rng, 4)
    idx.add([(f"k{i}", x[i]) for i in range(4)])
    idx.remove(["ghost", "k1", "ghost2"])  # mixed live/absent
    assert len(idx) == 3 and "k1" not in idx
    idx.remove(["k1"])  # double delete
    assert len(idx) == 3


def test_hnsw_compaction_reclaims_tombstoned_slots():
    """Deleting past ``tombstone_fraction`` of the slot high-water mark
    must rebuild the graph: dead slots reclaimed, survivors searchable."""
    from pathway_tpu.internals import native as _native

    if _native.load() is None:
        pytest.skip("native module unavailable: no slots to compact")
    rng = np.random.default_rng(7)
    idx = HnswIndex(D, metric="cos", tombstone_fraction=0.33)
    x = _unit(rng, 128)
    idx.add([(i, x[i]) for i in range(128)])
    assert idx._hw == 128 and idx.compactions == 0
    idx.remove(list(range(0, 128, 3)))  # ~33% dead: below the strict bound
    dead_now = idx._hw - len(idx._slot_of)
    if dead_now > 0:  # not yet compacted: push past the fraction
        idx.remove(list(range(1, 128, 3)))
    assert idx.compactions >= 1, (idx._hw, len(idx))
    assert idx._hw == len(idx._slot_of), "compaction left dead slots"
    survivors = sorted(idx.keys())
    res = idx.search(x[survivors[0]][None, :], 1)
    assert res[0][0][0] == survivors[0]
    # the counter the stats/metrics surface report
    assert idx.stats()["compactions"] == idx.compactions


# ---------------------------------------------------------------------------
# sharded slab satellite: dispatch handles across _grow


def test_sharded_pre_grow_handle_stays_valid():
    """A dispatch handle taken before a capacity grow must collect to
    the keys live at dispatch time: the handle's computation captured
    the pre-grow buffers and the generation tag in the handle keeps it
    from being confused with the new slab."""
    rng = np.random.default_rng(8)
    idx = ShardedKnnIndex(D, metric="cos", capacity=128)
    assert idx.capacity == 128
    x = _unit(rng, 100)
    idx.add_batch([f"a{i}" for i in range(100)], x)
    v0 = idx._version

    handle = idx.dispatch(x[:3], 1)
    # outstanding handle; now force a realloc with a second corpus
    y = _unit(rng, 64)
    idx.add_batch([f"b{i}" for i in range(64)], y)
    assert idx.capacity > 128 and idx._version > v0
    assert handle[3] == v0, "handle lost its pre-grow generation tag"

    rows = idx.collect(handle)
    assert [r[0][0] for r in rows] == ["a0", "a1", "a2"]
    # a post-grow dispatch sees the union
    rows2 = idx.collect(idx.dispatch(y[:1], 1))
    assert rows2[0][0][0] == "b0"


def test_sharded_remove_during_flight_quarantines_slot():
    """A slot freed while a handle is in flight must not be reused (and
    decoded to the wrong key) until every outstanding handle resolves."""
    rng = np.random.default_rng(9)
    idx = ShardedKnnIndex(D, metric="cos", capacity=128)
    x = _unit(rng, 8)
    idx.add_batch([f"a{i}" for i in range(8)], x)
    handle = idx.dispatch(x[:1], 2)
    idx.remove(["a5"])
    assert idx._quarantine and not idx._free
    idx.add_batch(["fresh"], _unit(rng))  # must NOT take a5's slot
    assert idx._slot_of["fresh"] not in idx._quarantine
    rows = idx.collect(handle)
    assert rows[0][0][0] == "a0"
    assert not idx._quarantine, "quarantine not drained after last collect"
    assert idx._free, "freed slot lost instead of returned to the pool"
