"""Device-resident sharded brute-force KNN index.

TPU re-design of the reference's Rust BruteForce KNN
(``src/external_integration/brute_force_knn_integration.rs:22-120``):
instead of a host ``Array2<f64>`` with scalar distance loops, the corpus
lives in TPU HBM as a fixed-capacity slab sharded row-wise over the mesh
``"data"`` axis.  Live upserts never recompile:

- slots are assigned host-side (freelist); updates are jitted donated
  scatters with the update batch padded to a power-of-two bucket and
  out-of-range pad slots dropped (``mode="drop"``);
- capacity grows 2x like the reference (``:115-119``) — a rare,
  amortized host-side realloc;
- queries: one ``[nq, d] @ [d, cap/shard]`` MXU matmul per shard +
  local top-k, then a k-sized ``all_gather`` over ICI and a final
  top-k — the network moves ``O(shards * k)`` per query, never the
  score matrix.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from pathway_tpu.internals import device_counters as _devctr
from pathway_tpu.internals import tracing as _tracing
from pathway_tpu.ops.bucketing import bucket_size, pad_rows
from pathway_tpu.ops.distances import dot_scores, l2sq_distances, normalize
from pathway_tpu.ops.topk import NEG_INF
from pathway_tpu.parallel.mesh import require_single_process

__all__ = ["ShardedKnnIndex"]

_MIN_SHARD_ROWS = 128  # one MXU tile of rows per shard minimum


class ShardedKnnIndex:
    """Incremental vector index with add/remove/search.

    metric: "cos" (cosine over L2-normalized vectors), "dot", or "l2sq".
    Keys are arbitrary hashable host objects; the device only sees slots.
    """

    # segment merges mutate the slab in place (remove+upsert scatters)
    merge_strategy = "inplace"

    def __init__(
        self,
        dim: int,
        *,
        metric: str = "cos",
        capacity: int = 1024,
        mesh: Mesh | None = None,
        data_axis: str = "data",
        dtype: Any = jnp.float32,
    ):
        if metric not in ("cos", "dot", "l2sq"):
            raise ValueError(f"unknown metric {metric!r}")
        require_single_process("ShardedKnnIndex")
        self.dim = dim
        self.metric = metric
        self.mesh = mesh
        self.data_axis = data_axis
        self.dtype = dtype
        self.shards = mesh.shape[data_axis] if mesh is not None else 1
        self.capacity = self._round_capacity(capacity)

        self._vec_sharding = (
            NamedSharding(mesh, P(data_axis, None)) if mesh is not None else None
        )
        self._valid_sharding = (
            NamedSharding(mesh, P(data_axis)) if mesh is not None else None
        )
        self._vectors = self._device_zeros((self.capacity, dim), dtype, self._vec_sharding)
        self._valid = self._device_zeros((self.capacity,), jnp.float32, self._valid_sharding)

        self._slot_of: dict[Any, int] = {}
        self._key_of: dict[int, Any] = {}
        self._free: list[int] = []
        self._cursor = 0  # next never-used slot
        self._search_cache: dict[tuple[int, int], Callable] = {}
        # freed slots are quarantined while dispatch handles are in flight,
        # so collect() never resolves a reused slot to the wrong key
        self._inflight = 0
        self._quarantine: list[int] = []
        # buffer generation: bumped on every realloc (_grow and
        # load_state_dict).  collect() branches on the generation in the
        # handle: anything at or past _reset_version decodes against the
        # live map (slot numbering is append-only across grows and freed
        # slots are quarantined), while a handle from before the last
        # load_state_dict is rejected — the slot->key map was replaced
        # wholesale, so decoding it would silently return wrong keys.
        self._version = 0
        self._reset_version = 0

    # ------------------------------------------------------------------
    def _round_capacity(self, cap: int) -> int:
        unit = self.shards * _MIN_SHARD_ROWS
        return max(unit, ((cap + unit - 1) // unit) * unit)

    @staticmethod
    def _device_zeros(shape, dtype, sharding):
        if sharding is None:
            return jnp.zeros(shape, dtype)
        return jax.device_put(np.zeros(shape, np.dtype(dtype)), sharding)

    def __len__(self) -> int:
        return len(self._slot_of)

    def __contains__(self, key: Any) -> bool:
        return key in self._slot_of

    @property
    def keys(self) -> list:
        return list(self._slot_of)

    # ------------------------------------------------------------------
    # updates

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _scatter_set(vectors, valid, slots, vals):
        vectors = vectors.at[slots].set(vals, mode="drop")
        valid = valid.at[slots].set(1.0, mode="drop")
        return vectors, valid

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0,))
    def _scatter_clear(valid, slots):
        return valid.at[slots].set(0.0, mode="drop")

    # non-donating twins: used whenever a dispatch handle is in flight —
    # donating would hand the searched buffers' memory to the scatter
    # output while the async search may still read them (satellite fix:
    # growth/updates under concurrent dispatch)
    @staticmethod
    @jax.jit
    def _scatter_set_safe(vectors, valid, slots, vals):
        vectors = vectors.at[slots].set(vals, mode="drop")
        valid = valid.at[slots].set(1.0, mode="drop")
        return vectors, valid

    @staticmethod
    @jax.jit
    def _scatter_clear_safe(valid, slots):
        return valid.at[slots].set(0.0, mode="drop")

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(4,))
    def _scatter_set_device_safe(vectors, valid, slots, vals, normalize):
        vals = vals.astype(jnp.float32)
        if normalize:
            n = jnp.linalg.norm(vals, axis=1, keepdims=True)
            vals = vals / jnp.maximum(n, 1e-30)
        vals = vals.astype(vectors.dtype)
        vectors = vectors.at[slots].set(vals, mode="drop")
        valid = valid.at[slots].set(1.0, mode="drop")
        return vectors, valid

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4,))
    def _scatter_set_device(vectors, valid, slots, vals, normalize):
        # normalize/cast on device: the device-resident ingest path never
        # moves the embeddings across the host link
        vals = vals.astype(jnp.float32)
        if normalize:
            n = jnp.linalg.norm(vals, axis=1, keepdims=True)
            vals = vals / jnp.maximum(n, 1e-30)
        vals = vals.astype(vectors.dtype)
        vectors = vectors.at[slots].set(vals, mode="drop")
        valid = valid.at[slots].set(1.0, mode="drop")
        return vectors, valid

    def _assign_slots(self, keys: Sequence[Any], pad_to: int) -> np.ndarray:
        """Slot per key (allocating new slots as needed, growing the slab
        when full); rows beyond ``len(keys)`` pad with ``capacity`` so the
        scatter's mode="drop" ignores them.  The ONE copy of the
        free-list/cursor bookkeeping, shared by the host and device
        ingest paths."""
        slot_of = self._slot_of
        n_new = sum(1 for key in keys if key not in slot_of)
        while len(slot_of) + n_new > self.capacity:
            self._grow()
        slots = np.full(pad_to, self.capacity, np.int32)
        key_of = self._key_of
        free = self._free
        for i, key in enumerate(keys):
            slot = slot_of.get(key)
            if slot is None:
                slot = free.pop() if free else self._cursor
                if slot == self._cursor:
                    self._cursor += 1
                slot_of[key] = slot
                key_of[slot] = key
            slots[i] = slot
        return slots

    def add(self, items: Sequence[tuple[Any, np.ndarray]]) -> None:
        """Upsert (key, vector) pairs; one donated scatter per epoch batch."""
        if not items:
            return
        keys = [key for key, _v in items]
        vecs = np.stack([np.asarray(v, np.float32).reshape(-1) for _k, v in items])
        self.add_batch(keys, vecs)

    def add_batch(self, keys: Sequence[Any], vectors: np.ndarray) -> None:
        """Columnar upsert: ``keys`` aligned with rows of ``vectors`` [n, dim].

        The fast ingest path — normalization/cast are whole-array numpy ops
        and slot assignment is the only per-row host work, so host prep no
        longer bounds bulk-load throughput (it did when ``add`` took per-row
        tuples).
        """
        vectors = np.ascontiguousarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dim:
            raise ValueError(f"vectors shape {vectors.shape} != (n, {self.dim})")
        n = len(keys)
        if n != vectors.shape[0]:
            raise ValueError(f"{n} keys vs {vectors.shape[0]} vectors")
        if n == 0:
            return
        b = bucket_size(n)
        with _tracing.span("slab_assign_slots"):
            slots = self._assign_slots(keys, pad_to=b)
        with _tracing.span("slab_scatter"):
            if self.metric == "cos":
                norms = np.linalg.norm(vectors, axis=1, keepdims=True)
                np.maximum(norms, 1e-30, out=norms)
                vectors = vectors / norms
            vals = vectors.astype(np.dtype(self.dtype), copy=False)
            vals = pad_rows(vals, b)
            _devctr.record_h2d(vals.nbytes + slots.nbytes)
            _devctr.bump(scatter_dispatches=1, scatter_rows=n, scatter_rows_padded=b)
            scatter = self._scatter_set if self._inflight == 0 else self._scatter_set_safe
            self._vectors, self._valid = scatter(
                self._vectors, self._valid, jnp.asarray(slots), jnp.asarray(vals)
            )

    def add_batch_device(
        self, keys: Sequence[Any], vectors: Any, n_valid: int | None = None
    ) -> None:
        """Upsert from a DEVICE array [b, dim] (an encoder's output)
        without reading the embeddings back to the host: slot assignment
        is the only host work; normalization, dtype cast and the scatter
        all run on device.  Rows at index >= len(keys) (encoder padding)
        scatter to an out-of-range slot and are dropped.

        The reference's embed+index pipeline round-trips every embedding
        through host memory (python/pathway/xpacks/llm/embedders.py:
        270-327 -> index add); on a TPU the vector store lives in the
        same HBM the encoder writes to, so the round trip is pure waste.
        """
        n = len(keys) if n_valid is None else n_valid
        b = int(vectors.shape[0])
        if int(vectors.shape[1]) != self.dim:
            raise ValueError(f"vectors dim {vectors.shape[1]} != {self.dim}")
        if n > b:
            raise ValueError(f"{n} keys but only {b} vector rows")
        with _tracing.span("slab_assign_slots"):
            slots = self._assign_slots(keys, pad_to=b)
        with _tracing.span("slab_scatter"):
            _devctr.bump(scatter_dispatches=1, scatter_rows=n, scatter_rows_padded=b)
            scatter = (
                self._scatter_set_device
                if self._inflight == 0
                else self._scatter_set_device_safe
            )
            self._vectors, self._valid = scatter(
                self._vectors,
                self._valid,
                jnp.asarray(slots),
                vectors,
                self.metric == "cos",
            )

    def remove(self, keys: Sequence[Any]) -> None:
        slots = []
        for key in keys:
            slot = self._slot_of.pop(key, None)
            if slot is not None:
                self._key_of.pop(slot, None)
                if self._inflight > 0:
                    self._quarantine.append(slot)
                else:
                    self._free.append(slot)
                slots.append(slot)
        if not slots:
            return
        arr = pad_rows(np.asarray(slots, np.int32), bucket_size(len(slots)), fill=self.capacity)
        clear = self._scatter_clear if self._inflight == 0 else self._scatter_clear_safe
        self._valid = clear(self._valid, jnp.asarray(arr))

    def _grow(self) -> None:
        """2x capacity realloc (host roundtrip; rare and amortized)."""
        new_cap = self._round_capacity(self.capacity * 2)
        host_vec = np.zeros((new_cap, self.dim), np.dtype(self.dtype))
        host_valid = np.zeros((new_cap,), np.float32)
        host_vec[: self.capacity] = np.asarray(self._vectors)
        host_valid[: self.capacity] = np.asarray(self._valid)
        self.capacity = new_cap
        # in-flight handles keep referencing the pre-grow buffers (their
        # computations captured them); bump the generation so they are
        # identifiable and never confused with the new slab
        self._version += 1
        self._vectors = (
            jax.device_put(host_vec, self._vec_sharding)
            if self._vec_sharding is not None
            else jnp.asarray(host_vec)
        )
        self._valid = (
            jax.device_put(host_valid, self._valid_sharding)
            if self._valid_sharding is not None
            else jnp.asarray(host_valid)
        )

    # ------------------------------------------------------------------
    # search

    def _score_fn(self) -> Callable:
        metric = self.metric
        if metric == "l2sq":
            return lambda q, v: -l2sq_distances(q, v)
        return dot_scores  # cos vectors are pre-normalized at add time

    def _search_jit(self, k: int):
        # keyed on (k, capacity): growth changes shard_rows baked into the
        # sharded program
        cached = self._search_cache.get((k, self.capacity))
        if cached is not None:
            return cached
        score = self._score_fn()
        normalize_q = self.metric == "cos"

        if self.mesh is None:

            @jax.jit
            def run(q, vectors, valid):
                if normalize_q:
                    q = normalize(q)
                s = score(q.astype(vectors.dtype), vectors)
                s = jnp.where(valid.astype(bool)[None, :], s, NEG_INF)
                return jax.lax.top_k(s, k)

            self._search_cache[(k, self.capacity)] = run
            return run

        axis = self.data_axis
        mesh = self.mesh
        shard_rows = self.capacity // self.shards

        def local(q, vectors, valid):
            # per-shard block: vectors [cap/shards, d], valid [cap/shards]
            if normalize_q:
                q = normalize(q)
            s = score(q.astype(vectors.dtype), vectors)
            s = jnp.where(valid.astype(bool)[None, :], s, NEG_INF)
            kk = min(k, shard_rows)
            ls, li = jax.lax.top_k(s, kk)  # [nq, kk]
            li = li + jax.lax.axis_index(axis) * shard_rows
            gs = jax.lax.all_gather(ls, axis)  # [shards, nq, kk] over ICI
            gi = jax.lax.all_gather(li, axis)
            nq = q.shape[0]
            gs = jnp.transpose(gs, (1, 0, 2)).reshape(nq, -1)
            gi = jnp.transpose(gi, (1, 0, 2)).reshape(nq, -1)
            vals, pos = jax.lax.top_k(gs, k)
            return vals, jnp.take_along_axis(gi, pos, axis=1)

        shmapped = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(self.data_axis, None), P(self.data_axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        run = jax.jit(shmapped)
        self._search_cache[(k, self.capacity)] = run
        return run

    def dispatch(self, queries: np.ndarray, k: int):
        """Asynchronously dispatch a search; returns an opaque handle.
        Dispatches pipeline on-device without host sync — a serving loop
        can keep several in flight and pay the host link latency once."""
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        nq = queries.shape[0]
        if nq == 0 or not self._slot_of:
            return (None, nq, k, self._version, 0)
        # k is baked into the compiled program, and callers' k moves with
        # the corpus (the segment layer over-fetches by its mask size, the
        # adapter clamps to the live key count): bucket it like every
        # other dynamic dimension — collect() trims each row back to k
        k_eff = min(bucket_size(k, min_bucket=16), self.capacity)
        qb = pad_rows(queries, bucket_size(nq, min_bucket=1))
        _devctr.record_h2d(qb.nbytes)
        _devctr.bump(
            search_dispatches=1, search_queries=nq, search_queries_padded=qb.shape[0]
        )
        out = self._search_jit(k_eff)(jnp.asarray(qb), self._vectors, self._valid)
        ticket = _tracing.chip.ticket()  # collect() waits on it
        # start the device->host copy NOW, without blocking: the result
        # transfer then overlaps later dispatches, so a serving loop with
        # several handles in flight waits for the device once per
        # pipeline fill, not once per query
        for a in out:
            a.copy_to_host_async()
        self._inflight += 1
        return (out, nq, k, self._version, ticket)

    def collect(self, handle) -> list[list[tuple[Any, float]]]:
        """Resolve a :meth:`dispatch` handle to [[(key, score), ...], ...].

        Valid across a ``_grow``: the handle's computation captured the
        dispatch-time buffers, slot numbering is grow-stable, and freed
        slots stay quarantined while any handle is outstanding — so a
        pre-grow handle decodes to exactly the keys that were live when
        it was dispatched.  NOT valid across ``load_state_dict``: that
        replaces the slot->key map wholesale, so the generation recorded
        in the handle gates the decode and a pre-restore handle raises
        instead of resolving to arbitrary wrong keys."""
        out, nq, k, version, ticket = handle
        if out is None:
            return [[] for _ in range(nq)]
        if version < self._reset_version:
            raise RuntimeError(
                "stale dispatch handle: the index was restored via "
                "load_state_dict after this dispatch; slot numbering is "
                "only stable across capacity grows, not restores"
            )
        self._inflight = max(0, self._inflight - 1)
        if self._inflight == 0 and self._quarantine:
            self._free.extend(self._quarantine)
            self._quarantine.clear()
        # one host readback for both arrays (each device_get is a full
        # host<->device round trip; they dominate single-query latency)
        with _tracing.span("search_readback"):
            vals, idx = jax.device_get(out)
            _tracing.chip.collected(ticket)
        _devctr.record_d2h(vals.nbytes + idx.nbytes)
        vals = vals[:nq]
        idx = idx[:nq]
        rows: list[list[tuple[Any, float]]] = []
        for qi in range(nq):
            row = []
            for slot, score in zip(idx[qi], vals[qi]):
                if score <= float(NEG_INF) / 2:
                    continue
                key = self._key_of.get(int(slot))
                if key is not None:
                    row.append((key, float(score)))
            rows.append(row[:k])
        return rows

    def search(
        self, queries: np.ndarray, k: int
    ) -> list[list[tuple[Any, float]]]:
        """Top-k per query: [[(key, score), ...], ...].  Scores: higher =
        closer for cos/dot; for l2sq the NEGATED squared distance."""
        return self.collect(self.dispatch(queries, k))

    # ------------------------------------------------------------------
    # persistence support

    def state_dict(self) -> dict:
        return {
            "dim": self.dim,
            "metric": self.metric,
            "capacity": self.capacity,
            "vectors": np.asarray(self._vectors),
            "valid": np.asarray(self._valid),
            "slot_of": dict(self._slot_of),
            "cursor": self._cursor,
            "free": list(self._free) + list(self._quarantine),
        }

    def load_state_dict(self, state: dict) -> None:
        self.capacity = self._round_capacity(state["capacity"])
        vec = np.zeros((self.capacity, self.dim), np.dtype(self.dtype))
        val = np.zeros((self.capacity,), np.float32)
        vec[: state["vectors"].shape[0]] = state["vectors"]
        val[: state["valid"].shape[0]] = state["valid"]
        self._vectors = (
            jax.device_put(vec, self._vec_sharding)
            if self._vec_sharding is not None
            else jnp.asarray(vec)
        )
        self._valid = (
            jax.device_put(val, self._valid_sharding)
            if self._valid_sharding is not None
            else jnp.asarray(val)
        )
        self._slot_of = dict(state["slot_of"])
        self._key_of = {s: k for k, s in self._slot_of.items()}
        self._cursor = state["cursor"]
        self._free = list(state["free"])
        # outstanding handles reference the pre-restore slot space:
        # invalidate them (collect() rejects their generation) and reset
        # the in-flight bookkeeping they would otherwise leak into
        self._version += 1
        self._reset_version = self._version
        self._inflight = 0
        self._quarantine = []
