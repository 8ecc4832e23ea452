"""IVF-flat approximate KNN, TPU-shaped.

A real ANN structure behind the ``UsearchKnn`` API (reference HNSW:
``src/external_integration/usearch_integration.rs:1-163``).  HNSW's
pointer-chasing graph walk is hostile to XLA (dynamic, scalar, branchy),
so the TPU re-design is an inverted-file index instead — the classic
matmul-friendly ANN:

- ``nlist`` k-means centroids live in HBM; assignment of a vector (or a
  query) to cells is one ``[n, d] @ [d, nlist]`` MXU matmul.
- vectors are stored GROUPED BY CELL in a static ``[nlist, cell_cap, d]``
  slab — static shapes, no recompilation on upserts; per-cell freelists
  are host-side.
- a query scans only its ``nprobe`` closest cells: ``jnp.take`` gathers
  those cells' rows (reads ``nprobe/nlist`` of the corpus from HBM
  instead of all of it — the whole point of IVF at 10M+ scale), then one
  einsum + top-k.  Queries are processed in fixed sub-batches via
  ``lax.map`` so the gather buffer stays bounded.
- cell overflow grows ``cell_cap`` 2x (amortized, like the reference's
  2x index growth); k-means (re)training is a few jitted Lloyd
  iterations on a sample.

Exactness contract: approximate — recall depends on nprobe/nlist and how
clustered the data is (tests assert recall@10 >= 0.95 on mixture data
with the defaults).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.internals import device_counters as _devctr
from pathway_tpu.ops.bucketing import bucket_size, pad_rows
from pathway_tpu.ops.topk import NEG_INF
from pathway_tpu.parallel.mesh import require_single_process

__all__ = ["IvfKnnIndex"]


@jax.jit
def _assign_ip(x, c):
    """Nearest centroid by inner product: [n, d] x [nlist, d] -> [n]."""
    return jnp.argmax(x @ c.T, axis=1)


def _kmeans(
    data: np.ndarray, nlist: int, iters: int = 8, seed: int = 0
) -> np.ndarray:
    """A few Lloyd iterations, assignment on device (one matmul/iter)."""
    rng = np.random.default_rng(seed)
    n = data.shape[0]
    cents = data[rng.choice(n, size=min(nlist, n), replace=False)].copy()
    if cents.shape[0] < nlist:  # degenerate: fewer points than cells
        cents = np.concatenate(
            [cents, rng.normal(size=(nlist - cents.shape[0], data.shape[1]))]
        ).astype(np.float32)

    @jax.jit
    def assign(x, c):
        # nearest centroid by L2 == max (c.x - |c|^2/2)
        scores = x @ c.T - 0.5 * jnp.sum(c * c, axis=1)[None, :]
        return jnp.argmax(scores, axis=1)

    xd = jnp.asarray(data)
    for _ in range(iters):
        a = np.asarray(assign(xd, jnp.asarray(cents)))
        for ci in range(nlist):
            members = data[a == ci]
            if len(members):
                cents[ci] = members.mean(axis=0)
            else:  # dead cell: re-seed on a random point
                cents[ci] = data[rng.integers(n)]
    return cents.astype(np.float32)


class IvfKnnIndex:
    """Incremental IVF-flat index with add/remove/search.

    metric: "cos" (vectors L2-normalized at add time) or "dot".
    Keys are arbitrary hashable host objects; the device sees (cell, slot).
    """

    # segment merges mutate the cell slabs in place (remove+upsert)
    merge_strategy = "inplace"

    def __init__(
        self,
        dim: int,
        *,
        metric: str = "cos",
        capacity: int = 1024,
        nlist: int | None = None,
        nprobe: int | None = None,
        train_size: int = 50_000,
        query_block: int = 8,
        dtype: Any = jnp.bfloat16,
        seed: int = 0,
    ):
        if metric not in ("cos", "dot"):
            raise ValueError(f"unsupported IVF metric {metric!r}")
        require_single_process("IvfKnnIndex")
        self.dim = dim
        self.metric = metric
        self.dtype = dtype
        self.seed = seed
        self.train_size = train_size
        self.query_block = query_block
        self.nlist = nlist or max(16, 1 << int(np.log2(max(capacity, 2) ** 0.5)))
        self.nprobe = nprobe or max(1, self.nlist // 8)
        self.cell_cap = max(
            64, bucket_size(4 * max(1, capacity // self.nlist))
        )

        self._centroids: Any = None  # [nlist, d] device
        self._cells = jnp.zeros((self.nlist, self.cell_cap, dim), dtype)
        self._valid = jnp.zeros((self.nlist, self.cell_cap), jnp.float32)
        # host bookkeeping
        self._slot_of: dict[Any, tuple[int, int]] = {}  # key -> (cell, slot)
        self._key_of: dict[tuple[int, int], Any] = {}
        self._free: list[list[int]] = [[] for _ in range(self.nlist)]
        self._cursor = np.zeros(self.nlist, np.int64)  # next fresh slot per cell
        self._pending: list[tuple[Any, np.ndarray]] = []  # rows awaiting training
        self._search_cache: dict[tuple, Callable] = {}

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        return len(self._slot_of) + len(self._pending)

    def __contains__(self, key: Any) -> bool:
        return key in self._slot_of or any(k == key for k, _v in self._pending)

    def keys(self) -> list:
        seen = list(self._slot_of)
        seen.extend(k for k, _v in self._pending if k not in self._slot_of)
        return seen

    @property
    def trained(self) -> bool:
        return self._centroids is not None

    def _normalize(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.ascontiguousarray(vectors, np.float32)
        if self.metric == "cos":
            norms = np.linalg.norm(vectors, axis=1, keepdims=True)
            np.maximum(norms, 1e-30, out=norms)
            vectors = vectors / norms
        return vectors

    def train(self, sample: np.ndarray | None = None) -> None:
        """Fit centroids; flushes any rows buffered before training.

        Re-training a populated index re-inserts every stored vector, so
        cell placement always matches the centroids used for probing —
        refitting without re-assigning would silently collapse recall."""
        if sample is None:
            if not self._pending:
                raise ValueError("nothing to train on")
            sample = np.stack([v for _k, v in self._pending])
        sample = self._normalize(sample)
        if sample.shape[0] > self.train_size:
            rng = np.random.default_rng(self.seed)
            sample = sample[
                rng.choice(sample.shape[0], size=self.train_size, replace=False)
            ]
        stored: list[tuple[Any, np.ndarray]] = []
        if self._slot_of:
            host_cells = np.asarray(self._cells, np.float32)
            for key, (ci, slot) in self._slot_of.items():
                stored.append((key, host_cells[ci, slot]))
            self._cells = jnp.zeros_like(self._cells)
            self._valid = jnp.zeros_like(self._valid)
            self._slot_of.clear()
            self._key_of.clear()
            self._free = [[] for _ in range(self.nlist)]
            self._cursor[:] = 0
        self._centroids = jnp.asarray(_kmeans(sample, self.nlist, seed=self.seed))
        pending, self._pending = self._pending, []
        for keys_vecs in (stored, pending):
            if keys_vecs:
                self.add_batch(
                    [k for k, _ in keys_vecs],
                    np.stack([v for _, v in keys_vecs]),
                )

    # ------------------------------------------------------------------
    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _scatter_set(cells, valid, cell_idx, slot_idx, vals):
        cells = cells.at[cell_idx, slot_idx].set(vals, mode="drop")
        valid = valid.at[cell_idx, slot_idx].set(1.0, mode="drop")
        return cells, valid

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0,))
    def _scatter_clear(valid, cell_idx, slot_idx):
        return valid.at[cell_idx, slot_idx].set(0.0, mode="drop")

    def _assign_cells(self, vectors: np.ndarray) -> np.ndarray:
        # cos/dot: nearest centroid by inner product (centroids come from
        # normalized data for cos).  Rows pad to a power-of-two bucket so
        # arbitrary batch sizes reuse a logarithmic set of compiled
        # programs (pad rows are zeros; their assignment is sliced off)
        n = vectors.shape[0]
        vpad = pad_rows(np.ascontiguousarray(vectors, np.float32), bucket_size(n))
        return np.asarray(_assign_ip(jnp.asarray(vpad), self._centroids))[:n]

    def add(self, items: Sequence[tuple[Any, np.ndarray]]) -> None:
        if not items:
            return
        keys = [k for k, _v in items]
        vecs = np.stack([np.asarray(v, np.float32).reshape(-1) for _k, v in items])
        self.add_batch(keys, vecs)

    def add_batch(self, keys: Sequence[Any], vectors: np.ndarray) -> None:
        vectors = self._normalize(vectors)
        if vectors.ndim != 2 or vectors.shape[1] != self.dim:
            raise ValueError(f"vectors shape {vectors.shape} != (n, {self.dim})")
        keys = list(keys)
        if len(keys) != vectors.shape[0]:
            raise ValueError(f"{len(keys)} keys vs {vectors.shape[0]} vectors")
        # duplicate keys within one batch: keep the LAST occurrence only
        # (upsert semantics) — otherwise two live slots map to one key and
        # remove() would leave an orphan forever searchable
        last = {key: i for i, key in enumerate(keys)}
        if len(last) != len(keys):
            sel = sorted(last.values())
            keys = [keys[i] for i in sel]
            vectors = vectors[sel]
        if self._centroids is None:
            # buffer until trained; auto-train once the buffer is useful
            self._pending.extend(zip(keys, vectors))
            if len(self._pending) >= max(self.nlist * 8, 1024):
                self.train()
            return
        # upserts: drop existing placements first (cell may change)
        existing = [k for k in keys if k in self._slot_of]
        if existing:
            self.remove(existing)
        cells = self._assign_cells(vectors)
        # overflow check (host counts; grow doubles cell_cap for all cells)
        counts = np.bincount(cells, minlength=self.nlist)
        for ci in np.nonzero(counts)[0]:
            while (
                self._cursor[ci] - len(self._free[ci]) + counts[ci] > self.cell_cap
            ):
                self._grow()
        slots = np.empty(len(keys), np.int32)
        for i, (key, ci) in enumerate(zip(keys, cells)):
            ci = int(ci)
            free = self._free[ci]
            slot = free.pop() if free else int(self._cursor[ci])
            if slot == self._cursor[ci]:
                self._cursor[ci] += 1
            slots[i] = slot
            self._slot_of[key] = (ci, slot)
            self._key_of[(ci, slot)] = key
        b = bucket_size(len(keys))
        cell_idx = pad_rows(cells.astype(np.int32), b, fill=self.nlist)  # dropped
        slot_idx = pad_rows(slots, b, fill=self.cell_cap)
        vals = pad_rows(vectors.astype(np.dtype(self.dtype), copy=False), b)
        self._cells, self._valid = self._scatter_set(
            self._cells,
            self._valid,
            jnp.asarray(cell_idx),
            jnp.asarray(slot_idx),
            jnp.asarray(vals),
        )

    def remove(self, keys: Sequence[Any]) -> None:
        cs, ss = [], []
        for key in keys:
            place = self._slot_of.pop(key, None)
            if place is None:
                # may still be sitting in the pre-training buffer
                self._pending = [(k, v) for k, v in self._pending if k != key]
                continue
            ci, slot = place
            self._key_of.pop(place, None)
            self._free[ci].append(slot)
            cs.append(ci)
            ss.append(slot)
        if not cs:
            return
        b = bucket_size(len(cs))
        cell_idx = pad_rows(np.asarray(cs, np.int32), b, fill=self.nlist)
        slot_idx = pad_rows(np.asarray(ss, np.int32), b, fill=self.cell_cap)
        self._valid = self._scatter_clear(
            self._valid, jnp.asarray(cell_idx), jnp.asarray(slot_idx)
        )

    def _grow(self) -> None:
        """Double cell_cap (host roundtrip; rare and amortized)."""
        new_cap = self.cell_cap * 2
        host_cells = np.zeros((self.nlist, new_cap, self.dim), np.dtype(self.dtype))
        host_valid = np.zeros((self.nlist, new_cap), np.float32)
        host_cells[:, : self.cell_cap] = np.asarray(self._cells)
        host_valid[:, : self.cell_cap] = np.asarray(self._valid)
        self.cell_cap = new_cap
        self._cells = jnp.asarray(host_cells)
        self._valid = jnp.asarray(host_valid)
        self._search_cache.clear()

    # ------------------------------------------------------------------
    def _search_jit(self, k: int, nprobe: int):
        sig = (k, nprobe, self.cell_cap, self.query_block)
        cached = self._search_cache.get(sig)
        if cached is not None:
            return cached
        qb = self.query_block
        cell_cap = self.cell_cap

        @jax.jit
        def run(queries, cents, cells, valid):
            # queries pre-padded to a multiple of qb: [nq, d]
            def block(qblk):
                # [qb, d] -> probe cells -> gather -> score -> top-k
                cscore = qblk @ cents.T  # [qb, nlist]
                _, probe = jax.lax.top_k(cscore, nprobe)  # [qb, nprobe]
                sub = jnp.take(cells, probe, axis=0)  # [qb, nprobe, cap, d]
                subv = jnp.take(valid, probe, axis=0)  # [qb, nprobe, cap]
                s = jnp.einsum(
                    "qd,qpcd->qpc",
                    qblk.astype(sub.dtype),
                    sub,
                    preferred_element_type=jnp.float32,
                )
                s = jnp.where(subv.astype(bool), s, NEG_INF)
                s = s.reshape(qb, nprobe * cell_cap)
                vals, pos = jax.lax.top_k(s, k)
                # flat slab id = cell * cell_cap + slot
                flat = (
                    probe[:, :, None] * cell_cap
                    + jnp.arange(cell_cap)[None, None, :]
                ).reshape(qb, nprobe * cell_cap)
                ids = jnp.take_along_axis(flat, pos, axis=1)
                return vals, ids

            blocks = queries.reshape(-1, qb, queries.shape[-1])
            vals, ids = jax.lax.map(block, blocks)
            return vals.reshape(-1, k), ids.reshape(-1, k)

        self._search_cache[sig] = run
        return run

    def search(
        self, queries: np.ndarray, k: int, *, nprobe: int | None = None
    ) -> list[list[tuple[Any, float]]]:
        """Top-k per query: [[(key, score), ...], ...] (higher = closer)."""
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        nq = queries.shape[0]
        if nq == 0:
            return []
        if self._centroids is None:
            if self._pending:
                self.train()
            else:
                return [[] for _ in range(nq)]
        if self.metric == "cos":
            queries = self._normalize(queries)
        nprobe = min(nprobe or self.nprobe, self.nlist)
        k_eff = min(k, nprobe * self.cell_cap)
        # pad the BLOCK COUNT to a power of two, not just the row count to
        # a multiple of query_block: multiple-of-block padding still
        # compiles one program per distinct block count (linear in the
        # query-batch range), which is a recompile storm under mixed
        # serving batch sizes
        pad_q = self.query_block * bucket_size(
            -(-nq // self.query_block), min_bucket=1
        )
        qpad = pad_rows(queries, pad_q)
        _devctr.record_h2d(qpad.nbytes)
        run = self._search_jit(k_eff, nprobe)
        out = run(jnp.asarray(qpad), self._centroids, self._cells, self._valid)
        vals, ids = jax.device_get(out)
        _devctr.record_d2h(vals.nbytes + ids.nbytes)
        rows: list[list[tuple[Any, float]]] = []
        for qi in range(nq):
            row = []
            for flat, score in zip(ids[qi], vals[qi]):
                if score <= float(NEG_INF) / 2:
                    continue
                place = (int(flat) // self.cell_cap, int(flat) % self.cell_cap)
                key = self._key_of.get(place)
                if key is not None:
                    row.append((key, float(score)))
            rows.append(row[:k])
        return rows

    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        return {
            "dim": self.dim,
            "metric": self.metric,
            "nlist": self.nlist,
            "cell_cap": self.cell_cap,
            "centroids": (
                np.asarray(self._centroids) if self._centroids is not None else None
            ),
            "cells": np.asarray(self._cells),
            "valid": np.asarray(self._valid),
            "slot_of": dict(self._slot_of),
            "cursor": self._cursor.copy(),
            "free": [list(f) for f in self._free],
            "pending": [(k, np.asarray(v)) for k, v in self._pending],
        }

    def load_state_dict(self, state: dict) -> None:
        self.nlist = state["nlist"]
        self.cell_cap = state["cell_cap"]
        self._centroids = (
            jnp.asarray(state["centroids"]) if state["centroids"] is not None else None
        )
        self._cells = jnp.asarray(state["cells"])
        self._valid = jnp.asarray(state["valid"])
        self._slot_of = dict(state["slot_of"])
        self._key_of = {p: k for k, p in self._slot_of.items()}
        self._cursor = np.asarray(state["cursor"]).copy()
        self._free = [list(f) for f in state["free"]]
        self._pending = [(k, np.asarray(v)) for k, v in state["pending"]]
        self._search_cache.clear()
