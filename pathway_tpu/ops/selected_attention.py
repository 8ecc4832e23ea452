"""Attention of a block of queries over the keys a mask selects, fused.

``selected_attention`` is the prefill form of sparse latent attention once
keys and values stand expanded per head: scores ``q_nope . k_nope + q_rope .
k_rope`` (the rope part of a key is shared by all heads), a softmax over the
keys ``selected`` marks, and the weighted sum of the values.  Written with
``jax.numpy`` alone this is bound by memory, not by the MXU: every
``[heads, queries, keys]`` block of float32 scores goes out to HBM and is
read back three times (the running maximum, the exponentials, the product
with the values).  The kernel keeps a ``[block_q, block_k]`` tile of scores
of a head in VMEM from the first product to the last, with the running
maximum, the normaliser and the accumulator in scratch across the key blocks
(the usual online softmax), so HBM sees the operands and the result.

The queries of a call are a prompt chunk that starts at ``start`` of its
sequence, ``length`` of its rows real and the rest padding.  They are cut
into tiles of ``block_q`` rows, and a tile visits only the key blocks its own
last row can see (:func:`query_tiles`): the chunk's upper triangle is not
scored, and a tile that holds no real row visits none and writes zeros.  The
grid is ``(heads / BLOCK_H, steps)``, ``BLOCK_H`` heads a step (they share
the selection's tile): the steps are the (query tile, key block) pairs of
that schedule in a flattened list, tile by tile and each tile's blocks from
the first, one step for a tile of padding, so no step of the grid is idle;
the list comes in as scalar-prefetch arrays and sets the grid's length.
Every row sees its key blocks in the same order, and a block past a tile's
last is one the causal bound masks from each of its rows (which would have
left them as they were), so a real row's result is what attention over
every block would give.  The kernel is bound by the vector unit, not the MXU
(a head's score tile is 131,072 exponentials), so what it does per score
is kept to an add, a maximum, a subtraction and the exponential: the
softmax scale comes in the queries, and the selection as a bfloat16 tile
that is added (0 or -1e30).

``grouped_attention`` is the same online softmax for grouped-query
attention: one key and one value per K/V head, each read once by a grid step
of the query heads that share it, and a sliding window.  A query tile then
starts at the first key block its first row's window reaches (the twin of
its causal end, :func:`window_tiles`), so a window layer multiplies about the
window and a block of keys a row, not the whole prefix.  Both kernels share
the flattened schedule; the latent one's tiles start at the first block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selected_attention", "query_tiles", "grouped_attention", "window_tiles"]

#: rows of a query tile (the key block's or the chunk's where either has
#: fewer) and heads a grid step (a divisor of the heads where they are not a
#: multiple): tiles of 256 x 512 scores, eight heads a step, measured on a
#: v5e at the answer cells' chunks against 128, 512 and 1,024 rows and 1, 2
#: and 4 heads a step (PERF.md §6, PR 38)
BLOCK_Q, BLOCK_H = 256, 8

#: what an unselected key's score is moved by, and where the running maximum
#: starts: far enough below any score that ``exp`` gives an exact zero, and the
#: floor above the moved scores so that a tile with no selected key leaves a row
#: as it found it
_MASKED, _FLOOR = -1e30, -1e29


def query_tiles(start, length, chunk: int, block_q: int = BLOCK_Q, block_k: int = 512):
    """The schedule of a chunk of ``chunk`` queries at ``start`` with
    ``length`` real rows: the rows of a query tile, and for each tile how many
    key blocks of ``block_k`` from the first it visits -- those its last row
    can see, none where every row of it is padding.  A tile multiplies
    ``rows * block_k`` query-key pairs a block it visits."""
    rows = min(block_q, block_k, chunk)
    if chunk % rows:
        raise ValueError(f"{chunk} queries are not a multiple of the query tile {rows}")
    i = jnp.arange(chunk // rows, dtype=jnp.int32)
    seen = (start + (i + 1) * rows + block_k - 1) // block_k
    return rows, jnp.where(i * rows < length, seen, 0).astype(jnp.int32)


def window_tiles(start, length, chunk: int, window: int | None, first_key=0, block_q: int = BLOCK_Q, block_k: int = 512):
    """:func:`query_tiles` with a lower bound: query row ``t`` of the chunk is
    key ``start + t`` and sees the keys ``first_key`` and up, and where
    ``window`` is given only the ``window`` keys up to its own.  Returns the
    rows of a tile, the first key block each tile visits (the one its first
    row's window reaches) and the end of its blocks (:func:`query_tiles`'s
    count from the first block, 0 for a tile of padding)."""
    rows, ends = query_tiles(start, length, chunk, block_q, block_k)
    i = jnp.arange(chunk // rows, dtype=jnp.int32)
    lowest = jnp.maximum(start + i * rows - window + 1, first_key) if window else jnp.full(i.shape, first_key, jnp.int32)
    return rows, jnp.where(ends > 0, lowest // block_k, 0).astype(jnp.int32), ends


def _accumulate(g, s, v, top_ref, mass_ref, acc_ref):
    """One head's tile of scores ``s`` (the mask already added) into its
    running maximum, normaliser and weighted sum of the values ``v``."""
    top = top_ref[g]
    new_top = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - new_top)  # an unselected key: exp(-1e30 - top) = 0
    shrink = jnp.exp(top - new_top)
    mass_ref[g] = mass_ref[g] * shrink + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[g] = acc_ref[g] * shrink + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    top_ref[g] = new_top


def _online_softmax(j, first, n, scores_into, o_ref, top_ref, mass_ref, acc_ref):
    """The steps of one query tile: the scratch set at its first block,
    ``scores_into`` over every block it visits, the result written at its
    last, zeros for a tile of padding (``n`` 0)."""

    @pl.when(j == first)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _FLOOR, jnp.float32)
        mass_ref[...] = jnp.zeros(mass_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    pl.when(j < n)(scores_into)

    @pl.when(j == n - 1)
    def _():
        o_ref[...] = (acc_ref[...] / mass_ref[...]).astype(o_ref.dtype)

    @pl.when(n == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


_CONTRACT_LAST = (((1,), (1,)), ((), ()))


def _kernel(tile_ref, block_ref, visits_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, bias_ref, o_ref, top_ref, mass_ref, acc_ref):
    step = pl.program_id(1)

    def scores_into():
        bias = bias_ref[...].astype(jnp.float32)
        for g in range(qn_ref.shape[0]):
            s = jax.lax.dot_general(qn_ref[g], kn_ref[g], _CONTRACT_LAST, preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(qr_ref[g], kr_ref[...], _CONTRACT_LAST, preferred_element_type=jnp.float32)
            _accumulate(g, s + bias, v_ref[g], top_ref, mass_ref, acc_ref)

    _online_softmax(block_ref[step], 0, visits_ref[tile_ref[step]], scores_into, o_ref, top_ref, mass_ref, acc_ref)


def _grouped_kernel(tile_ref, block_ref, first_ref, ends_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, top_ref, mass_ref, acc_ref):
    step = pl.program_id(1)
    tile = tile_ref[step]

    def scores_into():
        bias = bias_ref[...].astype(jnp.float32)
        for g in range(q_ref.shape[0]):  # the query heads of one K/V head: its block is read once for all
            s = jax.lax.dot_general(q_ref[g], k_ref[0], _CONTRACT_LAST, preferred_element_type=jnp.float32)
            _accumulate(g, s + bias, v_ref[0], top_ref, mass_ref, acc_ref)

    _online_softmax(block_ref[step], first_ref[tile], ends_ref[tile], scores_into, o_ref, top_ref, mass_ref, acc_ref)


def _steps(visits, key_blocks: int, first=None):
    """The flattened schedule: for each step of a grid as long as the list,
    the query tile and the key block (the list's length, which sets the
    grid's, is the third value: entries past it are never visited).  A tile
    visits its blocks from ``first`` (the first block where not given) to
    its end ``visits``; a tile of padding gets one step, its key block
    pinned to the block before the largest end (padding ends a chunk, so
    that is the last real tile's last), which fetches nothing new."""
    tiles = visits.shape[0]
    first = jnp.zeros_like(visits) if first is None else first
    per_tile = jnp.maximum(visits - first, 1)
    ends = jnp.cumsum(per_tile)
    s = jnp.arange(tiles * key_blocks, dtype=jnp.int32)
    tile = jnp.sum(s[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    block = jnp.where(visits[tile] > 0, first[tile] + s - (ends - per_tile)[tile], jnp.max(visits) - 1).astype(jnp.int32)
    return tile, block, ends[-1]


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def selected_attention(q_nope, q_rope, k_nope, k_rope, v, selected, start, length, *, block_q: int = BLOCK_Q, block_k: int = 512, interpret: bool = False):
    """``q_nope`` [H, C, dn] and ``q_rope`` [H, C, dr], the softmax scale
    already in them; ``k_nope`` [H, L, dn], ``k_rope`` [L, dr], ``v`` [H, L,
    dv]; ``selected`` [C, L] bool: the query attends to the key, inside the
    causal bound (query ``t`` is position ``start + t`` and selects no key
    after it); ``start`` and ``length`` int32 scalars: the chunk's first
    position and its real rows.  Every query selects at least one key.
    Returns [H, C, dv] in ``v``'s type: a real row's attention, zeros in a
    query tile of padding (a row of padding in a tile with a real one has
    its own attention)."""
    H, C, dn = q_nope.shape
    L, dr = k_rope.shape
    dv = v.shape[-1]
    if L % block_k:
        raise ValueError(f"{L} keys are not a multiple of the key block {block_k}")
    hb = math.gcd(H, BLOCK_H)
    rows, visits = query_tiles(start, length, C, block_q, block_k)
    tile, block, steps = _steps(visits, L // block_k)
    bias = jnp.where(selected, 0.0, _MASKED).astype(jnp.bfloat16)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(H // hb, steps),
        in_specs=[
            pl.BlockSpec((hb, rows, dn), lambda h, s, tile, block, visits: (h, tile[s], 0)),
            pl.BlockSpec((hb, rows, dr), lambda h, s, tile, block, visits: (h, tile[s], 0)),
            pl.BlockSpec((hb, block_k, dn), lambda h, s, tile, block, visits: (h, block[s], 0)),
            pl.BlockSpec((block_k, dr), lambda h, s, tile, block, visits: (block[s], 0)),
            pl.BlockSpec((hb, block_k, dv), lambda h, s, tile, block, visits: (h, block[s], 0)),
            pl.BlockSpec((rows, block_k), lambda h, s, tile, block, visits: (tile[s], block[s])),
        ],
        out_specs=pl.BlockSpec((hb, rows, dv), lambda h, s, tile, block, visits: (h, tile[s], 0)),
        scratch_shapes=[pltpu.VMEM((hb, rows, 1), jnp.float32), pltpu.VMEM((hb, rows, 1), jnp.float32), pltpu.VMEM((hb, rows, dv), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, C, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=96 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=2 * H * C * L * (dn + dr + dv), transcendentals=H * C * L,
            bytes_accessed=2 * (H * C * (dn + dr + dv) + H * L * (dn + dv) + L * dr) + 2 * H * C * L,
        ),
        name="selected_attention",
        interpret=interpret,
    )(tile, block, visits, q_nope, q_rope, k_nope, k_rope, v, bias)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def grouped_attention(q, k, v, visible, start, first_key, length, *, window: int | None = None, block_q: int = BLOCK_Q, block_k: int = 512, interpret: bool = False):
    """``q`` [H, C, d], the softmax scale already in it; ``k`` and ``v`` [G,
    L, d], query head ``j`` reading K/V head ``j // (H / G)``; ``visible``
    [C, L] bool: the query attends to the key; ``start``, ``first_key`` and
    ``length`` int32 scalars: query row ``t`` is key ``start + t``, no key
    before ``first_key`` is visible to any row, ``length`` rows are real.
    ``visible`` marks no key after a row's own, none before ``first_key``
    and, with ``window``, none ``window`` or more before it; every row sees
    its own.  Returns [H, C, d] in ``v``'s type: a real row's attention,
    zeros in a query tile of padding."""
    H, C, d = q.shape
    G, L, _ = k.shape
    if H % G:
        raise ValueError(f"{H} query heads do not share {G} K/V heads evenly")
    if L % block_k:
        raise ValueError(f"{L} keys are not a multiple of the key block {block_k}")
    rows, first, ends = window_tiles(start, length, C, window, first_key, block_q, block_k)
    tile, block, steps = _steps(ends, L // block_k, first)
    bias = jnp.where(visible, 0.0, _MASKED).astype(jnp.bfloat16)
    hq = H // G
    index = lambda h, s, tile, block, first, ends: (h, tile[s], 0)
    key_index = lambda h, s, tile, block, first, ends: (h, block[s], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G, steps),
        in_specs=[
            pl.BlockSpec((hq, rows, d), index),
            pl.BlockSpec((1, block_k, d), key_index),
            pl.BlockSpec((1, block_k, d), key_index),
            pl.BlockSpec((rows, block_k), lambda h, s, tile, block, first, ends: (tile[s], block[s])),
        ],
        out_specs=pl.BlockSpec((hq, rows, d), index),
        scratch_shapes=[pltpu.VMEM((hq, rows, 1), jnp.float32), pltpu.VMEM((hq, rows, 1), jnp.float32), pltpu.VMEM((hq, rows, d), jnp.float32)],
    )
    seen = C * min(L, (window or L) + block_k)  # about the pairs the schedule multiplies a head
    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, C, d), v.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=96 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=4 * H * seen * d, transcendentals=H * seen, bytes_accessed=2 * (2 * H * C * d + 2 * G * L * d) + 2 * seen,
        ),
        name="selected_attention",
        interpret=interpret,
    )(tile, block, first, ends, q, k, v, bias)
