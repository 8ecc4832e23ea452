"""The routed experts' feed-forward over rows grouped by expert, one weight
read per expert.

``grouped_experts`` is the prompt-chunk form of a layer's expert product
(:func:`pathway_tpu.models.decoder._experts_here` lays the rows out).  The
token-expert pairs come ordered by expert, each expert's run padded to whole
tiles of ``block`` rows, so a tile belongs to one expert; a grid step is one
tile, and the expert of each tile, the tile each step reads and writes, and
the number of tiles in use come in as scalar-prefetch arrays.  An expert's
matrices are one block each, whole, indexed by the expert of the step's
tile: consecutive tiles of one expert ask for the same block, so the pipeline
fetches it once for the run, while the tile before the run computes.  Steps
past the tiles in use repeat the last tile's blocks (nothing is fetched) and
compute nothing.  A row's result is its gate times the expert's
``activation(x, matrices, dtype)``: a padding row carries a zero gate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_experts"]


@functools.partial(jax.jit, static_argnames=("activation", "dtype", "block", "vmem_bytes", "interpret"))
def grouped_experts(x, gates, tile_expert, tile_at, used, experts, *, activation, dtype, block: int, vmem_bytes: int, interpret: bool = False):
    """``x`` [P, hidden] (``P / block`` tiles, each one expert's rows),
    ``gates`` [P, 1] float32, ``tile_expert`` / ``tile_at`` [P / block] int32
    (the expert of each grid step's tile and the tile it reads and writes),
    ``used`` [1] int32 (the steps that compute), ``experts`` a tree of
    ``[experts, ...]`` matrices.  Returns [P, hidden] float32: ``gates *
    activation(x, the tile's expert, dtype)`` on the tiles in use; the rows
    of a tile past them are left as they were."""
    P, H = x.shape
    leaves, tree = jax.tree.flatten(experts)

    def kernel(expert_ref, at_ref, used_ref, x_ref, g_ref, *refs):
        *w_refs, o_ref = refs

        @pl.when(pl.program_id(0) < used_ref[0])
        def _():
            p = jax.tree.unflatten(tree, [w[...] for w in w_refs])
            o_ref[...] = activation(x_ref[...], p, dtype) * g_ref[...]

    rows = lambda i, e, t, n: (t[i], 0)
    weight = lambda i, e, t, n: (e[i], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(P // block,),
        in_specs=[pl.BlockSpec((block, H), rows), pl.BlockSpec((block, 1), rows)]
        + [pl.BlockSpec((None, *w.shape[1:]), weight) for w in leaves],
        out_specs=pl.BlockSpec((block, H), rows),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name="grouped_experts",
    )(tile_expert, tile_at, used, x, gates, *leaves)
