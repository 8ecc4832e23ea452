"""Attention of a block of queries over the keys a mask selects, fused.

``selected_attention`` is the prefill form of sparse latent attention once
keys and values stand expanded per head: scores ``q_nope . k_nope + q_rope .
k_rope`` (the rope part of a key is shared by all heads), a softmax over the
keys ``selected`` marks, and the weighted sum of the values.  Written with
``jax.numpy`` alone this is bound by memory, not by the MXU: every
``[heads, queries, keys]`` block of float32 scores goes out to HBM and is
read back three times (the running maximum, the exponentials, the product
with the values).  The kernel keeps a ``[queries, block_k]`` tile of one
head's scores in VMEM from the first product to the last, with the running
maximum, the normaliser and the accumulator in scratch across the key
blocks (the usual online softmax), so HBM sees the operands and the result.

Grid ``(heads, key blocks)``, key blocks innermost.  ``key_blocks`` (a
scalar, prefetched) says how many key blocks any query of the call can see:
later grid steps neither compute nor fetch (their block index is pinned to
the last visible one).  All queries of a call are one block: a prompt chunk.
The kernel is bound by the vector unit, not the MXU (a score tile is a
million exponentials), so what it does per score is kept to an add, a
maximum, a subtraction and the exponential: the softmax scale comes in the
queries, and the selection as a bfloat16 tile that is added (0 or -1e30).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selected_attention"]

#: what an unselected key's score is moved by, and where the running maximum
#: starts: far enough below any score that ``exp`` gives an exact zero, and the
#: floor above the moved scores so that a tile with no selected key leaves a row
#: as it found it
_MASKED, _FLOOR = -1e30, -1e29


def _kernel(nkb_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, bias_ref, o_ref, top_ref, mass_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _FLOOR, jnp.float32)
        mass_ref[...] = jnp.zeros(mass_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < nkb_ref[0])
    def _():
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(qn_ref[0], kn_ref[0], contract_last, preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[...], contract_last, preferred_element_type=jnp.float32)
        s = s + bias_ref[...].astype(jnp.float32)
        top = top_ref[...]
        new_top = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - new_top)  # an unselected key: exp(-1e30 - top) = 0
        shrink = jnp.exp(top - new_top)
        mass_ref[...] = mass_ref[...] * shrink + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jnp.dot(p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
        top_ref[...] = new_top

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / mass_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def selected_attention(q_nope, q_rope, k_nope, k_rope, v, selected, key_blocks, *, block_k: int = 512, interpret: bool = False):
    """``q_nope`` [H, C, dn] and ``q_rope`` [H, C, dr], the softmax scale
    already in them; ``k_nope`` [H, L, dn], ``k_rope`` [L, dr], ``v`` [H, L,
    dv]; ``selected`` [C, L] bool: the query attends to the key;
    ``key_blocks`` int32 scalar: how many blocks of ``block_k`` keys, from
    the first, hold every selected key.  Every query selects at least one
    key.  Returns [H, C, dv] in ``v``'s type."""
    H, C, dn = q_nope.shape
    L, dr = k_rope.shape
    dv = v.shape[-1]
    if L % block_k:
        raise ValueError(f"{L} keys are not a multiple of the key block {block_k}")
    bias = jnp.where(selected, 0.0, _MASKED).astype(jnp.bfloat16)
    pinned = lambda j, nkb: jnp.minimum(j, nkb[0] - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, L // block_k),
        in_specs=[
            pl.BlockSpec((1, C, dn), lambda h, j, nkb: (h, 0, 0)),
            pl.BlockSpec((1, C, dr), lambda h, j, nkb: (h, 0, 0)),
            pl.BlockSpec((1, block_k, dn), lambda h, j, nkb: (h, pinned(j, nkb), 0)),
            pl.BlockSpec((block_k, dr), lambda h, j, nkb: (pinned(j, nkb), 0)),
            pl.BlockSpec((1, block_k, dv), lambda h, j, nkb: (h, pinned(j, nkb), 0)),
            pl.BlockSpec((C, block_k), lambda h, j, nkb: (0, pinned(j, nkb))),
        ],
        out_specs=pl.BlockSpec((1, C, dv), lambda h, j, nkb: (h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((C, 1), jnp.float32), pltpu.VMEM((C, 1), jnp.float32), pltpu.VMEM((C, dv), jnp.float32)],
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
    )(jnp.reshape(key_blocks, (1,)).astype(jnp.int32), q_nope, q_rope, k_nope, k_rope, v, bias)
