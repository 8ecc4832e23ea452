"""The selective state-space recurrence of a chunk of tokens, fused.

``h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t^T`` and ``y_t = h_t C_t + D
x_t`` for every channel of a Mamba layer: ``channels x states`` independent
first-order recurrences over the tokens, whose coefficients depend on the
token.  Written as a per-token ``lax.scan`` it is one dispatch of a few small
fusions a token; as an associative scan the ``[tokens, channels, states]``
coefficients go out to HBM and come back several times.  The kernel keeps the
state of 1,024 channels on chip (``states`` tiles of 8 x 128 in registers
across a block of tokens, in VMEM scratch between blocks), so HBM sees ``x``,
``dt``, ``B``, ``C`` once and ``y`` once.

Grid ``(channel blocks, token blocks)``, token blocks innermost.  A token of
a channel block is one 8 x 128 tile (``x`` and ``dt`` come in as ``[tokens,
channels / 1024, 8, 128]``: a row is read by its number, whole); ``B_t`` and
``C_t`` are scalars a state, read from SMEM and broadcast.  What the kernel
does a token, state and tile is a product, an exponential and five more
vector operations: the exponentials and the vector unit bound it, not HBM.

:func:`selective_scan` takes the plain shapes and runs the kernel on a TPU
(``interpret=True``: the same kernel interpreted, for the tests) and
:func:`selective_scan_reference` elsewhere: the same recurrence as a per-token
``lax.scan`` in ``jax.numpy``.  A token whose ``dt`` is 0 leaves the state as
it found it (``exp(0) = 1``, nothing added): how a caller masks padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan", "selective_scan_reference", "CHANNEL_BLOCK"]

#: channels a grid step holds: one 8 x 128 float32 tile a state
CHANNEL_BLOCK = 1024


def _kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref, y_ref, hT_ref, h_ref, *, states: int, tokens: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        h_ref[...] = h0_ref[:, 0]

    a = [a_ref[s, 0] for s in range(states)]
    d = d_ref[0]

    def token(t, h):
        dt, x = dt_ref[t, 0], x_ref[t, 0]
        dx, y, new = dt * x, d * x, []
        for s in range(states):
            hs = jnp.exp(dt * a[s]) * h[s] + dx * b_ref[t * states + s]
            y = y + hs * c_ref[t * states + s]
            new.append(hs)
        y_ref[t, 0] = y
        return tuple(new)

    h = jax.lax.fori_loop(0, tokens, token, tuple(h_ref[s] for s in range(states)))
    for s in range(states):
        h_ref[s] = h[s]

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        hT_ref[:, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def selective_scan(x, dt, a, b, c, d, h0, *, block_t: int = 256, interpret: bool = False):
    """``x``, ``dt`` [T, channels] float32; ``a`` [states, channels] (negative);
    ``b``, ``c`` [T, states]; ``d`` [channels]; ``h0`` [states, channels]:
    the state before the first token.  Returns ``y`` [T, channels] and the
    state after the last token, float32.  ``channels`` is a multiple of
    :data:`CHANNEL_BLOCK` and ``T`` of ``block_t``."""
    T, channels = x.shape
    states = a.shape[0]
    block_t = min(block_t, T)
    if channels % CHANNEL_BLOCK or T % block_t:
        raise ValueError(f"{channels} channels are not a multiple of {CHANNEL_BLOCK}, or {T} tokens of the token block {block_t}")
    n = channels // CHANNEL_BLOCK
    tiles = lambda v: v.astype(jnp.float32).reshape(*v.shape[:-1], n, 8, 128)
    per_token = pl.BlockSpec((block_t, 1, 8, 128), lambda i, j: (j, i, 0, 0))
    per_state = pl.BlockSpec((states, 1, 8, 128), lambda i, j: (0, i, 0, 0))
    scalars = pl.BlockSpec((block_t * states,), lambda i, j: (j,), memory_space=pltpu.SMEM)
    y, hT = pl.pallas_call(
        functools.partial(_kernel, states=states, tokens=block_t),
        grid=(n, T // block_t),
        in_specs=[scalars, scalars, per_token, per_token, per_state, pl.BlockSpec((1, 8, 128), lambda i, j: (i, 0, 0)), per_state],
        out_specs=[per_token, per_state],
        out_shape=[jax.ShapeDtypeStruct((T, n, 8, 128), jnp.float32), jax.ShapeDtypeStruct((states, n, 8, 128), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((states, 8, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * T * channels * states, transcendentals=T * channels * states, bytes_accessed=4 * (3 * T * channels + 2 * T * states),
        ),
        name="selective_scan",
        interpret=interpret,
    )(b.astype(jnp.float32).reshape(-1), c.astype(jnp.float32).reshape(-1), tiles(x), tiles(dt), tiles(a), tiles(d), tiles(h0))
    return y.reshape(T, channels), hT.reshape(states, channels)


def selective_scan_reference(x, dt, a, b, c, d, h0):
    """:func:`selective_scan`'s arguments and results, a token at a time."""

    def token(h, row):  # a, d: the float32 ones bound below
        xt, dtt, bt, ct = row
        h = jnp.exp(dtt[None, :] * a) * h + (dtt * xt)[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0) + d * xt

    f32 = lambda v: v.astype(jnp.float32)
    a, d = f32(a), f32(d)
    hT, y = jax.lax.scan(token, f32(h0), (f32(x), f32(dt), f32(b), f32(c)))
    return y, hT
