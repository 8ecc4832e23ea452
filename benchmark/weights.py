"""Seeds and filler rows, made on the device in jitted calls.

The benchmark makes the weights and the filler, not the program.  Each model
family draws its own parameter tree (``families/<family>.py``) from
``seed_key``; the filler rows of the slab are drawn here.

The seed enters as two uint32 words (any whole number up to 2**64 fits),
traced, so that one compiled program serves every seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative whole number and a stream id."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("rows", "dim"))
def _filler_block(key, block, *, rows, dim):
    x = jax.random.normal(jax.random.fold_in(key, block), (rows, dim), jnp.float32)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def filler_block(seed: int, block: int, rows: int, dim: int):
    """Block ``block`` of the filler: ``rows`` unit vectors, float32, on the
    device.  Same seed and block, same rows: the slab and the reference each
    call this and share nothing else."""
    return _filler_block(seed_key(seed, stream=2), jnp.uint32(block), rows=rows, dim=dim)
