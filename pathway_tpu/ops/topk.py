"""Masked top-k over score matrices."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["masked_top_k"]

# Python float, NOT a jnp device array: a device-resident constant
# captured by a jitted closure is an extra operand handed to every call,
# and importing this module would initialise a backend to hold it.
NEG_INF = -3.0e38


def masked_top_k(
    scores: jax.Array, valid: jax.Array | None, k: int
) -> tuple[jax.Array, jax.Array]:
    """Top-k column indices per row, ignoring columns where ``valid == 0``.

    scores [nq, n] (higher = better), valid [n] in {0,1} or None.
    Returns (values [nq, k], indices [nq, k]); masked-out slots surface
    as values <= NEG_INF/2 so callers can drop them.
    """
    s = scores.astype(jnp.float32)
    if valid is not None:
        s = jnp.where(valid.astype(bool)[None, :], s, NEG_INF)
    return jax.lax.top_k(s, k)
