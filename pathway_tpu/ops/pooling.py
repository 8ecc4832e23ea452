"""Mask-aware sequence pooling for sentence encoders."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["masked_mean_pool", "cls_pool", "packed_cls_pool", "packed_mean_pool"]


def masked_mean_pool(hidden: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean over valid positions. hidden [B, L, H], mask [B, L] {0,1}."""
    m = mask.astype(jnp.float32)[..., None]
    summed = jnp.sum(hidden.astype(jnp.float32) * m, axis=1)
    counts = jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return (summed / counts).astype(hidden.dtype)


def cls_pool(hidden: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """First-token ([CLS]) pooling."""
    return hidden[:, 0, :]


# Packed rows (``JittedEncoder._pack``): several texts lie end to end in one
# row, ``segments`` [B, L] numbers a row's texts from 1 (0 is padding) and
# ``first`` [T] is each text's first token as an index into the flattened
# [B * L] tokens, in the caller's order.


def packed_cls_pool(hidden: jax.Array, first: jax.Array) -> jax.Array:
    """Each text's first token. hidden [B, L, H], first [T] -> [T, H]."""
    return hidden.reshape(-1, hidden.shape[-1])[first]


def packed_mean_pool(
    hidden: jax.Array, segments: jax.Array, first: jax.Array
) -> jax.Array:
    """Mean over each text's own tokens -> [T, H].  One [T, B * L] 0/1
    product instead of a gather of [T, L, H]: exact, since every weight is
    0 or 1 and the sum is taken in float32 as :func:`masked_mean_pool`'s."""
    length = hidden.shape[1]
    seg = segments.reshape(-1)
    row = jnp.arange(seg.shape[0]) // length
    own = (row[None, :] == (first // length)[:, None]) & (
        seg[None, :] == seg[first][:, None]
    )
    summed = jnp.einsum(
        "tj,jh->th",
        own.astype(hidden.dtype),
        hidden.reshape(-1, hidden.shape[-1]),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    counts = jnp.maximum(jnp.sum(own, axis=1, keepdims=True), 1)
    return (summed / counts).astype(hidden.dtype)
