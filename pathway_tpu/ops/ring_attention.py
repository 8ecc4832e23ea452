"""Ring attention: sequence-parallel exact attention over a device mesh.

Long-context support (SURVEY.md §5): sequences too long for one device's
memory are sharded over the mesh ``"data"`` axis; each device holds a
Q/K/V block and K/V blocks rotate around the ring via ``ppermute`` over
ICI while a flash-attention-style running softmax (m, l, o accumulators)
keeps the computation exact.  Memory per device is O(L_local^2-free):
only the current K/V block is resident.

Non-causal (encoder) attention by default — the document-embedding
workload — with an optional key padding mask; causal masking composes
via the block position offsets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "local_attention"]

_NEG = -1e30


def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Plain single-device attention. q/k/v: [B, L, H, D]; mask: [B, L]
    (key positions)."""
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = s + jnp.where(mask.astype(bool)[:, None, None, :], 0.0, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", p, v)


def _ring_body(q, k0, v0, mask0, axis_name: str, n_shards: int):
    """Runs on ONE device inside shard_map: q/k0/v0 are the local blocks."""
    b, l_local, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, _):
        o, m, l, k_cur, v_cur, mask_cur = carry
        s = jnp.einsum("blhd,bmhd->bhlm", q, k_cur).astype(jnp.float32) * scale
        s = s + jnp.where(mask_cur.astype(bool)[:, None, None, :], 0.0, _NEG)
        m_blk = jnp.max(s, axis=-1)  # [b, h, l]
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhlm,bmhd->bhld", p, v_cur.astype(jnp.float32)
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt, mask_nxt), None

    o0 = jnp.zeros((b, h, l_local, d), jnp.float32)
    m0 = jnp.full((b, h, l_local), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, l_local), jnp.float32)
    (o, m, l, _k, _v, _mk), _ = jax.lax.scan(
        step, (o0, m0, l0, k0, v0, mask0), None, length=n_shards
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [b, l, h, d]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    *,
    mesh: Mesh,
    axis: str = "data",
) -> jax.Array:
    """Exact attention with the SEQUENCE dimension sharded over ``axis``.

    q/k/v: [B, L, H, D] global shapes (L divisible by the axis size);
    mask: [B, L] key validity.  Returns [B, L, H, D] sharded like q.
    """
    n = mesh.shape[axis]
    if mask is None:
        mask = jnp.ones(q.shape[:2], jnp.int32)

    body = functools.partial(_ring_body, axis_name=axis, n_shards=n)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis),
        ),
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )
    return mapped(q, k, v, mask)
