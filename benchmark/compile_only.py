"""Compile-only pass at the real shapes, for a v5e that is described and not
attached (on-chip-measurement guide, section 2).

    JAX_PLATFORMS=cpu python benchmark/compile_only.py

Compiles the encoder program at (256, 256), (128, 512), (8, 16) and (32, 64) for
BGE-large, and the slab search at ``[1, 1024] x [1,048,576, 1024]`` and
``[8, 384] x [4,420,992, 384]`` and ``[32, 384] x ...`` with k = 16, and prints
``memory_analysis()`` of each.  Nothing runs; a compile that passes is not a
chip run.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    from pathway_tpu.models.encoder import BGE_LARGE, TextEncoderModel
    from pathway_tpu.ops.distances import dot_scores, normalize
    from pathway_tpu.ops.topk import NEG_INF

    model = TextEncoderModel(BGE_LARGE)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32))
    params = jax.tree.map(lambda x: shape(x.shape, x.dtype), params)

    def apply_cast(params, ids, mask, tps):  # as JittedEncoder._apply_cast
        return model.apply(params, ids.astype(jnp.int32), mask.astype(jnp.int32), tps.astype(jnp.int32))

    for rows, tokens in ((256, 256), (128, 512), (8, 16), (32, 64)):
        args = (shape((rows, tokens), jnp.int16), shape((rows, tokens), jnp.uint8), shape((rows, tokens), jnp.uint8))
        compiled = jax.jit(apply_cast).lower(params, *args).compile()
        print(f"encoder BGE_LARGE ({rows}, {tokens}):", compiled.memory_analysis(), flush=True)

    def search(k):  # as ShardedKnnIndex._search_jit without a mesh
        def run(q, vectors, valid):
            q = normalize(q)
            s = dot_scores(q.astype(vectors.dtype), vectors)
            s = jnp.where(valid.astype(bool)[None, :], s, NEG_INF)
            return jax.lax.top_k(s, k)

        return jax.jit(run)

    for nq, rows, dim in ((1, 1_048_576, 1024), (8, 4_420_992, 384), (32, 4_420_992, 384)):
        args = (shape((nq, dim), jnp.float32), shape((rows, dim), jnp.float32), shape((rows,), jnp.float32))
        compiled = search(16).lower(*args).compile()
        print(f"search [{nq}, {dim}] x [{rows}, {dim}] k=16:", compiled.memory_analysis(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
