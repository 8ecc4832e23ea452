"""Distributed plane: device meshes, sharded indexes, batched executors.

The reference scales by key-sharding rows over timely workers connected
by TCP (``src/engine/dataflow.rs:1068-1072``, SURVEY.md §2.8).  The TPU
build splits the two planes:

- host plane: epoch-synchronous engine + connectors (see
  :mod:`pathway_tpu.engine`), shardable across processes;
- numeric plane: jit/shard_map programs over a ``jax.sharding.Mesh`` —
  XLA collectives over ICI/DCN replace NCCL/MPI-style transports.

Every device component is reached through this package, so this is the
one place that says where compiled programs are kept: BGE-large compiles
once per (batch bucket x length bucket), tens of seconds each, and a
process that starts cold pays all of it again.  ``JAX_COMPILATION_CACHE_DIR``
decides when it is set (JAX reads it; nothing here overrides it);
otherwise the cache lives at ``.jax_cache/`` in the checkout — a fixed
path, because the path is part of the cache key.
"""

import os as _os
import pathlib as _pathlib

import jax as _jax

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        str(_pathlib.Path(_os.path.abspath(__file__)).parents[2] / ".jax_cache"),
    )

from pathway_tpu.parallel.mesh import best_mesh, make_mesh, mesh_axis_size
from pathway_tpu.parallel.executor import JittedEncoder
from pathway_tpu.parallel.generation import JittedDecoder
from pathway_tpu.parallel.ivf_knn import IvfKnnIndex
from pathway_tpu.parallel.sharded_knn import ShardedKnnIndex

__all__ = [
    "make_mesh",
    "best_mesh",
    "mesh_axis_size",
    "JittedEncoder",
    "JittedDecoder",
    "IvfKnnIndex",
    "ShardedKnnIndex",
]
