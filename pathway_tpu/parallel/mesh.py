"""Device-mesh construction helpers.

Conventions across the framework:

- axis ``"data"``: batch / corpus sharding (DP + index shards);
- axis ``"model"``: tensor parallelism inside encoders.

A mesh is always optional — every numeric-plane component has a
single-device fast path.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

from pathway_tpu.internals.config import pathway_config

__all__ = ["make_mesh", "best_mesh", "mesh_axis_size", "require_single_process"]


def require_single_process(component: str) -> None:
    """Refuse to put a device component in a rank of a multi-process run.

    An accelerator belongs to one process.  Under ``pathway spawn -n N``
    every rank builds the whole graph, so every rank would construct the
    encoder and reach for the chip: one gets it, the others fail at
    backend start-up or come up on the CPU by JAX's own fallback and embed
    there without a word.  Checked from the environment, before any JAX
    call, so the refusal itself cannot take the chip.  A cluster pinned
    to the host on purpose (``JAX_PLATFORMS=cpu``: the test suite, a
    host-only deployment) is left alone.
    """
    processes = pathway_config.processes  # the topology pw.run will use
    if processes > 1:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            raise RuntimeError(
                f"{component} in a run of {processes} processes: each rank "
                "would initialise the accelerator, and a chip belongs to "
                "one process.  Run device pipelines in one process "
                "(`spawn -n 1`; threads scale the host plane), or set "
                "JAX_PLATFORMS=cpu to keep a multi-process run on the host."
            )


def make_mesh(
    axes: dict[str, int] | None = None, devices: list | None = None
) -> Mesh:
    """Build a Mesh from {axis: size}; sizes must multiply to len(devices).
    Default: 1-D ``("data",)`` over all devices."""
    devs = devices if devices is not None else jax.devices()
    if axes is None:
        axes = {"data": len(devs)}
    shape = tuple(axes.values())
    if int(np.prod(shape)) != len(devs):
        raise ValueError(
            f"mesh axes {axes} need {int(np.prod(shape))} devices, have {len(devs)}"
        )
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, tuple(axes.keys()))


def best_mesh(model_parallel: int = 1, devices: list | None = None) -> Mesh:
    """2-D ("data", "model") mesh with the requested TP degree; TP is
    clamped to a divisor of the device count."""
    devs = devices if devices is not None else jax.devices()
    n = len(devs)
    mp = max(1, model_parallel)
    while n % mp != 0:
        mp -= 1
    return make_mesh({"data": n // mp, "model": mp}, devs)


def mesh_axis_size(mesh: Mesh | None, axis: str) -> int:
    if mesh is None or axis not in mesh.shape:
        return 1
    return mesh.shape[axis]
