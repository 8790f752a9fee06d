"""Device-mesh construction and sharding helpers.

The reference exchanges gradients/weights through Python shared memory and
queues between threads (SURVEY.md §5.8a). The TPU-native equivalent is a
``jax.sharding.Mesh`` whose collectives ride ICI within a slice and DCN
across slices: data-parallel gradient reduction is ``lax.pmean`` inside
``shard_map`` (compiler-scheduled all-reduce), weight "publishing" is a no-op
because params are replicated by construction.

Multi-host: call ``jax.distributed.initialize`` before building the mesh and
order axes (dcn, ici) so the inner, bandwidth-hungry axis maps to ICI
(SURVEY.md §5.8b); ``make_mesh`` uses all visible devices either way.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"  # data parallel: envs + batch sharded, grads all-reduced
TP_AXIS = "tp"  # reserved: model-parallel axis for future large policies
TIME_AXIS = "sp"  # reserved: time-axis (sequence) sharding, parallel/timeshard

# THE import site of shard_map / axis_size for the whole framework (the
# analyzer's sharding pass resolves call sites through these names).
shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def make_mesh(
    mesh_shape: tuple[int, ...] = (-1,),
    mesh_axes: tuple[str, ...] = (DP_AXIS,),
    devices: list | None = None,
) -> Mesh:
    """Build a Mesh over all (or given) devices; one -1 dim is inferred."""
    devices = list(jax.devices()) if devices is None else list(devices)
    shape = list(mesh_shape)
    if -1 in shape:
        known = math.prod(s for s in shape if s != -1)
        if len(devices) % known:
            raise ValueError(
                f"{len(devices)} devices not divisible by mesh shape {mesh_shape}"
            )
        shape[shape.index(-1)] = len(devices) // known
    if math.prod(shape) != len(devices):
        raise ValueError(
            f"mesh shape {tuple(shape)} != device count {len(devices)}"
        )
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, mesh_axes)


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """All data-parallel mesh axes: every axis except the reserved
    model-parallel (tp) and time-sharding (sp) axes.

    A single-slice mesh is ``("dp",)``; a multi-slice/multi-host hybrid mesh
    is e.g. ``("dcn", "dp")`` with the inner, bandwidth-hungry axis on ICI
    (SURVEY.md §5.8b). Env batches shard — and gradients all-reduce — over
    the PRODUCT of these axes; collectives take the tuple directly
    (``lax.pmean(x, ("dcn", "dp"))``)."""
    return tuple(n for n in mesh.axis_names if n not in (TP_AXIS, TIME_AXIS))


def dp_size(mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in dp_axes(mesh))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def dp_sharded(mesh: Mesh) -> NamedSharding:
    """Shard the leading (env/batch) dim over ALL data-parallel axes."""
    return NamedSharding(mesh, P(dp_axes(mesh)))


def num_dp(mesh: Mesh) -> int:
    """Total data-parallel degree (product of all dp axes); alias of
    :func:`dp_size`."""
    return dp_size(mesh)
