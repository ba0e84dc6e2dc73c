"""Global device mesh registry.

The TPU-native replacement for the reference's comm-group machinery
(NCCLCommContext rings at platform/collective_helper.h:70, ProcessGroup
objects at distributed/collective/process_group.h:53): every parallelism
axis is a named dimension of one jax.sharding.Mesh; XLA partitioning turns
sharding annotations into ICI/DCN collectives on those axes. Comm "groups"
are mesh axis names instead of ranks+ring ids.
"""
from __future__ import annotations

import contextlib

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401

_global_mesh = None

# canonical hybrid axis order, matching the reference 4D topology
# [pp, sharding, mp, dp] (fleet/base/topology.py:145-148)
HYBRID_AXES = ("pp", "sharding", "mp", "dp")


def set_mesh(mesh):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh():
    """The current mesh. With none built, a one-axis 'dp' mesh over ALL
    ``jax.devices()`` is created and kept: on a four-chip host that is
    four-way data parallel, so an entry point that means one chip builds
    its mesh from ``jax.devices()[:1]`` and says so."""
    global _global_mesh
    if _global_mesh is None:
        devs = np.array(jax.devices())
        _global_mesh = Mesh(devs, ("dp",))
    return _global_mesh


def current_mesh():
    """The mesh somebody built or scoped, or None — never creates the
    all-devices default. For code that must not turn "no mesh" into
    "every chip of the host" (the flash kernel's shard_map)."""
    return _global_mesh


@contextlib.contextmanager
def scoped_mesh(mesh):
    """Make ``mesh`` the current mesh for the duration of a trace. The
    step builders (parallel.engine.CompiledTrainStep, serving.Engine)
    trace under their OWN mesh, so code that must know how the step is
    partitioned — the flash kernel's shard_map, activation sharding
    marks — sees that mesh and not whatever the process built last."""
    global _global_mesh
    prev = _global_mesh
    _global_mesh = mesh
    try:
        yield mesh
    finally:
        _global_mesh = prev


def build_hybrid_mesh(dp=1, mp=1, pp=1, sharding=1, sep=1, devices=None):
    """Create the 4-D (optionally 5-D with `sep` for sequence parallel)
    hybrid mesh. Axis order puts dp outermost and mp innermost so tensor
    parallelism rides the fastest ICI links — the same reasoning as the
    reference's order_=['dp','pp','sharding','mp'] (topology.py:169)."""
    devs = np.array(devices if devices is not None else jax.devices())
    total = dp * mp * pp * sharding * sep
    if devs.size != total:
        raise ValueError(
            "mesh degrees dp*mp*pp*sharding*sep=%d != device count %d"
            % (total, devs.size))
    axes = []
    shape = []
    for name, deg in (("dp", dp), ("pp", pp), ("sharding", sharding),
                      ("sep", sep), ("mp", mp)):
        if deg > 1 or name in ("dp", "mp"):
            axes.append(name)
            shape.append(deg)
    arr = devs.reshape(shape)
    mesh = Mesh(arr, tuple(axes))
    set_mesh(mesh)
    return mesh


def axis_size(axis, mesh=None):
    mesh = mesh or get_mesh()
    return mesh.shape.get(axis, 1)


def replicate(x, mesh=None):
    mesh = mesh or get_mesh()
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard(x, spec, mesh=None):
    mesh = mesh or get_mesh()
    return jax.device_put(x, NamedSharding(mesh, spec))
