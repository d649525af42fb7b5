"""Mesh construction helpers.

One mesh, up to two axes:
- "range": block/ID-range shards (collectives ride ICI) — the axis
  sketch/bloom merges reduce over;
- "window": independent compaction windows / job parallelism (no
  collectives cross it).

Mirrors how the reference splits work: windows are independent jobs
(P5), ranges within a job share merge state.

Every shard_map over this mesh passes check_vma=False: psum outputs are
intentionally per-window, not fully replicated.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

RANGE_AXIS = "range"
WINDOW_AXIS = "window"


def mesh_shape_for(n_devices: int) -> tuple[int, int]:
    """(window, range) shape: prefer 2 windows when devices allow."""
    if n_devices >= 4 and n_devices % 2 == 0:
        return (2, n_devices // 2)
    return (1, n_devices)


def get_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    w, r = mesh_shape_for(n)
    import numpy as np

    return Mesh(np.asarray(devs[:n]).reshape(w, r), (WINDOW_AXIS, RANGE_AXIS))


def compaction_mesh(n_devices: int | None = None) -> Mesh:
    """Single-job mesh: one window, all devices on the range axis.

    The engine's compaction driver runs one job at a time (reference:
    tempodb/compactor.go doCompaction picks one tenant per cycle), so all
    chips go to ID-range shards of that job and the sketch psum/pmax
    collectives reduce over the whole mesh.
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    import numpy as np

    return Mesh(np.asarray(devs[:n]).reshape(1, n), (WINDOW_AXIS, RANGE_AXIS))


def mesh_program(step, mesh: Mesh, in_specs: tuple, out_specs):
    """jit(shard_map(step)) whose operands live in the mesh's sharding.

    The program declares `NamedSharding(mesh, spec)` for every input and
    output (a committed operand placed elsewhere is refused, not resharded
    inside the call) and carries its input shardings as
    `mesh_in_shardings`: `devicetiming.timed_dispatch` ships host operands
    straight into them, each device receiving only its own slice."""

    def named(spec):
        return NamedSharding(mesh, spec)

    ins = tuple(named(s) for s in in_specs)
    outs = jax.tree_util.tree_map(
        named, out_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    prog = jax.jit(
        jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        in_shardings=ins, out_shardings=outs)
    prog.mesh_in_shardings = ins
    return prog
