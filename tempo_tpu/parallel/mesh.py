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
from jax.sharding import Mesh

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
