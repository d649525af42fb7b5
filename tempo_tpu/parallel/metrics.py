"""Mesh-sharded TraceQL metrics: row-group slot batches fanned across
devices, counts psum-merged over ICI.

Mirrors parallel/search.py (P4): up to W*R (block, row-group) units
stack on the mesh per dispatch, every device bincounts its shard's
combined (series, bin, bucket) slot ids, and `psum` over the range axis
folds the partials — the same collective the compactor's HLL/count-min
sketches ride, legal here because metric counts are integers that merge
by addition (ops/sketch.py HistogramPlan contract). The result is
bit-identical to the host path at ANY shard count: sharding moves
where the adds happen, never what they sum to.

Host-side work per unit stays what the host path pays (column decode +
filter mask + slot computation); the device amortizes the reduction
across many row groups per dispatch rather than paying one dispatch
per row group (the per-dispatch cost on the current machine is not
measured — PERF.md).
"""

from __future__ import annotations

import logging

import numpy as np
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tempo_tpu.parallel import accounting
from tempo_tpu.parallel.accounting import count_dispatch
from tempo_tpu.parallel.mesh import RANGE_AXIS, WINDOW_AXIS
from tempo_tpu.parallel.search import dispatch_lock as _dispatch_lock
from tempo_tpu.util.devicetiming import timed_dispatch

log = logging.getLogger(__name__)


@lru_cache(maxsize=32)
def make_sharded_bincount(mesh, n_slots: int):
    """Jitted sharded segmented bincount over RUN-COMPRESSED slots.

    Inputs (stacked over the (W, R) mesh axes):
      slots   (W, R, N) int32 — combined slot id per entry; -1 = drop
      weights (W, R, N) int32 — rows carried by each entry (1 for raw
              streams; the run length for compress_slot_runs streams —
              the device consumes the compressed form directly)
    Returns:
      counts (W, n_slots) int32 — per-window totals, psum-merged over
      the range axis (replicated across range shards post-collective)
    """

    def local(slots, weights):
        idx = jnp.where(slots >= 0, slots, n_slots)  # OOB + drop mode
        counts = jnp.zeros((n_slots,), jnp.int32).at[idx].add(
            weights, mode="drop"
        )
        return jax.lax.psum(counts, RANGE_AXIS)

    def step(slots, weights):
        return local(slots[0, 0], weights[0, 0])[None]

    spec = P(WINDOW_AXIS, RANGE_AXIS)
    return jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=P(WINDOW_AXIS),
            check_vma=False,
        )
    )


class MeshMetricsEvaluator:
    """Mesh-sharded multi-block metrics evaluation (the query_range
    analog of MeshSearcher). Feeds a HostAccumulator: counts come from
    the mesh reduction, exemplars/series bookkeeping stay host-side."""

    def __init__(self, mesh, bucket_for):
        self.mesh = mesh
        self.w = mesh.shape[WINDOW_AXIS]
        self.r = mesh.shape[RANGE_AXIS]
        self.bucket_for = bucket_for
        self.last_stats: dict = {}

    def evaluate_blocks(self, blocks, plan, acc, on_block_error=None,
                        on_block_ok=None) -> None:
        """One metrics job on the mesh: see _evaluate_blocks. The span
        and the clock cover the job's host work (parallel/accounting)."""
        with accounting.job("evaluate_blocks") as clock:
            self._evaluate_blocks(blocks, plan, acc, on_block_error,
                                  on_block_ok, clock)

    def _evaluate_blocks(self, blocks, plan, acc, on_block_error, on_block_ok,
                         clock) -> None:
        """blocks: iterable of lazily-opened VtpuBackendBlocks. Row
        groups are zone-map/time pruned with zero reads, surviving units
        evaluate host-side to slot ids, and slot batches dispatch in
        stacked (W, R) chunks under the process-wide mesh lock.

        Failure domains mirror MeshSearcher.search_blocks: a block
        deleted mid-query (NotFound) is skipped, but any other read
        error raises — a metrics job must fail loudly and let the
        worker's retry taxonomy / frontend shard budget decide, never
        return silently-reduced counts that look complete. The
        on_block_error/on_block_ok callbacks feed quarantine accounting."""
        from tempo_tpu.encoding.vtpu.block import (
            pruned_row_groups_total,
            zone_maps_enabled,
        )
        from tempo_tpu.metrics_engine.evaluate import (
            _lower_prunes,
            eval_batch,
            rg_eval_view,
            rg_prunes,
        )

        stats = self.last_stats = {"dispatches": 0, "units": 0, "h2d_bytes": 0}
        zm = zone_maps_enabled()
        all_conds = plan.pipeline.conditions().all_conditions
        cap = self.w * self.r
        scan = make_sharded_bincount(self.mesh, plan.n_slots)
        pending: list = []  # run-compressed (slots, weights) pairs
        opened: list = []

        def flush():
            if not pending:
                return
            clock.lap("plan", "mesh_bincount")
            with clock.phase("stack"):
                pad = self.bucket_for(max(len(s) for s, _ in pending))
                stacked = np.full((cap, pad), -1, np.int32)
                wstack = np.zeros((cap, pad), np.int32)
                shard_rows = [0] * cap
                for i, (s, w) in enumerate(pending):
                    stacked[i, : len(s)] = s
                    wstack[i, : len(s)] = w if w is not None else 1
                    shard_rows[i] = len(s)
            with clock.dispatching(_dispatch_lock):
                # raw host arrays: the seam ships them (h2d bytes +
                # transfer stage measured at the boundary)
                out = timed_dispatch(
                    "mesh_bincount", scan,
                    stacked.reshape(self.w, self.r, pad),
                    wstack.reshape(self.w, self.r, pad),
                )
            # the psum reduces one (n_slots,) int32 vector a window
            count_dispatch("mesh_bincount", len(pending), shard_rows, pad,
                           4 * self.w * plan.n_slots)
            with clock.phase("collect"):
                acc.counts += np.asarray(out).sum(axis=0, dtype=np.int64)
            stats["dispatches"] += 1
            stats["units"] += len(pending)
            stats["h2d_bytes"] += stacked.nbytes + wstack.nbytes
            pending.clear()

        from tempo_tpu.backend.base import NotFound

        for blk in blocks:
            opened.append(blk)
            # buffer this block's contributions and commit them only once
            # the WHOLE block has evaluated: counts are integer adds with
            # no dedupe, so a block deleted mid-scan (NotFound below)
            # must contribute nothing — its spans live on in the
            # compaction output that replaced it, and a half-committed
            # block would double-count them in a response that carries no
            # partial flag
            blk_batches: list = []  # (slots, weights) pairs
            blk_results: list = []  # (res, view) for exemplars
            blk_spans = 0
            blk_pruned = 0
            from tempo_tpu.backend.faults import with_retries

            try:
                d = with_retries(blk.dictionary)
                resolvers, impossible = _lower_prunes(plan, d)
                if impossible:
                    acc.stats["inspectedBlocks"] += 1
                    if on_block_ok is not None:
                        on_block_ok(blk.meta.block_id)
                    continue
                for rg in with_retries(blk.index).row_groups:
                    if rg.end_s < plan.start_s or rg.start_s > plan.end_s:
                        continue
                    if zm and resolvers and rg_prunes(plan, rg, resolvers, all_conds):
                        blk_pruned += 1
                        continue
                    # encoded-space filters + lazy projection, same
                    # seam as the host path (filter columns never
                    # expand; a dead run-space verdict skips the unit)
                    view, premask, dead = with_retries(
                        lambda b=blk, r=rg: rg_eval_view(plan, b, r, d))
                    blk_spans += rg.n_spans
                    if dead:
                        continue
                    res = with_retries(
                        lambda v=view, p=premask: eval_batch(
                            plan, v, d, acc.series, premask=p))
                    blk_results.append((res, view))
                    live = res.slots[res.slots >= 0]
                    if len(live):
                        # run-compressed: the device bincount consumes
                        # (slot, weight) pairs, not raw rows
                        from tempo_tpu.ops.pallas_kernels import compress_slot_runs

                        blk_batches.append(compress_slot_runs(live))
            except NotFound as e:  # deleted mid-query: benign, skip whole block
                log.warning("mesh metrics: block %s deleted mid-query: %s",
                            blk.meta.block_id, e)
                continue
            except Exception as e:
                log.warning("mesh metrics: block %s failed: %s",
                            blk.meta.block_id, e)
                if on_block_error is not None:
                    on_block_error(blk.meta.block_id, e)
                raise
            acc.stats["inspectedBlocks"] += 1
            acc.stats["inspectedSpans"] += blk_spans
            if blk_pruned:
                acc.stats["prunedRowGroups"] += blk_pruned
                blk.pruned_row_groups += blk_pruned
                pruned_row_groups_total.inc(blk_pruned)
            for res, view in blk_results:
                acc.observe_exemplars(res, view)
            for live in blk_batches:
                pending.append(live)
                if len(pending) >= cap:
                    flush()
            if on_block_ok is not None:
                on_block_ok(blk.meta.block_id)
        flush()
        acc.stats["inspectedBytes"] += sum(b.bytes_read for b in opened)
        acc.stats["decodedBytes"] += sum(
            getattr(b, "decoded_bytes", 0) for b in opened)
