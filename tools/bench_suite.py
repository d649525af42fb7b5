"""BASELINE.md benchmark configs as runnable harnesses.

Implements the reference-derived benchmark configurations:

  (1) ingest   — 10k-span OTLP-shaped ingest -> flush -> compact on the
      local backend (BASELINE config 1; mirrors the reference's
      integration/bench flow).
  (2) sweep    — 100 synthetic blocks, compaction-window sweep until the
      blocklist converges (BASELINE config 2; mirrors
      tempodb/compactor_test.go BenchmarkCompaction:696).
  (4) search   — multi-block tag search + bloom-gated find-by-ID over a
      multi-tenant blockset (BASELINE config 4, scaled to fit the box).
  (6) metrics  — TraceQL metrics query_range (rate by service +
      duration quantiles) over the same multi-tenant blockset (ISSUE 5;
      no reference analog — the metrics engine is new here).

Each subcommand prints one JSON object with timings, throughput and
recall stats, tagged with the platform, device kind and device count it
ran on. `python tools/bench_suite.py all` runs every config. There is
no CPU fallback: the run refuses to start unless JAX resolved a TPU, or
the caller set JAX_PLATFORMS=cpu explicitly (then every line says cpu).
(Config 3 — generator span-metrics over an OTel stream — is covered by
tools/smoke.py's generator path; config 5 — 1 TB sharded compaction —
needs a v5e-8 and is represented by the mesh-sharded engine path that
bench.py and dryrun_multichip exercise.)
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np


def _db(tmp, **kw):
    from tempo_tpu.db import DBConfig, TempoDB

    return TempoDB(DBConfig(backend="local", backend_path=tmp, **kw))


def _storage_summary(db) -> dict:
    """Storage-health numbers for the JSON line (BENCH_r06+ tracks
    compression/debt/zone-map coverage beside the perf numbers)."""
    from tempo_tpu.db.analytics import StorageScanner

    fleet = StorageScanner(db).scan_once()["fleet"]
    return {
        "compression_ratio": fleet["compressionRatio"],
        "zonemap_coverage": fleet["zonemapCoverageRatio"],
        "debt_row_groups": fleet["compactionDebtRowGroups"],
        "debt_payoff": fleet["compactionDebtPayoff"],
        "codec_pages": fleet["codecPages"],
    }


def _cost_rollup() -> dict:
    """Per-tenant cost vectors accumulated during this config's run."""
    from tempo_tpu.util import usage

    return usage.ACCOUNTANT.snapshot()


def bench_ingest(n_spans: int = 10_000) -> dict:
    """Config 1: 10k spans through ingester cut/complete/flush + compaction."""
    from tempo_tpu.modules.ingester import Ingester, IngesterConfig
    from tempo_tpu.modules.overrides import Overrides
    from tempo_tpu.model import synth
    from tempo_tpu.model import trace as tr

    spans_per_trace = 10
    n_traces = n_spans // spans_per_trace
    traces = synth.make_traces(n_traces, seed=1, spans_per_trace=spans_per_trace)
    with tempfile.TemporaryDirectory() as tmp:
        db = _db(tmp + "/blocks", wal_path=tmp + "/wal")
        ing = Ingester(db, Overrides(), IngesterConfig(max_block_duration_s=10**9))

        t0 = time.perf_counter()
        for t in traces:
            ing.instance("bench").push_batch(tr.traces_to_batch([t]))
        t_push = time.perf_counter() - t0

        t0 = time.perf_counter()
        inst = ing.instance("bench")
        inst.cut_complete_traces(immediate=True)
        inst.cut_block_if_ready(immediate=True)
        inst.complete_and_flush()
        t_flush = time.perf_counter() - t0

        # split into 2 blocks? one block suffices for config 1; compact a
        # self-pair by writing a second copy (RF dedupe work)
        db.write_batch("bench", tr.traces_to_batch(traces).sorted_by_trace())
        db.poll_now()
        t0 = time.perf_counter()
        jobs = db.compact_once("bench")
        t_compact = time.perf_counter() - t0

        got = db.find("bench", traces[0].trace_id)
        return {
            "config": "ingest_10k",
            "spans": n_spans,
            "push_s": round(t_push, 3),
            "flush_s": round(t_flush, 3),
            "compact_s": round(t_compact, 3),
            "compact_jobs": jobs,
            "spans_per_s_ingest": round(n_spans / t_push),
            "find_ok": bool(got is not None and got.span_count() == spans_per_trace),
        }


def bench_sweep(n_blocks: int = 100, traces_per_block: int = 200) -> dict:
    """Config 2: 100-block compaction sweep (compactor_test.go:696)."""
    from tempo_tpu.model import synth

    with tempfile.TemporaryDirectory() as tmp:
        db = _db(tmp)
        total_spans = 0
        for b in range(n_blocks):
            batch = synth.make_batch(traces_per_block, 8, seed=b)
            total_spans += batch.num_spans
            db.write_batch("bench", batch)
        db.poll_now()

        storage_before = _storage_summary(db)
        t0 = time.perf_counter()
        cycles = jobs = 0
        while True:
            n = db.compact_once("bench")
            cycles += 1
            if n == 0 or cycles > 200:
                break
            jobs += n
            db.poll_now()
        dt = time.perf_counter() - t0
        remaining = len(db.blocklist.metas("bench"))
        m = db.compactor_driver.metrics
        return {
            "config": "sweep_100_blocks",
            "input_blocks": n_blocks,
            "total_spans": total_spans,
            "jobs": jobs,
            "blocks_in": m.blocks_in,
            "seconds": round(dt, 3),
            "blocks_per_s": round(m.blocks_in / dt, 3),
            "remaining_blocks": remaining,
            # the sweep's whole point, measured: overlap debt paid down
            "storage_before": storage_before,
            "storage_after": _storage_summary(db),
        }


def bench_search(n_tenants: int = 3, blocks_per_tenant: int = 6,
                 traces_per_block: int = 2000) -> dict:
    """Config 4: multi-tenant multi-block tag search + find-by-ID."""
    from tempo_tpu.encoding.common import SearchRequest
    from tempo_tpu.model import synth

    with tempfile.TemporaryDirectory() as tmp:
        db = _db(tmp)
        sample_ids = {}
        total_spans = 0
        for ti in range(n_tenants):
            tenant = f"tenant-{ti}"
            for b in range(blocks_per_tenant):
                batch = synth.make_batch(traces_per_block, 8, seed=ti * 100 + b)
                total_spans += batch.num_spans
                db.write_batch(tenant, batch)
                if b == 0:
                    sample_ids[tenant] = np.unique(batch.cols["trace_id"], axis=0)[:20]
        db.poll_now()

        from tempo_tpu.util import usage

        usage.ACCOUNTANT.reset()
        t0 = time.perf_counter()
        hits = 0
        for ti in range(n_tenants):
            tenant = f"tenant-{ti}"
            with usage.attribute(tenant, "search"):
                resp = db.search(tenant, SearchRequest(tags={"service": "cart"}, limit=50))
            hits += len(resp.traces)
        t_search = time.perf_counter() - t0

        t0 = time.perf_counter()
        found = tried = 0
        for tenant, ids in sample_ids.items():
            with usage.attribute(tenant, "find"):
                for limbs in ids:
                    tid = np.asarray(limbs, dtype=">u4").tobytes()
                    tried += 1
                    if db.find(tenant, tid) is not None:
                        found += 1
        t_find = time.perf_counter() - t0

        return {
            "config": "multiblock_search",
            "tenants": n_tenants,
            "blocks": n_tenants * blocks_per_tenant,
            "total_spans": total_spans,
            "search_s": round(t_search, 3),
            "search_hits": hits,
            "find_s": round(t_find, 3),
            "find_recall": found / max(tried, 1),
            # rollup captured BEFORE the storage scan: the scan's
            # kind=analytics charges must not pollute the bench cost
            "tenant_cost": _cost_rollup(),
            "storage": _storage_summary(db),
        }


def bench_metrics(n_tenants: int = 2, blocks_per_tenant: int = 4,
                  traces_per_block: int = 2000) -> dict:
    """Config 6 (ISSUE 5): TraceQL metrics query_range over a
    multi-tenant multi-block store — rate-by-service + duration
    quantiles straight off stored blocks via the metrics engine."""
    from tempo_tpu.metrics_engine import (
        compile_metrics_plan,
        evaluate_block,
        make_accumulator,
    )
    from tempo_tpu.model import synth

    with tempfile.TemporaryDirectory() as tmp:
        db = _db(tmp)
        total_spans = 0
        for ti in range(n_tenants):
            for b in range(blocks_per_tenant):
                batch = synth.make_batch(traces_per_block, 8, seed=ti * 100 + b)
                total_spans += batch.num_spans
                db.write_batch(f"tenant-{ti}", batch)
        db.poll_now()

        from tempo_tpu.util import usage

        usage.ACCOUNTANT.reset()
        queries = {
            "rate": "{} | rate() by (resource.service.name)",
            "quantile": "{} | quantile_over_time(duration, 0.5, 0.99)",
        }
        out = {"config": "traceql_metrics", "tenants": n_tenants,
               "blocks": n_tenants * blocks_per_tenant, "total_spans": total_spans}
        start, end, step = 1_700_000_000, 1_700_000_060, 10
        for qname, q in queries.items():
            t0 = time.perf_counter()
            series = inspected = 0
            for ti in range(n_tenants):
                tenant = f"tenant-{ti}"
                plan = compile_metrics_plan(q, start, end, step)
                acc = make_accumulator(plan, device=False)
                with usage.attribute(tenant, "query_range"):
                    for m in db.blocklist.metas(tenant):
                        blk = db.encoding_for(m.version).open_block(m, db.backend, db.cfg.block)
                        evaluate_block(plan, blk, acc)
                        acc.stats["inspectedBytes"] += blk.bytes_read
                series += len(acc.series.slots)
                inspected += acc.stats["inspectedBytes"]
            out[f"{qname}_s"] = round(time.perf_counter() - t0, 3)
            out[f"{qname}_series"] = series
            out[f"{qname}_inspected_bytes"] = inspected
        out["tenant_cost"] = _cost_rollup()  # before the scan's analytics charges
        out["storage"] = _storage_summary(db)
        return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config", choices=["ingest", "sweep", "search", "metrics", "all"])
    args = ap.parse_args()
    import os, sys as _sys
    _sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from tempo_tpu.util import backend

    try:
        device = backend.require_measurable()
    except backend.NoAccelerator as e:
        print(f"bench_suite.py: {e}", file=sys.stderr)
        return 1
    runs = {
        "ingest": [bench_ingest],
        "sweep": [bench_sweep],
        "search": [bench_search],
        "metrics": [bench_metrics],
        "all": [bench_ingest, bench_sweep, bench_search, bench_metrics],
    }[args.config]
    for fn in runs:
        out = fn()
        out.update(device)
        print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main())
