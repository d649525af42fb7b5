"""Engine tests against a local backend in tmp dirs — the reference's
full-engine test pattern (tempodb/tempodb_test.go: write/read/compact/
retention cycles; compactor_test.go: multi-block compaction sweeps)."""

import time

import numpy as np
import pytest

from tempo_tpu.db import DBConfig, TempoDB
from tempo_tpu.db.compaction import CompactionConfig, TimeWindowBlockSelector
from tempo_tpu.db.pool import JobPool
from tempo_tpu.encoding.common import BlockConfig, SearchRequest
from tempo_tpu.model import synth
from tempo_tpu.model import trace as tr


def make_db(tmp_path, **kw):
    cfg = DBConfig(
        backend="local",
        backend_path=str(tmp_path / "blocks"),
        wal_path=str(tmp_path / "wal"),
        **kw,
    )
    return TempoDB(cfg)


def write_traces(db, tenant, traces):
    return db.write_batch(tenant, tr.traces_to_batch(traces).sorted_by_trace())


class TestWriteFind:
    def test_find_across_blocks(self, tmp_path):
        db = make_db(tmp_path)
        t1 = synth.make_traces(10, seed=1)
        t2 = synth.make_traces(10, seed=2)
        write_traces(db, "tenant", t1)
        write_traces(db, "tenant", t2)
        got = db.find("tenant", t1[3].trace_id)
        assert got is not None and got.span_count() == t1[3].span_count()
        got = db.find("tenant", t2[7].trace_id)
        assert got is not None

    def test_find_combines_partial_traces(self, tmp_path):
        # same trace split across two blocks (pre-compaction reality)
        db = make_db(tmp_path)
        t = synth.make_trace(seed=3, n_spans=10)
        spans = list(t.all_spans())
        resource = t.batches[0][0]
        t_a = tr.Trace(trace_id=t.trace_id, batches=[(resource, spans[:6])])
        t_b = tr.Trace(trace_id=t.trace_id, batches=[(resource, spans[4:])])
        write_traces(db, "tenant", [t_a])
        write_traces(db, "tenant", [t_b])
        got = db.find("tenant", t.trace_id)
        assert got is not None and got.span_count() == 10

    def test_find_missing(self, tmp_path):
        db = make_db(tmp_path)
        write_traces(db, "tenant", synth.make_traces(5, seed=4))
        assert db.find("tenant", b"\x99" * 16) is None

    def test_tenant_isolation(self, tmp_path):
        db = make_db(tmp_path)
        ta = synth.make_traces(5, seed=5)
        write_traces(db, "a", ta)
        assert db.find("b", ta[0].trace_id) is None

    def test_shard_range_pruning(self, tmp_path):
        db = make_db(tmp_path)
        traces = synth.make_traces(10, seed=6)
        write_traces(db, "tenant", traces)
        tid = traces[0].trace_id
        hex_id = tid.hex()
        # a shard range that excludes the trace must not find it
        lo = "0" * 32
        hi = format(int(hex_id, 16) - 1, "032x")
        assert db.find("tenant", tid, block_start=lo, block_end=hi) is None
        assert db.find("tenant", tid, block_start=hex_id, block_end="f" * 32) is not None


class TestSearchEngine:
    def test_search_across_blocks(self, tmp_path):
        db = make_db(tmp_path)
        t1 = synth.make_traces(15, seed=7)
        t2 = synth.make_traces(15, seed=8)
        write_traces(db, "tenant", t1)
        write_traces(db, "tenant", t2)
        svc = t1[0].batches[0][0]["service.name"]
        resp = db.search("tenant", SearchRequest(tags={"service.name": svc}, limit=0))
        want = {
            t.trace_id.hex()
            for t in t1 + t2
            if any(r.get("service.name") == svc for r, _ in t.batches)
        }
        assert {m.trace_id_hex for m in resp.traces} == want


class TestPollerEngine:
    def test_poll_discovers_blocks(self, tmp_path):
        db = make_db(tmp_path)
        write_traces(db, "t1", synth.make_traces(3, seed=9))
        write_traces(db, "t2", synth.make_traces(3, seed=10))
        # fresh engine over the same dir discovers via poll
        db2 = make_db(tmp_path)
        assert db2.blocklist.tenants() == []
        db2.poll_now()
        assert set(db2.blocklist.tenants()) == {"t1", "t2"}
        assert len(db2.blocklist.metas("t1")) == 1

    def test_tenant_index_built_and_used(self, tmp_path):
        db = make_db(tmp_path, build_tenant_index=True)
        write_traces(db, "t1", synth.make_traces(3, seed=11))
        db.poll_now()  # builder writes index.json.gz
        db3 = make_db(tmp_path)  # non-builder reads the index
        db3.poll_now()
        assert len(db3.blocklist.metas("t1")) == 1


class TestCompactionEngine:
    def test_compact_two_blocks(self, tmp_path):
        db = make_db(tmp_path)
        shared = synth.make_traces(5, seed=12)
        write_traces(db, "tenant", shared + synth.make_traces(5, seed=13))
        write_traces(db, "tenant", shared + synth.make_traces(5, seed=14))
        assert len(db.blocklist.metas("tenant")) == 2
        jobs = db.compact_once("tenant")
        assert jobs == 1
        metas = db.blocklist.metas("tenant")
        assert len(metas) == 1
        assert metas[0].total_objects == 15
        assert metas[0].compaction_level == 1
        # originals now carry compacted markers in the backend
        assert len(db.blocklist.compacted_metas("tenant")) == 2
        # trace still findable through the new block
        got = db.find("tenant", shared[0].trace_id)
        assert got is not None

    def test_slow_compaction_job_warns(self, tmp_path, caplog, monkeypatch):
        """A job outliving slow_job_warn_s logs loudly and bumps the
        counter — the only defense against an uncancellable wedged
        device call. The job is made
        deterministically slow so the timer always fires first."""
        import logging
        import time as _time

        from tempo_tpu.db.compaction import compaction_slow_jobs
        from tempo_tpu.encoding.vtpu.compactor import VtpuCompactor

        orig = VtpuCompactor.compact

        def slow_compact(self, *a, **k):
            _time.sleep(0.1)  # >> warn threshold below
            return orig(self, *a, **k)

        monkeypatch.setattr(VtpuCompactor, "compact", slow_compact)
        db = TempoDB(DBConfig(
            backend="local", backend_path=str(tmp_path / "b"),
            compaction=CompactionConfig(slow_job_warn_s=0.01),
        ))
        for b in range(2):
            db.write_batch("t", synth.make_batch(200, 8, seed=b))
        db.poll_now()
        before = compaction_slow_jobs.value(tenant="t")
        with caplog.at_level(logging.WARNING, logger="tempo_tpu.db.compaction"):
            assert db.compact_once("t") == 1
        assert compaction_slow_jobs.value(tenant="t") == before + 1
        assert "still running" in caplog.text
        # threshold disabled: no timer at all
        db2 = TempoDB(DBConfig(
            backend="local", backend_path=str(tmp_path / "b2"),
            compaction=CompactionConfig(slow_job_warn_s=0),
        ))
        for b in range(2):
            db2.write_batch("t", synth.make_batch(200, 8, seed=b))
        db2.poll_now()
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="tempo_tpu.db.compaction"):
            assert db2.compact_once("t") == 1
        assert "still running" not in caplog.text

    def test_compaction_sweep_many_blocks(self, tmp_path):
        """Mirrors tempodb/compactor_test.go's synthetic multi-block sweep."""
        db = make_db(tmp_path)
        all_traces = []
        for i in range(8):
            batch = synth.make_traces(4, seed=100 + i)
            all_traces += batch
            write_traces(db, "tenant", batch)
        total_jobs = 0
        for _ in range(10):
            jobs = db.compact_once("tenant")
            total_jobs += jobs
            if jobs == 0:
                break
        assert len(db.blocklist.metas("tenant")) < 8
        assert sum(m.total_objects for m in db.blocklist.metas("tenant")) == 32
        for t in all_traces[::5]:
            assert db.find("tenant", t.trace_id) is not None

    def test_selector_groups_same_window(self):
        from tempo_tpu.backend.base import BlockMeta

        now = int(time.time())
        cfg = CompactionConfig(window_s=3600, max_input_blocks=4)
        metas = [
            BlockMeta(tenant_id="t", end_time=now, total_objects=10, size_bytes=100)
            for _ in range(5)
        ]
        sel = TimeWindowBlockSelector(metas, cfg)
        group, h = sel.blocks_to_compact()
        assert 2 <= len(group) <= 4
        assert h.startswith("t-")

    def test_selector_respects_caps(self):
        from tempo_tpu.backend.base import BlockMeta

        now = int(time.time())
        cfg = CompactionConfig(window_s=3600, max_objects=15)
        metas = [
            BlockMeta(tenant_id="t", end_time=now, total_objects=10, size_bytes=1)
            for _ in range(4)
        ]
        sel = TimeWindowBlockSelector(metas, cfg)
        group, _ = sel.blocks_to_compact()
        assert len(group) == 1 or sum(m.total_objects for m in group) <= 15


class TestRetentionEngine:
    def test_two_phase_retention(self, tmp_path):
        db = make_db(tmp_path)
        old = synth.make_traces(3, seed=15, base_time_ns=10**9 * 1000)  # ancient
        write_traces(db, "tenant", old)
        assert len(db.blocklist.metas("tenant")) == 1
        bid = db.blocklist.metas("tenant")[0].block_id

        db.retain_once()  # phase 1: mark compacted
        assert db.blocklist.metas("tenant") == []
        assert len(db.blocklist.compacted_metas("tenant")) == 1

        # phase 2 after compacted retention expires
        db.retain_once(now=time.time() + db.compaction_cfg.compacted_retention_s + 1)
        assert db.blocklist.compacted_metas("tenant") == []
        db.poll_now()
        assert db.blocklist.metas("tenant") == []


class TestWalManager:
    def test_rescan_after_restart(self, tmp_path):
        db = make_db(tmp_path)
        wal = db.wal
        blk = wal.new_block("tenant")
        blk.append(tr.traces_to_batch(synth.make_traces(3, seed=40)))
        blk2 = wal.new_block("other")
        blk2.append(tr.traces_to_batch(synth.make_traces(2, seed=41)))
        # junk dir gets skipped
        import os

        os.makedirs(tmp_path / "wal" / "not-a-wal-block", exist_ok=True)

        db2 = make_db(tmp_path)
        found = db2.wal.rescan_blocks()
        assert {b.tenant for b in found} == {"tenant", "other"}
        total = sum(b.all_spans().num_spans for b in found)
        assert total == blk.all_spans().num_spans + blk2.all_spans().num_spans


class TestPollErrorHandling:
    def test_transient_error_aborts_poll(self, tmp_path):
        from tempo_tpu.backend import MockBackend
        from tempo_tpu.db import DBConfig, TempoDB

        raw = MockBackend()
        db = TempoDB(DBConfig(backend="mock"), raw_backend=raw)
        write_traces(db, "tenant", synth.make_traces(3, seed=42))
        db.poll_now()
        assert len(db.blocklist.metas("tenant")) == 1
        raw.fail_every = 1  # every op fails
        with pytest.raises(Exception):
            db.poll_now()
        # previous blocklist retained
        assert len(db.blocklist.metas("tenant")) == 1


class TestJobPool:
    def test_early_exit(self):
        pool = JobPool(4)
        ran = []

        def mk(i):
            def job():
                ran.append(i)
                time.sleep(0.01 * i)
                return i

            return job

        results, errors = pool.run_jobs([mk(i) for i in range(10)], stop_when=lambda r: True)
        assert not errors
        assert len(results) >= 1

    def test_errors_collected(self):
        pool = JobPool(2)

        def bad():
            raise RuntimeError("boom")

        results, errors = pool.run_jobs([bad, lambda: 42])
        assert 42 in results
        assert len(errors) == 1
