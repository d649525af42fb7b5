"""Engine tests against a local backend in tmp dirs — the reference's
full-engine test pattern (tempodb/tempodb_test.go: write/read/compact/
retention cycles; compactor_test.go: multi-block compaction sweeps)."""

import time

import numpy as np
import pytest

from tempo_tpu.backend.base import CompactedBlockMeta
from tempo_tpu.db import DBConfig, TempoDB, find_block_probes
from tempo_tpu.db.compaction import CompactionConfig, TimeWindowBlockSelector
from tempo_tpu.db.pool import JobPool
from tempo_tpu.encoding.common import BlockConfig, SearchRequest
from tempo_tpu.model import synth
from tempo_tpu.model import trace as tr
from tempo_tpu.modules.frontend import create_block_boundaries


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
        meta = write_traces(db, "tenant", traces)
        tid = traces[0].trace_id
        block_hex = meta.block_id.replace("-", "")
        # a shard is a slice of the BLOCK-ID space, half-open: one that
        # ends at the block's own ID must not open the block, wherever
        # the trace ID lies, and the slice that starts there must
        lo, hi = "0" * 32, "f" * 32
        assert db.find("tenant", tid, block_start=lo, block_end=block_hex) is None
        assert db.find("tenant", tid, block_start=block_hex, block_end=hi) is not None
        # a cut at the trace ID (the old reading of the bounds) prunes nothing
        cut = format(int(tid.hex(), 16) - 1, "032x")
        below = db.find("tenant", tid, block_start=lo, block_end=cut)
        above = db.find("tenant", tid, block_start=cut, block_end=hi)
        assert (below is None) != (above is None)


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


class TestFindAcrossCompaction:
    """A find is the union of its shard jobs, each with its own snapshot
    of the blocklist, and the shards partition the blocks by block ID.
    A compaction swaps inputs {A, B} for an output C of another slice,
    so the inputs stay candidates for two polls after the swap
    (reference: includeCompactedBlock tempodb.go:519)."""

    TENANT = "tenant"
    N_SHARDS = 4
    # A in the first slice, B in the last: C's slice differs from one of them
    BLOCK_IDS = ("10000000-0000-4000-8000-00000000000a",
                 "f0000000-0000-4000-8000-00000000000b")

    def _compacted_store(self, tmp_path):
        """-> (whole trace, a querier's db whose list still holds {A, B},
        the compactor's db that swapped them for C on the same backend)"""
        t = synth.make_trace(seed=3, n_spans=10)
        spans = list(t.all_spans())
        resource = t.batches[0][0]
        halves = [tr.Trace(trace_id=t.trace_id, batches=[(resource, spans[:6])]),
                  tr.Trace(trace_id=t.trace_id, batches=[(resource, spans[4:])])]
        querier = make_db(tmp_path)
        for j, (half, block_id) in enumerate(zip(halves, self.BLOCK_IDS)):
            batch = tr.traces_to_batch([half] + synth.make_traces(4, seed=70 + j))
            querier.write_batch(self.TENANT, batch.sorted_by_trace(), block_id=block_id)
        compactor = make_db(tmp_path)
        compactor.poll_now()
        assert compactor.compact_once(self.TENANT) == 1
        assert [m.block_id for m in querier.blocklist.metas(self.TENANT)] == list(self.BLOCK_IDS)
        (out,) = compactor.blocklist.metas(self.TENANT)
        assert out.block_id not in self.BLOCK_IDS
        return t, querier, compactor

    def _swap(self, how, querier, compactor):
        if how == "poll":
            querier.poll_now()
        else:  # what compaction.py does to the compactor's own list
            querier.blocklist.update(
                self.TENANT,
                adds=compactor.blocklist.metas(self.TENANT),
                removes=querier.blocklist.metas(self.TENANT),
                compacted_adds=compactor.blocklist.compacted_metas(self.TENANT))

    def _shards(self):
        bounds = create_block_boundaries(self.N_SHARDS)
        return list(zip(bounds, bounds[1:]))

    @staticmethod
    def _span_ids(traces):
        got = tr.combine_traces([t for t in traces if t is not None])
        return None if got is None else sorted(s.span_id for s in got.all_spans())

    @pytest.mark.parametrize("how", ["update", "poll"])
    @pytest.mark.parametrize("order", ["ascending", "descending"])
    def test_blocklist_swap_between_the_shard_jobs_of_one_find(self, tmp_path, how, order):
        t, querier, compactor = self._compacted_store(tmp_path)
        shards = self._shards()
        if order == "descending":
            shards.reverse()
        want = sorted(s.span_id for s in t.all_spans())
        for swap_before in range(self.N_SHARDS + 1):
            # every round starts from the list of before the compaction
            querier.blocklist.apply_poll_results(
                {self.TENANT: [c.meta for c in compactor.blocklist.compacted_metas(self.TENANT)]}, {})
            parts = []
            for i, (lo, hi) in enumerate(shards):
                if i == swap_before:
                    self._swap(how, querier, compactor)
                parts.append(querier.find(self.TENANT, t.trace_id, block_start=lo, block_end=hi))
            assert self._span_ids(parts) == want, (how, order, swap_before)

    @pytest.mark.parametrize("how", ["update", "poll"])
    def test_shard_jobs_spread_over_a_stale_and_a_fresh_querier(self, tmp_path, how):
        t, stale, compactor = self._compacted_store(tmp_path)
        if how == "update":
            fresh = compactor  # the all-in-one that ran the compaction
        else:
            fresh = make_db(tmp_path)  # a querier that polled after it
            fresh.poll_now()
        assert len(fresh.blocklist.metas(self.TENANT)) == 1
        want = sorted(s.span_id for s in t.all_spans())
        shards = self._shards()
        for assignment in range(1 << self.N_SHARDS):
            parts = [
                (fresh if assignment >> i & 1 else stale).find(
                    self.TENANT, t.trace_id, block_start=lo, block_end=hi)
                for i, (lo, hi) in enumerate(shards)
            ]
            assert self._span_ids(parts) == want, (how, bin(assignment))

    @pytest.mark.parametrize("how", ["update", "poll"])
    def test_blocklist_swap_between_the_two_reads_of_one_shard_job(self, tmp_path, monkeypatch, how):
        """The live list is read first: a swap before the compacted list
        is read shows an input in both, and it is opened once."""
        t, querier, compactor = self._compacted_store(tmp_path)
        live = querier.blocklist.metas(self.TENANT)
        monkeypatch.setattr(querier.blocklist, "metas", lambda tenant: live)
        self._swap(how, querier, compactor)
        probes = find_block_probes.value()
        got = querier.find(self.TENANT, t.trace_id)
        assert self._span_ids([got]) == sorted(s.span_id for s in t.all_spans())
        assert find_block_probes.value() - probes == 2

    def test_compacted_inputs_leave_the_candidates_after_two_polls(self, tmp_path):
        t, _, db = self._compacted_store(tmp_path)
        want = sorted(s.span_id for s in t.all_spans())
        probes = find_block_probes.value()
        assert self._span_ids([db.find(self.TENANT, t.trace_id)]) == want
        assert find_block_probes.value() - probes == 3  # C and, for now, A and B
        # the same list two polls later: every querier has seen C by then
        aged = [
            CompactedBlockMeta(meta=c.meta,
                               compacted_time=c.compacted_time - 2 * db.cfg.blocklist_poll_s - 1)
            for c in db.blocklist.compacted_metas(self.TENANT)
        ]
        db.blocklist.apply_poll_results(
            {self.TENANT: db.blocklist.metas(self.TENANT)}, {self.TENANT: aged})
        probes = find_block_probes.value()
        assert self._span_ids([db.find(self.TENANT, t.trace_id)]) == want
        assert find_block_probes.value() - probes == 1


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
