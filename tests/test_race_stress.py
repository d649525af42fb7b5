"""Seeded concurrency stress harness — the repo's race-detection analog.

The reference's only sanitizer is `go test -race` across the suite
(Makefile:38); Python has no TSan, so this harness shakes the
lock-protected structures instead: N threads run SEEDED random op
schedules against one component with sys.setswitchinterval() dropped to
~10us (maximal forced interleaving), then invariants are checked.
Failures reproduce from the printed seed. Scenarios cover the shared
mutable state added across rounds: ingester instance maps, the ring KV
cache, the mesh searcher's column LRU, and the write-behind cache queue.
"""

from __future__ import annotations

import random
import sys
import threading

import pytest


@pytest.fixture(autouse=True)
def _shake_scheduler():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    yield
    sys.setswitchinterval(old)


def run_threads(n, fn, seeds):
    """Run fn(seed) on n threads; re-raise the first exception with its
    seed so failures are reproducible."""
    errors: list = []

    def wrap(seed):
        try:
            fn(seed)
        except Exception as e:  # noqa: BLE001
            errors.append((seed, e))

    threads = [threading.Thread(target=wrap, args=(s,)) for s in seeds[:n]]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    stuck = [t.name for t in threads if t.is_alive()]
    assert not stuck, f"deadlocked workers still alive: {stuck}"
    if errors:
        seed, e = errors[0]
        raise AssertionError(f"seed {seed} raised {type(e).__name__}: {e}") from e


class TestIngesterStress:
    def test_concurrent_push_cut_flush_search(self, tmp_path):
        """Pushes, cuts, completes, flushes, and searches interleave on
        one app; every pushed trace must be findable afterwards."""
        from tempo_tpu.app import App, AppConfig
        from tempo_tpu.db import DBConfig
        from tempo_tpu.model import synth

        tmp = str(tmp_path)
        app = App(AppConfig(db=DBConfig(backend="local", backend_path=f"{tmp}/b",
                                        wal_path=f"{tmp}/w")))
        pushed: list = []
        lock = threading.Lock()

        def worker(seed):
            rng = random.Random(seed)
            for i in range(30):
                op = rng.random()
                if op < 0.5:
                    traces = synth.make_traces(2, seed=seed * 10_000 + i, spans_per_trace=3)
                    app.push_traces(traces)
                    with lock:
                        pushed.extend(t.trace_id for t in traces)
                elif op < 0.7:
                    app.sweep_all(immediate=rng.random() < 0.5)
                elif op < 0.85:
                    with lock:
                        tid = rng.choice(pushed) if pushed else None
                    if tid is not None:
                        app.find_trace(tid)  # may be None mid-flight; must not raise
                else:
                    app.db.poll_now()

        try:
            run_threads(4, worker, seeds=[11, 22, 33, 44])
            # final settle: cut + flush everything -> all traces findable
            app.sweep_all(immediate=True)
            app.db.poll_now()
            missing = [tid.hex() for tid in pushed if app.find_trace(tid) is None]
            assert not missing, f"{len(missing)} pushed traces unfindable: {missing[:3]}"
        finally:
            app.shutdown()


class TestKVStress:
    def test_concurrent_cas_and_watch(self, tmp_path):
        """Counters incremented from racing threads over the HTTP KV land
        exactly once each (CAS discipline), with watchers running."""
        from tempo_tpu.api.server import TempoServer
        from tempo_tpu.app import App, AppConfig
        from tempo_tpu.db import DBConfig
        from tempo_tpu.modules.netkv import HttpKV

        tmp = str(tmp_path)
        app = App(AppConfig(db=DBConfig(backend="local", backend_path=f"{tmp}/b",
                                        wal_path=f"{tmp}/w")))
        srv = TempoServer(app).start()
        clients = [HttpKV(srv.url, "stress", watch=(i % 2 == 0)) for i in range(4)]

        def worker(seed):
            rng = random.Random(seed)
            kv = clients[seed % len(clients)]
            me = f"c{seed}"
            for _ in range(15):
                kv.update(lambda d: {**d, me: d.get(me, 0) + 1})
                if rng.random() < 0.3:
                    kv.get()

        try:
            run_threads(4, worker, seeds=[0, 1, 2, 3])
            final = clients[1].update(lambda d: d)  # read-through latest
            assert all(final[f"c{s}"] == 15 for s in range(4)), final
        finally:
            for c in clients:
                c.close()
            srv.stop()
            app.shutdown()


class TestMeshSearcherStress:
    def test_concurrent_searches_share_the_cache(self):
        """Racing searches through the process-wide decoded-column cache
        (colcache.shared_cache — the mesh searcher's former private LRU
        was promoted there): results stay correct and the LRU byte
        counter stays consistent under maximal interleaving."""
        from tempo_tpu.backend import MockBackend
        from tempo_tpu.db import DBConfig, TempoDB
        from tempo_tpu.encoding.common import SearchRequest
        from tempo_tpu.encoding.vtpu.colcache import shared_cache
        from tempo_tpu.model import synth
        from tempo_tpu.model import trace as tr

        cache = shared_cache()
        if cache is None:
            pytest.skip("shared column cache disabled (TEMPO_TPU_COLCACHE_MB=0)")
        db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
        traces = []
        for i in range(6):
            ts = synth.make_traces(10, seed=500 + i, spans_per_trace=3)
            db.write_batch("t", tr.traces_to_batch(ts).sorted_by_trace())
            traces.extend(ts)
        svcs = sorted({t.batches[0][0].get("service.name", "") for t in traces} - {""})
        baseline = {
            svc: {x.trace_id_hex for x in db.search("t", SearchRequest(tags={"service.name": svc}, limit=0)).traces}
            for svc in svcs
        }

        def worker(seed):
            rng = random.Random(seed)
            for _ in range(8):
                svc = rng.choice(svcs)
                if rng.random() < 0.2:
                    cache.clear()  # eviction storms race the loaders
                got = db.search("t", SearchRequest(tags={"service.name": svc}, limit=0))
                assert {x.trace_id_hex for x in got.traces} == baseline[svc]

        run_threads(4, worker, seeds=[7, 8, 9, 10])
        # byte counter must equal the true sum after all the racing —
        # checked in ONE lock hold (prefetch loaders from other tests may
        # still land puts; _bytes and _lru only ever mutate together
        # under the lock, so a single-acquisition snapshot is the
        # consistency contract, racing loaders of one miss must not
        # double-count)
        with cache._lock:
            true_bytes = sum(v.nbytes for v in cache._lru.values())
            assert cache._bytes == true_bytes


class TestBackgroundCacheStress:
    def test_store_fetch_stop_interleaved(self):
        from tempo_tpu.cache import BackgroundCache, LRUCache

        inner = LRUCache(max_bytes=1 << 20)
        bg = BackgroundCache(inner, max_queued=64)

        def worker(seed):
            rng = random.Random(seed)
            for i in range(200):
                k = f"k{seed}-{i % 17}"
                if rng.random() < 0.6:
                    bg.store([k], [bytes([seed % 251]) * rng.randint(1, 64)])
                else:
                    bg.fetch([k])

        run_threads(4, worker, seeds=[101, 102, 103, 104])
        bg.flush()
        bg.stop()
        # post-conditions: inner LRU byte accounting consistent
        with inner._lock:
            assert inner._size == sum(len(v) for v in inner._data.values())


class TestGeneratorStress:
    def test_concurrent_pushes_pair_the_same_edges(self):
        """Four pushers share one tenant's processors while `expire`
        deletes behind them (a small wait_s): the service-graph pairing
        stores are mutated and iterated by every push. No exception, and
        the edges (every pair lies inside one push) are a serial run's."""
        from tempo_tpu.model import synth
        from tempo_tpu.modules.generator import (
            TenantGeneratorInstance,
            servicegraphs,
            spanmetrics,
        )
        from tempo_tpu.modules.generator.servicegraphs import ServiceGraphsProcessor
        from tempo_tpu.modules.overrides import Limits, Overrides

        rounds = 20
        batches = {seed: [synth.make_graph_batch(16, 6, seed=seed * 1000 + i)
                          for i in range(rounds)] for seed in (1, 2, 3, 4)}

        def instance():
            inst = TenantGeneratorInstance("acme", Overrides(Limits()))
            graphs = next(p for p in inst.processors if isinstance(p, ServiceGraphsProcessor))
            graphs.wait_s = 1e-4  # halves left by earlier pushes expire in every push
            return inst, graphs

        serial, serial_graphs = instance()
        for seed in batches:
            for b in batches[seed]:
                serial.push_batch(b)
        assert serial_graphs.edges_emitted > 0 and serial_graphs.expired > 0

        shared, graphs = instance()

        def worker(seed):
            for b in batches[seed]:
                shared.push_batch(b)

        run_threads(4, worker, seeds=[1, 2, 3, 4])
        assert graphs.edges_emitted == serial_graphs.edges_emitted
        # every half that found no partner left again, once
        pending = len(graphs.pending_clients) + len(graphs.pending_servers)
        serial_pending = len(serial_graphs.pending_clients) + len(serial_graphs.pending_servers)
        assert graphs.expired + pending == serial_graphs.expired + serial_pending
        # the registry both processors write to (it has a lock of its own)
        # counted every call and every edge of every push

        def counters(inst):
            return sorted((s.name, s.labels, s.value) for s in inst.registry.collect(now_ms=1)
                          if s.name in (spanmetrics.CALLS, servicegraphs.REQ_TOTAL,
                                        servicegraphs.REQ_FAILED))

        assert counters(shared) == counters(serial)
