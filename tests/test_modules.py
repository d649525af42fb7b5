"""Service-module tests: ring math, overrides reload, the full
distributor -> ingester -> WAL -> block -> query write path (in-process
all-in-one, the reference's TestAllInOne shape), frontend sharding,
fair queue, generator processors."""

import json
import random
import time
import uuid

import numpy as np
import pytest

from tempo_tpu.app import App, AppConfig
from tempo_tpu.backend.base import BlockMeta
from tempo_tpu.db import DBConfig, _in_shard, find_block_probes
from tempo_tpu.encoding.common import SearchRequest
from tempo_tpu.model import synth
from tempo_tpu.model import trace as tr
from tempo_tpu.modules.distributor import RateLimited
from tempo_tpu.metrics_engine import compile_metrics_plan
from tempo_tpu.modules.frontend import (
    FrontendConfig,
    _metrics_blocks_job,
    create_block_boundaries,
    metrics_job_blocks_total,
)
from tempo_tpu.modules.ingester import MaxLiveTraces, TraceTooLarge
from tempo_tpu.modules.overrides import Limits, Overrides
from tempo_tpu.modules.queue import RequestQueue, TooManyRequests
from tempo_tpu.modules.ring import FileKV, MemoryKV, Ring
from tempo_tpu.util import stagetimings


def make_app(tmp_path, **kw):
    defaults = dict(
        db=DBConfig(backend="local", backend_path=str(tmp_path / "blocks"),
                    wal_path=str(tmp_path / "wal")),
    )
    defaults.update(kw)
    return App(AppConfig(**defaults))


class TestRing:
    def test_replicas_distinct_and_stable(self):
        ring = Ring(MemoryKV(), replication_factor=3)
        for i in range(5):
            ring.register(f"ing-{i}")
        reps = ring.get_replicas(12345)
        assert len(reps) == 3
        assert len({r.instance_id for r in reps}) == 3
        assert [r.instance_id for r in ring.get_replicas(12345)] == [
            r.instance_id for r in reps
        ]

    def test_distribution_roughly_uniform(self):
        ring = Ring(MemoryKV(), replication_factor=1)
        for i in range(4):
            ring.register(f"ing-{i}")
        counts = {}
        rng = np.random.default_rng(0)
        for t in rng.integers(0, 2**32, 4000):
            iid = ring.get_replicas(int(t))[0].instance_id
            counts[iid] = counts.get(iid, 0) + 1
        assert len(counts) == 4
        assert min(counts.values()) > 4000 / 4 * 0.5  # no pathological skew

    def test_unhealthy_skipped(self):
        ring = Ring(MemoryKV(), replication_factor=1, heartbeat_timeout_s=0.1)
        ring.register("a")
        ring.register("b")
        # age out a's heartbeat
        ring.kv.update(lambda s: {**s, "a": {**s["a"], "heartbeat": time.time() - 10}})
        for t in (1, 2**31, 2**32 - 5):
            assert ring.get_replicas(t)[0].instance_id == "b"

    def test_file_kv_shared(self, tmp_path):
        path = str(tmp_path / "ring.json")
        r1 = Ring(FileKV(path))
        r2 = Ring(FileKV(path))
        r1.register("a")
        assert [i.instance_id for i in r2.instances()] == ["a"]

    def test_shuffle_shard_deterministic(self):
        ring = Ring(MemoryKV())
        for i in range(6):
            ring.register(f"g-{i}")
        s1 = [i.instance_id for i in ring.shuffle_shard("tenant-x", 2)]
        s2 = [i.instance_id for i in ring.shuffle_shard("tenant-x", 2)]
        assert s1 == s2 and len(s1) == 2

    def test_zone_aware_replication_spreads_zones(self):
        """RF=3 across 3 zones: every replica set holds one instance per
        zone (reference: dskit ring zone-awareness)."""
        from tempo_tpu.modules.ring import MemoryKV, Ring

        ring = Ring(MemoryKV(), replication_factor=3, zone_awareness=True)
        for z in ("a", "b", "c"):
            for i in range(2):  # two instances per zone
                ring.register(f"ing-{z}{i}", zone=z, seed=hash((z, i)) & 0xFFFF)
        snap = ring.snapshot()
        import random as _r

        rng = _r.Random(3)
        for _ in range(200):
            reps = snap.get_replicas(rng.randrange(0, 2**32))
            assert len(reps) == 3
            assert sorted(r.zone for r in reps) == ["a", "b", "c"], [
                (r.instance_id, r.zone) for r in reps]

    def test_zone_aware_overflow_when_fewer_zones_than_rf(self):
        """RF=3 with only 2 zones still yields 3 DISTINCT instances
        (spread-then-overflow, never fewer replicas)."""
        from tempo_tpu.modules.ring import MemoryKV, Ring

        ring = Ring(MemoryKV(), replication_factor=3, zone_awareness=True)
        for z in ("a", "b"):
            for i in range(3):
                ring.register(f"ing-{z}{i}", zone=z, seed=hash((z, i)) & 0xFFFF)
        snap = ring.snapshot()
        reps = snap.get_replicas(12345)
        assert len(reps) == 3
        assert len({r.instance_id for r in reps}) == 3
        assert {r.zone for r in reps} == {"a", "b"}

    def test_zone_awareness_off_ignores_zones(self):
        from tempo_tpu.modules.ring import MemoryKV, Ring

        ring = Ring(MemoryKV(), replication_factor=2, zone_awareness=False)
        ring.register("x1", zone="a", seed=1)
        ring.register("x2", zone="a", seed=2)
        reps = ring.get_replicas(999)
        assert len(reps) == 2  # same-zone pair is fine without awareness

    def test_owns_partitions_work(self):
        ring = Ring(MemoryKV())
        ring.register("c-0")
        ring.register("c-1")
        owned = {"c-0": 0, "c-1": 0}
        for h in range(200):
            for iid in owned:
                if ring.owns(iid, h * 21652301):
                    owned[iid] += 1
        assert sum(owned.values()) == 200  # exactly one owner each
        assert min(owned.values()) > 0


class TestOverrides:
    def test_defaults_and_per_tenant(self, tmp_path):
        p = tmp_path / "overrides.json"
        p.write_text(json.dumps({"overrides": {"acme": {"max_traces_per_user": 7}}}))
        ov = Overrides(Limits(max_traces_per_user=100), str(p))
        assert ov.for_tenant("acme").max_traces_per_user == 7
        assert ov.for_tenant("other").max_traces_per_user == 100

    def test_yaml_overrides_file(self, tmp_path):
        """The reference's runtimeconfig overrides file is YAML; JSON
        keeps working as a YAML subset."""
        p = tmp_path / "overrides.yaml"
        p.write_text("overrides:\n  acme:\n    max_traces_per_user: 7\n    forwarders: [otlp-a]\n")
        ov = Overrides(Limits(max_traces_per_user=100), str(p))
        assert ov.for_tenant("acme").max_traces_per_user == 7
        assert ov.for_tenant("acme").forwarders == ("otlp-a",)
        assert ov.tenants_with_overrides() == ["acme"]

    def test_yaml_empty_overrides_clears_tenants(self, tmp_path):
        """`overrides:` with no tenants (YAML None) clears all overrides
        instead of crashing the reload and serving stale limits."""
        p = tmp_path / "overrides.yaml"
        p.write_text("overrides:\n  acme:\n    max_traces_per_user: 7\n")
        ov = Overrides(Limits(max_traces_per_user=100), str(p))
        assert ov.tenants_with_overrides() == ["acme"]
        p.write_text("overrides:\n")
        ov._load(force=True)
        assert ov.tenants_with_overrides() == []
        # an empty tenant block is fine too (all defaults)
        p.write_text("overrides:\n  acme:\n")
        ov._load(force=True)
        assert ov.for_tenant("acme").max_traces_per_user == 100

    def test_hot_reload(self, tmp_path):
        p = tmp_path / "overrides.json"
        p.write_text(json.dumps({"overrides": {}}))
        ov = Overrides(Limits(), str(p))
        assert ov.for_tenant("a").max_traces_per_user == 10_000
        time.sleep(0.02)
        p.write_text(json.dumps({"overrides": {"a": {"max_traces_per_user": 1}}}))
        import os

        os.utime(p, (time.time() + 5, time.time() + 5))
        ov.maybe_reload()
        assert ov.for_tenant("a").max_traces_per_user == 1

    def test_unknown_key_keeps_previous(self, tmp_path):
        p = tmp_path / "overrides.json"
        p.write_text(json.dumps({"overrides": {"a": {"max_traces_per_user": 5}}}))
        ov = Overrides(Limits(), str(p))
        assert ov.for_tenant("a").max_traces_per_user == 5
        p.write_text(json.dumps({"overrides": {"a": {"not_a_knob": 1}}}))
        import os

        os.utime(p, (time.time() + 5, time.time() + 5))
        ov.maybe_reload()
        assert ov.for_tenant("a").max_traces_per_user == 5  # kept previous good

    def test_global_rate_strategy(self):
        ov = Overrides(Limits(ingestion_rate_limit_bytes=100, ingestion_rate_strategy="global"))
        assert ov.ingestion_rate_bytes("t", ring_size=4) == 25


class TestAllInOne:
    """Push -> live query -> cut/flush -> backend query -> compact ->
    query again, all through the composed app."""

    def test_write_then_read(self, tmp_path):
        app = make_app(tmp_path)
        traces = synth.make_traces(12, seed=50)
        app.push_traces(traces)
        # live: findable via ingester before any cut
        got = app.find_trace(traces[0].trace_id)
        assert got is not None and got.span_count() == traces[0].span_count()

        app.sweep_all(immediate=True)  # cut + complete + flush
        assert len(app.db.blocklist.metas("single-tenant")) >= 1
        got = app.find_trace(traces[5].trace_id)
        assert got is not None and got.span_count() == traces[5].span_count()

        svc = traces[0].batches[0][0]["service.name"]
        resp = app.search(SearchRequest(tags={"service.name": svc}, limit=0))
        want = {
            t.trace_id.hex() for t in traces
            if any(r.get("service.name") == svc for r, _ in t.batches)
        }
        assert {m.trace_id_hex for m in resp.traces} == want
        app.shutdown()

    def test_replication_factor_dedupe(self, tmp_path):
        app = make_app(tmp_path, n_ingesters=3, replication_factor=2)
        traces = synth.make_traces(10, seed=51)
        app.push_traces(traces)
        app.sweep_all(immediate=True)
        app.db.compact_once("single-tenant")
        for t in traces[:5]:
            got = app.find_trace(t.trace_id)
            assert got is not None
            assert got.span_count() == t.span_count()  # RF copies deduped
        app.shutdown()

    def test_traceql_through_app(self, tmp_path):
        app = make_app(tmp_path)
        traces = synth.make_traces(10, seed=52)
        app.push_traces(traces)
        app.sweep_all(immediate=True)
        res = app.traceql("{ status = error }", limit=0)
        want = {
            t.trace_id.hex() for t in traces
            if any(s.status_code == 2 for s in t.all_spans())
        }
        assert {r.trace_id_hex for r in res} == want
        app.shutdown()

    def test_live_search_before_flush(self, tmp_path):
        app = make_app(tmp_path)
        traces = synth.make_traces(6, seed=53)
        app.push_traces(traces)
        svc = traces[0].batches[0][0]["service.name"]
        resp = app.search(SearchRequest(tags={"service.name": svc}, limit=0))
        assert resp.traces  # found in live data
        app.shutdown()

    def test_multitenancy(self, tmp_path):
        app = make_app(tmp_path, multitenancy_enabled=True)
        traces = synth.make_traces(3, seed=54)
        app.push_traces(traces, org_id="team-a")
        with pytest.raises(PermissionError):
            app.push_traces(traces)
        assert app.find_trace(traces[0].trace_id, org_id="team-b") is None
        assert app.find_trace(traces[0].trace_id, org_id="team-a") is not None
        app.shutdown()


class TestIngestLimits:
    def test_rate_limit(self, tmp_path):
        app = make_app(tmp_path, limits=Limits(ingestion_rate_limit_bytes=10, ingestion_burst_size_bytes=10))
        with pytest.raises(RateLimited):
            app.push_traces(synth.make_traces(5, seed=55))
        app.shutdown()

    def test_max_live_traces(self, tmp_path):
        app = make_app(tmp_path, limits=Limits(max_traces_per_user=2))
        with pytest.raises(Exception) as ei:
            app.push_traces(synth.make_traces(5, seed=56))
        assert "max live traces" in str(ei.value) or isinstance(ei.value, MaxLiveTraces)
        app.shutdown()

    def test_trace_too_large(self, tmp_path):
        app = make_app(tmp_path, limits=Limits(max_spans_per_trace=3))
        with pytest.raises(Exception) as ei:
            app.push_traces(synth.make_traces(1, seed=57, spans_per_trace=10))
        assert "spans" in str(ei.value)
        app.shutdown()


class TestWalRecovery:
    def test_ingester_crash_replay(self, tmp_path):
        app = make_app(tmp_path)
        traces = synth.make_traces(8, seed=58)
        app.push_traces(traces)
        # cut to WAL but "crash" before complete/flush
        for ing in app.ingesters.values():
            for inst in ing.instances.values():
                inst.cut_complete_traces(immediate=True)
                inst.cut_block_if_ready(immediate=True)
        # new app over the same dirs (same wal subdirs via instance ids)
        app2 = make_app(tmp_path)
        app2.sweep_all(immediate=True)  # replayed blocks complete+flush
        app2.db.poll_now()
        got = app2.find_trace(traces[3].trace_id)
        assert got is not None and got.span_count() == traces[3].span_count()
        app.shutdown()
        app2.shutdown()


class TestFrontend:
    def test_block_boundaries_uniform(self):
        b = create_block_boundaries(4)
        assert b[0] == "0" * 32 and b[-1] == "f" * 32
        assert len(b) == 5
        assert b == sorted(b)

    @pytest.mark.parametrize("n_shards", [1, 2, 4, 50])
    def test_block_id_in_exactly_one_shard(self, n_shards):
        bounds = create_block_boundaries(n_shards)
        assert len(bounds) == n_shards + 1
        rng = random.Random(n_shards)
        ids = [rng.getrandbits(128) for _ in range(300)]
        for b in bounds:  # the boundary values themselves and their neighbours
            ids += [v for v in (int(b, 16) - 1, int(b, 16), int(b, 16) + 1)
                    if 0 <= v < 1 << 128]
        for v in ids:
            meta = BlockMeta(block_id=str(uuid.UUID(int=v)))
            hits = [i for i in range(n_shards)
                    if _in_shard(meta, bounds[i], bounds[i + 1])]
            assert len(hits) == 1, (meta.block_id, hits)
            # the default bounds (vulture, cli, serverless, mode="all")
            assert _in_shard(meta, "0" * 32, "f" * 32)

    def _sharded_app(self, tmp_path, query_shards):
        return make_app(
            tmp_path,
            frontend=FrontendConfig(query_shards=query_shards, hedge_after_s=0),
            generator_enabled=False,
        )

    @pytest.mark.parametrize("query_shards", [1, 4, 7])
    def test_find_probes_each_candidate_block_once(self, tmp_path, query_shards):
        app = self._sharded_app(tmp_path, query_shards)
        try:
            blocks = [synth.make_traces(12, seed=60 + j) for j in range(4)]
            metas = [
                app.db.write_batch("single-tenant",
                                   tr.traces_to_batch(ts).sorted_by_trace())
                for ts in blocks
            ]
            want = blocks[2][5]
            # absent from every block, inside every block's ID range
            absent = bytes.fromhex(format(int(want.trace_id.hex(), 16) ^ 1, "032x"))
            for tid, found in ((want.trace_id, True), (absent, False)):
                hex_id = tid.hex()
                candidates = sum(m.min_id <= hex_id <= m.max_id for m in metas)
                assert candidates >= 2
                probes = find_block_probes.value()
                finds = stagetimings.stage_seconds_hist.count(
                    stage="queue_wait", kind="find")
                got = app.find_trace(tid)
                if found:
                    assert got.span_count() == want.span_count()
                else:
                    assert got is None
                assert find_block_probes.value() - probes == candidates
                # the per-layer metric's denominator: one observation a find
                assert stagetimings.stage_seconds_hist.count(
                    stage="queue_wait", kind="find") - finds == 1
        finally:
            app.shutdown()

    @pytest.mark.parametrize("query_shards", [1, 4, 7])
    def test_query_range_places_each_candidate_block_once(
            self, tmp_path, monkeypatch, query_shards):
        app = self._sharded_app(tmp_path, query_shards)
        try:
            base = 1_700_000_000
            # three blocks inside a one-hour window, one a day later
            metas = [
                app.db.write_batch("single-tenant", synth.make_batch(
                    40, 4, seed=70 + j, base_time_ns=(base + off) * 10**9))
                for j, off in enumerate((30, 1790, 3500, 86_400))
            ]
            descs = []
            run = app.frontend._run_jobs
            monkeypatch.setattr(
                app.frontend, "_run_jobs",
                lambda tenant, ds: (descs.extend(ds), run(tenant, ds))[1])
            placed = metrics_job_blocks_total.value()
            asked = stagetimings.stage_seconds_hist.count(
                stage="queue_wait", kind="query_range")
            doc = app.query_range("{} | rate()", base, base + 3600, 60)
            assert sum(float(v) * 60 for s in doc["result"] for _, v in s["values"]) \
                == pytest.approx(3 * 160)
            jobs = [d for d in descs if d["kind"] == "metrics_blocks"]
            in_jobs = [b for d in jobs for b in d["block_ids"]]
            assert sorted(in_jobs) == sorted(m.block_id for m in metas[:3])
            # the counter reads the descriptors: one count a block ID placed
            assert metrics_job_blocks_total.value() - placed == len(in_jobs) == 3
            # the per-layer metric's denominator: one observation a query_range
            assert stagetimings.stage_seconds_hist.count(
                stage="queue_wait", kind="query_range") - asked == 1
        finally:
            app.shutdown()

    @pytest.mark.parametrize("blocks, start, end, step, window", [
        # data on a step boundary, a window one step either side: one bin
        ([(1060, 1063)], 1000, 1180, 60, (1060, 1120)),
        # the group's earliest start to its latest end, step-aligned outward
        ([(1130, 1190), (1070, 1075), (1200, 1241)], 1000, 1600, 60, (1060, 1300)),
        # a block that began before the window or ends after it is clipped to the grid
        ([(400, 1010)], 1000, 1600, 60, (1000, 1060)),
        ([(1500, 9000)], 1000, 1600, 60, (1480, 1600)),
        ([(0, 9000)], 1000, 1600, 60, (1000, 1600)),
        # a window that is no whole number of steps: the last bin ends with it
        ([(1590, 1592)], 1000, 1600, 45, (1585, 1600)),
        # a block ON the window's own last second passes the inclusive
        # candidate test and still gets a bin, the last one
        ([(1600, 1603)], 1000, 1600, 60, (1540, 1600)),
        ([(990, 1000)], 1000, 1600, 60, (1000, 1060)),
    ])
    def test_metrics_job_window_is_the_hull_on_the_plans_grid(
            self, blocks, start, end, step, window):
        plan = compile_metrics_plan("{} | rate()", start, end, step)
        group = [BlockMeta(start_time=a, end_time=b) for a, b in blocks]
        d = _metrics_blocks_job(plan, group, {"q": "{} | rate()", "step": step})
        assert (d["start"], d["end"]) == window
        assert d["block_ids"] == [m.block_id for m in group]
        assert (d["start"] - start) % step == 0
        assert start <= d["start"] < d["end"] <= end
        assert (d["kind"], d["q"], d["step"]) == ("metrics_blocks", "{} | rate()", step)

    @pytest.mark.parametrize("block_ids, slices", [
        (("10000000-0000-4000-8000-000000000001", "90000000-0000-4000-8000-000000000002"), [0, 2]),
        # either side of a boundary, and the boundary itself in the upper slice
        (("3fffffff-ffff-ffff-ffff-ffffffffffff", "40000000-0000-0000-0000-000000000000"), [0, 1]),
        (("00000000-0000-0000-0000-000000000000", "ffffffff-ffff-ffff-ffff-ffffffffffff"), [0, 3]),
    ])
    def test_find_combines_a_trace_split_over_shards(self, tmp_path, block_ids, slices):
        t = synth.make_trace(seed=3, n_spans=10)
        spans = list(t.all_spans())
        resource = t.batches[0][0]
        halves = [tr.Trace(trace_id=t.trace_id, batches=[(resource, spans[:6])]),
                  tr.Trace(trace_id=t.trace_id, batches=[(resource, spans[4:])])]
        answers = {}
        for query_shards in (1, 4):
            app = self._sharded_app(tmp_path, query_shards)
            try:
                if query_shards == 1:  # write once, re-read at both shard counts
                    for half, block_id in zip(halves, block_ids):
                        app.db.write_batch(
                            "single-tenant",
                            tr.traces_to_batch([half]).sorted_by_trace(),
                            block_id=block_id)
                app.db.poll_now()
                bounds = create_block_boundaries(4)
                by_id = {m.block_id: m for m in app.db.blocklist.metas("single-tenant")}
                assert [
                    [i for i in range(4) if _in_shard(by_id[b], bounds[i], bounds[i + 1])]
                    for b in block_ids
                ] == [[s] for s in slices]
                got = app.find_trace(t.trace_id)
                assert got.span_count() == 10
                answers[query_shards] = sorted(
                    s.span_id for s in got.all_spans())
            finally:
                app.shutdown()
        assert answers[1] == answers[4] == sorted(s.span_id for s in spans)

    def test_queue_fairness(self):
        q = RequestQueue(max_per_tenant=100)
        order = []
        for i in range(3):
            q.enqueue("heavy", lambda i=i: order.append(("heavy", i)))
        q.enqueue("light", lambda: order.append(("light", 0)))
        for _ in range(4):
            tenant, job = q.dequeue(timeout=0.1)
            job()
        # light tenant is served before heavy drains completely
        assert order.index(("light", 0)) < 3

    def test_queue_backpressure(self):
        q = RequestQueue(max_per_tenant=2)
        q.enqueue("t", lambda: None)
        q.enqueue("t", lambda: None)
        with pytest.raises(TooManyRequests):
            q.enqueue("t", lambda: None)


class TestGenerator:
    def test_spanmetrics_counts(self, tmp_path):
        app = make_app(tmp_path)
        traces = synth.make_traces(10, seed=59)
        app.push_traces(traces)
        reg = app.generator.instance("single-tenant").registry
        samples = {s.name: 0.0 for s in reg.collect()}
        total_calls = sum(
            s.value for s in reg.collect() if s.name == "traces_spanmetrics_calls_total"
        )
        assert total_calls == sum(t.span_count() for t in traces)
        assert any(s.name.startswith("traces_spanmetrics_latency") for s in reg.collect())
        app.shutdown()

    def test_servicegraph_edges(self):
        from tempo_tpu.modules.generator.registry import ManagedRegistry
        from tempo_tpu.modules.generator.servicegraphs import ServiceGraphsProcessor

        reg = ManagedRegistry("t")
        p = ServiceGraphsProcessor(reg)
        tid = b"\x07" * 16
        client = tr.Span(trace_id=tid, span_id=b"\x01" * 8, name="call",
                         kind=tr.KIND_CLIENT, duration_nano=10**8)
        server = tr.Span(trace_id=tid, span_id=b"\x02" * 8, parent_span_id=b"\x01" * 8,
                         name="serve", kind=tr.KIND_SERVER, duration_nano=5 * 10**7,
                         status_code=2)
        t1 = tr.Trace(trace_id=tid, batches=[({"service.name": "A"}, [client])])
        t2 = tr.Trace(trace_id=tid, batches=[({"service.name": "B"}, [server])])
        p.push(tr.traces_to_batch([t1]))
        p.push(tr.traces_to_batch([t2]))
        assert p.edges_emitted == 1
        vals = {(s.name, s.labels): s.value for s in reg.collect()}
        assert vals[("traces_service_graph_request_total", (("client", "A"), ("server", "B")))] == 1.0
        assert vals[("traces_service_graph_request_failed_total", (("client", "A"), ("server", "B")))] == 1.0
        assert p.distinct_edges_estimate() >= 1.0

    def test_registry_staleness_and_limits(self):
        from tempo_tpu.modules.generator.registry import ManagedRegistry

        reg = ManagedRegistry("t", max_active_series=2, stale_after_s=1.0)
        reg.inc_counter("m", (("a", "1"),), 1, now=100.0)
        reg.inc_counter("m", (("a", "2"),), 1, now=100.0)
        reg.inc_counter("m", (("a", "3"),), 1, now=100.0)  # over limit -> dropped
        assert reg.active_series() == 2
        assert reg.series_dropped == 1
        assert reg.remove_stale(now=102.0) == 2
        assert reg.active_series() == 0


class TestReviewRegressions:
    def test_servicegraph_long_names_stay_distinct(self):
        """Edge sketch keys must hash the full (client, server) pair —
        a >=15-char client name used to truncate the server out of the key."""
        from tempo_tpu.modules.generator.registry import ManagedRegistry
        from tempo_tpu.modules.generator.servicegraphs import ServiceGraphsProcessor

        reg = ManagedRegistry("t")
        p = ServiceGraphsProcessor(reg)
        client_svc = "checkout-service-production"
        for i in range(30):
            tid = bytes([i]) * 16
            c = tr.Span(trace_id=tid, span_id=b"\x01" * 8, name="call",
                        kind=tr.KIND_CLIENT, duration_nano=10**7)
            s = tr.Span(trace_id=tid, span_id=b"\x02" * 8, parent_span_id=b"\x01" * 8,
                        name="serve", kind=tr.KIND_SERVER, duration_nano=10**6)
            t1 = tr.Trace(trace_id=tid, batches=[({"service.name": client_svc}, [c])])
            t2 = tr.Trace(trace_id=tid, batches=[({"service.name": f"downstream-{i}"}, [s])])
            p.push(tr.traces_to_batch([t1]))
            p.push(tr.traces_to_batch([t2]))
        assert p.edges_emitted == 30
        est = p.distinct_edges_estimate()
        assert 20 <= est <= 40, est

    def test_frontend_raises_on_partial_shard_failure(self, tmp_path):
        """A failed shard must fail the query, not silently truncate it."""
        app = make_app(tmp_path)
        traces = synth.make_traces(5, seed=3)
        app.push_traces(traces)
        orig = app.querier.find_trace_by_id
        calls = {"n": 0}

        def flaky(tenant, trace_id, mode="all", **kw):
            calls["n"] += 1
            if mode == "blocks" and calls["n"] % 2 == 0:
                raise OSError("backend read failed")
            return orig(tenant, trace_id, mode=mode, **kw)

        app.querier.find_trace_by_id = flaky
        app.frontend.cfg.max_retries = 0
        # worker errors travel the job protocol as JobError with the
        # original message (the process boundary can't carry the type)
        with pytest.raises(Exception, match="backend read failed"):
            app.frontend.find_trace_by_id("single-tenant", traces[0].trace_id)
        app.shutdown()

    def test_compactor_module_heartbeats_with_ring(self, tmp_path):
        from tempo_tpu.db import DBConfig, TempoDB
        from tempo_tpu.modules.compactor_module import CompactorModule

        db = TempoDB(DBConfig(backend="local", backend_path=str(tmp_path / "b"),
                              wal_path=str(tmp_path / "w")))
        ring = Ring(MemoryKV(), heartbeat_timeout_s=0.2, replication_factor=1)
        mod = CompactorModule(db, ring=ring, cycle_s=3600)
        time.sleep(0.3)  # past the timeout: without heartbeats it'd be dead
        ring.heartbeat(mod.instance_id)  # deterministic beat (loop period is 10s)
        assert mod.owns("tenant-window-job")
        mod.stop()
        db.shutdown()

    def test_filekv_concurrent_updates_do_not_lose_registrations(self, tmp_path):
        import multiprocessing as mp

        path = str(tmp_path / "ring.json")
        ctx = mp.get_context("spawn")  # fork from threaded pytest can deadlock
        procs = [ctx.Process(target=_register_in_ring, args=(path, i)) for i in range(6)]
        [p.start() for p in procs]
        [p.join() for p in procs]
        assert all(p.exitcode == 0 for p in procs)
        state = FileKV(path).get()
        assert sorted(state) == [f"ing-{i}" for i in range(6)]

    def test_heartbeat_reregisters_lost_instance(self):
        kv = MemoryKV()
        ring = Ring(kv)
        ring.register("ing-0")
        kv.update(lambda s: {})  # state wiped
        ring.heartbeat("ing-0")
        assert "ing-0" in kv.get()


def _register_in_ring(path, i):  # top-level: spawn target must be picklable
    Ring(FileKV(path)).register(f"ing-{i}")


class TestHedgedJobs:
    def test_slow_shard_completes_via_hedge(self):
        """A worker that wedges on the FIRST pull of a job must not stall
        the query: after hedge_after_s a duplicate dispatches and its
        result wins (reference: the frontend's hedged-requests
        middleware, hedged_requests.go:26)."""
        import threading
        import time as _time

        from tempo_tpu.modules.frontend import Frontend, FrontendConfig
        from tempo_tpu.modules.worker import JobBroker

        broker = JobBroker(lease_s=60.0)
        fe = Frontend(broker, db=None,
                      cfg=FrontendConfig(hedge_after_s=0.2, job_timeout_s=10.0,
                                         max_retries=0))
        wedged_once = threading.Event()
        stop = threading.Event()

        def worker():
            while not stop.is_set():
                item = broker.pull(timeout=0.2)
                if item is None:
                    continue
                job_id, _tenant, desc = item
                if desc.get("wedge") and not wedged_once.is_set():
                    wedged_once.set()
                    stop.wait(30)  # simulate a stuck worker holding the lease
                    continue
                broker.complete(job_id, result={"ok": desc.get("n")})

        threads = [threading.Thread(target=worker, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        t0 = _time.monotonic()
        results, errors = fe._run_jobs("t", [{"wedge": True, "n": 1}, {"n": 2}])
        dt = _time.monotonic() - t0
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert not errors, errors
        assert sorted(r["ok"] for r in results) == [1, 2]
        assert dt < 8.0, f"hedge did not rescue the wedged shard ({dt:.1f}s)"
        assert wedged_once.is_set()
