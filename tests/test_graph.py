"""Trace-graph analytics plane: kernels, stored-block aggregation,
live-vs-stored edge parity, shard/host-device invariance, seeded walks,
the /api/graph/* endpoints, usage charging, and the `_self_` dogfood.

Invariants under test (the same contracts parallel/metrics.py keeps):
- host numpy and the two-limb device critical-path accumulation are
  bit-identical;
- dependencies/critical-path results are bit-identical at ANY job
  sharding (partials are integer adds / min / max);
- live-generator edges == stored-block aggregation on identical ingest;
- seeded walks replay bit-identically across processes.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from tempo_tpu import graph
from tempo_tpu.api.server import TempoServer
from tempo_tpu.app import App, AppConfig
from tempo_tpu.db import DBConfig
from tempo_tpu.encoding.common import BlockConfig, SearchRequest
from tempo_tpu.graph import walks as walks_mod
from tempo_tpu.model import synth
from tempo_tpu.model.columnar import trace_segmentation
from tempo_tpu.model.trace import (
    KIND_CLIENT,
    KIND_INTERNAL,
    KIND_SERVER,
    STATUS_ERROR,
    STATUS_OK,
    Span,
    Trace,
    batch_to_traces,
)
from tempo_tpu.modules.frontend import FrontendConfig
from tempo_tpu.modules.generator.servicegraphs import (
    EXPIRED_TOTAL,
    REQ_FAILED,
    REQ_TOTAL,
    ServiceGraphsProcessor,
)
from tempo_tpu.ops import graph as ops_graph

BASE_NS = 1_700_000_000 * 10**9


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def chain_trace(seed: int, fail: bool = False) -> Trace:
    """frontend(SERVER root) -> frontend(CLIENT) -> cart(SERVER) ->
    cart(CLIENT db.query): one cross-service edge frontend->cart."""
    rng = np.random.default_rng(seed)
    tid = rng.bytes(16)
    base = BASE_NS + seed * 10**9
    s = [rng.bytes(8) for _ in range(4)]
    t = Trace(trace_id=tid)
    t.batches.append(({"service.name": "frontend"}, [
        Span(tid, s[0], "GET /", b"\x00" * 8, base, 50_000_000, kind=KIND_SERVER),
        Span(tid, s[1], "call cart", s[0], base + 1_000_000, 40_000_000,
             kind=KIND_CLIENT),
    ]))
    t.batches.append(({"service.name": "cart"}, [
        Span(tid, s[2], "POST /cart", s[1], base + 2_000_000, 35_000_000,
             kind=KIND_SERVER,
             status_code=STATUS_ERROR if fail else STATUS_OK),
        Span(tid, s[3], "db.query", s[2], base + 3_000_000, 20_000_000,
             kind=KIND_CLIENT),
    ]))
    return t


def batch_cols(batch) -> dict:
    return {c: batch.cols[c] for c in graph.GRAPH_COLUMNS}


def strip_volatile(doc: dict) -> dict:
    """Drop per-run noise so documents compare bit-exactly: timings, and
    the byte counters (the process-wide column cache serves repeat runs
    from memory, so bytes_read depends on cache state, not sharding)."""
    doc = dict(doc)
    stats = dict(doc.get("stats") or {})
    for k in ("stageSeconds", "deviceDispatches", "elapsedMs",
              "inspectedBytes", "decodedBytes"):
        stats.pop(k, None)
    doc["stats"] = stats
    return doc


# ---------------------------------------------------------------------------
# ops/graph kernels
# ---------------------------------------------------------------------------


class TestKernels:
    def test_parent_row_join_matches_bruteforce(self):
        rng = np.random.default_rng(5)
        n_traces, per = 40, 12
        seg = np.repeat(np.arange(n_traces), per)
        n = len(seg)
        sid = rng.integers(1, 40, size=(n, 2)).astype(np.uint32)
        par = rng.integers(0, 40, size=(n, 2)).astype(np.uint32)
        got = ops_graph.parent_row_join(seg, sid, par)
        for i in range(n):
            want = -1
            for j in range(n):  # LAST matching row wins (dict insert order)
                if seg[j] == seg[i] and (sid[j] == par[i]).all():
                    want = j
            if want == i:  # self-parenting resolves to root
                want = -1
            assert got[i] == want, (i, got[i], want)

    def test_self_times_clamped(self):
        parent = np.array([-1, 0, 0])
        dur = np.array([100, 70, 60], np.uint64)  # children sum > parent
        self_ns = ops_graph.self_times_ns(parent, dur)
        assert self_ns.tolist() == [0, 70, 60]

    def test_critical_path_hand_computed(self):
        # root(100) -> a(60) -> b(30); c(20) under root
        seg = np.zeros(4, np.int64)
        parent = np.array([-1, 0, 1, 0])
        dur = np.array([100, 60, 30, 20], np.uint64)
        firsts = np.array([0])
        self_ns, on_path, path_ns = ops_graph.critical_path(
            parent, dur, seg, firsts, device=False)
        assert self_ns.tolist() == [20, 30, 30, 20]
        assert on_path.tolist() == [True, True, True, False]
        assert path_ns.tolist() == [80]

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_host_device_bit_identical(self, seed):
        """The two-limb uint32 device accumulation == host uint64,
        including durations far beyond 32 bits."""
        rng = np.random.default_rng(seed)
        b = synth.make_graph_batch(200, 9, seed=seed)
        dur = b.cols["duration_nano"].copy()
        dur[rng.integers(0, len(dur), 50)] += np.uint64(2**40)  # > u32
        _, seg, firsts = trace_segmentation(b.cols["trace_id"])
        pr = ops_graph.parent_row_join(seg, b.cols["span_id"],
                                       b.cols["parent_span_id"])
        self_ns = ops_graph.self_times_ns(pr, dur)
        host = ops_graph.root_path_sums_host(pr, self_ns)
        dev = ops_graph.root_path_sums_device(pr, self_ns,
                                              bucket_for=BlockConfig().bucket_for)
        assert np.array_equal(host, dev)

    def test_cycle_terminates(self):
        """Malformed parent cycles must terminate, not hang."""
        seg = np.zeros(2, np.int64)
        sid = np.array([[0, 1], [0, 2]], np.uint32)
        par = np.array([[0, 2], [0, 1]], np.uint32)  # 0 <-> 1 cycle
        pr = ops_graph.parent_row_join(seg, sid, par)
        self_ns, on_path, path_ns = ops_graph.critical_path(
            pr, np.array([10, 10], np.uint64), seg, np.array([0]), device=False)
        assert on_path.any()


# ---------------------------------------------------------------------------
# edge aggregation + critical-path partials
# ---------------------------------------------------------------------------


class TestAggregation:
    def test_exact_edges_from_chain_traces(self):
        b = synth.make_graph_batch(50, 8, seed=11)
        wire = graph.deps_partial(batch_cols(b), b.dictionary)
        # 8 spans/trace: SERVER hops at 0,2,4,6 -> 3 cross-service edges
        # per trace; root server + trailing client stay unpaired
        assert sum(e["count"] for e in wire["edges"].values()) == 150
        assert wire["unpaired"] == 100
        for e in wire["edges"].values():
            assert sum(e["hist"].values()) == e["count"]
            assert 0 <= e["failed"] <= e["count"]

    def test_internal_spans_never_pair(self):
        rng = np.random.default_rng(1)
        tid = rng.bytes(16)
        s = [rng.bytes(8) for _ in range(2)]
        t = Trace(trace_id=tid)
        t.batches.append(({"service.name": "a"}, [
            Span(tid, s[0], "root", b"\x00" * 8, BASE_NS, 10**7,
                 kind=KIND_INTERNAL)]))
        t.batches.append(({"service.name": "b"}, [
            Span(tid, s[1], "child", s[0], BASE_NS, 10**6,
                 kind=KIND_SERVER)]))
        from tempo_tpu.model.trace import traces_to_batch

        b = traces_to_batch([t]).sorted_by_trace()
        wire = graph.deps_partial(batch_cols(b), b.dictionary)
        assert not wire["edges"]  # parent is INTERNAL, not CLIENT

    def test_cp_partial_shares(self):
        b = synth.make_graph_batch(30, 6, seed=13)
        wire = graph.cp_partial(batch_cols(b), b.dictionary, device=False)
        doc = graph.finalize_cp(wire)
        assert doc["traces"] == 30
        assert doc["groups"] and abs(
            sum(g["share"] for g in doc["groups"]) - 1.0) < 1e-3
        # nested chain: every span lies on the single path
        assert sum(g["spans"] for g in doc["groups"]) == 30 * 6

    def test_cp_by_name(self):
        b = synth.make_graph_batch(10, 4, seed=17)
        wire = graph.cp_partial(batch_cols(b), b.dictionary, by="name",
                                device=False)
        assert set(wire["groups"]) <= set(synth.OP_NAMES)

    def test_root_filter_validation(self):
        assert graph.parse_root_filter("") is None
        assert graph.parse_root_filter("{}") is None
        assert graph.parse_root_filter('{ name = `x` }') is not None
        with pytest.raises(ValueError, match="spanset filters only"):
            graph.parse_root_filter("{} | rate()")
        with pytest.raises(ValueError, match="spanset filters only"):
            graph.parse_root_filter("{} | by(name)")


# ---------------------------------------------------------------------------
# live generator vs stored blocks (satellite: shared edge semantics)
# ---------------------------------------------------------------------------


class TestLiveStoredParity:
    @pytest.fixture()
    def app(self, tmp_path):
        app = App(AppConfig(
            db=DBConfig(backend="local", backend_path=str(tmp_path / "blocks"),
                        wal_path=str(tmp_path / "wal")),
            frontend=FrontendConfig(hedge_after_s=0, max_retries=0),
        ))
        yield app
        app.shutdown()

    def test_live_edges_equal_stored_aggregation(self, app):
        """Identical ingest (RF=1): the live processor's edge counters
        and the stored-block aggregation must agree edge for edge —
        both planes run the ONE shared pairing/failure definition."""
        traces = [batch_to_traces(synth.make_graph_batch(
            20, 6, seed=500 + i))[j] for i in range(2) for j in range(20)]
        app.push_traces(traces)
        app.sweep_all(immediate=True)
        app.db.poll_now()

        stored = app.graph_dependencies()
        got = {(e["client"], e["server"]): (e["count"], e["failed"])
               for e in stored["edges"]}

        live = {}
        inst = app.generator.instance("single-tenant")
        for s in inst.registry.collect():
            if s.name not in (REQ_TOTAL, REQ_FAILED):
                continue
            lab = dict(s.labels)
            slot = live.setdefault((lab["client"], lab["server"]), [0, 0])
            slot[0 if s.name == REQ_TOTAL else 1] = int(s.value)
        live = {k: tuple(v) for k, v in live.items()}
        assert got == live
        assert got  # the parity is not 0 == 0

    def test_expired_unpaired_counter_labeled(self):
        """Satellite fix: spans leaving the pairing store without a match
        are a LABELED counter (store x reason), not an opaque int."""
        from tempo_tpu.modules.generator.registry import ManagedRegistry

        reg = ManagedRegistry("t")
        proc = ServiceGraphsProcessor(reg, wait_s=1.0, max_items=2)
        b = synth.make_graph_batch(1, 2, seed=3)  # server root + client
        proc.push(b, now=100.0)
        assert proc.pending_clients  # the trailing client waits
        assert proc.pending_servers  # the root server too
        proc.expire(now=200.0)
        assert not proc.pending_clients and not proc.pending_servers
        got = {s.labels: s.value for s in reg.collect() if s.name == EXPIRED_TOTAL}
        assert got == {
            (("store", "client"), ("reason", "expired")): 1.0,
            (("store", "server"), ("reason", "expired")): 1.0,
        }
        assert proc.expired == 2


# ---------------------------------------------------------------------------
# shard invariance + determinism (satellite: same contract as
# parallel/metrics.py tests)
# ---------------------------------------------------------------------------


class TestShardInvariance:
    @pytest.fixture(scope="class")
    def store(self, tmp_path_factory):
        from tempo_tpu.backend import LocalBackend, TypedBackend
        from tempo_tpu.encoding import from_version

        tmp = tmp_path_factory.mktemp("graph_store")
        backend = TypedBackend(LocalBackend(str(tmp)))
        enc = from_version("vtpu1")
        cfg = BlockConfig(row_group_spans=256)
        metas = [
            enc.create_block([synth.make_graph_batch(128, 8, seed=700 + j)],
                             "t", backend, cfg)
            for j in range(4)
        ]
        return backend, enc, cfg, metas

    def _block_wire(self, store, meta, want, device=False):
        backend, enc, cfg, _ = store
        blk = enc.open_block(meta, backend, cfg)
        rows = graph.collect_block_rows(blk, None)
        wire = graph.new_deps_wire() if want == "deps" else graph.new_cp_wire()
        if rows is not None:
            if want == "deps":
                graph.deps_partial(rows, blk.dictionary(), wire=wire)
            else:
                graph.cp_partial(rows, blk.dictionary(), device=device,
                                 bucket_for=cfg.bucket_for, wire=wire)
        return wire

    @pytest.mark.parametrize("want", ["deps", "cp"])
    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_grouping_invariant(self, store, want, n_shards):
        """Merging per-block partials through ANY job grouping produces
        the same wire: integer adds commute, min/max are associative."""
        _, _, _, metas = store
        merge = graph.merge_deps_wire if want == "deps" else graph.merge_cp_wire
        new = graph.new_deps_wire if want == "deps" else graph.new_cp_wire
        merged = new()
        for g in range(n_shards):
            shard = new()
            for m in metas[g::n_shards]:
                merge(shard, self._block_wire(store, m, want))
            merge(merged, shard)
        ref = new()
        for m in metas:
            merge(ref, self._block_wire(store, m, want))
        assert merged == ref

    def test_cp_host_device_wires_identical(self, store):
        _, _, _, metas = store
        for m in metas:
            host = self._block_wire(store, m, "cp", device=False)
            dev = self._block_wire(store, m, "cp", device=True)
            assert host == dev

    def test_frontend_shard_counts_bit_identical(self, tmp_path):
        docs = {}
        for shards in (1, 2, 4):
            app = App(AppConfig(
                db=DBConfig(backend="local",
                            backend_path=str(tmp_path / "blocks"),
                            wal_path=str(tmp_path / f"wal{shards}")),
                frontend=FrontendConfig(query_shards=shards, hedge_after_s=0,
                                        max_retries=0,
                                        target_bytes_per_job=1),
                generator_enabled=False,
            ))
            try:
                if shards == 1:  # write once, re-read at every shard count
                    for j in range(4):
                        app.db.write_batch(
                            "single-tenant",
                            synth.make_graph_batch(64, 8, seed=40 + j))
                app.db.poll_now()
                docs[shards] = (
                    strip_volatile(app.graph_dependencies()),
                    strip_volatile(app.graph_critical_path(by="name")),
                )
            finally:
                app.shutdown()
        assert docs[1] == docs[2] == docs[4]


class TestWalkDeterminism:
    EDGES = {
        "a\x1fb": {"count": 10, "minStartS": 100, "maxStartS": 200},
        "b\x1fc": {"count": 5, "minStartS": 150, "maxStartS": 250},
        "b\x1fd": {"count": 5, "minStartS": 50, "maxStartS": 90},
        "c\x1fa": {"count": 1, "minStartS": 240, "maxStartS": 260},
    }

    def test_same_seed_replays(self):
        a = walks_mod.sample_walks(self.EDGES, seed=42, walks=20, steps=5)
        b = walks_mod.sample_walks(self.EDGES, seed=42, walks=20, steps=5)
        assert a == b
        c = walks_mod.sample_walks(self.EDGES, seed=43, walks=20, steps=5)
        assert a != c  # the seed actually steers

    def test_temporal_constraint(self):
        """From a at t>=100, the b->d edge (maxStartS 90) predates the
        walk's present and must never be taken."""
        out = walks_mod.sample_walks(self.EDGES, seed=1, walks=50, steps=4,
                                     start="a")
        assert all("d" not in w["path"] for w in out["walks"])

    def test_window_bounds_lookahead(self):
        """window_s=10 from t=100: b->c (minStartS 150) is beyond the
        temporal window, so walks stop at b."""
        out = walks_mod.sample_walks(self.EDGES, seed=1, walks=20, steps=4,
                                     window_s=10, start="a")
        for w in out["walks"]:
            assert w["path"] == ["a", "b"]

    def test_cross_process_determinism(self):
        """Like the fault-plan subprocess pair: PYTHONHASHSEED must not
        leak into the walk schedule."""
        prog = (
            "import json\n"
            "from tempo_tpu.graph import walks\n"
            "edges = {'a\\x1fb': {'count': 3, 'minStartS': 1, 'maxStartS': 9},\n"
            "         'b\\x1fc': {'count': 2, 'minStartS': 2, 'maxStartS': 9},\n"
            "         'a\\x1fc': {'count': 5, 'minStartS': 1, 'maxStartS': 9}}\n"
            "out = walks.sample_walks(edges, seed=7, walks=25, steps=6)\n"
            "print(json.dumps(out, sort_keys=True))\n"
        )
        runs = []
        for hashseed in ("1", "2"):
            env = dict(os.environ, PYTHONHASHSEED=hashseed,
                       JAX_PLATFORMS="cpu")
            r = subprocess.run([sys.executable, "-c", prog], env=env,
                               capture_output=True, text=True, timeout=120)
            assert r.returncode == 0, r.stderr
            runs.append(r.stdout.strip())
        assert runs[0] == runs[1], "walk schedule varies with PYTHONHASHSEED"


# ---------------------------------------------------------------------------
# end to end: HTTP endpoints, usage charging, recent window, dogfood
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("graph_e2e")
    app = App(AppConfig(
        db=DBConfig(backend="local", backend_path=str(tmp / "blocks"),
                    wal_path=str(tmp / "wal")),
        frontend=FrontendConfig(hedge_after_s=0, max_retries=0),
        generator_enabled=False,
    ))
    server = TempoServer(app).start()
    traces = [batch_to_traces(synth.make_graph_batch(15, 8, seed=900 + i))[j]
              for i in range(2) for j in range(15)]
    app.push_traces(traces)
    app.sweep_all(immediate=True)
    app.db.poll_now()
    yield app, server
    server.stop()
    app.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.status, json.loads(r.read())


class TestHTTP:
    def test_dependencies(self, served):
        _, server = served
        status, doc = _get(f"{server.url}/api/graph/dependencies")
        assert status == 200 and doc["status"] == "success"
        assert doc["edges"] and sum(e["count"] for e in doc["edges"]) == 30 * 3
        e = doc["edges"][0]
        assert {"client", "server", "count", "failed", "errorRate",
                "p50Ms", "p95Ms", "p99Ms"} <= set(e)
        assert int(doc["stats"]["inspectedBytes"]) > 0
        assert "stageSeconds" in doc["stats"]

    def test_critical_path(self, served):
        _, server = served
        status, doc = _get(f"{server.url}/api/graph/critical-path?by=name")
        assert status == 200 and doc["by"] == "name"
        assert doc["traces"] == 30
        assert doc["groups"][0]["seconds"] > 0

    def test_walks(self, served):
        _, server = served
        qs = urllib.parse.urlencode({"walks": 16, "steps": 4, "seed": 9})
        status, doc = _get(f"{server.url}/api/graph/walks?{qs}")
        assert status == 200 and doc["walks"] and doc["visits"]
        _, doc2 = _get(f"{server.url}/api/graph/walks?{qs}")
        assert doc["walks"] == doc2["walks"]  # seeded replay over HTTP

    def test_traceql_root_filter(self, served):
        app, server = served
        full = app.graph_dependencies()
        some_server = full["edges"][0]["server"]
        q = urllib.parse.quote(
            '{ resource.service.name = `%s` }' % some_server)
        status, doc = _get(f"{server.url}/api/graph/dependencies?q={q}")
        assert status == 200
        # the filtered graph is a strict subgraph of the full one: the
        # filter selects TRACES (never clips spans), so every filtered
        # edge exists in the full graph with count >= the filtered count
        full_counts = {(e["client"], e["server"]): e["count"]
                       for e in full["edges"]}
        assert doc["edges"]
        total_full = sum(full_counts.values())
        total_filtered = sum(e["count"] for e in doc["edges"])
        assert 0 < total_filtered < total_full
        for e in doc["edges"]:
            assert full_counts.get((e["client"], e["server"]), 0) >= e["count"]

    def test_client_errors(self, served):
        _, server = served
        for qs in (
            "q=" + urllib.parse.quote("{} | rate()"),  # metrics stage
            "by=bogus",
            "start=200&end=100",
            "walks=100000",
            "q=" + urllib.parse.quote("{ nonsense ==== }"),
        ):
            url = (f"{server.url}/api/graph/critical-path?{qs}"
                   if "by=" in qs else
                   f"{server.url}/api/graph/dependencies?{qs}")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url, timeout=30)
            assert ei.value.code == 400, qs

    def test_unknown_walk_start_is_client_error(self, served):
        """A typo'd `from` node must 400 with guidance, never read as
        'the graph is empty' (silent 200 with zero walks)."""
        _, server = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{server.url}/api/graph/walks?from=no-such-svc", timeout=30)
        assert ei.value.code == 400
        assert b"no outgoing edges" in ei.value.read()

    def test_usage_charged_as_graph_kind(self, served):
        """Satellite: /api/graph/* charges the cost planes — the cost
        vector lands under kind=graph and the attribution stays exact
        (vector delta == untagged counter delta while only graph runs)."""
        from tempo_tpu.encoding.vtpu.block import inspected_bytes_total
        from tempo_tpu.util import usage

        def attributed(field):
            total = 0.0
            for kinds in usage.ACCOUNTANT.snapshot().values():
                for fields in kinds.values():
                    total += fields.get(field, 0.0)
            return total

        app, server = served
        before_ctr = inspected_bytes_total.total()
        before_vec = attributed("inspected_bytes")
        before_kind = (usage.ACCOUNTANT.snapshot("single-tenant")
                       .get("single-tenant", {}).get("graph", {})
                       .get("inspected_bytes", 0.0))
        status, _ = _get(f"{server.url}/api/graph/dependencies")
        assert status == 200
        d_ctr = inspected_bytes_total.total() - before_ctr
        d_vec = attributed("inspected_bytes") - before_vec
        d_kind = (usage.ACCOUNTANT.snapshot("single-tenant")
                  ["single-tenant"]["graph"]["inspected_bytes"] - before_kind)
        assert d_ctr > 0
        assert d_vec == pytest.approx(d_ctr, abs=1e-6)
        assert d_kind == pytest.approx(d_ctr, abs=1e-6)

    def test_graph_queries_counter_moves(self, served):
        _, server = served
        before = graph.graph_queries_total.total()
        _get(f"{server.url}/api/graph/dependencies")
        assert graph.graph_queries_total.total() == before + 1


class TestRecentWindow:
    def test_unflushed_data_served_by_graph_recent(self, tmp_path):
        """Graph queries must see not-yet-flushed ingester data (the
        recent job), same contract as search_recent."""
        import time as _time

        app = App(AppConfig(
            db=DBConfig(backend="local", backend_path=str(tmp_path / "blocks"),
                        wal_path=str(tmp_path / "wal")),
            frontend=FrontendConfig(hedge_after_s=0, max_retries=0),
            generator_enabled=False,
        ))
        try:
            now = int(_time.time())
            b = synth.make_graph_batch(10, 6, seed=77,
                                       base_time_ns=(now - 60) * 10**9)
            app.push_traces(batch_to_traces(b))  # NOT flushed
            doc = app.graph_dependencies(start_s=now - 600, end_s=now + 60)
            assert sum(e["count"] for e in doc["edges"]) == 10 * 2
            cp = app.graph_critical_path(start_s=now - 600, end_s=now + 60)
            assert cp["traces"] == 10
        finally:
            app.shutdown()


class TestSelfDogfood:
    def test_self_critical_path_end_to_end(self, tmp_path):
        """The acceptance recipe: on a dogfooding single binary, the
        system's own queue->fetch->decode->kernel time is a graph query
        — critical path by NAME over `_self_` surfaces the engine's own
        operations."""
        from tempo_tpu.util import tracing

        app = App(AppConfig(
            db=DBConfig(backend="local", backend_path=str(tmp_path / "blocks"),
                        wal_path=str(tmp_path / "wal")),
            frontend=FrontendConfig(hedge_after_s=0, max_retries=0),
            generator_enabled=False,
            self_tracing=tracing.SelfTracingConfig(enabled=True),
        ))
        try:
            app.push_traces(synth.make_traces(8, seed=41))
            app.sweep_all(immediate=True)
            app.db.poll_now()
            # a user query generates self-traces (frontend -> worker ->
            # tempodb spans land under `_self_` synchronously)
            app.search(SearchRequest(limit=0))
            doc = app.graph_critical_path(by="name",
                                          org_id=tracing.SELF_TENANT)
            assert doc["traces"] >= 1
            names = {g["name"] for g in doc["groups"]}
            assert any(n.startswith(("frontend/", "worker/", "tempodb/"))
                       for n in names), names
            # the dominant self-time holders are real engine stages
            assert doc["totalSeconds"] > 0
        finally:
            tracing.TRACER.exporter = None
            app.shutdown()


# ---------------------------------------------------------------------------
# CLI offline mode
# ---------------------------------------------------------------------------


class TestCLI:
    def test_graph_dependencies_offline(self, tmp_path, capsys):
        from tempo_tpu.backend import LocalBackend, TypedBackend
        from tempo_tpu.cli import main as cli_main
        from tempo_tpu.encoding import from_version

        backend = TypedBackend(LocalBackend(str(tmp_path)))
        enc = from_version("vtpu1")
        for j in range(2):
            enc.create_block([synth.make_graph_batch(32, 6, seed=60 + j)],
                             "t", backend, BlockConfig())
        rc = cli_main(["--path", str(tmp_path), "graph", "dependencies", "t",
                       "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["edges"] and sum(e["count"] for e in doc["edges"]) == 64 * 2
        rc = cli_main(["--path", str(tmp_path), "graph", "critical-path", "t",
                       "--by", "name", "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["traces"] == 64 and doc["groups"]
