"""Self-observability plane: traceparent propagation, span error
handling, abandoned-trace sweep, the dogfood (`_self_` tenant) export
loop, per-query stage waterfalls, and device dispatch timing.

The propagation satellite's core assertion lives in
TestEndToEndSelfTrace: ONE search through the single-binary app yields
ONE trace whose spans cross the frontend→worker→querier boundary with
correct parent/child links, queryable back out of the engine itself.
"""

import re
import threading
import time

import pytest

from tempo_tpu.app import App, AppConfig
from tempo_tpu.db import DBConfig
from tempo_tpu.encoding.common import SearchRequest
from tempo_tpu.model import synth
from tempo_tpu.model.trace import STATUS_ERROR
from tempo_tpu.util import stagetimings, tracing


def make_app(tmp_path, **kw):
    defaults = dict(
        db=DBConfig(backend="local", backend_path=str(tmp_path / "blocks"),
                    wal_path=str(tmp_path / "wal")),
        generator_enabled=False,
    )
    defaults.update(kw)
    return App(AppConfig(**defaults))


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Never leak an exporter into other tests."""
    yield
    tracing.TRACER.exporter = None


# ---------------------------------------------------------------------------
# tracer core: error handling + abandoned-trace sweep (satellite 1)
# ---------------------------------------------------------------------------


class TestTracerErrorHandling:
    def test_exception_sets_status_and_error_attr(self):
        exported = []
        t = tracing.Tracer(exporter=exported.append)
        with pytest.raises(ValueError):
            with t.span("op"):
                raise ValueError("boom")
        span = list(exported[0][0].all_spans())[0]
        assert span.status_code == STATUS_ERROR
        assert span.attributes["error"] == "ValueError: boom"

    def test_nested_exception_marks_every_enclosing_span(self):
        exported = []
        t = tracing.Tracer(exporter=exported.append)
        with pytest.raises(RuntimeError):
            with t.span("root"):
                with t.span("child"):
                    raise RuntimeError("inner")
        spans = {s.name: s for s in exported[0][0].all_spans()}
        assert spans["child"].status_code == STATUS_ERROR
        assert spans["root"].status_code == STATUS_ERROR
        assert "inner" in spans["child"].attributes["error"]

    def test_abandoned_root_swept_and_flushed(self):
        """A child span whose root never finishes (crashed thread) must
        not pin its _open_traces entry forever: the bounded-age sweep
        flushes the partial trace and releases the entry."""
        exported = []
        t = tracing.Tracer(exporter=exported.append, max_open_age_s=5.0)

        # simulate the crash: open root + child on a thread that dies
        # between the child's finish and the root's. The root's context
        # manager is pinned (holds) so GC can't sneak its finally in.
        holds = []

        def crashed():
            root_cm = t.span("root")
            holds.append(root_cm)
            root_cm.__enter__()
            with t.span("child"):
                pass
            # thread "dies" here: root_cm.__exit__ never called

        th = threading.Thread(target=crashed)
        th.start()
        th.join()
        assert t.open_trace_count() == 1
        assert exported == []  # nothing flushed yet

        # too young: sweep keeps it
        assert t.sweep_open(now=time.monotonic() + 1.0) == 0
        assert t.open_trace_count() == 1

        # past max age: flushed as a partial trace, entry released
        assert t.sweep_open(now=time.monotonic() + 10.0) == 1
        assert t.open_trace_count() == 0
        spans = list(exported[0][0].all_spans())
        assert [s.name for s in spans] == ["child"]
        assert spans[0].attributes.get("abandoned") is True

    def test_finish_triggers_opportunistic_sweep(self):
        exported = []
        t = tracing.Tracer(exporter=exported.append, max_open_age_s=0.0)
        t._open_traces[b"x" * 16] = []
        t._open_last[b"x" * 16] = time.monotonic() - 1.0
        t._last_sweep = time.monotonic() - 1.0
        with t.span("normal"):
            pass
        assert t.open_trace_count() == 0  # stale entry swept by _finish


# ---------------------------------------------------------------------------
# W3C traceparent propagation
# ---------------------------------------------------------------------------


class TestTraceparent:
    def test_format_parse_roundtrip(self):
        tid, sid = b"\x01" * 16, b"\x02" * 8
        hdr = tracing.format_traceparent(tid, sid)
        assert re.fullmatch(r"00-[0-9a-f]{32}-[0-9a-f]{16}-01", hdr)
        rp = tracing.parse_traceparent(hdr)
        assert rp.trace_id == tid and rp.span_id == sid

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-abcd-01",
        "00-" + "0" * 32 + "-" + "12" * 8 + "-01",  # zero trace id
        "00-" + "12" * 16 + "-" + "0" * 16 + "-01",  # zero span id
        "00-" + "zz" * 16 + "-" + "12" * 8 + "-01",  # non-hex
    ])
    def test_malformed_headers_ignored(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_remote_context_parents_local_spans(self):
        exported = []
        tracing.install_exporter(exported.append)
        tid, sid = b"\xaa" * 16, b"\xbb" * 8
        hdr = tracing.format_traceparent(tid, sid)
        with tracing.remote_context(hdr):
            assert tracing.current_traceparent() == hdr
            with tracing.span("local-root"):
                with tracing.span("local-child"):
                    pass
        # the LOCAL root flushes its fragment under the REMOTE trace id
        spans = {s.name: s for s in exported[0][0].all_spans()}
        assert spans["local-root"].trace_id == tid
        assert spans["local-root"].parent_span_id == sid
        assert spans["local-child"].parent_span_id == spans["local-root"].span_id

    def test_remote_context_does_not_override_active_span(self):
        exported = []
        tracing.install_exporter(exported.append)
        foreign = tracing.format_traceparent(b"\xcc" * 16, b"\xdd" * 8)
        with tracing.span("outer") as outer:
            with tracing.remote_context(foreign):
                with tracing.span("inner"):
                    pass
        spans = {s.name: s for s in exported[0][0].all_spans()}
        assert spans["inner"].trace_id == outer.trace_id
        assert spans["inner"].parent_span_id == outer.span_id

    def test_current_traceparent_none_without_span(self):
        assert tracing.current_traceparent() is None


# ---------------------------------------------------------------------------
# dogfood exporter dampers (rate bound, sampling, governor)
# ---------------------------------------------------------------------------


class _Gov:
    def __init__(self, level):
        self._level = level

    def level(self):
        return self._level


class TestSelfTraceExporter:
    def _traces(self, n=1):
        return synth.make_traces(n, seed=77)

    def test_exports_through_push(self):
        got = []
        exp = tracing.SelfTraceExporter(lambda tenant, traces: got.append((tenant, traces)))
        exp(self._traces(2))
        assert got and got[0][0] == tracing.SELF_TENANT
        assert len(got[0][1]) == 2

    def test_rate_bound_drops_not_blocks(self):
        got = []
        cfg = tracing.SelfTracingConfig(max_spans_per_s=0.0, burst_spans=0.0)
        exp = tracing.SelfTraceExporter(
            lambda tenant, traces: got.append(traces), cfg)
        before = exp.dropped_total.value(reason="rate_limited")
        exp(self._traces(3))
        assert got == []
        assert exp.dropped_total.value(reason="rate_limited") == before + 3

    def test_pressure_drops(self):
        got = []
        exp = tracing.SelfTraceExporter(
            lambda tenant, traces: got.append(traces), governor=_Gov(1))
        exp(self._traces(1))
        assert got == []
        exp.governor = _Gov(0)
        exp(self._traces(1))
        assert got

    def test_push_failure_never_raises(self):
        """Non-amplification: a shed/failed self-push is DROPPED —
        retrying self-traffic during an overload is how observation
        becomes load."""
        from tempo_tpu.util.resource import ResourceExhausted

        def push(tenant, traces):
            raise ResourceExhausted("shed", retry_after_s=5)

        exp = tracing.SelfTraceExporter(push)
        before = exp.dropped_total.value(reason="push_failed")
        exp(self._traces(1))  # must not raise
        assert exp.dropped_total.value(reason="push_failed") == before + 1

    def test_sampling_deterministic(self):
        cfg = tracing.SelfTracingConfig(sample_ratio=0.5)
        exp = tracing.SelfTraceExporter(lambda t, tr: None, cfg)
        traces = synth.make_traces(40, seed=9)
        kept = {t.trace_id for t in traces if exp._sampled(t.trace_id)}
        kept2 = {t.trace_id for t in traces if exp._sampled(t.trace_id)}
        assert kept == kept2  # head sampling is by id, not by dice
        assert 0 < len(kept) < 40


# ---------------------------------------------------------------------------
# end-to-end: one search = one trace across frontend→worker→querier,
# stored in and queryable from the engine itself (`_self_`)
# ---------------------------------------------------------------------------


class TestEndToEndSelfTrace:
    def test_search_yields_one_linked_trace(self, tmp_path):
        app = make_app(
            tmp_path,
            self_tracing=tracing.SelfTracingConfig(enabled=True),
        )
        try:
            app.push_traces(synth.make_traces(8, seed=41))
            app.sweep_all(immediate=True)  # flush so block jobs exist

            hits = app.search(SearchRequest(limit=0))
            assert hits.traces  # the user query itself works

            # the dogfood loop ran synchronously: the frontend span
            # flushed into the `_self_` tenant's live traces. Find it.
            self_hits = app.search(
                SearchRequest(tags={"name": "frontend/search"}, limit=0),
                org_id=tracing.SELF_TENANT,
            )
            assert self_hits.traces, "no self-trace stored under _self_"
            tid = bytes.fromhex(self_hits.traces[0].trace_id_hex)
            trace = app.find_trace(tid, org_id=tracing.SELF_TENANT)
            assert trace is not None
            spans = list(trace.all_spans())
            by_name: dict = {}
            for s in spans:
                by_name.setdefault(s.name, []).append(s)

            # ONE coherent trace: every span carries the same trace id
            assert {s.trace_id for s in spans} == {tid}

            frontend = by_name["frontend/search"][0]
            workers = [s for n, ss in by_name.items() if n.startswith("worker/")
                       for s in ss]
            assert workers, f"no worker spans in {sorted(by_name)}"
            # frontend→worker: the desc-stamped traceparent parents the
            # worker span across the broker boundary
            for w in workers:
                assert w.parent_span_id == frontend.span_id
            # worker→querier: block scans are children of their worker
            block_spans = by_name.get("tempodb/search_block", [])
            assert block_spans, f"no block-scan spans in {sorted(by_name)}"
            worker_ids = {w.span_id for w in workers}
            for b in block_spans:
                assert b.parent_span_id in worker_ids
        finally:
            app.shutdown()

    def test_self_tenant_addressable_without_multitenancy(self, tmp_path):
        app = make_app(tmp_path)
        try:
            assert app.resolve_tenant(tracing.SELF_TENANT) == tracing.SELF_TENANT
            assert app.resolve_tenant(None) == "single-tenant"
        finally:
            app.shutdown()

    def test_shutdown_uninstalls_only_own_exporter(self, tmp_path):
        app = make_app(
            tmp_path, self_tracing=tracing.SelfTracingConfig(enabled=True))
        assert tracing.TRACER.exporter is app._self_exporter
        other = lambda traces: None  # noqa: E731
        tracing.install_exporter(other)
        app.shutdown()
        assert tracing.TRACER.exporter is other  # newer install survives
        tracing.TRACER.exporter = None

    def test_nondistributor_role_exports_via_endpoint(self, tmp_path):
        """Microservices dogfood: a role WITHOUT a distributor ships its
        spans as OTLP/HTTP to self_tracing.endpoint, so query-path spans
        exist in `_self_` even when the frontend/querier/compactor run
        in their own processes."""
        from tempo_tpu.api.server import TempoServer

        sink = make_app(
            tmp_path, self_tracing=tracing.SelfTracingConfig(enabled=False))
        srv = TempoServer(sink).start()
        role = App(AppConfig(
            target="query-frontend",
            db=DBConfig(backend="local",
                        backend_path=str(tmp_path / "blocks"),  # shared store
                        wal_path=str(tmp_path / "wal-fe")),
            generator_enabled=False,
            self_tracing=tracing.SelfTracingConfig(
                enabled=True, endpoint=srv.url),
        ))
        try:
            assert tracing.TRACER.enabled  # the role process records
            with tracing.span("role-span", role="query-frontend"):
                pass
            hits = sink.search(
                SearchRequest(tags={"name": "role-span"}, limit=0),
                org_id=tracing.SELF_TENANT,
            )
            assert hits.traces, "role span never reached the sink's _self_"
        finally:
            role.shutdown()
            srv.stop()
            sink.shutdown()

    def test_role_without_endpoint_records_nothing(self, tmp_path):
        role = App(AppConfig(
            target="query-frontend",
            db=DBConfig(backend="local", backend_path=str(tmp_path / "b"),
                        wal_path=str(tmp_path / "w")),
            generator_enabled=False,
            self_tracing=tracing.SelfTracingConfig(enabled=True),
        ))
        try:
            assert not tracing.TRACER.enabled
        finally:
            role.shutdown()

    def test_push_failure_records_error_span(self, tmp_path, monkeypatch):
        """A push failing under injected faults records STATUS_ERROR
        spans (the flush path here: TEMPO_TPU_FAULTS write errors make
        complete_block fail) WITHOUT amplifying load — the dogfood
        export of those error traces is itself fault-tolerant."""
        monkeypatch.setenv("TEMPO_TPU_FAULTS", "write=1.0,seed=5")
        exported = []
        app = make_app(tmp_path)
        try:
            tracing.install_exporter(exported.append)
            app.push_traces(synth.make_traces(2, seed=42))
            app.sweep_all(immediate=True)  # flush fails on every write
            err_spans = [
                s for tr_list in exported for s in tr_list[0].all_spans()
                if s.status_code == STATUS_ERROR
            ]
            assert err_spans, "injected write faults produced no error spans"
            assert any("ingester/complete_block" == s.name for s in err_spans)
            assert all("error" in s.attributes for s in err_spans)
        finally:
            app.shutdown()


# ---------------------------------------------------------------------------
# stage waterfall
# ---------------------------------------------------------------------------


class TestStageTimings:
    def test_exclusive_nesting(self):
        with stagetimings.request() as st:
            with stagetimings.stage("decode"):
                with stagetimings.stage("fetch"):
                    time.sleep(0.05)
                time.sleep(0.02)
        assert st.seconds["fetch"] >= 0.045
        assert st.seconds["decode"] >= 0.015
        # exclusive: decode does NOT include fetch's 50ms
        assert st.seconds["decode"] < 0.045

    def test_add_counts_once_inside_stage(self):
        with stagetimings.request() as st:
            with stagetimings.stage("decode"):
                stagetimings.add("kernel", 0.5)
        assert st.seconds["kernel"] == 0.5
        assert st.seconds.get("decode", 0.0) < 0.4  # kernel time excluded

    def test_noop_without_active_request(self):
        with stagetimings.stage("fetch"):
            pass
        stagetimings.add("kernel", 1.0)
        stagetimings.count_dispatch()
        assert stagetimings.active() is None

    def test_wire_roundtrip_merge(self):
        a = stagetimings.StageTimings()
        a.add("fetch", 0.25)
        a.count_dispatch(3)
        b = stagetimings.StageTimings()
        b.merge_wire(a.to_wire())
        b.merge_wire(a.to_wire())
        assert b.seconds["fetch"] == pytest.approx(0.5)
        assert b.dispatches == 6

    def test_pool_threads_share_request_accumulator(self):
        from tempo_tpu.db.pool import JobPool

        pool = JobPool(4)
        with stagetimings.request() as st:
            def job():
                with stagetimings.stage("fetch"):
                    time.sleep(0.01)
                return 1

            results, errors = pool.run_jobs([job] * 4)
        assert not errors and len(results) == 4
        assert st.seconds["fetch"] >= 0.035  # all four jobs recorded


class TestSearchWaterfall:
    def test_response_carries_waterfall_summing_to_wall(self, tmp_path):
        # ONE worker so job times serialize: the stage sum is then
        # comparable to wall clock (parallel workers would legitimately
        # sum past it)
        app = make_app(tmp_path, query_workers=1)
        try:
            app.push_traces(synth.make_traces(16, seed=43))
            app.sweep_all(immediate=True)
            # a process's first search pays one-time set-up (lazy imports,
            # first-use caches: 11 ms of "other" against 2 ms) inside the
            # first job while the second queues behind it, and the two
            # waits then sum past the wall: time the second search
            app.search(SearchRequest(limit=1))
            t0 = time.perf_counter()
            resp = app.search(SearchRequest(limit=0))
            wall = time.perf_counter() - t0
            assert resp.traces
            assert resp.stage_seconds, "search response carries no waterfall"
            # the worker-side stages travelled back over the job wire
            assert "other" in resp.stage_seconds
            assert "queue_wait" in resp.stage_seconds
            assert "admission" in resp.stage_seconds
            assert "fetch" in resp.stage_seconds  # block IO attributed
            assert set(resp.stage_seconds) <= set(stagetimings.STAGES)
            assert all(v > 0 for v in resp.stage_seconds.values())
            # the sums' own identity, whatever the host's scheduling
            # (no margin on a sandbox's clock: ROADMAP C11): stages nest
            # exclusively, the one worker runs the jobs one after the
            # other, and admission and merge come before and after them,
            # so every stage but the queue wait is an interval of its
            # own inside the request and together they cannot pass its
            # wall. (A saturated host deschedules threads in gaps no
            # stage owns, so there is no lower bound to hold.)
            work = sum(v for k, v in resp.stage_seconds.items() if k != "queue_wait")
            assert work <= wall
        finally:
            app.shutdown()

    def test_query_range_stats_carry_waterfall(self, tmp_path):
        app = make_app(tmp_path, query_workers=1)
        try:
            app.push_traces(synth.make_traces(8, seed=44))
            app.sweep_all(immediate=True)
            now = int(time.time())
            doc = app.query_range("{} | rate()", now - 120, now + 60, 30)
            stats = doc.get("stats", {})
            assert "stageSeconds" in stats
            assert isinstance(stats["stageSeconds"], dict)
            assert "deviceDispatches" in stats
        finally:
            app.shutdown()

    def test_traceql_stats_carry_waterfall(self, tmp_path):
        app = make_app(tmp_path, query_workers=1)
        try:
            app.push_traces(synth.make_traces(8, seed=45))
            app.sweep_all(immediate=True)
            stats: dict = {}
            hits = app.traceql("{}", stats=stats, limit=0)
            assert hits
            assert isinstance(stats.get("stageSeconds"), dict)
            assert stats["stageSeconds"]  # at least one stage recorded
        finally:
            app.shutdown()


class TestDeviceTiming:
    def test_timed_dispatch_records_histogram_and_stage(self):
        from tempo_tpu.util.devicetiming import dispatch_hist, dispatch_total, timed_dispatch

        before_n = dispatch_hist.count(kernel="unit-test")
        before_c = dispatch_total.value(kernel="unit-test")
        with stagetimings.request() as st:
            out = timed_dispatch("unit-test", lambda x: x + 1, 41)
        assert out == 42
        assert dispatch_hist.count(kernel="unit-test") == before_n + 1
        assert dispatch_total.value(kernel="unit-test") == before_c + 1
        assert st.dispatches == 1
        assert "kernel" in st.seconds

    def test_timed_dispatch_propagates_errors(self):
        from tempo_tpu.util.devicetiming import dispatch_hist, timed_dispatch

        before = dispatch_hist.count(kernel="unit-err")
        with pytest.raises(ValueError):
            timed_dispatch("unit-err", lambda: (_ for _ in ()).throw(ValueError("x")).__next__())
        assert dispatch_hist.count(kernel="unit-err") == before + 1


# ---------------------------------------------------------------------------
# /status/profile formats + device profile
# ---------------------------------------------------------------------------


class TestProfiling:
    def test_collapsed_format_pipes_to_flamegraph(self):
        from tempo_tpu.util.profiling import sample_profile

        stop = threading.Event()

        def busy():
            while not stop.is_set():
                sum(range(2000))

        th = threading.Thread(target=busy, daemon=True)
        th.start()
        try:
            out = sample_profile(0.3, hz=200, fmt="collapsed")
        finally:
            stop.set()
            th.join()
        lines = [ln for ln in out.splitlines() if ln]
        assert lines, "collapsed profile captured nothing"
        for ln in lines:
            # "<root>;...;<leaf> <count>" — flamegraph.pl's input contract
            assert re.fullmatch(r"\S+ \d+", ln), ln
        assert any(";" in ln for ln in lines)

    def test_text_format_unchanged_default(self):
        from tempo_tpu.util.profiling import sample_profile

        out = sample_profile(0.15, hz=100)
        assert out.startswith("# sampling profile:")
        assert "## hottest frames" in out

    def test_profile_endpoints(self, tmp_path):
        import json
        import urllib.request

        from tempo_tpu.api.server import TempoServer

        app = make_app(tmp_path)
        srv = TempoServer(app).start()
        try:
            with urllib.request.urlopen(
                    srv.url + "/status/profile?seconds=0.2&fmt=collapsed") as r:
                assert r.status == 200
            with urllib.request.urlopen(
                    srv.url + "/status/profile/device?seconds=0.2") as r:
                doc = json.loads(r.read())
            assert "supported" in doc
            if doc["supported"]:
                assert doc["dir"]
            # bad fmt is a client error
            try:
                urllib.request.urlopen(srv.url + "/status/profile?fmt=nope")
                raise AssertionError("expected 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
        finally:
            srv.stop()
            app.shutdown()


# ---------------------------------------------------------------------------
# HTTP propagation: client header -> server span
# ---------------------------------------------------------------------------


class TestGrpcPropagation:
    def test_metadata_traceparent_parents_ingest_span(self, tmp_path):
        grpc = pytest.importorskip("grpc")
        from tempo_tpu.receivers import otlp
        from tempo_tpu.receivers.grpc_server import (
            OTLP_EXPORT_METHOD,
            TraceGrpcServer,
        )

        exported = []
        app = make_app(tmp_path)
        srv = TraceGrpcServer(app.push_traces, host="127.0.0.1", port=0).start()
        chan = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        try:
            tracing.install_exporter(exported.append)
            tid, sid = b"\x42" * 16, b"\x24" * 8
            hdr = tracing.format_traceparent(tid, sid)
            payload = otlp.encode_traces_request(synth.make_traces(1, seed=46))
            chan.unary_unary(OTLP_EXPORT_METHOD)(
                payload, metadata=(("traceparent", hdr),))
            grpc_spans = [
                s for tl in exported for s in tl[0].all_spans()
                if s.name == "grpc/export"
            ]
            assert grpc_spans
            assert grpc_spans[0].trace_id == tid
            assert grpc_spans[0].parent_span_id == sid
        finally:
            chan.close()
            srv.stop()
            app.shutdown()


class TestHTTPPropagation:
    def test_client_injects_server_extracts(self, tmp_path):
        from tempo_tpu.api.server import TempoServer
        from tempo_tpu.backend.httpclient import PooledHTTPClient

        exported = []
        app = make_app(tmp_path)
        srv = TempoServer(app).start()
        client = PooledHTTPClient(srv.url)
        try:
            tracing.install_exporter(exported.append)
            with tracing.span("client-root") as root:
                status, _, _ = client.request("GET", "/api/search?limit=5")
            assert status == 200

            # the server's http span landed in the CLIENT's trace. The
            # server span closes AFTER it writes the response, so under
            # host load the client can get here first — poll boundedly
            # rather than flake on the export race.
            def http_spans_now():
                return [
                    s for tl in exported for s in tl[0].all_spans()
                    if s.name.startswith("http/GET /api/search")
                ]

            deadline = time.monotonic() + 5.0
            http_spans = http_spans_now()
            while not http_spans and time.monotonic() < deadline:
                time.sleep(0.02)
                http_spans = http_spans_now()
            assert http_spans, [
                s.name for tl in exported for s in tl[0].all_spans()]
            assert http_spans[0].trace_id == root.trace_id
            assert http_spans[0].parent_span_id == root.span_id
        finally:
            client.close()
            srv.stop()
            app.shutdown()


# ---------------------------------------------------------------------------
# the seams on the profiler's clock, and a capture that reduces itself
# ---------------------------------------------------------------------------

MS = 1e6  # the reducer's inputs are nanoseconds
D0 = "/device:TPU:0"  # the one device plane of the one-chip cases


class TestReduceCapture:
    """util/profiling.reduce_capture on synthetic intervals: no device,
    no profiler."""

    # one 100 ms window; thread 0 serves a request, thread 1 is its worker
    ANNOTATIONS = [
        (0, "http/GET /api/search", 10 * MS, 80 * MS),
        (0, "frontend/wait", 20 * MS, 60 * MS),
        (1, "worker/search_blocks", 25 * MS, 50 * MS),
        (1, "stage/fetch", 30 * MS, 10 * MS),
        (1, "dispatch/pallas_in_set", 45 * MS, 20 * MS),
        (1, "transfer/pallas_in_set", 46 * MS, 4 * MS),
    ]
    PROGRAMS = [
        (D0, "jit__scan(123456789)", 52 * MS, 6 * MS),      # inside the dispatch
        (D0, "jit__scan(987654321)", 64 * MS, 3 * MS),      # 1 ms inside, 2 ms past its end
        (D0, "jit_block_sketch_build(42)", 95 * MS, 2 * MS),  # no dispatch near it
    ]
    OPS = [(D0, 52 * MS, 6 * MS), (D0, 64 * MS, 3 * MS), (D0, 95 * MS, 2 * MS)]

    def reduce(self, **kw):
        from tempo_tpu.util.profiling import reduce_capture

        args = dict(window_ns=100 * MS, annotations=self.ANNOTATIONS,
                    programs=self.PROGRAMS, ops=self.OPS)
        args.update(kw)
        return reduce_capture(**args)

    def test_partition_sums_to_idle_exactly(self):
        s = self.reduce()
        assert s["device"]["busy_s"] == pytest.approx(0.011)
        assert s["device"]["idle_s"] == pytest.approx(0.089)
        assert sum(s["idle"].values()) == pytest.approx(s["device"]["idle_s"], rel=1e-12)
        assert all(v >= 0 for v in s["idle"].values())

    def test_idle_is_blamed_on_the_innermost_annotation(self):
        idle = self.reduce()["idle"]
        # 0-10 ms and 90-100 ms less the 2 ms the last program ran
        assert idle["none"] == pytest.approx(0.018)
        # the root's self time: 10-20 and 80-90 ms
        assert idle["http/GET /api/search"] == pytest.approx(0.020)
        assert idle["stage/fetch"] == pytest.approx(0.010)
        # the dispatch's self time less what the device ran inside it:
        # 45-46 and 50-65 ms, busy 52-58 and 64-65 ms
        assert idle["dispatch/pallas_in_set"] == pytest.approx(0.009)
        assert idle["transfer/pallas_in_set"] == pytest.approx(0.004)
        # worker self time: 25-30, 40-45, 65-75 ms, busy 65-67 ms
        assert idle["worker/search_blocks"] == pytest.approx(0.018)

    def test_a_waiting_thread_is_blamed_only_when_no_other_works(self):
        idle = self.reduce()["idle"]
        # the frontend waits 20-80 ms; the worker is inside an annotation
        # 25-75 ms of it, so only 20-25 and 75-80 ms fall to the wait
        assert idle["frontend/wait"] == pytest.approx(0.010)

    def test_working_threads_share_an_idle_instant_equally(self):
        s = self.reduce(annotations=[(0, "stage/decode", 0, 40 * MS),
                                     (1, "stage/fetch", 20 * MS, 40 * MS),
                                     (2, "frontend/wait", 0, 100 * MS)],
                        programs=[], ops=[])
        assert s["idle"]["stage/decode"] == pytest.approx(0.030)  # 20 alone + half of 20
        assert s["idle"]["stage/fetch"] == pytest.approx(0.030)
        assert s["idle"]["frontend/wait"] == pytest.approx(0.040)  # 60-100 ms
        assert s["idle"]["none"] == 0.0
        assert s["alignment"]["share"] is None  # no program ran

    def test_device_seconds_inside_a_dispatch_never_pass_its_wall(self):
        s = self.reduce()
        row = s["dispatch"]["pallas_in_set"]
        assert row["count"] == 1
        assert row["wall_s"] == pytest.approx(0.020)
        assert row["transfer_s"] == pytest.approx(0.004)
        # 6 ms of the first run + the 1 ms of the second that lies inside
        assert row["device_s"] == pytest.approx(0.007)
        # a program longer than its dispatch is clipped to it
        s = self.reduce(programs=[(D0, "jit__scan(1)", 40 * MS, 50 * MS)],
                        ops=[(D0, 40 * MS, 50 * MS)])
        row = s["dispatch"]["pallas_in_set"]
        assert row["device_s"] == pytest.approx(row["wall_s"])

    def test_program_names_lose_their_hash_and_split_by_dispatch(self):
        programs = {(r["program"], r["inside"]): r for r in self.reduce()["device"]["programs"]}
        assert programs[("jit__scan", "dispatch")]["runs"] == 2
        assert programs[("jit__scan", "dispatch")]["device_s"] == pytest.approx(0.009)
        assert programs[("jit_block_sketch_build", "none")]["runs"] == 1

    def test_alignment_counts_runs_at_or_just_after_a_dispatch(self):
        a = self.reduce()["alignment"]
        assert (a["runs"], a["aligned"], a["early"]) == (3, 2, 0)
        # a run that starts within 1 ms after its dispatch closed still counts
        a = self.reduce(programs=[(D0, "jit__scan(1)", 65.5 * MS, 1 * MS)])["alignment"]
        assert a["share"] == 1.0
        a = self.reduce(programs=[(D0, "jit__scan(1)", 66.5 * MS, 1 * MS)])["alignment"]
        assert (a["share"], a["early"]) == (0.0, 0)
        # one the device stamped just before its dispatch opened does not:
        # it is counted apart, as `early` (the device's stamps lead)
        s = self.reduce(programs=[(D0, "jit__scan(1)", 44.5 * MS, 0.25 * MS)])
        assert (s["alignment"]["share"], s["alignment"]["early"]) == (0.0, 1)
        assert s["device"]["programs"][0]["inside"] == "none"
        # early, but it reaches into the dispatch: aligned, and not early
        a = self.reduce(programs=[(D0, "jit__scan(1)", 44.5 * MS, 1 * MS)])["alignment"]
        assert (a["share"], a["early"]) == (1.0, 0)
        a = self.reduce(programs=[(D0, "jit__scan(1)", 42 * MS, 1 * MS)])["alignment"]
        assert (a["share"], a["early"]) == (0.0, 0)

    def test_what_lies_outside_the_window_is_clipped_away(self):
        # the window is the interval the seams were armed in: a run, an
        # operation or an annotation that crosses its edge counts only
        # for its part inside, and one wholly outside not at all
        s = self.reduce(
            annotations=[(0, "stage/fetch", -10 * MS, 30 * MS),
                         (0, "stage/merge", 120 * MS, 5 * MS)],
            programs=[(D0, "jit__scan(1)", -5 * MS, 8 * MS), (D0, "jit__scan(2)", 98 * MS, 6 * MS),
                      (D0, "jit__scan(3)", 101 * MS, 1 * MS)],
            ops=[(D0, -5 * MS, 8 * MS), (D0, 98 * MS, 6 * MS), (D0, 101 * MS, 1 * MS)])
        assert s["device"]["busy_s"] == pytest.approx(0.005)  # 0-3 and 98-100 ms
        assert s["idle"]["stage/fetch"] == pytest.approx(0.017)  # 0-20 ms less 3 busy
        assert "stage/merge" not in s["idle"]
        assert sum(s["idle"].values()) == pytest.approx(s["device"]["idle_s"], rel=1e-12)
        [row] = s["device"]["programs"]
        assert (row["runs"], row["device_s"]) == (2, pytest.approx(0.005))
        assert s["alignment"]["runs"] == 2

    def test_without_a_device_plane_only_the_host_side_is_reduced(self):
        s = self.reduce(programs=None, ops=None)
        assert s["device"] is None and s["idle"] is None and s["alignment"] is None
        assert s["dispatch"]["pallas_in_set"]["count"] == 1

    def test_one_device_plane_reads_as_before_and_has_no_skew(self):
        s = self.reduce()
        assert s["device"]["per_device"] == [
            {"device": D0, "busy_s": pytest.approx(s["device"]["busy_s"])}]
        assert all("skew_s" not in row for row in s["dispatch"].values())

    # four device planes under one mesh dispatch of 20 ms (40-60 ms): every
    # shard starts at 42 ms, shard k ends 2 ms after shard k-1; then a second
    # dispatch (70-80 ms) whose runs end together, and one run on shard 3 alone
    MESH = [f"/device:TPU:{k}" for k in range(4)]
    MESH_ANNOTATIONS = [
        (0, "mesh/search_blocks", 30 * MS, 60 * MS),
        (0, "mesh/stack", 32 * MS, 6 * MS),
        (0, "dispatch/mesh_rle_scan", 40 * MS, 20 * MS),
        (0, "mesh/collect", 61 * MS, 5 * MS),
        (0, "dispatch/mesh_rle_scan", 70 * MS, 10 * MS),
        (1, "mesh/wait", 45 * MS, 15 * MS),
    ]
    MESH_PROGRAMS = (
        [(d, "jit_step(7)", 42 * MS, (4 + 2 * k) * MS) for k, d in enumerate(MESH)]
        + [(d, "jit_step(7)", 72 * MS, 3 * MS) for d in MESH]
        + [(MESH[3], "jit_convert(9)", 50 * MS, 1 * MS)])  # a second run, before its last
    MESH_OPS = [(d, a, n) for d, _, a, n in MESH_PROGRAMS]

    def reduce_mesh(self):
        return self.reduce(annotations=self.MESH_ANNOTATIONS, programs=self.MESH_PROGRAMS,
                           ops=self.MESH_OPS)

    def test_four_planes_each_have_their_busy_seconds(self):
        dev = self.reduce_mesh()["device"]
        assert [d["device"] for d in dev["per_device"]] == self.MESH
        # 4, 6, 8, 10 ms of the first dispatch + 3 ms of the second; shard 3's
        # extra run lies inside its long one
        assert [d["busy_s"] for d in dev["per_device"]] == [
            pytest.approx(x) for x in (0.007, 0.009, 0.011, 0.013)]
        # some device ran: 42-52 and 72-75 ms
        assert dev["busy_s"] == pytest.approx(0.013)
        assert dev["busy_s"] <= sum(d["busy_s"] for d in dev["per_device"])

    def test_a_mesh_dispatchs_device_seconds_never_pass_its_wall(self):
        row = self.reduce_mesh()["dispatch"]["mesh_rle_scan"]
        assert (row["count"], row["wall_s"]) == (2, pytest.approx(0.030))
        # runs of four devices that overlap count once: 10 ms + 3 ms
        assert row["device_s"] == pytest.approx(0.013)
        assert row["device_s"] <= row["wall_s"]

    def test_skew_is_latest_end_less_earliest_end_over_the_devices(self):
        row = self.reduce_mesh()["dispatch"]["mesh_rle_scan"]
        # first dispatch: shards end at 46, 48, 50, 52 ms (shard 3's extra run
        # ends at 51, before its last); the second's end together
        assert row["skew_s"] == pytest.approx(0.006)

    def test_with_four_planes_the_idle_labels_still_sum_to_the_idle_seconds(self):
        s = self.reduce_mesh()
        assert s["device"]["idle_s"] == pytest.approx(0.087)
        assert sum(s["idle"].values()) == pytest.approx(s["device"]["idle_s"], rel=1e-12)
        # the mesh path's host work is named: stack 32-38 ms, collect 61-66 ms,
        # the job's own self time 30-32, 38-40, 60-61, 66-70 and 80-90 ms
        assert s["idle"]["mesh/stack"] == pytest.approx(0.006)
        assert s["idle"]["mesh/collect"] == pytest.approx(0.005)
        assert s["idle"]["mesh/search_blocks"] == pytest.approx(0.019)
        # a thread waiting for the dispatch lock is blamed only when no other
        # works: thread 0 is inside an annotation for all of 45-60 ms
        assert s["idle"]["mesh/wait"] == 0.0


class TestSeamsWithoutACapture:
    def test_stage_outside_a_request_is_the_shared_null_context(self):
        from tempo_tpu.util import profiling

        assert not profiling.capturing
        assert stagetimings.stage("fetch") is profiling.NULL_CONTEXT

    def test_span_without_an_exporter_is_the_shared_null_context(self):
        from tempo_tpu.util import profiling

        assert tracing.TRACER.exporter is None
        assert tracing.span("tempodb/find", tenant="t") is profiling.NULL_CONTEXT
        assert profiling.annotation("frontend/wait") is profiling.NULL_CONTEXT
        assert profiling.request_scope() is profiling.NULL_CONTEXT
        assert profiling.current_req() == 0


def _get_json(url, timeout=60):
    import json
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


class TestCaptureOnTheCPU:
    def test_capture_holds_the_seams_and_reduces_itself(self, tmp_path):
        """One capture through /status/profile/device while a search, a
        push and a dispatch run: the three seams' intervals are in the
        profiler's own host plane, the reply carries `summary`, and with
        no device plane it says so instead of inventing device numbers."""
        import urllib.request

        from jax.profiler import ProfileData

        from tempo_tpu.api.server import TempoServer
        from tempo_tpu.receivers import otlp
        from tempo_tpu.util import profiling
        from tempo_tpu.util.devicetiming import timed_dispatch

        app = make_app(tmp_path, query_workers=1)
        srv = TempoServer(app).start()
        reply = {}
        try:
            app.push_traces(synth.make_traces(16, seed=51))
            app.sweep_all(immediate=True)
            t = threading.Thread(target=lambda: reply.update(
                _get_json(srv.url + "/status/profile/device?seconds=1.5")))
            t.start()
            deadline = time.monotonic() + 10
            while not profiling.capturing and time.monotonic() < deadline:
                time.sleep(0.01)
            assert profiling.capturing
            _get_json(srv.url + "/api/search?limit=5")
            body = otlp.encode_traces_request(synth.make_traces(4, seed=52))
            req = urllib.request.Request(
                srv.url + "/v1/traces", data=body,
                headers={"Content-Type": "application/x-protobuf"})
            assert urllib.request.urlopen(req).status == 200
            with stagetimings.request():
                assert timed_dispatch("unit-capture", lambda x: x + 1, 1) == 2
            t.join(timeout=60)
        finally:
            srv.stop()
            app.shutdown()
        assert not profiling.capturing
        assert reply["supported"], reply
        assert "hint" not in reply
        summary = reply["summary"]
        assert "error" not in summary, summary
        assert summary["device"] is None and summary["idle"] is None
        assert summary["dispatch"]["unit-capture"]["count"] == 1
        # the armed interval (the sleep of 1.5 s), not the profiler's window
        assert 1.5 <= summary["window_s"] < 2.5
        # and the events themselves, read back from the trace on disk
        [xplane] = [f for f in reply["files"] if f.endswith(".xplane.pb")]
        profile = ProfileData.from_file(reply["dir"] + "/" + xplane)
        host = next(p for p in profile.planes if p.name == "/host:CPU")
        by_req: dict = {}
        for line in host.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "req" in stats:
                    by_req.setdefault(e.name, set()).add(stats["req"])
        names = set(by_req)
        assert "http/GET /api/search" in names, sorted(names)
        assert "http/POST /v1/traces" in names
        assert "dispatch/unit-capture" in names and "transfer/unit-capture" in names
        assert "capture/armed" in names
        assert any(n.startswith("stage/") for n in names), sorted(names)
        assert {"frontend/search", "frontend/wait", "receiver/decode", "distributor/push",
                "ingester/append"} <= names, sorted(names)
        # one request, one id: the search's root, its frontend wait and
        # its worker's job (another thread) carry the same nonzero `req`
        search_req = by_req["http/GET /api/search"]
        assert len(search_req) == 1 and 0 not in search_req
        assert search_req <= by_req["frontend/wait"]
        assert search_req <= by_req["worker/search_blocks"] | by_req.get(
            "worker/search_recent", set())
        # the Python tracer is off: a capture this short stays small
        assert sum(len(list(ln.events)) for ln in host.lines) < 20000


class TestPushWaterfall:
    def test_push_stages_sum_to_the_handlers_wall(self, tmp_path):
        import urllib.request

        from tempo_tpu.api.server import TempoServer
        from tempo_tpu.receivers import otlp

        app = make_app(tmp_path)
        srv = TempoServer(app).start()
        hist = stagetimings.stage_seconds_hist
        stages = ("decode", "admission", "fan_out", "live", "other")

        def push(n_before):
            """One push; its wall at the client, once the handler has
            published its stages (it does so after the reply is sent)."""
            t0 = time.perf_counter()
            urllib.request.urlopen(req).read()
            wall = time.perf_counter() - t0
            deadline = time.monotonic() + 10
            while (hist.count(stage="other", kind="push") <= n_before
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            return wall

        try:
            body = otlp.encode_traces_request(synth.make_traces(256, seed=53))
            req = urllib.request.Request(
                srv.url + "/v1/traces", data=body,
                headers={"Content-Type": "application/x-protobuf"})
            push(hist.count(stage="other", kind="push"))  # warm: imports, first-touch paths
            before = {s: hist.sum(stage=s, kind="push") for s in stages}
            wall = push(hist.count(stage="other", kind="push"))
            grew = {s: hist.sum(stage=s, kind="push") - before[s] for s in stages}
        finally:
            srv.stop()
            app.shutdown()
        assert grew["decode"] > 0 and grew["fan_out"] > 0 and grew["live"] > 0
        # the client's wall adds the connection; the handler's runs on a
        # little after the reply is sent
        assert sum(grew.values()) == pytest.approx(wall, rel=0.10)


class TestJitCompileCount:
    def test_a_new_shape_counts_once_and_a_repeat_not_at_all(self):
        import jax
        import numpy as np

        from tempo_tpu.util import xla_cache

        xla_cache.ensure_persistent_cache()
        f = jax.jit(lambda x: x * 3 + 1)
        total = xla_cache.jit_compiles_total.total
        n0 = total()
        f(np.ones(7, np.float32))
        assert total() == n0 + 1
        f(np.ones(7, np.float32))
        assert total() == n0 + 1
        f(np.ones(9, np.float32))
        assert total() == n0 + 2
        assert xla_cache.jit_compiles_total.total(source="backend") >= 2
