"""TraceQL metrics engine: stage parity vs a pure-numpy reference,
quantile-sketch error bounds, shard-count invariance of the psum merge,
zone-map pruning parity, WAL-tail inclusion, and the HTTP endpoint.

Reference: Tempo's TraceQL metrics (`{...} | rate() by (...)` over
stored blocks -> Prometheus range vectors). Every aggregate here reduces
to ONE segmented bincount over a combined (series, time-bin[, bucket])
slot index, so the invariant under test is simple: host numpy, the
Pallas device kernel, and the mesh psum reduction must produce the SAME
counts bit-for-bit, and those counts must match what a straightforward
numpy pass over the raw span arrays computes.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from tempo_tpu.api import params as api_params
from tempo_tpu.api.params import BadRequest
from tempo_tpu.backend import LocalBackend, TypedBackend
from tempo_tpu.encoding import from_version
from tempo_tpu.encoding.common import BlockConfig
from tempo_tpu.metrics_engine import (
    DeviceAccumulator,
    HostAccumulator,
    compile_metrics_plan,
    eval_batch,
    evaluate_block,
    finalize_matrix,
    merge_wire,
    new_wire,
)
from tempo_tpu.model import synth
from tempo_tpu.ops.sketch import HistogramPlan, hist_init, hist_update, np_hist_quantile
from tempo_tpu.parallel.mesh import RANGE_AXIS, WINDOW_AXIS
from tempo_tpu.parallel.metrics import MeshMetricsEvaluator
from tempo_tpu.traceql.parser import ParseError, parse

BASE_S = 1_700_000_000


def _plan(q, start=BASE_S, end=BASE_S + 60, step=10, **kw):
    return compile_metrics_plan(q, start, end, step, **kw)


def _run_host(plan, batches):
    acc = HostAccumulator(plan)
    for b in batches:
        acc.add(eval_batch(plan, b, b.dictionary, acc.series), b)
    return acc


def _matrix(plan, acc):
    m = new_wire()
    merge_wire(m, acc.to_wire(), plan)
    return finalize_matrix(plan, m)


def _series_totals(doc):
    """{frozenset(metric labels minus __name__): sum of values}."""
    out = {}
    for s in doc["result"]:
        key = tuple(sorted((k, v) for k, v in s["metric"].items() if k != "__name__"))
        out[key] = out.get(key, 0.0) + sum(float(v) for _, v in s["values"])
    return out


# ---------------------------------------------------------------------------
# grammar / validation
# ---------------------------------------------------------------------------


class TestGrammar:
    def test_parse_shapes(self):
        for q in (
            "{} | rate()",
            "{ span.http.status_code >= 500 } | rate() by (resource.service.name)",
            "{} | count_over_time() by (name)",
            "{} | quantile_over_time(duration, 0.5, 0.9, 0.99)",
            "{} | histogram_over_time(duration) by (span.http.method)",
        ):
            parse(q)

    def test_metrics_stage_must_be_final_and_single(self):
        with pytest.raises(ParseError):
            parse("{} | rate() | rate()")
        with pytest.raises(ParseError):
            parse("{} | rate() | count()")

    def test_quantile_needs_qs_in_range(self):
        with pytest.raises(ParseError):
            parse("{} | quantile_over_time(duration)")
        with pytest.raises(ParseError):
            parse("{} | quantile_over_time(duration, 1.5)")

    def test_spanset_engine_rejects_metrics_queries(self):
        from tempo_tpu.traceql import execute

        with pytest.raises(ParseError):
            execute("{} | rate()", lambda spec, s, e: [])

    def test_query_range_requires_metrics_pipeline(self):
        with pytest.raises(ParseError):
            _plan("{ name = `x` }")

    def test_plan_size_limits(self):
        with pytest.raises(ValueError):
            _plan("{} | rate()", start=0, end=10**9, step=1)  # bins explode
        with pytest.raises(ValueError):
            _plan("{} | rate()", step=0)
        with pytest.raises(ValueError):
            _plan("{} | rate()", start=BASE_S + 60, end=BASE_S)


class TestParseTimeRange:
    def test_defaults_and_validation(self):
        s, e, st = api_params.parse_time_range(0, 0, 0, require_range=True, now_s=10_000)
        assert (s, e) == (10_000 - 3600, 10_000) and st >= 1
        with pytest.raises(BadRequest):
            api_params.parse_time_range(20, 10)  # inverted -> 400, not empty
        with pytest.raises(BadRequest):
            api_params.parse_time_range("x", 10)
        # search semantics: zeros pass through un-defaulted
        assert api_params.parse_time_range(0, 0) == (0, 0, 0)

    def test_query_range_request(self):
        req = api_params.parse_query_range_request(
            {"q": ["{} | rate()"], "start": ["100"], "end": ["200"], "step": ["30s"]}
        )
        assert (req.start_s, req.end_s, req.step_s) == (100, 200, 30)
        with pytest.raises(BadRequest):
            api_params.parse_query_range_request({"start": ["1"], "end": ["2"]})
        with pytest.raises(BadRequest):
            api_params.parse_query_range_request(
                {"q": ["{} | rate()"], "start": ["200"], "end": ["100"]}
            )


# ---------------------------------------------------------------------------
# stage parity vs pure-numpy reference
# ---------------------------------------------------------------------------


class TestStageParity:
    """Every stage against a from-scratch numpy computation over the raw
    span arrays of the same synth batch."""

    @pytest.fixture(scope="class")
    def batch(self):
        return synth.make_batch(400, 8, seed=11)

    def test_rate_by_service(self, batch):
        plan = _plan("{} | rate() by (resource.service.name)")
        doc = _matrix(plan, _run_host(plan, [batch]))
        d = batch.dictionary
        t = batch.cols["start_unix_nano"].astype(np.int64)
        got = _series_totals(doc)
        for key, total in got.items():
            svc = dict(key)["resource.service.name"]
            code = d.get(svc)
            rows = (batch.cols["service"] == code) & (t >= BASE_S * 10**9) & (
                t < (BASE_S + 60) * 10**9
            )
            assert total * plan.step_s == pytest.approx(int(rows.sum()))
        # every span lands in the window: totals cover the whole batch
        assert sum(got.values()) * plan.step_s == pytest.approx(batch.num_spans)

    def test_filtered_rate(self, batch):
        plan = _plan("{ span.http.status_code >= 500 } | rate()")
        doc = _matrix(plan, _run_host(plan, [batch]))
        want = int((batch.cols["http_status"] >= 500).sum())
        got = sum(float(v) * plan.step_s for s in doc["result"] for _, v in s["values"])
        assert got == pytest.approx(want)

    def test_count_over_time_bins(self, batch):
        plan = _plan("{} | count_over_time()")
        doc = _matrix(plan, _run_host(plan, [batch]))
        t = batch.cols["start_unix_nano"].astype(np.int64)
        ref = np.bincount((t - BASE_S * 10**9) // (plan.step_s * 10**9),
                          minlength=plan.n_bins)
        (series,) = doc["result"]
        got = np.array([float(v) for _, v in series["values"]])
        assert (got == ref[: plan.n_bins]).all()

    def test_histogram_over_time(self, batch):
        plan = _plan("{} | histogram_over_time(duration)", step=60)
        doc = _matrix(plan, _run_host(plan, [batch]))
        # buckets partition the spans: per-le counts sum to num_spans
        total = sum(float(v) for s in doc["result"] for _, v in s["values"])
        assert total == batch.num_spans
        # per-bucket counts match a numpy histogram over the same edges
        dur = batch.cols["duration_nano"].astype(np.float64)
        for s in doc["result"]:
            le = float(s["metric"]["le"]) / plan.value_scale
            idx = plan.hist.np_bucket_of(dur)
            want = int(np.isclose(plan.hist.bucket_upper(idx), le, rtol=1e-9).sum())
            got = sum(float(v) for _, v in s["values"])
            assert got == want

    def test_quantile_over_time_vs_numpy(self, batch):
        plan = _plan("{} | quantile_over_time(duration, 0.5, 0.9)", step=60)
        doc = _matrix(plan, _run_host(plan, [batch]))
        dur_s = batch.cols["duration_nano"].astype(np.float64) * 1e-9
        for s in doc["result"]:
            q = float(s["metric"]["p"])
            exact = np.quantile(dur_s, q)
            got = float(s["values"][0][1])
            # one-bucket-width relative error bound (sub=8 -> 12.5%)
            assert abs(got - exact) / exact <= 1.0 / plan.hist.sub + 1e-9

    def test_grouped_quantile_matches_per_group_reference(self, batch):
        plan = _plan("{} | quantile_over_time(duration, 0.9) by (resource.service.name)",
                     step=60)
        doc = _matrix(plan, _run_host(plan, [batch]))
        d = batch.dictionary
        dur_s = batch.cols["duration_nano"].astype(np.float64) * 1e-9
        assert doc["result"]
        for s in doc["result"]:
            svc = s["metric"]["resource.service.name"]
            rows = batch.cols["service"] == d.get(svc)
            exact = np.quantile(dur_s[rows], 0.9)
            got = float(s["values"][0][1])
            assert abs(got - exact) / exact <= 1.0 / plan.hist.sub + 1e-9

    def test_series_cap_drops_and_counts(self, batch):
        plan = _plan("{} | rate() by (name)", max_series=2)
        acc = _run_host(plan, [batch])
        wire = acc.to_wire()
        assert len(wire["series"]) <= 2
        assert wire["stats"]["seriesDropped"] > 0


# ---------------------------------------------------------------------------
# quantile sketch: device/host bucketing parity + error bound
# ---------------------------------------------------------------------------


class TestHistogramSketch:
    def test_host_device_bucket_parity(self):
        p = HistogramPlan(min_exp=10, max_exp=42, sub=8)
        rng = np.random.default_rng(3)
        vals = rng.lognormal(mean=14.0, sigma=3.0, size=4096)  # ns scale
        host = np.bincount(p.np_bucket_of(vals), minlength=p.n_buckets)
        dev = np.asarray(hist_update(hist_init(p), vals, p))
        assert (host == dev).all()

    def test_quantile_error_bound(self):
        p = HistogramPlan(min_exp=10, max_exp=42, sub=8)
        rng = np.random.default_rng(7)
        vals = rng.lognormal(mean=16.0, sigma=2.0, size=20000)
        counts = np.bincount(p.np_bucket_of(vals), minlength=p.n_buckets)
        for q in (0.1, 0.5, 0.9, 0.99):
            got = np_hist_quantile(counts, [q], p)[0]
            exact = np.quantile(vals, q)
            assert abs(got - exact) / exact <= 1.0 / p.sub + 1e-9

    def test_merge_is_exact_addition(self):
        p = HistogramPlan()
        rng = np.random.default_rng(9)
        a, b = rng.lognormal(15, 2, 1000), rng.lognormal(15, 2, 1000)
        whole = np.bincount(p.np_bucket_of(np.concatenate([a, b])), minlength=p.n_buckets)
        parts = (np.bincount(p.np_bucket_of(a), minlength=p.n_buckets)
                 + np.bincount(p.np_bucket_of(b), minlength=p.n_buckets))
        assert (whole == parts).all()


# ---------------------------------------------------------------------------
# stored blocks: shard invariance, device parity, pruning, sharded merge
# ---------------------------------------------------------------------------


QUERIES = (
    "{} | rate() by (resource.service.name)",
    "{ span.http.status_code >= 500 } | rate() by (resource.service.name)",
    "{} | quantile_over_time(duration, 0.5, 0.9)",
)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("metrics-store")
    backend = TypedBackend(LocalBackend(str(tmp)))
    enc = from_version("vtpu1")
    cfg = BlockConfig(row_group_spans=2048)
    metas = [
        enc.create_block([synth.make_batch(600, 8, seed=40 + i)], "t", backend, cfg)
        for i in range(3)
    ]
    return backend, enc, cfg, metas


class TestStoredBlocks:
    def _host_ref(self, plan, store):
        backend, enc, cfg, metas = store
        acc = HostAccumulator(plan)
        for m in metas:
            evaluate_block(plan, enc.open_block(m, backend, cfg), acc)
        return acc

    @pytest.mark.parametrize("q", QUERIES)
    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_mesh_psum_bit_identical_at_any_shard_count(self, q, n_shards, store):
        backend, enc, cfg, metas = store
        plan = _plan(q)
        ref = self._host_ref(plan, store)
        mesh = Mesh(np.asarray(jax.devices()[:n_shards]).reshape(1, n_shards),
                    (WINDOW_AXIS, RANGE_AXIS))
        acc = HostAccumulator(plan)
        ev = MeshMetricsEvaluator(mesh, cfg.bucket_for)
        ev.evaluate_blocks((enc.open_block(m, backend, cfg) for m in metas), plan, acc)
        assert (acc.counts == ref.counts).all()
        assert acc.series.slots == ref.series.slots

    def test_device_accumulator_parity(self, store):
        backend, enc, cfg, metas = store
        plan = _plan(QUERIES[0])
        ref = self._host_ref(plan, store)
        acc = DeviceAccumulator(plan, flush_rows=4096)
        for m in metas:
            evaluate_block(plan, enc.open_block(m, backend, cfg), acc)
        assert (acc.merged_counts() == ref.counts).all()
        assert acc.dispatches >= 1

    def test_pruned_vs_unpruned_parity(self, store, monkeypatch):
        backend, enc, cfg, metas = store
        # selective needle: present in every dictionary, rows in none —
        # presence sets must prune every row group with zero reads
        plan = _plan('{ resource.service.name = `cart` } | rate()')
        monkeypatch.setenv("TEMPO_TPU_ZONEMAPS", "0")
        unpruned = self._host_ref(plan, store)
        monkeypatch.setenv("TEMPO_TPU_ZONEMAPS", "1")
        pruned = self._host_ref(plan, store)
        assert (pruned.counts == unpruned.counts).all()
        assert unpruned.stats["prunedRowGroups"] == 0
        # 'cart' occurs in every block of this synth corpus, so pruning
        # here comes only from row groups that genuinely lack it
        doc_p = _matrix(plan, pruned)
        doc_u = _matrix(plan, unpruned)
        assert doc_p["result"] == doc_u["result"]

    def test_or_with_opaque_arm_disables_pruning(self, store):
        # `kind >= 0` has no zone-map lowering (only =/!= lower for
        # kind); an OR with such an opaque arm must not prune on the
        # remaining arms — spans matching only the opaque arm live in
        # row groups the selective arm would prove empty
        from tempo_tpu.metrics_engine.evaluate import _lower_prunes

        backend, enc, cfg, metas = store
        d = enc.open_block(metas[0], backend, cfg).dictionary()
        opaque_or = _plan(
            "{ resource.service.name = `cart` || kind >= 0 } | rate()")
        resolvers, impossible = _lower_prunes(opaque_or, d)
        assert resolvers == [] and not impossible  # no arm may prune
        # the same selective arm AND-composed still lowers to a pruner
        conj = _plan("{ resource.service.name = `cart` && kind >= 0 } | rate()")
        resolvers, impossible = _lower_prunes(conj, d)
        assert len(resolvers) == 1 and not impossible

    def test_time_pruning_skips_out_of_window_row_groups(self, store):
        backend, enc, cfg, metas = store
        plan = _plan("{} | rate()", start=BASE_S + 10**6, end=BASE_S + 10**6 + 60)
        acc = self._host_ref(plan, store)
        assert acc.counts.sum() == 0
        assert acc.stats["inspectedSpans"] == 0  # zero row groups decoded

    def test_frontend_bin_offset_merge(self, store):
        """Time-range sharding: two step-aligned sub-window evaluations
        merged with bin offsets must equal the whole-window evaluation."""
        backend, enc, cfg, metas = store
        q = QUERIES[0]
        whole = _plan(q, start=BASE_S, end=BASE_S + 60, step=10)
        ref = _matrix(whole, self._host_ref(whole, store))
        merged = new_wire()
        for w0, w1 in ((BASE_S, BASE_S + 30), (BASE_S + 30, BASE_S + 60)):
            sub = _plan(q, start=w0, end=w1, step=10)
            acc = HostAccumulator(sub)
            for m in metas:
                evaluate_block(sub, enc.open_block(m, backend, cfg), acc)
            merge_wire(merged, acc.to_wire(), whole,
                       bin_offset=(w0 - BASE_S) // whole.step_s)
        assert finalize_matrix(whole, merged)["result"] == ref["result"]


# ---------------------------------------------------------------------------
# end to end: app + HTTP endpoint + WAL tail
# ---------------------------------------------------------------------------


@pytest.fixture()
def served_app(tmp_path):
    from tempo_tpu.api.server import TempoServer
    from tempo_tpu.app import App, AppConfig
    from tempo_tpu.db import DBConfig

    app = App(AppConfig(db=DBConfig(backend="local",
                                    backend_path=str(tmp_path / "blocks"),
                                    wal_path=str(tmp_path / "wal"))))
    server = TempoServer(app).start()
    yield app, server
    server.stop()
    app.shutdown()


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.loads(r.read())


@pytest.fixture()
def frontend_app(tmp_path):
    """App factory over one store: each call re-opens it with its own
    FrontendConfig (hedging off, so a job runs exactly once)."""
    from tempo_tpu.app import App, AppConfig
    from tempo_tpu.db import DBConfig
    from tempo_tpu.modules.frontend import FrontendConfig

    apps = []

    def make(**frontend):
        app = App(AppConfig(
            db=DBConfig(backend="local", backend_path=str(tmp_path / "blocks"),
                        wal_path=str(tmp_path / "wal")),
            frontend=FrontendConfig(hedge_after_s=0, **frontend),
            generator_enabled=False))
        apps.append(app)
        return app

    yield make
    for app in apps:
        app.shutdown()


def _write_block(app, seed, start_s, span_s=1, n_traces=120):
    """One flushed block whose span starts spread over
    [start_s, start_s + span_s)."""
    b = synth.make_batch(n_traces, 4, seed=seed, base_time_ns=start_s * 10**9)
    rng = np.random.default_rng(1000 + seed)
    n = len(b.cols["start_unix_nano"])
    starts = start_s * 10**9 + rng.integers(0, span_s * 10**9, size=n)
    starts[:2] = (start_s * 10**9, (start_s + span_s) * 10**9 - 1)  # the range's own ends
    b.cols["start_unix_nano"] = starts.astype(np.uint64)
    return app.db.write_batch("single-tenant", b)


def _block_jobs(app, monkeypatch):
    """The metrics_blocks descriptors of every query_range from here on."""
    seen = []
    run = app.frontend._run_jobs

    def spy(tenant, descs):
        seen.extend(dict(d) for d in descs if d["kind"] == "metrics_blocks")
        return run(tenant, descs)

    monkeypatch.setattr(app.frontend, "_run_jobs", spy)
    return seen


def _one_pass(app, q, start, end, step, **kw):
    """The matrix of ONE evaluator over the whole window and every block."""
    plan = _plan(q, start=start, end=end, step=step, **kw)
    enc = app.db.default_encoding()
    acc = HostAccumulator(plan)
    for m in app.db.blocklist.metas("single-tenant"):
        evaluate_block(plan, enc.open_block(m, app.db.backend, app.db.cfg.block), acc)
    return _matrix(plan, acc)


def _hull(metas, start, end, step):
    """Step-aligned hull of the metas' time ranges on the grid of
    [start, end) — what a job over them has to carry as its window."""
    last = -(-(end - start) // step) - 1
    k0 = min(max((min(m.start_time for m in metas) - start) // step, 0), last)
    k1 = min(max((max(m.end_time for m in metas) - start) // step, k0), last) + 1
    return start + k0 * step, min(end, start + k1 * step)


class TestEndToEnd:
    def test_http_query_range_matrix(self, served_app):
        import urllib.parse

        app, server = served_app
        traces = synth.make_traces(40, seed=21, spans_per_trace=4)
        app.push_traces(traces)
        for ing in app.ingesters.values():
            ing.flush_all()
        app.db.poll_now()
        t0 = min(s.start_unix_nano for t in traces for s in t.all_spans()) // 10**9
        t1 = max(s.start_unix_nano for t in traces for s in t.all_spans()) // 10**9 + 1
        qs = urllib.parse.urlencode({
            "q": "{} | rate() by (resource.service.name)",
            "start": t0, "end": t1, "step": 60,
        })
        status, doc = _get_json(f"{server.url}/api/metrics/query_range?{qs}")
        assert status == 200 and doc["status"] == "success"
        assert doc["data"]["resultType"] == "matrix"
        total = sum(float(v) * 60 for s in doc["data"]["result"] for _, v in s["values"])
        assert total == pytest.approx(sum(1 for t in traces for _ in t.all_spans()))
        assert int(doc["metrics"]["inspectedBytes"]) > 0
        # timestamps step-aligned to the request grid
        for s in doc["data"]["result"]:
            for ts, _ in s["values"]:
                assert (ts - t0) % 60 == 0

    def test_http_client_errors(self, served_app):
        _, server = served_app
        for qs in (
            "q=%7B%7D%20%7C%20rate()&start=200&end=100&step=10",  # inverted
            "q=%7B%20name%20%3D%20%60x%60%20%7D&start=1&end=100&step=10",  # no stage
            "start=1&end=100&step=10",  # missing q
            "q=%7B%7D%20%7C%20rate()&start=1&end=99999999&step=1",  # too many bins
        ):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{server.url}/api/metrics/query_range?{qs}", timeout=30)
            assert ei.value.code == 400

    def test_wal_tail_inclusion(self, served_app):
        """Unflushed ingester data (live traces + head/completing WAL
        blocks) must contribute the recent-time tail of the range
        vector before any block reaches the backend."""
        app, server = served_app
        now = int(time.time())
        traces = synth.make_traces(20, seed=23, spans_per_trace=3,
                                   base_time_ns=(now - 120) * 10**9)
        app.push_traces(traces)  # NOT flushed
        doc = app.query_range("{} | count_over_time()", now - 600, now + 300, 60)
        got = sum(float(v) for s in doc["result"] for _, v in s["values"])
        assert got == sum(1 for t in traces for _ in t.all_spans())
        # after a cut to the WAL head block the spans must still count once
        for ing in app.ingesters.values():
            for inst in ing.instances.values():
                inst.cut_complete_traces(immediate=True)
        doc2 = app.query_range("{} | count_over_time()", now - 600, now + 300, 60)
        got2 = sum(float(v) for s in doc2["result"] for _, v in s["values"])
        assert got2 == got

    def test_exemplars_round_trip(self, served_app):
        import urllib.parse

        app, server = served_app
        traces = synth.make_traces(10, seed=29, spans_per_trace=3)
        app.push_traces(traces)
        for ing in app.ingesters.values():
            ing.flush_all()
        app.db.poll_now()
        t0 = min(s.start_unix_nano for t in traces for s in t.all_spans()) // 10**9
        qs = urllib.parse.urlencode({
            "q": "{} | rate() by (resource.service.name)",
            "start": t0, "end": t0 + 60, "step": 60, "exemplars": 2,
        })
        status, doc = _get_json(f"{server.url}/api/metrics/query_range?{qs}")
        assert status == 200 and doc["exemplars"]
        sent_ids = {t.trace_id.hex() for t in traces}
        for ex in doc["exemplars"]:
            assert ex["traceID"] in sent_ids
            assert "value" in ex and "timestamp" in ex

    def test_sharded_frontend_merge_matches_single_job(self, served_app, tmp_path):
        """Many blocks + query_shards > 1: the sharded/merged matrix must
        equal a direct single-evaluator pass over the same blocks."""
        app, server = served_app
        for seed in range(4):
            app.db.write_batch("single-tenant", synth.make_batch(200, 4, seed=seed))
        app.db.poll_now()
        q = "{} | rate() by (resource.service.name)"
        doc = app.query_range(q, BASE_S, BASE_S + 600, 60)
        enc = app.db.default_encoding()
        plan = _plan(q, start=BASE_S, end=BASE_S + 600, step=60)
        acc = HostAccumulator(plan)
        for m in app.db.blocklist.metas("single-tenant"):
            evaluate_block(plan, enc.open_block(m, app.db.backend, app.db.cfg.block), acc)
        ref = _matrix(plan, acc)
        assert doc["result"] == ref["result"]

    @pytest.mark.parametrize("q", [QUERIES[0], QUERIES[2]])
    def test_blocks_on_a_step_boundary_are_read_once(self, q, frontend_app, monkeypatch):
        """The benchmark's layout: two blocks whose data starts exactly
        on a step boundary, a window one step either side of it. Each
        block is in exactly one job, the job carries the one bin that
        holds the data, and the matrix equals one evaluator's pass."""
        app = frontend_app()
        metas = [_write_block(app, seed, BASE_S + 60, span_s=2) for seed in (0, 1)]
        assert all(m.start_time == BASE_S + 60 for m in metas)
        jobs = _block_jobs(app, monkeypatch)
        doc = app.query_range(q, BASE_S, BASE_S + 180, 60)
        assert [(sorted(d["block_ids"]), d["start"], d["end"]) for d in jobs] == [
            (sorted(m.block_id for m in metas), BASE_S + 60, BASE_S + 120)]
        assert doc["result"] == _one_pass(app, q, BASE_S, BASE_S + 180, 60)["result"]
        assert doc["result"]

    @pytest.mark.parametrize("query_shards", [1, 2, 4])
    def test_block_straddling_shard_edges_is_in_one_job(
            self, query_shards, frontend_app, monkeypatch):
        """A one-hour window at step 60 used to be cut at 15, 30 and 45
        minutes when query_shards was 4: one block here crosses all three
        edges, one crosses two, one none. Whatever query_shards says, the
        jobs are the same, no block ID is in two of them, and the matrix
        is one evaluator's."""
        app = frontend_app(query_shards=query_shards)
        metas = [
            _write_block(app, 0, BASE_S + 600, span_s=2400),   # 10' .. 50'
            _write_block(app, 1, BASE_S + 1200, span_s=1200),  # 20' .. 40'
            _write_block(app, 2, BASE_S + 3000, span_s=30),    # 50' .. 50'30"
        ]
        q = "{} | rate() by (resource.service.name)"
        jobs = _block_jobs(app, monkeypatch)
        doc = app.query_range(q, BASE_S, BASE_S + 3600, 60)
        placed = [b for d in jobs for b in d["block_ids"]]
        assert sorted(placed) == sorted(m.block_id for m in metas)
        assert [(d["block_ids"], d["start"], d["end"]) for d in jobs] == [
            ([m.block_id for m in metas], *_hull(metas, BASE_S, BASE_S + 3600, 60))]
        assert (jobs[0]["start"], jobs[0]["end"]) == (BASE_S + 600, BASE_S + 3060)
        assert doc["result"] == _one_pass(app, q, BASE_S, BASE_S + 3600, 60)["result"]

    @pytest.mark.parametrize("q", [QUERIES[0], QUERIES[2]])
    def test_far_apart_blocks_get_their_own_hull(self, q, frontend_app, monkeypatch):
        """A byte budget of one byte a job: every block is its own job,
        in start_time order, each with the hull of its own time range
        (so its own bin_offset at the merge) and not the whole window."""
        app = frontend_app(target_bytes_per_job=1)
        # written out of time order, and one block outside the window
        starts = {0: BASE_S + 1830, 1: BASE_S + 45, 2: BASE_S + 3590, 3: BASE_S + 700,
                  4: BASE_S + 7200}
        metas = {seed: _write_block(app, seed, t0, span_s=90) for seed, t0 in starts.items()}
        start, end, step = BASE_S, BASE_S + 3600, 60
        jobs = _block_jobs(app, monkeypatch)
        doc = app.query_range(q, start, end, step)
        inside = [metas[seed] for seed in (1, 3, 0, 2)]
        assert [(d["block_ids"], d["start"], d["end"]) for d in jobs] == [
            ([m.block_id], *_hull([m], start, end, step)) for m in inside]
        for d in jobs:
            assert (d["start"] - start) % step == 0 and start <= d["start"] < d["end"] <= end
            assert d["end"] - d["start"] <= 3 * step  # 90 s of data, never the hour
        assert len({(d["start"] - start) // step for d in jobs}) == 4  # four bin_offsets
        assert doc["result"] == _one_pass(app, q, start, end, step)["result"]

    def test_sharded_series_cap_fails_loud(self, frontend_app, monkeypatch):
        """Each job caps series in its own first-seen order, so an
        overflow in one of several jobs could leave silent zero-bin holes
        — the frontend must fail the query instead of merging them. The
        several jobs come from the byte budget: one block a job."""
        app = frontend_app(target_bytes_per_job=1)
        for seed in range(4):  # 8 services each
            app.db.write_batch("single-tenant", synth.make_batch(
                200, 4, seed=seed, base_time_ns=(BASE_S + seed * 180) * 10**9))
        jobs = _block_jobs(app, monkeypatch)
        with pytest.raises(ValueError, match="max_series"):
            app.query_range("{} | rate() by (resource.service.name)",
                            BASE_S, BASE_S + 600, 60, max_series=2)
        assert len(jobs) == 4
