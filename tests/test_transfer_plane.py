"""Device data-movement plane (ISSUE 14).

Contracts under test:

1. **Transfer split exactness** — timed_dispatch splits the old
   all-in-`kernel` wall into EXCLUSIVE `transfer` + `kernel` stages
   (their sum bounds the dispatch wall), sizes h2d/d2h/resident from
   the arg/result pytrees, and charges per-tenant `transfer_bytes`
   vectors that sum BIT-EXACTLY to the untagged
   tempo_tpu_device_transfer_bytes_total deltas — across the mesh
   search, mesh metrics, and graph critical-path dispatch paths.
2. **Ghost-LRU what-if** — the stack-distance simulation matches a
   hand-computed fixture and its miss curve is monotone non-increasing
   in budget.
3. **PageHeat ledger** — re-ship counts and amplification accrue from
   block-reader touch points, memory stays bounded (idle TTL + entry
   cap + stream ring), and /status/device serves the hot-set report +
   a monotone curve over >= 4 budgets on a real multi-block drive,
   correlated with /status/profile/device's ledger window.
"""

from __future__ import annotations

import json
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tempo_tpu.util import pageheat, stagetimings, usage
from tempo_tpu.util.devicetiming import (
    count_transfer,
    moved_total,
    timed_dispatch,
    transfer_bytes_total,
)


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


# ---------------------------------------------------------------------------
# the timed_dispatch transfer split
# ---------------------------------------------------------------------------


class TestTransferSplit:
    def test_stages_are_exclusive_and_bound_the_wall(self):
        """transfer + kernel partition the dispatch wall: their sum can
        never exceed what the old all-in-kernel stage reported."""
        f = jax.jit(lambda x: x * 2)
        x = np.arange(1 << 16, dtype=np.int32)
        np.asarray(f(jnp.asarray(x)))  # warm the jit cache
        with stagetimings.request() as st:
            t0 = time.perf_counter()
            out = timed_dispatch("tx-split", f, x)
            wall = time.perf_counter() - t0
        np.testing.assert_array_equal(np.asarray(out), x * 2)
        assert "kernel" in st.seconds
        total = st.seconds["kernel"] + st.seconds.get("transfer", 0.0)
        assert total <= wall + 1e-6, (st.seconds, wall)

    def test_h2d_d2h_sized_from_pytrees(self):
        f = jax.jit(lambda a, b: a + b)
        a = np.arange(4096, dtype=np.int32)
        b = np.arange(4096, dtype=np.int32)
        h0 = transfer_bytes_total.value(direction="h2d", kernel="tx-bytes")
        d0 = transfer_bytes_total.value(direction="d2h", kernel="tx-bytes")
        out = timed_dispatch("tx-bytes", f, a, b)
        assert transfer_bytes_total.value(
            direction="h2d", kernel="tx-bytes") - h0 == a.nbytes + b.nbytes
        assert transfer_bytes_total.value(
            direction="d2h", kernel="tx-bytes") - d0 == out.nbytes

    def test_device_resident_args_counted_resident_not_shipped(self):
        f = jax.jit(lambda a: a * 3)
        dev = jnp.arange(2048, dtype=jnp.int32)
        jax.block_until_ready(dev)
        h0 = transfer_bytes_total.value(direction="h2d", kernel="tx-res")
        r0 = transfer_bytes_total.value(direction="resident", kernel="tx-res")
        timed_dispatch("tx-res", f, dev)
        assert transfer_bytes_total.value(
            direction="h2d", kernel="tx-res") - h0 == 0
        assert transfer_bytes_total.value(
            direction="resident", kernel="tx-res") - r0 == dev.nbytes

    def test_scalar_args_pass_through(self):
        # the unit-test shape the tracing plane relies on: no arrays,
        # no transfer, everything still lands in kernel
        with stagetimings.request() as st:
            assert timed_dispatch("tx-scalar", lambda x: x + 1, 41) == 42
        assert "kernel" in st.seconds
        assert st.seconds.get("transfer", 0.0) == 0.0

    def test_usage_charge_splits_the_measurement(self):
        """The per-vector charge and the untagged counters move at the
        same statement: collected transfer_bytes == moved delta."""
        f = jax.jit(lambda x: x + 1)
        x = np.arange(8192, dtype=np.int32)
        before = moved_total()
        with usage.collect() as vec:
            timed_dispatch("tx-usage", f, x)
        delta = moved_total() - before
        assert delta > 0
        assert vec.snapshot().get("transfer_bytes") == delta

    def test_count_transfer_exactness_for_async_sites(self):
        before = moved_total()
        with usage.collect() as vec:
            count_transfer("tx-async", h2d=1000, d2h=24, resident=5000)
        assert moved_total() - before == 1024
        assert vec.snapshot()["transfer_bytes"] == 1024  # resident excluded


class TestExactnessAcrossDispatchPaths:
    """Per-tenant transfer_bytes vectors sum bit-exactly to the untagged
    counter deltas across the mesh search / mesh metrics / graph
    critical-path dispatch paths (the PR 10 attribution pattern)."""

    def test_mesh_and_graph_paths_sum_to_untagged_deltas(self):
        from tempo_tpu.ops.graph import root_path_sums_device
        from tempo_tpu.parallel.mesh import get_mesh
        from tempo_tpu.parallel.metrics import make_sharded_bincount
        from tempo_tpu.parallel.search import (
            make_sharded_tag_scan_per_shard,
        )

        mesh = get_mesh(8)
        w, r = mesh.devices.shape
        rng = np.random.default_rng(0)
        vectors: dict[str, usage.CostVector] = {}
        before = moved_total()

        # mesh search: sharded tag scan (the MeshSearcher dispatch)
        scan = make_sharded_tag_scan_per_shard(mesh, n_cols=1, max_codes=4)
        cols = rng.integers(0, 8, (w, r, 1, 256), dtype=np.uint32)
        codes = np.full((w, r, 1, 4), 0xFFFFFFFF, np.uint32)
        codes[..., 0] = 3
        valid = np.ones((w, r, 256), bool)
        with usage.collect() as vec:
            timed_dispatch("mesh_scan", scan, cols, codes, valid)
        vectors["search-tenant"] = vec

        # mesh metrics: sharded bincount (the MeshMetricsEvaluator flush)
        bc = make_sharded_bincount(mesh, 128)
        slots = rng.integers(-1, 128, (w, r, 512)).astype(np.int32)
        weights = np.ones((w, r, 512), np.int32)
        with usage.collect() as vec:
            timed_dispatch("mesh_bincount", bc, slots, weights)
        vectors["metrics-tenant"] = vec

        # graph: the device critical-path accumulation
        parent = np.array([-1, 0, 1, 0, -1, 4], np.int64)
        self_ns = np.array([5, 7, 11, 13, 17, 19], np.uint64)
        with usage.collect() as vec:
            dev = root_path_sums_device(parent, self_ns)
        vectors["graph-tenant"] = vec
        from tempo_tpu.ops.graph import root_path_sums_host

        np.testing.assert_array_equal(dev, root_path_sums_host(parent, self_ns))

        delta = moved_total() - before
        attributed = sum(v.snapshot().get("transfer_bytes", 0.0)
                         for v in vectors.values())
        assert delta > 0
        assert attributed == delta  # bit-exact, not approx
        # every path actually moved bytes
        for name, v in vectors.items():
            assert v.snapshot().get("transfer_bytes", 0) > 0, name


# ---------------------------------------------------------------------------
# ghost-LRU what-if simulation
# ---------------------------------------------------------------------------


class TestGhostLRU:
    def test_matches_hand_computed_fixture(self):
        """Pages A/B/C, 100 encoded bytes each, every access moves 400.
        Stream: A B A C A B.
          A@2: distance = B(100)+A(100) = 200 -> hit iff budget >= 200
          C@3: cold miss everywhere
          A@4: distance = C+A = 200        -> hit iff budget >= 200
          B@5: distance = C+A+B = 300      -> hit iff budget >= 300
        Misses (moved bytes): budget 100 -> all 6 (2400);
        200 -> A@2,A@4 hit (1600); 300 -> +B@5 hit (1200);
        10**6 -> same 1200 (first ships are unavoidable)."""
        A, B, C = 0, 1, 2
        stream = [(A, 100, 400), (B, 100, 400), (A, 100, 400),
                  (C, 100, 400), (A, 100, 400), (B, 100, 400)]
        sim = pageheat.ghost_lru_curve(stream, [100, 200, 300, 10**6])
        assert sim["totalMovedBytes"] == 2400
        miss = {c["budgetBytes"]: c["missBytes"] for c in sim["curve"]}
        assert miss == {100: 2400, 200: 1600, 300: 1200, 10**6: 1200}
        saved = {c["budgetBytes"]: c["savedRatio"] for c in sim["curve"]}
        assert saved[300] == pytest.approx(0.5)

    def test_monotone_in_budget_on_random_streams(self):
        rng = np.random.default_rng(7)
        for trial in range(5):
            n = 400
            kids = rng.integers(0, 40, n)
            encs = rng.integers(64, 4096, 40)
            stream = [(int(k), int(encs[k]), int(encs[k]) * 3) for k in kids]
            budgets = sorted(int(b) for b in rng.integers(64, 200_000, 8))
            sim = pageheat.ghost_lru_curve(stream, budgets)
            misses = [c["missBytes"] for c in sim["curve"]]
            assert misses == sorted(misses, reverse=True), (trial, misses)

    def test_empty_stream(self):
        sim = pageheat.ghost_lru_curve([], [100, 200])
        assert sim["totalMovedBytes"] == 0
        assert all(c["missBytes"] == 0 for c in sim["curve"])


# ---------------------------------------------------------------------------
# the page-heat ledger
# ---------------------------------------------------------------------------


class TestPageHeatLedger:
    def test_reship_counts_and_amplification(self):
        led = pageheat.PageHeatLedger()
        for _ in range(4):
            led.touch("blk-1", "service", 0, moved_bytes=4000,
                      encoded_bytes=100)
        led.touch("blk-2", "name", 64, moved_bytes=500, encoded_bytes=500)
        snap = led.snapshot()
        assert snap["trackedPages"] == 2
        assert snap["totalShips"] == 5
        assert snap["totalMovedBytes"] == 4 * 4000 + 500
        hot = snap["hotSet"][0]
        assert (hot["block"], hot["column"]) == ("blk-1", "service")
        assert hot["ships"] == 4
        assert hot["amplification"] == pytest.approx(160.0)  # 16000/100
        # pinning blk-1's 100 encoded bytes saves its 15900 re-ship bytes
        assert snap["pinning"][0]["pages"] == 1
        assert snap["pinning"][0]["savedBytes"] == 16000 - 100

    def test_bounded_memory_entry_cap_and_ttl(self):
        led = pageheat.PageHeatLedger(max_pages=16, stream_cap=32)
        for i in range(100):
            led.touch(f"b{i}", "c", 0, moved_bytes=10, encoded_bytes=10)
        led.evict_idle(older_than_s=10**6)  # TTL passes; cap must bite
        snap = led.snapshot()
        assert snap["trackedPages"] <= 16
        assert snap["streamEntries"] <= 32
        # lifetime totals are eviction-immune
        assert snap["lifetimeShips"] == 100
        assert snap["lifetimeMovedBytes"] == 1000
        assert led.evict_idle(older_than_s=0) > 0
        assert led.snapshot()["trackedPages"] == 0

    def test_what_if_report_has_default_budget_curve(self):
        led = pageheat.PageHeatLedger()
        rng = np.random.default_rng(3)
        for _ in range(200):
            i = int(rng.integers(0, 10))
            led.touch(f"b{i % 3}", f"col{i}", i * 64,
                      moved_bytes=2048, encoded_bytes=256)
        rep = pageheat.what_if_report(ledger=led)
        assert len(rep["curve"]) >= 4
        misses = [c["missBytes"] for c in rep["curve"]]
        assert misses == sorted(misses, reverse=True)
        # the full-working-set budget eliminates everything but cold ships
        assert rep["curve"][-1]["savedBytes"] > 0

    def test_window_report_correlates_marks(self):
        led = pageheat.PageHeatLedger()
        led.touch("b0", "c", 0, moved_bytes=100, encoded_bytes=10)
        mark = led.mark()
        led.touch("b1", "c", 0, moved_bytes=300, encoded_bytes=30)
        win = led.window_report(mark)
        assert win["accesses"] == 1
        assert win["movedBytes"] == 300
        assert win["pages"][0]["block"] == "b1"


# ---------------------------------------------------------------------------
# e2e: /status/device + /status/profile/device + cli analyse device
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def driven(tmp_path_factory):
    """Real multi-block drive: ingest -> flush -> searches + metrics so
    block pages are re-shipped and the ledger heats up."""
    from tempo_tpu.api.server import TempoServer
    from tempo_tpu.app import App, AppConfig
    from tempo_tpu.db import DBConfig
    from tempo_tpu.encoding.common import SearchRequest
    from tempo_tpu.model import synth

    tmp = tmp_path_factory.mktemp("transfer_plane")
    app = App(AppConfig(
        db=DBConfig(backend="local", backend_path=str(tmp / "blocks"),
                    wal_path=str(tmp / "wal")),
        generator_enabled=False,
    ))
    server = TempoServer(app).start()
    pageheat.LEDGER.reset()
    # counters are process-global and monotonic; the ledger just reset —
    # the ledger==counters invariant is checked on DELTAS from here
    base = {"ships": pageheat.ships_total.value(),
            "bytes": pageheat.ship_bytes_total.value()}
    # several flushes -> several blocks
    for seed in (1, 2, 3):
        app.push_traces(synth.make_traces(25, seed=seed, spans_per_trace=4))
        app.sweep_all(immediate=True)
    app.db.poll_now()
    for _ in range(3):  # repeated queries = re-ships of the same pages
        app.search(SearchRequest(tags={"service": "cart"}, limit=1000))
        app.query_range("{} | rate() by (resource.service.name)",
                        1_699_999_000, 1_700_001_000, 60)
    yield app, server, base
    server.stop()
    app.shutdown()


class TestStatusDeviceEndpoint:
    def test_hot_set_and_monotone_curve(self, driven):
        _app, server, _tmp = driven
        status, doc = _get(server.url + "/status/device")
        assert status == 200
        heat = doc["pageHeat"]
        assert heat["trackedPages"] > 0
        assert heat["totalShips"] > heat["trackedPages"]  # re-ships happened
        assert heat["hotSet"][0]["ships"] >= 2
        assert heat["amplification"] > 0
        curve = doc["whatIf"]["curve"]
        assert len(curve) >= 4
        misses = [c["missBytes"] for c in curve]
        assert misses == sorted(misses, reverse=True)
        # repeated queries => a residency budget saves transfer bytes
        assert curve[-1]["savedBytes"] > 0
        assert "transfer" in doc and "byKernel" in doc["transfer"]
        # the resolved backend rides the same document (what the off-JAX
        # chip smoke reads to learn the child's device)
        be = doc["backend"]
        assert be["platform"] == "cpu" and be["pallas"] == "interpret"
        assert be["device_count"] == len(be["devices"]) >= 1

    def test_explicit_budgets_param(self, driven):
        _app, server, _tmp = driven
        status, doc = _get(server.url + "/status/device?budgets_mb=1,2,4,8")
        assert status == 200
        got = [c["budgetBytes"] for c in doc["whatIf"]["curve"]]
        assert got == [1 << 20, 2 << 20, 4 << 20, 8 << 20]

    def test_ledger_equals_counters(self, driven):
        """The loadtest gate's invariant, proven in-process: lifetime
        ledger totals == the pageheat counter deltas (the counters are
        process-global, so equality is on deltas from the fixture's
        ledger reset — in a fresh loadtest process base is zero and the
        gate compares absolutes)."""
        _app, server, base = driven
        status, doc = _get(server.url + "/status/device")
        assert status == 200
        assert doc["pageHeat"]["lifetimeMovedBytes"] == \
            pageheat.ship_bytes_total.value() - base["bytes"]
        assert doc["pageHeat"]["lifetimeShips"] == \
            pageheat.ships_total.value() - base["ships"]

    def test_profile_device_links_transfer_ledger(self, driven):
        app, server, _tmp = driven
        from tempo_tpu.encoding.common import SearchRequest

        import threading

        # touch pages DURING the capture window from a side thread so the
        # correlated ledger window is provably the capture's window
        t = threading.Thread(target=lambda: app.search(
            SearchRequest(tags={"service": "cart"}, limit=10)))
        t.start()
        status, doc = _get(server.url + "/status/profile/device?seconds=0.5")
        t.join()
        assert status == 200
        led = doc["transferLedger"]
        assert "accesses" in led and "movedBytes" in led


class TestExporterAndCLI:
    def test_exporter_snapshot_and_cli_analyse_device(self, driven, tmp_path,
                                                      capsys):
        from tempo_tpu.cli import main as cli_main

        exp = pageheat.PageHeatExporter(interval_s=3600,
                                        export_dir=str(tmp_path / "heat"))
        doc = exp.export_once()
        assert doc["pageHeat"]["trackedPages"] > 0
        assert exp.last_path is not None
        snap = str(tmp_path / "heat" / pageheat.PageHeatExporter.SNAPSHOT_NAME)
        # offline analysis over the same ledger snapshot, default budgets
        assert cli_main(["--path", str(tmp_path), "analyse", "device",
                         snap, "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["pageHeat"]["trackedPages"] > 0
        assert len(out["whatIf"]["curve"]) >= 4
        # re-simulated at explicit budgets from the carried access stream
        assert cli_main(["--path", str(tmp_path), "analyse", "device",
                         snap, "--budgets-mb", "1,4,16,64", "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        got = [c["budgetBytes"] for c in out["whatIf"]["curve"]]
        assert got == [1 << 20, 4 << 20, 16 << 20, 64 << 20]
        misses = [c["missBytes"] for c in out["whatIf"]["curve"]]
        assert misses == sorted(misses, reverse=True)
        # human-readable form renders
        assert cli_main(["--path", str(tmp_path), "analyse", "device",
                         snap]) == 0
        text = capsys.readouterr().out
        assert "what-if HBM residency" in text

    def test_exporter_publishes_miss_ratio_gauges(self, driven):
        pageheat.what_if_report(publish_gauges=True)
        vals = [v for _labels, v in pageheat.miss_ratio_gauge.series()]
        assert vals, "no per-budget miss-ratio gauges published"
        assert all(0.0 <= v <= 1.0 for v in vals)


class TestMeshSearcherStats:
    def test_mesh_search_stats_match_transfer_plane(self):
        """MeshSearcher's per-job h2d accounting and the process-wide
        transfer counters move together on the same dispatch."""
        from tempo_tpu.parallel.mesh import get_mesh
        from tempo_tpu.parallel.search import MeshSearcher

        mesh = get_mesh(8)
        # no blocks: nothing dispatches, stats must stay zero and the
        # counters untouched (the cheap half of the invariant)
        searcher = MeshSearcher(mesh, bucket_for=lambda n: max(
            1024, 1 << (n - 1).bit_length()))
        before = moved_total()

        class Req:
            tags = {}
            query = ""
            limit = 1
            min_duration_ns = 0
            max_duration_ns = 0
            start_seconds = 0
            end_seconds = 0

        resp = searcher.search_blocks([], Req())
        assert resp.inspected_blocks == 0
        assert searcher.last_stats["h2d_bytes"] == 0
        assert moved_total() == before
