"""Device-resident hot tier + batched multi-query dispatch.

The tier's contract has three legs, each tested here:
1. CORRECTNESS — a scan served from the resident tier (device decode
   fused into the predicate kernel) is bit-identical to the host path
   for every lightweight codec (rle/dct/dbp), and the batched
   multi-query scan is bit-identical to N sequential scans (on 1-, 2-
   and 4-shard meshes too).
2. ECONOMY — repeat queries over a resident working set move ZERO h2d
   payload bytes (the avoided counter climbs instead), and N coalesced
   queries cost ceil(N / batch) dispatches, not N.
3. SAFETY — admission only at the ghost-LRU knee (hot pages in, cold
   pages out), and the tier sheds under governor pressure HARDER than
   the host cache (device memory yields first).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from tempo_tpu.backend import MockBackend
from tempo_tpu.db import DBConfig, TempoDB
from tempo_tpu.encoding.common import SearchRequest
from tempo_tpu.encoding.vtpu import colcache, lightweight as lw
from tempo_tpu.model import synth
from tempo_tpu.model import trace as tr
from tempo_tpu.ops import scan as scan_mod
from tempo_tpu.util import devicetiming, pageheat


@pytest.fixture
def device_tier():
    """A private DeviceTier installed as the process tier, admission
    forced open (the admission POLICY has its own tests below); always
    uninstalled afterwards so other tests see the tier disabled."""
    tier = colcache.DeviceTier(32 << 20, refresh_s=3600.0)
    tier.should_admit = lambda page_keys: True
    old = colcache._shared_device
    colcache._arm_device_metrics()
    colcache._shared_device = tier
    try:
        yield tier
    finally:
        colcache._shared_device = old


def _mk_db(n_blocks=6, seed=100):
    db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
    traces = []
    for i in range(n_blocks):
        ts = synth.make_traces(12, seed=seed + i, spans_per_trace=4)
        db.write_batch("t", tr.traces_to_batch(ts).sorted_by_trace())
        traces.extend(ts)
    return db, traces


def _svc(traces):
    return next(t.batches[0][0]["service.name"] for t in traces
                if t.batches[0][0].get("service.name"))


def _ids(resp):
    return {t.trace_id_hex for t in resp.traces}


# ---------------------------------------------------------------------------
# 1. bit-exactness: resident device decode == host decode, per codec
# ---------------------------------------------------------------------------


class TestResidentBitExactness:
    def _res(self, codec, arrays, meta, host_bytes=0):
        return colcache._Resident(
            codec, {k: jnp.asarray(v) for k, v in arrays.items()},
            meta, host_bytes)

    def test_rle_in_set_and_range(self):
        rng = np.random.default_rng(0)
        rows = np.sort(rng.integers(0, 6, 300).astype(np.uint32))
        page = lw.rle_encode(rows)
        v, l = lw.rle_decode_runs(page, np.dtype("uint32"), rows.shape)
        res = self._res("rle", {"values": v.astype(np.uint32),
                                "lengths": l.astype(np.int32)},
                        {"n": rows.size})
        for codes in ([1, 4], [], [0xFFFFFFFF]):
            codes = np.asarray(codes, np.uint32)
            got = scan_mod.resident_in_set_mask(res, codes)
            np.testing.assert_array_equal(got, np.isin(rows, codes))
            got = scan_mod.resident_in_set_mask(res, codes, invert=True)
            np.testing.assert_array_equal(got, np.isin(rows, codes, invert=True))
        got = scan_mod.resident_range_mask(res, 2, 4)
        np.testing.assert_array_equal(got, (rows >= 2) & (rows <= 4))

    def test_rle_sentinel_value_in_column(self):
        """A column that CONTAINS the 0xFFFFFFFF sentinel still matches
        bit-exactly — the pad-by-repeating-codes[0] trick, not a
        sentinel pad, keeps device membership == np.isin."""
        rows = np.array([1, 1, 0xFFFFFFFF, 0xFFFFFFFF, 7], np.uint32)
        page = lw.rle_encode(rows)
        v, l = lw.rle_decode_runs(page, np.dtype("uint32"), rows.shape)
        res = self._res("rle", {"values": v.astype(np.uint32),
                                "lengths": l.astype(np.int32)},
                        {"n": rows.size})
        codes = np.array([0xFFFFFFFF, 7], np.uint32)
        np.testing.assert_array_equal(
            scan_mod.resident_in_set_mask(res, codes), np.isin(rows, codes))

    def test_dct_in_set_and_range(self):
        rng = np.random.default_rng(1)
        rows = rng.integers(0, 900, 400).astype(np.uint32)
        page = lw.dct_encode(rows)
        dvals, idx = lw.dct_indices(page, np.dtype("uint32"), rows.shape)
        res = self._res("dct", {"values": dvals.astype(np.uint32),
                                "idx": idx.astype(np.int32)},
                        {"n": rows.size})
        codes = np.unique(rng.choice(rows, 6)).astype(np.uint32)
        np.testing.assert_array_equal(
            scan_mod.resident_in_set_mask(res, codes), np.isin(rows, codes))
        np.testing.assert_array_equal(
            scan_mod.resident_range_mask(res, 100, 700),
            (rows >= 100) & (rows <= 700))

    def test_dbp_range_u64(self):
        rng = np.random.default_rng(2)
        rows = (np.cumsum(rng.integers(0, 60, 500))
                + 17_000_000_000_000).astype(np.uint64)
        page = lw.dbp_encode(rows)
        first, _a, widths, streams, n = lw.dbp_parts(
            page, np.dtype("uint64"), rows.shape)
        assert len(widths) == 1
        raw = bytes(streams[0])
        words = np.frombuffer(raw + b"\x00" * ((-len(raw)) % 4 + 4), "<u4")
        res = self._res("dbp", {"words": words},
                        {"n": n, "first": int(first[0]),
                         "width": int(widths[0])})
        lo, hi = int(rows[40]), int(rows[460])
        np.testing.assert_array_equal(
            scan_mod.resident_range_mask(res, lo, hi),
            (rows >= lo) & (rows <= hi))
        # dbp answers ranges only; in-set falls back to the host path
        assert scan_mod.resident_in_set_mask(res, np.array([1], np.uint32)) is None

    def test_single_block_resident_serving(self, device_tier):
        """The per-column resident path (EncodedColumn -> ops.scan
        resident kernels): a repeat search over one block serves its
        predicate pages from the tier — hits climb, avoided bytes climb,
        results stay bit-identical to the tier-off path."""
        from tempo_tpu.encoding import from_version

        db, traces = _mk_db(1, seed=900)
        enc = from_version("vtpu1")
        meta = next(iter(db.blocklist.metas("t")))
        req = SearchRequest(tags={"service.name": _svc(traces)}, limit=0)

        blk = enc.open_block(meta, db.backend, db.cfg.block)
        warm = blk.search(req)       # builds payloads + admits
        hits0, avoided0 = device_tier.hits, device_tier.avoided_bytes
        hot = blk.search(req)        # serves resident
        assert device_tier.hits > hits0
        assert device_tier.avoided_bytes > avoided0
        colcache._shared_device = None
        cold = enc.open_block(meta, db.backend, db.cfg.block).search(req)
        assert _ids(warm) == _ids(hot) == _ids(cold)
        assert _ids(cold)

    def test_search_parity_tier_on_vs_off(self, device_tier):
        """End-to-end: the same searches with the hot tier warm return
        bit-identical hits to the tier-disabled path."""
        db, traces = _mk_db(5)
        reqs = [
            SearchRequest(tags={"service.name": _svc(traces)}, limit=0),
            SearchRequest(min_duration_ns=1, limit=0),
        ]
        warm = [db.search("t", r) for r in reqs]       # admits
        hot = [db.search("t", r) for r in reqs]        # serves resident
        colcache._shared_device = None                 # tier off
        cold = [db.search("t", r) for r in reqs]
        for w, h, c in zip(warm, hot, cold):
            assert _ids(w) == _ids(h) == _ids(c)
            assert _ids(c)


# ---------------------------------------------------------------------------
# 2. admission at the what-if knee
# ---------------------------------------------------------------------------


class TestAdmissionPolicy:
    def _ledger(self):
        led = pageheat.PageHeatLedger()
        # hot pages: re-shipped every query; cold: shipped once
        for _ in range(50):
            for c in ("service", "name"):
                led.touch("blk-hot", c, 0, moved_bytes=200_000,
                          encoded_bytes=8_000)
        for i in range(40):
            led.touch(f"blk-cold-{i}", "service", 0,
                      moved_bytes=150_000, encoded_bytes=9_000)
        return led

    def test_knee_budget_finds_elbow(self):
        led = self._ledger()
        rep = pageheat.what_if_report(ledger=led)
        knee = pageheat.knee_budget(rep["curve"])
        assert knee > 0
        assert knee in {r["budgetBytes"] for r in rep["curve"]}
        # the knee covers the hot working set (2 pages x 8 KB encoded)
        # without paying for the cold tail (40 more pages)
        assert knee < rep["uniqueEncodedBytes"]

    def test_candidates_rank_hot_pages_first(self):
        led = self._ledger()
        cands = pageheat.admission_candidates(10**9, ledger=led, min_ships=2)
        assert cands, "hot pages must be candidates"
        assert all(c["block"] == "blk-hot" for c in cands)
        # cold pages shipped once never qualify (min_ships)
        assert not any("cold" in c["block"] for c in cands)

    def test_knee_budget_empty_and_flat(self):
        assert pageheat.knee_budget([]) == 0
        flat = [{"budgetBytes": b, "savedBytes": 0} for b in (10, 20, 30)]
        assert pageheat.knee_budget(flat) == 0

    def test_a_working_set_that_fits_the_budget_is_not_rationed_by_the_knee(self):
        """The knee rations a budget the working set exceeds. With room
        for every page the ledger holds, every re-shipped page is a
        candidate at the first refresh; a cold ledger still admits none."""
        led = self._ledger()
        unique = pageheat.what_if_report(ledger=led)["uniqueEncodedBytes"]
        tight = pageheat.admission_report(budget_bytes=unique - 1, ledger=led)
        assert tight["effectiveBudgetBytes"] == tight["kneeBudgetBytes"] < unique
        roomy = pageheat.admission_report(budget_bytes=unique, ledger=led)
        assert roomy["effectiveBudgetBytes"] == unique > roomy["kneeBudgetBytes"]
        assert {(c["block"], c["column"]) for c in roomy["candidates"]} == {
            ("blk-hot", "service"), ("blk-hot", "name")}
        cold = pageheat.PageHeatLedger()
        for i in range(10):
            cold.touch(f"b{i}", "service", 0, moved_bytes=150_000, encoded_bytes=9_000)
        rep = pageheat.admission_report(budget_bytes=1 << 30, ledger=cold)
        assert rep["effectiveBudgetBytes"] == 0 and not rep["candidates"]

    def test_tier_admits_only_inside_admission_set(self):
        tier = colcache.DeviceTier(32 << 20, refresh_s=3600.0)
        tier._admit_keys = frozenset({("blk-hot", "service", 0)})
        tier._admit_at = float("inf")  # freeze the set for this test
        arrays = {"values": np.arange(8, dtype=np.uint32)}
        assert tier.offer(("blk-hot", "service", 0), "rle", dict(arrays))
        assert not tier.offer(("blk-cold-1", "service", 0), "rle", dict(arrays))
        # composite entries admit only when EVERY backing page is hot
        assert not tier.offer(
            ("stack",), "rle_stack", dict(arrays),
            page_keys=[("blk-hot", "service", 0), ("blk-cold-1", "service", 0)])
        assert tier.stats()["admissions"] == 1


# ---------------------------------------------------------------------------
# 3. eviction under pressure: device yields before host
# ---------------------------------------------------------------------------


class _Gov:
    def __init__(self, lvl=0):
        self.lvl = lvl

    def level(self):
        return self.lvl


class TestPressureShedding:
    def _fill(self, tier, n=8, kb=512):
        tier.should_admit = lambda page_keys: True
        for i in range(n):
            assert tier.offer((f"b{i}", "service", 0), "rle",
                              {"values": np.zeros(kb * 256, np.uint32)})
        return tier

    def test_pressure_quarters_critical_empties(self):
        gov = _Gov()
        budget = 8 * 512 * 1024
        tier = self._fill(colcache.DeviceTier(budget, governor=gov))
        assert tier.stats()["bytes"] == budget
        gov.lvl = 1  # PRESSURE
        tier.shed()
        st = tier.stats()
        assert 0 < st["bytes"] <= budget // 4
        assert st["evictions"] >= 6
        gov.lvl = 2  # CRITICAL
        tier.shed()
        assert tier.stats()["bytes"] == 0
        assert tier.stats()["entries"] == 0

    def test_device_sheds_harder_than_host(self):
        """The shed order device -> host -> ingest is encoded in the
        pressure factors: at every level the device tier keeps a
        smaller fraction than the host cache."""
        for lvl in (1, 2):
            dev = colcache.DeviceTier._PRESSURE_FACTORS[lvl]
            host = colcache.ColumnCache._PRESSURE_FACTORS[lvl]
            assert dev < host

    def test_respect_governor_false_never_sheds(self):
        gov = _Gov(2)
        tier = self._fill(colcache.DeviceTier(
            8 * 512 * 1024, governor=gov, respect_governor=False))
        tier.shed()
        assert tier.stats()["entries"] == 8

    def test_oversized_offer_refused(self):
        tier = colcache.DeviceTier(1024, governor=_Gov())
        tier.should_admit = lambda page_keys: True
        assert not tier.offer(("b", "c", 0), "rle",
                              {"values": np.zeros(4096, np.uint32)})
        assert tier.stats()["entries"] == 0


# ---------------------------------------------------------------------------
# 4. batched multi-query dispatch: parity + dispatch economy
# ---------------------------------------------------------------------------


class TestBatchedDispatch:
    def _runs(self, rng, n):
        rows = np.sort(rng.integers(0, 9, n).astype(np.uint32))
        page = lw.rle_encode(rows)
        v, l = lw.rle_decode_runs(page, np.dtype("uint32"), rows.shape)
        return rows, v.astype(np.uint32), l.astype(np.int32)

    def test_single_device_batched_equals_sequential(self):
        from tempo_tpu.ops.pallas_kernels import batched_rle_in_set

        rng = np.random.default_rng(3)
        n, C, K, Q = 256, 2, 4, 5
        rows, pads = [], 1
        cols = []
        for _ in range(C):
            r, v, l = self._runs(rng, n)
            cols.append((r, v, l))
            pads = max(pads, len(v))
        run_pad = 1 << (pads - 1).bit_length()
        values = np.full((C, run_pad), 0xFFFFFFFF, np.uint32)
        lengths = np.zeros((C, run_pad), np.int32)
        for c, (_, v, l) in enumerate(cols):
            values[c, : len(v)] = v
            lengths[c, : len(l)] = l
        codes = np.full((Q, C, K), 0xFFFFFFFF, np.uint32)
        live = np.zeros((Q, C), bool)
        rng2 = np.random.default_rng(4)
        for q in range(Q):
            for c in range(C):
                if rng2.random() < 0.7:
                    cs = rng2.integers(0, 9, rng2.integers(1, K + 1))
                    codes[q, c, : len(cs)] = cs
                    live[q, c] = True
        valid = np.ones(n, bool)
        before = devicetiming.dispatch_total.total(kernel="batched_rle_scan")
        got = batched_rle_in_set(values, lengths, codes, live, valid, n)
        after = devicetiming.dispatch_total.total(kernel="batched_rle_scan")
        assert after - before == 1  # Q queries, ONE launch
        assert got.shape == (Q, n)
        for q in range(Q):
            want = np.ones(n, bool)
            for c, (r, _, _) in enumerate(cols):
                if live[q, c]:
                    cs = codes[q, c][codes[q, c] != 0xFFFFFFFF]
                    want &= np.isin(r, cs)
            np.testing.assert_array_equal(got[q], want)

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_mesh_multi_matches_sequential(self, shards):
        from tempo_tpu.encoding import from_version
        from tempo_tpu.parallel.mesh import get_mesh
        from tempo_tpu.parallel.search import MeshSearcher

        db, traces = _mk_db(6, seed=300)
        svcs = sorted({t.batches[0][0]["service.name"] for t in traces
                       if t.batches[0][0].get("service.name")})
        reqs = [SearchRequest(tags={"service.name": s}, limit=0)
                for s in svcs[:3]]
        reqs.append(SearchRequest(tags={"service.name": svcs[0]},
                                  min_duration_ns=1, limit=0))
        metas = list(db.blocklist.metas("t"))
        enc = from_version("vtpu1")

        def blocks():
            return (enc.open_block(m, db.backend, db.cfg.block) for m in metas)

        searcher = MeshSearcher(get_mesh(shards), db.cfg.block.bucket_for)
        multi = searcher.search_blocks_multi(blocks(), reqs)
        for req, got in zip(reqs, multi):
            want = searcher.search_blocks(blocks(), req)
            assert _ids(got) == _ids(want)
        assert any(_ids(r) for r in multi)

    def test_multi_dispatch_count_batches(self, device_tier):
        """N queries through search_blocks_multi cost at most
        ceil(N / max_query_batch) batched launches per chunk — and a
        repeat of the same fan moves zero payload bytes once the stack
        is resident (avoided climbs, h2d stays flat)."""
        db, traces = _mk_db(6, seed=500)
        svcs = sorted({t.batches[0][0]["service.name"] for t in traces
                       if t.batches[0][0].get("service.name")})
        reqs = [SearchRequest(tags={"service.name": s}, limit=0)
                for s in (svcs * 4)[:10]]  # N=10, batch=8 -> 2 launches
        searcher = db.mesh_searcher()
        assert searcher is not None

        d0 = devicetiming.dispatch_total.total(kernel="batched_rle_scan")
        first = db.search_multi("t", reqs)
        d1 = devicetiming.dispatch_total.total(kernel="batched_rle_scan")
        chunks = max(1, -(-searcher.last_stats["units_scanned"]
                          // (searcher.w * searcher.r)))
        assert d1 - d0 <= chunks * -(-len(reqs) // device_tier.max_query_batch)

        h0 = devicetiming.transfer_bytes_total.total(
            direction="h2d", kernel="batched_rle_scan")
        a0 = devicetiming.avoided_total()
        hit0 = device_tier.hits
        second = db.search_multi("t", reqs)
        h1 = devicetiming.transfer_bytes_total.total(
            direction="h2d", kernel="batched_rle_scan")
        assert device_tier.hits > hit0          # served resident
        assert devicetiming.avoided_total() > a0  # economy measured
        # only codes/live/valid ship on the hot fan — never the payload
        st = searcher.last_stats
        assert h1 - h0 <= st["h2d_bytes"] * 2
        for a, b in zip(first, second):
            assert _ids(a) == _ids(b)

    def test_multi_respects_per_query_limits(self):
        db, traces = _mk_db(5, seed=700)
        svc = _svc(traces)
        reqs = [SearchRequest(tags={"service.name": svc}, limit=2),
                SearchRequest(tags={"service.name": svc}, limit=0)]
        out = db.search_multi("t", reqs)
        assert len(out[0].traces) <= 2
        assert _ids(out[0]) <= _ids(out[1])


# ---------------------------------------------------------------------------
# 5. observability: per-tier stats + metrics split
# ---------------------------------------------------------------------------


class TestTierObservability:
    def test_stats_carry_tier_labels(self, device_tier):
        host = colcache.ColumnCache(1 << 20)
        assert host.stats()["tier"] == "host"
        assert device_tier.stats()["tier"] == "device"

    def test_metrics_split_by_tier(self, device_tier):
        from tempo_tpu.util import metrics

        device_tier.should_admit = lambda page_keys: True
        device_tier.offer(("b", "service", 0), "rle",
                          {"values": np.arange(64, dtype=np.uint32)})
        text = metrics.expose()
        assert 'tempo_tpu_colcache_bytes{tier="device"}' in text
        assert 'tempo_tpu_device_transfer_bytes_avoided_total' in text

    def test_device_report_exposes_resident_set(self, device_tier):
        device_tier.offer(("blk-x", "service", 128), "rle",
                          {"values": np.arange(32, dtype=np.uint32)})
        rep = colcache.device_tier_report()
        assert rep["enabled"]
        pages = rep["residentPages"]
        assert any(p.get("block") == "blk-x" and p.get("column") == "service"
                   for p in pages)
        assert rep["stats"]["entries"] == 1

    def test_report_disabled_without_tier(self):
        assert colcache._shared_device is None
        assert colcache.device_tier_report() == {"enabled": False}


# ---------------------------------------------------------------------------
# 6. resident payloads in bucketed shapes: one program a bucket, masks
#    bit-identical to the host path at the padding's edges
# ---------------------------------------------------------------------------


def _page(codec: str, rows: np.ndarray):
    """EncodedColumn over one encoded page held in memory: the real
    resident_payload / in_set_mask / range_mask, no block behind it
    (`_colcache` None: the host path, never a tier)."""
    import types

    from tempo_tpu.encoding.vtpu.block import EncodedColumn

    page = {"rle": lw.rle_encode, "dct": lw.dct_encode, "dbp": lw.dbp_encode}[codec](rows)
    col = object.__new__(EncodedColumn)
    col.blk = types.SimpleNamespace(_colcache=None)
    col.name, col.codec, col.n = "c", codec, rows.shape[0]
    col.pm = types.SimpleNamespace(shape=rows.shape, dtype=rows.dtype.str,
                                   offset=0, codec=codec)
    col._page = lambda: page
    col.runs = lambda: lw.rle_decode_runs(page, rows.dtype.str, rows.shape)
    col._dct_indices = lambda: lw.dct_indices(page, rows.dtype.str, rows.shape)
    return col


def _resident_of(col):
    codec, arrays, meta, host_bytes = col.resident_payload()
    return colcache._Resident(
        codec, {k: jnp.asarray(v) for k, v in arrays.items()}, meta, host_bytes)


def _rle_rows(rng, n: int, runs: int, values) -> np.ndarray:
    """n rows in exactly `runs` runs, neighbours distinct, from `values`."""
    cuts = np.sort(rng.choice(np.arange(1, n), runs - 1, replace=False))
    lengths = np.diff(np.concatenate([[0], cuts, [n]]))
    vals = [int(rng.choice(values))]
    while len(vals) < runs:
        v = int(rng.choice(values))
        if v != vals[-1]:
            vals.append(v)
    return np.repeat(np.asarray(vals, np.uint32), lengths)


def _dct_rows(rng, n: int, dict_size: int, lo: int = 0) -> np.ndarray:
    """n rows over a page dictionary of exactly `dict_size` entries."""
    entries = (lo + 3 * np.arange(dict_size)).astype(np.uint32)
    rows = np.concatenate([entries, rng.choice(entries, n - dict_size)])
    return rng.permutation(rows).astype(np.uint32)


def _dbp_rows(rng, n: int, max_delta: int) -> np.ndarray:
    return (np.cumsum(rng.integers(0, max_delta, n))
            + 17_000_000_000_000).astype(np.uint64)


def _is_pow2(k: int) -> bool:
    return k >= 1 and k & (k - 1) == 0


class TestBucketedResidentShapes:
    N = 512

    @pytest.mark.parametrize("codec,jits,pages", [
        # five run counts in (32, 64], then one in (64, 128]
        ("rle", ("_rle_in_set_resident_jit", "_rle_between_resident_jit"),
         [(33,), (40,), (51,), (57,), (64,), (65,)]),
        # five dictionary sizes in (16, 32], then one in (32, 64]
        ("dct", ("_dct_in_set_resident_jit", "_dct_between_resident_jit"),
         [(17,), (20,), (25,), (31,), (32,), (33,)]),
        # delta widths 4, 5, 6, 7, 7 bits: 65, 81, 97, 113, 113 words, all
        # in (64, 128]; then 9 bits, 145 words, in (128, 256]
        ("dbp", ("_dbp_between_resident_jit",),
         [(8,), (16,), (32,), (64,), (60,), (250,)]),
    ])
    def test_one_resident_program_a_bucket(self, codec, jits, pages):
        """Pages whose data-dependent length differs compile at most one
        resident program a power-of-two bucket; each mask equals the
        host path's (numpy's for dbp, which the host path cannot answer)."""
        rng = np.random.default_rng(7)
        fns = [getattr(scan_mod, j) for j in jits]
        buckets = set()
        size0 = [f._cache_size() for f in fns]
        for (k,) in pages:
            if codec == "rle":
                rows = _rle_rows(rng, self.N, k, np.arange(1, 9))
            elif codec == "dct":
                rows = _dct_rows(rng, self.N, k, lo=5)
            else:
                rows = _dbp_rows(rng, self.N, k)
            col = _page(codec, rows)
            res = _resident_of(col)
            for name, a in res.arrays.items():
                if name != "idx":  # one a row: the page's own row count
                    assert _is_pow2(a.shape[0]), (name, a.shape)
            buckets.add(tuple(sorted((n, a.shape[0]) for n, a in res.arrays.items())))
            lo, hi = int(rows[40]), int(rows[460])
            lo, hi = min(lo, hi), max(lo, hi)
            want = (rows >= lo) & (rows <= hi)
            np.testing.assert_array_equal(scan_mod.resident_range_mask(res, lo, hi), want)
            if codec != "dbp":
                np.testing.assert_array_equal(col.range_mask(lo, hi), want)
                codes = np.unique(rows[:3]).astype(np.uint32)[:2]
                np.testing.assert_array_equal(
                    scan_mod.resident_in_set_mask(res, codes), col.in_set_mask(codes))
        assert len(buckets) == 2, buckets
        for f, s0 in zip(fns, size0):
            assert f._cache_size() - s0 <= len(buckets), (f, s0, f._cache_size())

    @pytest.mark.parametrize("case", [
        "rle_zero_length_pad_runs", "rle_column_holds_zero", "rle_invert",
        "rle_short_page", "dct_repeated_entry_pad", "dct_column_holds_zero",
        "dct_invert", "dct_short_page", "dbp_zero_words_pad", "dbp_short_page",
    ])
    def test_masks_equal_the_host_path_at_the_paddings_edges(self, case):
        rng = np.random.default_rng(11)
        codec, _, what = case.partition("_")
        n = 37 if what == "short_page" else self.N
        if codec == "rle":
            # 5 runs pad to 8: three zero-length runs whose value is 0
            values = np.arange(0, 4) if what == "column_holds_zero" else np.arange(1, 9)
            rows = _rle_rows(rng, n, 5, values)
        elif codec == "dct":
            # 5 entries pad to 8 by repeating the first, which the column holds
            rows = _dct_rows(rng, n, 5, lo=0 if what == "column_holds_zero" else 9)
        else:
            rows = _dbp_rows(rng, n, 40)
        col = _page(codec, rows)
        res = _resident_of(col)
        payload = col.resident_payload()[1]
        if codec == "rle":
            assert payload["lengths"].shape[0] == 8
            assert int(payload["lengths"].sum()) == n and (payload["lengths"][5:] == 0).all()
        elif codec == "dct":
            assert payload["values"].shape[0] == 8
            assert (payload["values"][5:] == payload["values"][0]).all()
            assert int(payload["idx"].max()) < 5
        else:
            unpadded = lw.dbp_parts(col._page(), rows.dtype.str, rows.shape)[3][0]
            real = (len(bytes(unpadded)) + 3) // 4 + 1  # words + the guard word
            assert _is_pow2(payload["words"].shape[0]) and payload["words"].shape[0] >= real
            assert not payload["words"][real:].any()
        # the pad is part of what the tier charges its budget with
        assert res.nbytes == sum(a.nbytes for a in payload.values())
        lo, hi = sorted((int(rows[3]), int(rows[n - 4])))
        for a, b in ((lo, hi), (0, 0), (int(rows.min()), int(rows.max())), (hi + 1, hi + 2)):
            want = (rows >= a) & (rows <= b)
            np.testing.assert_array_equal(scan_mod.resident_range_mask(res, a, b), want)
            if codec != "dbp":
                np.testing.assert_array_equal(col.range_mask(a, b), want)
        if codec == "dbp":
            return
        first = int(payload["values"][0])
        for codes in ([0], [first], [first, int(rows[1])], [], [0xFFFFFFFF]):
            codes = np.asarray(codes, np.uint32)
            for invert in (False, True):
                if what == "invert" and not invert:
                    continue
                got = scan_mod.resident_in_set_mask(res, codes, invert=invert)
                np.testing.assert_array_equal(got, np.isin(rows, codes, invert=invert))
                np.testing.assert_array_equal(got, col.in_set_mask(codes, invert=invert))


# ---------------------------------------------------------------------------
# 7. one resident dispatch a shape bucket, not one a page
# ---------------------------------------------------------------------------


def _resident_dispatches() -> float:
    return sum(devicetiming.dispatch_total.total(kernel=f"resident_{c}_scan")
               for c in ("rle", "dct", "dbp"))


class TestOneDispatchABucket:
    N = 256

    def _pages(self, codec, sizes, n=None):
        rng = np.random.default_rng(23)
        n = n or self.N
        make = {"rle": lambda k: _rle_rows(rng, n, k, np.arange(0, 8)),
                "dct": lambda k: _dct_rows(rng, n, k, lo=0),
                "dbp": lambda k: _dbp_rows(rng, n, k)}[codec]
        cols = [_page(codec, make(k)) for k in sizes]
        return cols, [_resident_of(c) for c in cols]

    @pytest.mark.parametrize("codec,sizes,dispatches", [
        # run counts: five in (16, 32] and two in (32, 64]: two programs' shapes
        ("rle", (17, 20, 25, 31, 32, 33, 40), 2),
        ("dct", (17, 20, 25, 31, 32, 33, 40), 2),
        # 4..7 bit deltas share a word bucket at 256 rows, 9 bits is the next
        ("dbp", (8, 16, 32, 64, 60, 250), 2),
        # 17 pages of one shape: the group is cut at 16
        ("rle", (20,) * 17, 2),
        # 5 pages of one shape: one dispatch, extended to 8 by repeating the last
        ("dct", (20,) * 5, 1),
    ])
    def test_many_pages_one_dispatch_a_shape_group(self, codec, sizes, dispatches):
        cols, entries = self._pages(codec, sizes)
        rows = [lw.rle_decode(c._page(), c.pm.dtype, c.pm.shape) if codec == "rle"
                else lw.dct_decode(c._page(), c.pm.dtype, c.pm.shape) if codec == "dct"
                else lw.dbp_decode(c._page(), c.pm.dtype, c.pm.shape) for c in cols]
        lo, hi = sorted((int(rows[0][9]), int(rows[-1][200])))
        d0 = _resident_dispatches()
        got = scan_mod.resident_range_masks(entries, lo, hi)
        assert _resident_dispatches() - d0 == dispatches
        for r, m in zip(rows, got):
            np.testing.assert_array_equal(m, (r >= lo) & (r <= hi))
        if codec == "dbp":
            assert scan_mod.resident_in_set_masks(entries, np.array([1], np.uint32)) \
                == [None] * len(entries)
            return
        codes = np.array([0, int(rows[0][1])], np.uint32)
        for invert in (False, True):
            d0 = _resident_dispatches()
            got = scan_mod.resident_in_set_masks(entries, codes, invert=invert)
            assert _resident_dispatches() - d0 == dispatches
            for c, r, m in zip(cols, rows, got):
                np.testing.assert_array_equal(m, np.isin(r, codes, invert=invert))
                np.testing.assert_array_equal(m, c.in_set_mask(codes, invert=invert))

    def test_codecs_and_row_counts_mixed_in_one_call(self):
        """A short last row group and pages of every codec in one list:
        each comes back in its own place, an empty page without a
        dispatch."""
        rle_c, rle_e = self._pages("rle", (20, 21))
        short_c, short_e = self._pages("rle", (20,), n=37)
        dct_c, dct_e = self._pages("dct", (9,))
        dbp_c, dbp_e = self._pages("dbp", (16,))
        empty = colcache._Resident("rle", {"values": jnp.zeros(1, jnp.uint32),
                                           "lengths": jnp.zeros(1, jnp.int32)},
                                   {"n": 0}, 0)
        entries = [dbp_e[0], rle_e[0], short_e[0], empty, dct_e[0], rle_e[1]]
        d0 = _resident_dispatches()
        got = scan_mod.resident_range_masks(entries, 2, 5)
        assert _resident_dispatches() - d0 == 4   # dbp, rle x 2 rows counts, dct
        assert got[3].shape == (0,)
        for c, m in zip((rle_c[0], short_c[0], dct_c[0], rle_c[1]),
                        (got[1], got[2], got[4], got[5])):
            np.testing.assert_array_equal(m, c.range_mask(2, 5))
        dbp_rows = lw.dbp_decode(dbp_c[0]._page(), dbp_c[0].pm.dtype, dbp_c[0].pm.shape)
        np.testing.assert_array_equal(got[0], (dbp_rows >= 2) & (dbp_rows <= 5))

    @pytest.mark.parametrize("tags,min_duration_ns", [
        (True, 0), (False, 1), (True, 1)])
    def test_block_search_asks_each_predicate_of_all_row_groups_at_once(
            self, device_tier, tags, min_duration_ns):
        """A tag search over a block of many row groups, its pages
        resident: dispatches count the predicates' shape groups, not the
        pages; every page is looked up once a predicate; the hits equal
        the tier-off search's."""
        from tempo_tpu.encoding import from_version
        from tempo_tpu.encoding.common import BlockConfig

        # 256 spans a row group: the size at which durations go dbp
        db = TempoDB(DBConfig(backend="mock", block=BlockConfig(row_group_spans=256)),
                     raw_backend=MockBackend())
        traces = synth.make_traces(600, seed=41, spans_per_trace=4)
        db.write_batch("t", tr.traces_to_batch(traces).sorted_by_trace())
        meta = next(iter(db.blocklist.metas("t")))
        blk = from_version("vtpu1").open_block(meta, db.backend, db.cfg.block)
        n_rgs = len(blk.index().row_groups)
        assert n_rgs >= 8
        req = SearchRequest(tags={"service.name": _svc(traces)} if tags else {},
                            min_duration_ns=min_duration_ns, limit=0)
        warm = blk.search(req)                     # admits every page it asks for
        d0, l0 = _resident_dispatches(), device_tier.hits + device_tier.misses
        hot = blk.search(req)
        dispatched = _resident_dispatches() - d0
        lookups = device_tier.hits + device_tier.misses - l0
        predicates = int(tags) + int(bool(min_duration_ns))
        assert 1 <= lookups <= predicates * n_rgs
        assert 1 <= dispatched < lookups           # a group holds several pages
        colcache._shared_device = None
        cold = from_version("vtpu1").open_block(meta, db.backend, db.cfg.block).search(req)
        assert _ids(warm) == _ids(hot) == _ids(cold) and _ids(cold)

    def test_a_tier_answered_duration_leaves_the_hits_the_cached_column(
            self, device_tier):
        """The host path's duration compare reads the decoded column and
        hands it to the hit collection; a mask the tier answered leaves
        none behind. Where the host cache holds the decoded column the
        hits index it: as many page gathers as with the tier off, and
        the same hits."""
        from tempo_tpu.encoding import from_version
        from tempo_tpu.encoding.common import BlockConfig

        db = TempoDB(DBConfig(backend="mock", block=BlockConfig(row_group_spans=256)),
                     raw_backend=MockBackend())
        traces = synth.make_traces(600, seed=43, spans_per_trace=4)
        db.write_batch("t", tr.traces_to_batch(traces).sorted_by_trace())
        meta = next(iter(db.blocklist.metas("t")))
        blk = from_version("vtpu1").open_block(meta, db.backend, db.cfg.block)
        req = SearchRequest(tags={"service.name": _svc(traces)}, min_duration_ns=1,
                            start_seconds=1, end_seconds=1 << 33, limit=0)
        from tempo_tpu.encoding.vtpu.block import gathers_total

        def dbp_gathers():
            return gathers_total.total(codec="dbp")

        colcache._shared_device = None
        cold = blk.search(req)          # decodes duration_nano into the host cache
        g0 = dbp_gathers()
        cold = blk.search(req)
        off = dbp_gathers() - g0
        colcache._shared_device = device_tier
        blk.search(req)                 # admits
        g0, d0 = dbp_gathers(), _resident_dispatches()
        hot = blk.search(req)
        assert _resident_dispatches() > d0
        assert dbp_gathers() - g0 == off > 0
        assert _ids(hot) == _ids(cold) and _ids(cold)
