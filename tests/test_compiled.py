"""Compiled-query tier: shape-keyed fused executables vs the interpreter.

The tier's whole contract is "faster, never different", so every test
here is some flavor of bit-identity plus an economy claim:

1. CORRECTNESS — the fused device program (filter -> time-bin ->
   bincount in ONE launch) produces byte-identical series to the
   interpreter for every lightweight codec (rle/dct/dbp) and every
   predicate mode (eq/ne/regex/negated-regex/duration ranges), with
   TEMPO_TPU_COMPILED=0 as the bit-identical kill switch; legacy
   entropy-tier blocks fall back inside the executor, same answer.
2. INVARIANCE — partitioning the block set across 1/2/4 shards and
   psum-style merging the partial wires changes nothing (integer adds
   commute, same argument as the mesh metrics reduction).
3. ECONOMY — a literal or time-window swap re-enters the SAME traced
   executable (compiles counter flat, shape-cache hit), and N
   concurrent same-shape queries cost the dispatches of one (the
   batched lanes ride one stacked page set).
4. SAFETY — the executable cache sheds under governor pressure
   (programs first: they hold device memory), honors the LRU cap, and
   check_config warns about the multitenant-uncapped and
   HBM-oversubscribed footguns.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from tempo_tpu import compiled
from tempo_tpu.backend import MockBackend
from tempo_tpu.compiled import cache as cache_mod
from tempo_tpu.config import check_config, parse_config
from tempo_tpu.db import DBConfig, TempoDB
from tempo_tpu.metrics_engine import (
    HostAccumulator,
    compile_metrics_plan,
    evaluate_block,
    merge_wire,
    new_wire,
)
from tempo_tpu.model import synth
from tempo_tpu.model import trace as tr
from tempo_tpu.modules.querier import Querier
from tempo_tpu.util import devicetiming

BASE_S = 1_700_000_000


class _env:
    def __init__(self, **kv):
        self.kv = kv
        self.old = {}

    def __enter__(self):
        for k, v in self.kv.items():
            self.old[k] = os.environ.get(k)
            os.environ[k] = v

    def __exit__(self, *a):
        for k, v in self.old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _plan(q, start=BASE_S, end=BASE_S + 60, step=10, **kw):
    return compile_metrics_plan(q, start, end, step, **kw)


def _mk_db(n_blocks=4, seed=100, lightweight=True):
    """A block set that exercises ALL THREE lightweight codecs on the
    compiled path: trace-shaped blocks give dct service + dbp duration;
    one sorted-service block gives rle (long runs survive the
    trace-order sort)."""
    env = {} if lightweight else {"TEMPO_TPU_LIGHTWEIGHT": "0"}
    with _env(**env):
        db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
        for i in range(n_blocks - 1):
            ts = synth.make_traces(40, seed=seed + i, spans_per_trace=4)
            db.write_batch("t", tr.traces_to_batch(ts).sorted_by_trace())
        b = synth.make_batch(400, 8, seed=seed + 50)
        b.cols["service"] = np.sort(b.cols["service"].copy())
        db.write_batch("t", b.sorted_by_trace())
    return db, list(db.blocklist.metas("t"))


def _interp_wire(db, metas, plan):
    """The interpreter reference: per-block evaluate_block folded into
    one accumulator, exactly the querier host path's arithmetic."""
    acc = HostAccumulator(plan)
    for m in metas:
        blk = db.encoding_for(m.version).open_block(m, db.backend,
                                                    db.cfg.block)
        acc.stats["inspectedBlocks"] += 1
        evaluate_block(plan, blk, acc)
        acc.stats["inspectedBytes"] += blk.bytes_read
        acc.stats["decodedBytes"] += getattr(blk, "decoded_bytes", 0)
    return acc.to_wire()


@pytest.fixture(scope="module")
def corpus():
    return _mk_db()


@pytest.fixture
def fresh_cache():
    """A private ShapeCache installed as the process cache so per-test
    hit/miss/compile accounting starts from zero."""
    old = cache_mod._shared
    cache_mod._shared = cache_mod.ShapeCache()
    try:
        yield cache_mod._shared
    finally:
        cache_mod._shared = old


QUERIES = [
    "{} | rate()",
    "{} | count_over_time()",
    "{ resource.service.name = `cart` } | rate()",
    "{ resource.service.name != `cart` } | rate()",
    "{ resource.service.name =~ `c.*` } | rate()",
    "{ resource.service.name !~ `c.*` } | rate()",
    "{ resource.service.name = `no-such-svc` } | rate()",
    "{ duration > 1ms } | rate()",
    "{ duration >= 1000000 } | rate()",
    "{ duration < 2ms } | count_over_time()",
    "{ duration <= 5000000 } | rate()",
    "{ resource.service.name = `cart` && duration > 100us } | rate()",
]


# ---------------------------------------------------------------------------
# 1. bit-identity: fused program == interpreter, per codec and predicate
# ---------------------------------------------------------------------------


class TestBitIdentity:
    def test_corpus_spans_all_three_codecs(self, corpus):
        """The claim 'bit-identical across rle/dct/dbp' is only as good
        as the corpus — assert all three codecs actually bind on the
        predicate columns the queries touch."""
        db, metas = corpus
        seen = set()
        for m in metas:
            blk = db.encoding_for(m.version).open_block(
                m, db.backend, db.cfg.block)
            for rg in blk.index().row_groups:
                for col in ("service", "duration_nano"):
                    enc = blk.encoded_column(rg, col)
                    payload = enc.resident_payload() if enc else None
                    if payload is not None:
                        seen.add(payload[0])
        assert {"rle", "dct", "dbp"} <= seen

    @pytest.mark.parametrize("q", QUERIES)
    def test_compiled_matches_interpreter(self, corpus, fresh_cache, q):
        db, metas = corpus
        plan = _plan(q)
        ref = _interp_wire(db, metas, plan)
        got = compiled.try_query_range(db, "t", plan, metas)
        assert got is not None, f"expected {q!r} to lower"
        assert got.pop("compiledShape") in ("hit", "miss")
        assert got["series"] == ref["series"]
        # row-group accounting agrees too (bytes differ by design: the
        # compiled path reads encoded pages, never decoded columns)
        for k in ("inspectedBlocks", "inspectedSpans", "prunedRowGroups"):
            assert got["stats"][k] == ref["stats"][k], k
        assert ref["series"] or "no-such" in q or "!~" not in q

    def test_kill_switch_is_bit_identical_end_to_end(self, corpus,
                                                     fresh_cache):
        """TEMPO_TPU_COMPILED=0 through the querier job path: same
        series, only the compiledShape verdict differs."""
        db, metas = corpus
        qr = Querier(db)
        ids = [m.block_id for m in metas]
        q = "{ resource.service.name = `cart` } | rate()"
        on = qr.query_range_blocks("t", ids, q, BASE_S, BASE_S + 60, 10)
        with _env(TEMPO_TPU_COMPILED="0"):
            off = qr.query_range_blocks("t", ids, q, BASE_S, BASE_S + 60, 10)
        assert on.pop("compiledShape") in ("hit", "miss")
        assert off.pop("compiledShape") == "fallback"
        assert on["series"] == off["series"]
        assert on["series"]  # the corpus matches

    def test_legacy_entropy_blocks_fall_back_bit_identically(self,
                                                             fresh_cache):
        """Blocks written entirely on the entropy tier bind zero units:
        the executor's per-row-group interpreter fallback answers, with
        ZERO fused dispatches and the same series."""
        db, metas = _mk_db(n_blocks=2, seed=300, lightweight=False)
        plan = _plan("{ resource.service.name = `cart` } | rate()")
        ref = _interp_wire(db, metas, plan)
        d0 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        got = compiled.try_query_range(db, "t", plan, metas)
        d1 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        assert got is not None
        assert got.pop("compiledShape") in ("hit", "miss")
        assert got["series"] == ref["series"]
        assert d1 == d0  # nothing bound, nothing launched


    def test_absorbed_errors_are_counted(self, corpus, fresh_cache,
                                         monkeypatch):
        """A fused program that raises still answers None (interpreter
        fallback, unchanged) — and tempo_tpu_compiled_errors_total says
        it happened, on the single and the batched entry."""
        from tempo_tpu.compiled import executor

        db, metas = corpus
        plan = _plan("{ resource.service.name = `cart` } | rate()")

        def boom(*a, **k):
            raise RuntimeError("kernel does not compile here")

        monkeypatch.setattr(executor, "run_query_range", boom)
        e0 = cache_mod.compiled_errors_total.total()
        assert compiled.try_query_range(db, "t", plan, metas) is None
        assert compiled.try_query_range_many(db, "t", [plan], metas) == [None]
        assert cache_mod.compiled_errors_total.total() == e0 + 2


# ---------------------------------------------------------------------------
# 2. shard invariance: partition + merge == one shot
# ---------------------------------------------------------------------------


class TestShardInvariance:
    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_partition_merge_invariance(self, corpus, fresh_cache,
                                        n_shards):
        db, metas = corpus
        plan = _plan("{ duration > 100us } | rate()")
        whole = compiled.try_query_range(db, "t", plan, metas)
        assert whole is not None
        one_shot = new_wire()
        merge_wire(one_shot, whole, plan)
        merged = new_wire()
        for s in range(n_shards):
            shard = metas[s::n_shards]
            w = compiled.try_query_range(db, "t", plan, shard)
            assert w is not None
            merge_wire(merged, w, plan)
        assert merged["series"] == one_shot["series"]
        assert whole["series"]


# ---------------------------------------------------------------------------
# 3. economy: literal swaps retrace nothing; N queries, one launch
# ---------------------------------------------------------------------------


class TestExecutableReuse:
    def test_literal_and_window_swap_hit_without_retrace(self, corpus,
                                                         fresh_cache):
        db, metas = corpus
        first = compiled.try_query_range(
            db, "t",
            _plan("{ resource.service.name = `cart` } | rate()"), metas)
        assert first["compiledShape"] == "miss"
        s1 = fresh_cache.stats()
        assert s1["compiles"] >= 1

        # literal swap AND a shifted dashboard window: same shape, same
        # traced executable — zero new compiles is the whole tier
        again = compiled.try_query_range(
            db, "t",
            _plan("{ resource.service.name = `frontend` } | rate()",
                  start=BASE_S + 10, end=BASE_S + 70), metas)
        assert again["compiledShape"] == "hit"
        s2 = fresh_cache.stats()
        assert s2["compiles"] == s1["compiles"]
        assert s2["hits"] == s1["hits"] + 1
        assert s2["shapes"] == s1["shapes"] == 1

    def test_unlowerable_shape_is_remembered(self, corpus, fresh_cache):
        db, metas = corpus
        q = "{ span.http.status_code >= 500 } | rate()"  # int attr: no
        assert compiled.try_query_range(db, "t", _plan(q), metas) is None
        assert compiled.try_query_range(db, "t", _plan(q), metas) is None
        s = fresh_cache.stats()
        assert s["misses"] == 1 and s["hits"] == 1  # no AST re-walk

    def test_batched_queries_share_one_launch(self, corpus, fresh_cache):
        """3 same-shape lanes cost exactly the dispatches of 1 — the
        acceptance bar's O(1) dispatches per query."""
        db, metas = corpus
        single = _plan("{ resource.service.name = `cart` } | rate()")
        d0 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        ref = compiled.try_query_range(db, "t", single, metas)
        d1 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        per_query = d1 - d0
        assert 1 <= per_query <= 2  # one per codec group (rle + dct)

        plans = [_plan("{ resource.service.name = `%s` } | rate()" % s)
                 for s in ("cart", "checkout", "frontend")]
        wires = compiled.try_query_range_many(db, "t", plans, metas)
        d2 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        assert d2 - d1 == per_query  # 3 lanes, one stacked launch
        assert all(w is not None for w in wires)
        assert wires[0]["series"] == ref["series"]
        for p, w in zip(plans, wires):
            assert w["series"] == _interp_wire(db, metas, p)["series"]

    @pytest.mark.parametrize("cap,launches", [(2, 3), (4, 2), (16, 1)])
    def test_a_job_of_more_units_than_a_launch_takes_is_cut(
            self, fresh_cache, monkeypatch, cap, launches):
        """The program is traced for its unit count and compiles the
        longer the more units it holds (executor.MAX_UNITS): a codec
        group goes in launches of at most that many, and integer adds
        make the counts the interpreter's whatever the cut."""
        from tempo_tpu.compiled import executor

        db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
        for i in range(5):  # five one-row-group blocks of one codec group
            b = synth.make_batch(64, 8, seed=300 + i)
            b.cols["service"] = np.sort(b.cols["service"].copy())
            db.write_batch("t", b.sorted_by_trace())
        metas = list(db.blocklist.metas("t"))
        plan = _plan("{ resource.service.name = `cart` && duration > 100us } | rate()")
        monkeypatch.setattr(executor, "MAX_UNITS", cap)
        d0 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        wire = compiled.try_query_range(db, "t", plan, metas)
        d1 = devicetiming.dispatch_total.total(kernel="compiled_metrics")
        assert wire is not None and wire["series"]
        assert wire["series"] == _interp_wire(db, metas, plan)["series"]
        assert d1 - d0 == launches

    def test_batched_multi_matches_sequential(self, corpus, fresh_cache):
        db, metas = corpus
        qr = Querier(db)
        ids = [m.block_id for m in metas]
        qs = ["{ resource.service.name = `cart` } | rate()",
              "{ duration > 1ms } | rate()",
              "{ span.http.status_code >= 500 } | rate()"]  # mixed lanes
        many = qr.query_range_blocks_multi("t", ids, qs, BASE_S,
                                           BASE_S + 60, 10)
        for q, w in zip(qs, many):
            one = qr.query_range_blocks("t", ids, q, BASE_S, BASE_S + 60, 10)
            assert w["series"] == one["series"]


# ---------------------------------------------------------------------------
# 4. safety: governor sheds, LRU cap, config footguns
# ---------------------------------------------------------------------------


class _Gov:
    def __init__(self, lvl=0):
        self.lvl = lvl

    def level(self):
        return self.lvl


class TestGovernorShed:
    def _loaded(self, **kw):
        gov = _Gov()
        c = cache_mod.ShapeCache(governor=gov, **kw)
        for i in range(8):
            c.store(f"shape-{i}", lowerable=True)
        c.program(("sig", 0), lambda sig: object())
        c.program(("sig", 1), lambda sig: object())
        return gov, c

    def test_pressure_drops_programs_first(self):
        gov, c = self._loaded()
        gov.lvl = 1
        n = c.shed()
        s = c.stats()
        assert s["programs"] == 0  # device executables go at ANY pressure
        assert s["shapes"] == 2    # quarter of 8 survive
        assert n == s["evictions"] == 8
        # recovery: the next program() call re-jits and counts a compile
        c.program(("sig", 0), lambda sig: object())
        assert c.stats()["compiles"] == 3

    def test_critical_clears_everything(self):
        gov, c = self._loaded()
        gov.lvl = 2
        c.shed()
        s = c.stats()
        assert s["programs"] == 0 and s["shapes"] == 0

    def test_respect_governor_false_detaches(self):
        gov, c = self._loaded(respect_governor=False)
        gov.lvl = 2
        assert c.shed() == 0
        s = c.stats()
        assert s["programs"] == 2 and s["shapes"] == 8

    def test_lru_cap_evicts_oldest_shape(self):
        c = cache_mod.ShapeCache(max_shapes=2, governor=_Gov())
        for i in range(3):
            c.store(f"shape-{i}", lowerable=True)
        entry, hit = c.lookup("shape-0")
        assert entry is None and not hit  # oldest fell off
        assert c.lookup("shape-2")[1]
        assert c.stats()["evictions"] == 1


class TestConfigWarnings:
    def test_multitenant_uncapped_shapes_warns(self):
        cfg = parse_config("multitenancy_enabled: true\n")
        assert any("compiled.max_shapes" in w for w in check_config(cfg))
        cfg = parse_config(
            "multitenancy_enabled: true\ncompiled:\n  max_shapes: 512\n")
        assert not any("compiled.max_shapes" in w for w in check_config(cfg))

    def test_disabled_tier_suppresses_warning(self):
        cfg = parse_config(
            "multitenancy_enabled: true\ncompiled:\n  enabled: false\n")
        assert not any("compiled" in w for w in check_config(cfg))

    def test_config_section_round_trips(self):
        cfg = parse_config(
            "compiled:\n  enabled: true\n  max_shapes: 64\n"
            "  respect_governor: false\n")
        assert cfg.app.compiled.max_shapes == 64
        assert cfg.app.compiled.respect_governor is False
