"""`allinone-readtiers` (benchmark/configs) at a small size: one `App`
with the result cache and the device-resident page tier switched on, its
store written through the ingester's flush path (two blocks, the second
re-sending a quarter of the first), asked over HTTP as the cell
`allinone.read-repeat` asks: the five fixed dashboard queries and the ad
hoc kinds, cold, again, and with new literals. Every answer is compared
with a plain numpy reference over the pushed columns and, bit for bit,
with the same server's answer with both tiers off; and the tiers'
counters have to move, so the test cannot pass with a tier silently off.
"""

import json
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from tempo_tpu import resultcache as rc_mod
from tempo_tpu.api.server import TempoServer
from tempo_tpu.app import App, AppConfig
from tempo_tpu.db import DBConfig
from tempo_tpu.encoding.common import BlockConfig
from tempo_tpu.encoding.vtpu import colcache
from tempo_tpu.model import synth
from tempo_tpu.model.columnar import SpanBatch
from tempo_tpu.resultcache import ResultCacheConfig
from tempo_tpu.util import devicetiming

TRACES, SPANS, RESENT = 64, 16, 16  # 25 % of a block re-sent by the next
ROW_GROUP = 256  # four row groups a block
STEP_S = 60
BASE_S = (int(time.time()) // STEP_S) * STEP_S - 600
RANGE = {"start": BASE_S - STEP_S, "end": BASE_S + 2 * STEP_S, "step": STEP_S}
QUANTILES = (0.5, 0.99)
LIMIT = 1_000_000
RESIDENT = ("resident_rle_scan", "resident_dct_scan", "resident_dbp_scan")
# the cell's deck (benchmark/traffic/repeat.json): what a dashboard re-asks
FIXED = [("rate_by_name", "cart", 0), ("rate_total", "cart", 0), ("quantiles", "cart", 0),
         ("search_tags", "cart", 995_000), ("traceql_filter", 500, 990_000)]
# ... and what an engineer asks once; the second literal of each is the "new" one
AD_HOC = [("search_tags", "checkout", 991_000), ("search_tags", "checkout", 300_000),
          ("traceql_filter", 404, 985_000), ("traceql_filter", 200, 500_000),
          ("rate_by_name", "frontend", 250_000), ("rate_by_name", "frontend", 400_000),
          ("rate_total", "frontend", 125_000), ("rate_by_service", "payment", 375_000),
          ("quantiles", "payment", 100_000), ("quantiles", "payment", 480_000)]


def make_block(r: int, prev):
    fresh = synth.make_batch(TRACES - (RESENT if r else 0), SPANS, seed=350 + r,
                             base_time_ns=BASE_S * 10**9)
    if prev is None:
        return fresh, fresh
    return SpanBatch.concat([prev.select(np.arange(RESENT * SPANS)), fresh]), fresh


class Reference:
    """Plain numpy over the pushed columns. Counts add across blocks (a
    re-sent span is counted in each block that holds it); a trace is a
    hit once, whatever the number of blocks that hold it."""

    def __init__(self, blocks: list):
        d = blocks[0].dictionary
        self.code = {d[int(c)]: int(c) for c in np.unique(blocks[0].cols["service"])}
        self.names = {int(c): d[int(c)] for b in blocks for c in np.unique(b.cols["name"])}
        cat = lambda k: np.concatenate([b.cols[k] for b in blocks])  # noqa: E731
        self.service, self.name, self.trace = cat("service"), cat("name"), cat("trace_id")
        self.status, self.span = cat("http_status"), cat("span_id")
        self.dur = cat("duration_nano").astype(np.int64)

    def _hex(self, rows) -> frozenset:
        return frozenset(r.astype(">u4").tobytes().hex() for r in self.trace[rows])

    def answer(self, op: str, what, us: int):
        ns = us * 1000
        if op == "search_tags":
            return self._hex((self.service == self.code[what]) & (self.dur >= ns))
        if op == "traceql_filter":
            return self._hex((self.status == what) & (self.dur > ns))
        m = (self.service == self.code[what]) & (self.dur > ns)
        if op == "rate_by_name":
            return {nm: int((m & (self.name == c)).sum()) for c, nm in self.names.items()
                    if (m & (self.name == c)).any()}
        if op == "quantiles":
            return {q: float(np.quantile(self.dur[m], q, method="lower")) / 1e9
                    for q in QUANTILES}
        return {what if op == "rate_by_service" else "": int(m.sum())}

    def spans_of(self, trace_hex: str) -> int:
        """Distinct span ids of the trace (a re-sent trace is deduplicated)."""
        tid = np.frombuffer(bytes.fromhex(trace_hex), ">u4").astype(np.uint32)
        rows = (self.trace == tid).all(axis=1)
        return len({r.tobytes() for r in self.span[rows]})


def url_of(op: str, what, us: int) -> str:
    """The request as benchmark/traffic.py spells it."""
    if op == "find":
        return f"/api/traces/{what}"
    if op == "search_tags":
        return "/api/search?" + urllib.parse.urlencode(
            {"tags": f"service.name={what}", "minDuration": f"{us}us", "limit": LIMIT})
    if op == "traceql_filter":
        q = f"{{ span.http.status_code = {what} && duration > {us}us }}"
        return "/api/search?" + urllib.parse.urlencode({"q": q, "limit": LIMIT})
    sel = f'{{ resource.service.name = "{what}" && duration > {us}us }}'
    q = {"rate_by_name": f"{sel} | rate() by (name)",
         "rate_total": f"{sel} | rate()",
         "rate_by_service": f"{sel} | rate() by (resource.service.name)",
         "quantiles": f"{sel} | quantile_over_time(duration, "
                      f"{', '.join(map(str, QUANTILES))})"}[op]
    return "/api/metrics/query_range?" + urllib.parse.urlencode({"q": q, **RANGE})


class Deployment:
    def __init__(self, root):
        block = BlockConfig(row_group_spans=ROW_GROUP, min_device_bucket=ROW_GROUP)
        self.app = App(AppConfig(
            db=DBConfig(backend="local", backend_path=str(root / "blocks"),
                        wal_path=str(root / "wal"), block=block,
                        result_cache=ResultCacheConfig(enabled=True)),
            # refresh_s 0: the admission set follows the ledger at every lookup, so
            # a page is admitted the first time it is asked for after its second ship
            device_tier=colcache.DeviceTierConfig(budget_mb=64, refresh_s=0.0),
            generator_enabled=False))
        self.tier = colcache.shared_device_tier()
        assert self.tier is not None and self.app.db.result_cache.enabled()
        self.server = TempoServer(self.app).start()
        self.blocks, self.fresh = [], None

    def flush_block(self) -> None:
        """One more block through the ingester's flush path."""
        batch, self.fresh = make_block(len(self.blocks), self.fresh)
        self.blocks.append(batch)
        self.app.push_spans(batch)
        self.app.sweep_all(immediate=True)
        self.app.db.poll_now()

    def get(self, path: str):
        req = urllib.request.Request(self.server.url + path)
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def ask(self, op: str, what, us: int):
        """(what the comparison needs, the answer's content as served)."""
        doc = self.get(url_of(op, what, us))
        if op == "find":
            spans = {s["spanId"] for b in doc["resourceSpans"]
                     for ss in b["scopeSpans"] for s in ss["spans"]}
            return len(spans), sorted(spans)
        if op in ("search_tags", "traceql_filter"):
            ids = [t["traceID"] for t in doc["traces"]]
            assert len(ids) == len(set(ids)), "a trace twice in one answer"
            return frozenset(ids), sorted(doc["traces"], key=lambda t: t["traceID"])
        result = doc["data"]["result"]
        if op == "quantiles":
            out = {}
            for s in result:
                out.setdefault(float(s["metric"]["p"]), []).extend(
                    float(v[1]) for v in s["values"] if float(v[1]) > 0)
            return out, result
        label = {"rate_by_name": "name", "rate_by_service": "resource.service.name"}.get(op)
        counts = {}
        for s in result:  # rate x step, summed over the steps: the spans of the series
            total = round(sum(float(v[1]) for v in s["values"]) * STEP_S)
            if total:
                counts[s["metric"].get(label, "") if label else ""] = total
        return counts, result

    def close(self) -> None:
        self.server.stop()
        self.app.shutdown()


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    old = colcache._shared_device
    dep = Deployment(tmp_path_factory.mktemp("readtiers"))
    try:
        dep.flush_block()
        dep.flush_block()
        yield dep
    finally:
        dep.close()
        colcache._shared_device = old  # the tier is the process's: other tests see none


def check(op: str, got, want) -> None:
    if op == "quantiles":
        for q, true in want.items():
            assert len(got[q]) == 1, "one step holds every span"
            assert abs(got[q][0] - true) / true <= 0.125  # the sketch's documented error
    else:
        assert got == want and len(got) > 0


def tiers_moved() -> dict:
    resident = sum(v for labels, v in devicetiming.dispatch_total.series()
                   if labels.get("kernel") in RESIDENT)
    return {"hits": sum(rc_mod.rc_hits.value(kind=k) + rc_mod.rc_negative.value(kind=k)
                        for k in ("search", "metrics")),
            "stores": sum(rc_mod.rc_stores.value(kind=k) for k in ("search", "metrics")),
            "resident": resident}


def tiers_off(monkeypatch) -> None:
    """The same server with both tiers off: the result cache's kill switch,
    and no process tier for a page to be resident in."""
    monkeypatch.setenv("TEMPO_TPU_RESULT_CACHE", "0")
    monkeypatch.setattr(colcache, "_shared_device", None)


@pytest.mark.parametrize("op,what,us", FIXED + AD_HOC)
def test_cold_again_and_tiers_off_agree_with_the_reference(deployment, monkeypatch,
                                                           op, what, us):
    """Asked cold (a miss and a store, unless an earlier case left the
    partial), again (a hit on every block) and once more (pages that
    shipped twice are resident now): the same content each time, equal
    to the numpy reference, and to the tiers-off answer bit for bit."""
    ref = Reference(deployment.blocks)
    want = ref.answer(op, what, us)
    before = tiers_moved()
    served = []
    for _ in range(3):
        got, content = deployment.ask(op, what, us)
        check(op, got, want)
        served.append(content)
    after = tiers_moved()
    if op == "traceql_filter":  # one job over every block: no per-block partial to keep
        assert after["hits"] == before["hits"]
    else:
        assert after["hits"] >= before["hits"] + 2 * len(deployment.blocks)
    with monkeypatch.context() as m:
        tiers_off(m)
        frozen = tiers_moved()
        got, content = deployment.ask(op, what, us)
        check(op, got, want)
        assert tiers_moved() == frozen, "a tier answered while it was off"
    assert served[0] == served[1] == served[2] == content


def test_resident_scans_were_dispatched_and_counted(deployment):
    """New literals over columns whose pages have shipped before: nothing
    in the result cache answers, so the blocks are scanned, and the scan's
    predicates run over resident pages. The tier's own numbers, the
    dispatch counters and /metrics (the names the cell's metrics read)
    agree that they did."""
    for us in (111_000, 222_000, 333_000):  # every predicate page ships at least twice
        deployment.ask("search_tags", "cart", us)
    before, stats0 = tiers_moved(), deployment.tier.stats()
    ref = Reference(deployment.blocks)
    for op, what, us in [("search_tags", "cart", 444_000), ("traceql_filter", 500, 555_000),
                         ("rate_by_name", "cart", 666_000)]:
        check(op, deployment.ask(op, what, us)[0], ref.answer(op, what, us))
    after, stats = tiers_moved(), deployment.tier.stats()
    assert after["resident"] > before["resident"], "no resident scan was dispatched"
    assert after["stores"] > before["stores"] and after["hits"] == before["hits"]
    assert stats["hits"] > stats0["hits"] and stats["avoided_bytes"] > stats0["avoided_bytes"]
    assert stats["admissions"] > 0 and stats["entries"] > 0
    with urllib.request.urlopen(deployment.server.url + "/metrics", timeout=30) as r:
        text = r.read().decode()
    series = dict(ln.rsplit(" ", 1) for ln in text.splitlines() if ln and ln[0] != "#")
    assert float(series['tempo_tpu_colcache_admissions{tier="device"}']) == stats["admissions"]
    assert float(series['tempo_tpu_colcache_hits{tier="device"}']) >= stats["hits"]
    assert sum(float(v) for k, v in series.items()
               if k.startswith("tempo_tpu_device_transfer_bytes_avoided_total")) > 0


def test_find_by_id_is_answered_beside_the_tiers(deployment):
    ref = Reference(deployment.blocks)
    resent = deployment.blocks[1].cols["trace_id"][0]  # held by both blocks
    for tid in (resent, deployment.blocks[0].cols["trace_id"][-1]):
        h = tid.astype(">u4").tobytes().hex()
        n, _ = deployment.ask("find", h, 0)
        assert n == ref.spans_of(h) == SPANS


@pytest.mark.parametrize("op,what,us", [FIXED[0], FIXED[2], ("search_tags", "cart", 600_000)])
def test_a_repeat_across_a_new_block_hits_the_old_and_computes_the_new(
        tmp_path, monkeypatch, op, what, us):
    """A dashboard query asked, a block flushed, the query asked again:
    the blocks it saw before hit, the new one is computed and stored, and
    the answer is the reference's over all three (and the tiers-off one)."""
    old = colcache._shared_device
    dep = Deployment(tmp_path)
    try:
        dep.flush_block()
        dep.flush_block()
        check(op, dep.ask(op, what, us)[0], Reference(dep.blocks).answer(op, what, us))
        dep.flush_block()
        before = tiers_moved()
        want = Reference(dep.blocks).answer(op, what, us)
        got, content = dep.ask(op, what, us)
        after = tiers_moved()
        check(op, got, want)
        assert want != Reference(dep.blocks[:2]).answer(op, what, us), "the new block adds nothing"
        assert after["hits"] - before["hits"] == 2, "the two old blocks hit"
        assert after["stores"] - before["stores"] == 1, "the new block is computed and stored"
        with monkeypatch.context() as m:
            tiers_off(m)
            assert dep.ask(op, what, us)[1] == content
    finally:
        dep.close()
        colcache._shared_device = old
