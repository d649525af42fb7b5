"""The columnar metrics-generator against the span-by-span loop it
replaced.

The oracle below is the registry, the service-graph pairing loop and the
span-metrics series loop as they were before the processors became
columnar, copied whole. Both run the same seeded streams of pushes under
one fake clock; every published series (order included), exemplar,
refusal count, pending half, eviction and expiry count and the sketches
must be equal. `_sum` values may differ by float reassociation only:
sums added per push in bulk round differently from sums added one span
at a time.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from tempo_tpu.encoding.vtpu import format as fmt
from tempo_tpu.graph import edge_hash_limbs, span_failed
from tempo_tpu.model.columnar import ATTR_COLUMNS, Dictionary, SpanBatch, _empty_cols
from tempo_tpu.model.trace import KIND_CLIENT, KIND_INTERNAL, KIND_SERVER
from tempo_tpu.modules.generator import Generator, TenantGeneratorInstance
from tempo_tpu.modules.generator.registry import Exemplar, ManagedRegistry, Sample
from tempo_tpu.modules.generator.servicegraphs import (
    DEFAULT_BOUNDS as SG_BOUNDS,
    EXPIRED_TOTAL,
    REQ_FAILED,
    REQ_SECONDS,
    REQ_TOTAL,
    ServiceGraphsProcessor,
)
from tempo_tpu.modules.generator.spanmetrics import (
    CALLS,
    DEFAULT_BOUNDS as SM_BOUNDS,
    KIND_NAMES,
    LATENCY,
    SIZE,
    STATUS_NAMES,
    SpanMetricsProcessor,
)
from tempo_tpu.ops import sketch


# ---------------------------------------------------------------------------
# the oracle: the span-by-span generator, as it was
# ---------------------------------------------------------------------------


class OracleRegistry:
    def __init__(self, tenant, max_active_series=0, stale_after_s=900.0):
        self.tenant = tenant
        self.max_active_series = max_active_series
        self.stale_after_s = stale_after_s
        self.lock = threading.Lock()
        self.counters = {}
        self.histograms = {}
        self.bucket_bounds = {}
        self.series_dropped = 0

    def _can_add(self, n_current):
        if not self.max_active_series:
            return True
        return n_current < self.max_active_series

    def inc_counter(self, name, labels, delta, now=None):
        now = now or time.time()
        key = (name, labels)
        with self.lock:
            cur = self.counters.get(key)
            if cur is None:
                if not self._can_add(len(self.counters) + len(self.histograms)):
                    self.series_dropped += 1
                    return
                cur = [0.0, now]
                self.counters[key] = cur
            cur[0] += delta
            cur[1] = now

    def observe_histogram(self, name, labels, bounds, bucket_counts, total_sum,
                          total_count, now=None, exemplar=None):
        now = now or time.time()
        key = (name, labels)
        with self.lock:
            self.bucket_bounds[name] = list(bounds)
            h = self.histograms.get(key)
            if h is None:
                if not self._can_add(len(self.counters) + len(self.histograms)):
                    self.series_dropped += 1
                    return
                h = {"buckets": [0] * (len(bounds) + 1), "sum": 0.0, "count": 0,
                     "last": now, "exemplar": None}
                self.histograms[key] = h
            for i, c in enumerate(bucket_counts):
                h["buckets"][i] += int(c)
            h["sum"] += float(total_sum)
            h["count"] += int(total_count)
            h["last"] = now
            if exemplar is not None:
                h["exemplar"] = exemplar

    def remove_stale(self, now=None):
        now = now or time.time()
        removed = 0
        with self.lock:
            for d, last_getter in ((self.counters, lambda v: v[1]),
                                   (self.histograms, lambda v: v["last"])):
                for k in [k for k, v in d.items() if now - last_getter(v) > self.stale_after_s]:
                    del d[k]
                    removed += 1
        return removed

    def active_series(self):
        return len(self.counters) + len(self.histograms)

    def collect(self, now_ms=None):
        now_ms = now_ms or int(time.time() * 1000)
        out = []
        for (name, labels), (val, _) in self.counters.items():
            out.append(Sample(name, labels, val, now_ms))
        for (name, labels), h in self.histograms.items():
            bounds = self.bucket_bounds.get(name, [])
            cum = 0
            for i, b in enumerate(bounds):
                cum += h["buckets"][i]
                out.append(Sample(f"{name}_bucket", labels + (("le", str(b)),), cum, now_ms))
            cum += h["buckets"][-1]
            out.append(Sample(f"{name}_bucket", labels + (("le", "+Inf"),), cum,
                              now_ms, exemplar=h.get("exemplar")))
            out.append(Sample(f"{name}_sum", labels, h["sum"], now_ms))
            out.append(Sample(f"{name}_count", labels, h["count"], now_ms))
        return out


class OracleServiceGraphs:
    def __init__(self, registry, wait_s=10.0, max_items=10_000):
        self.registry = registry
        self.wait_s = wait_s
        self.max_items = max_items
        self.bounds = SG_BOUNDS
        self.pending_clients = {}
        self.pending_servers = {}
        self.expired = 0
        self.edges_emitted = 0
        self.hll = sketch.hll_init(sketch.HLLPlan(12))
        self.cm = sketch.cm_init(sketch.CMPlan())
        self._edge_keys = []

    def push(self, batch, now=None):
        self._push_locked(batch, now or time.time())

    def _push_locked(self, batch, now):
        c = batch.cols
        d = batch.dictionary
        kinds = c["kind"]
        for row in np.flatnonzero((kinds == KIND_CLIENT) | (kinds == KIND_SERVER)):
            tid = c["trace_id"][row].tobytes()
            svc = d[int(c["service"][row])]
            if kinds[row] == KIND_CLIENT:
                key = (tid, c["span_id"][row].tobytes())
                srv = self.pending_servers.pop(key, None)
                if srv is not None:
                    self._emit(svc, srv[0], srv[1], srv[2])
                else:
                    self._put(self.pending_clients, key, (svc, now))
            else:
                key = (tid, c["parent_span_id"][row].tobytes())
                dur_s = float(c["duration_nano"][row]) / 1e9
                failed = span_failed(int(c["status_code"][row]))
                cli = self.pending_clients.pop(key, None)
                if cli is not None:
                    self._emit(cli[0], svc, dur_s, failed)
                else:
                    self._put(self.pending_servers, key, (svc, dur_s, failed, now))
        self.expire(now)
        self._flush_sketches()

    def _put(self, store, key, value):
        if len(store) >= self.max_items:
            store.pop(next(iter(store)), None)
            self._count_unpaired(store, "evicted")
        store[key] = value

    def _count_unpaired(self, store, reason):
        self.expired += 1
        half = "client" if store is self.pending_clients else "server"
        self.registry.inc_counter(EXPIRED_TOTAL, (("store", half), ("reason", reason)), 1.0)

    def _emit(self, client_svc, server_svc, dur_s, failed):
        if client_svc == server_svc:
            return
        labels = (("client", client_svc), ("server", server_svc))
        self.registry.inc_counter(REQ_TOTAL, labels, 1.0)
        if failed:
            self.registry.inc_counter(REQ_FAILED, labels, 1.0)
        bidx = int(np.searchsorted(np.asarray(self.bounds), dur_s, side="left"))
        counts = [0] * (len(self.bounds) + 1)
        counts[bidx] = 1
        self.registry.observe_histogram(REQ_SECONDS, labels, self.bounds, counts, dur_s, 1)
        self.edges_emitted += 1
        self._edge_keys.append(edge_hash_limbs(client_svc, server_svc))

    def _flush_sketches(self):
        if not self._edge_keys:
            return
        keys = jnp.asarray(np.stack(self._edge_keys))
        self.hll = sketch.hll_update(self.hll, keys, sketch.HLLPlan(12))
        self.cm = sketch.cm_update(self.cm, keys, sketch.CMPlan())
        self._edge_keys = []

    def expire(self, now):
        for store, ts_idx in ((self.pending_clients, 1), (self.pending_servers, 3)):
            dead = [k for k, v in store.items() if now - v[ts_idx] > self.wait_s]
            for k in dead:
                del store[k]
                self._count_unpaired(store, "expired")


class OracleSpanMetrics:
    def __init__(self, registry):
        self.registry = registry
        self.bounds = SM_BOUNDS

    def push(self, batch):
        n = batch.num_spans
        if n == 0:
            return
        c = batch.cols
        d = batch.dictionary
        keys = np.stack(
            [c["service"].astype(np.uint64), c["name"].astype(np.uint64),
             c["kind"].astype(np.uint64), c["status_code"].astype(np.uint64)], axis=1
        )
        uniq, first_row, inverse = np.unique(keys, axis=0, return_index=True, return_inverse=True)
        inverse = inverse.ravel()
        counts = np.bincount(inverse, minlength=len(uniq))
        secs = c["duration_nano"].astype(np.float64) / 1e9
        sums = np.bincount(inverse, weights=secs, minlength=len(uniq))
        sizes = np.bincount(inverse, weights=np.full(n, batch.nbytes() / max(n, 1)),
                            minlength=len(uniq))
        bidx = np.searchsorted(np.asarray(self.bounds), secs, side="left")
        flat = inverse * (len(self.bounds) + 1) + bidx
        bucket_counts = np.bincount(flat, minlength=len(uniq) * (len(self.bounds) + 1)).reshape(
            len(uniq), len(self.bounds) + 1)
        for g in range(len(uniq)):
            svc, name_c, kind, status = uniq[g]
            labels = (
                ("service", d[int(svc)]),
                ("span_name", d[int(name_c)]),
                ("span_kind", KIND_NAMES.get(int(kind), str(int(kind)))),
                ("status_code", STATUS_NAMES.get(int(status), str(int(status)))),
            )
            self.registry.inc_counter(CALLS, labels, float(counts[g]))
            self.registry.inc_counter(SIZE, labels, float(sizes[g]))
            r = int(first_row[g])
            ex = Exemplar(trace_id=fmt.id_to_hex(c["trace_id"][r]), value=float(secs[r]),
                          timestamp_ms=int(c["start_unix_nano"][r]) // 10**6)
            self.registry.observe_histogram(LATENCY, labels, self.bounds, bucket_counts[g],
                                            float(sums[g]), int(counts[g]), exemplar=ex)


# ---------------------------------------------------------------------------
# seeded streams of pushes
# ---------------------------------------------------------------------------

SERVICES = ["frontend", "checkout", "cart", "payments", "shipping", "db"]
NAMES = ["GET /", "POST /cart", "charge", "query", "render", "ship"]


def make_push(rng, n, *, traces, span_ids, services, error_rate, kinds=None, prefix=""):
    """n spans over a small pool of trace and span IDs, so halves meet
    within a push, across pushes and twice under one key. With a prefix
    the push's services are `services` names of its own."""
    d = Dictionary()
    svc_names = [f"{prefix}svc{j}" for j in range(services)] if prefix else SERVICES[:services]
    # a dictionary of its own a push, strings in a random order, so codes
    # and their order differ from push to push
    for s in rng.permutation(svc_names + NAMES):
        d.add(str(s))
    tid = rng.integers(0, traces, size=n)
    span = rng.integers(1, span_ids + 1, size=n)
    parent = rng.integers(1, span_ids + 1, size=n)
    kind = rng.choice(kinds or [KIND_SERVER, KIND_CLIENT, KIND_SERVER, KIND_CLIENT, KIND_INTERNAL],
                      size=n).astype(np.uint8)
    status = np.where(rng.random(n) < error_rate, 2, rng.integers(0, 2, size=n)).astype(np.uint8)
    cols = {
        "trace_id": np.stack([tid, tid * 7, np.zeros(n), np.ones(n)], 1).astype(np.uint32),
        "span_id": np.stack([span, np.zeros(n)], 1).astype(np.uint32),
        "parent_span_id": np.stack([parent, np.zeros(n)], 1).astype(np.uint32),
        "start_unix_nano": (1_700_000_000 * 10**9 + rng.integers(0, 10**12, size=n)).astype(np.uint64),
        "duration_nano": rng.integers(10**5, 2 * 10**10, size=n).astype(np.uint64),
        "kind": kind,
        "status_code": status,
        "name": np.array([d.get(NAMES[i]) for i in rng.integers(0, len(NAMES), size=n)], np.uint32),
        "service": np.array([d.get(svc_names[i]) for i in rng.integers(0, services, size=n)],
                            np.uint32),
        "http_status": np.zeros(n, np.uint16),
        "http_method": np.zeros(n, np.uint32),
        "http_url": np.zeros(n, np.uint32),
    }
    return SpanBatch(cols=cols, attrs=_empty_cols(ATTR_COLUMNS), dictionary=d)


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _samples(reg):
    return reg.collect(now_ms=1)


def assert_same_samples(got, want):
    assert [(s.name, s.labels) for s in got] == [(s.name, s.labels) for s in want]
    for g, w in zip(got, want):
        if g.name.endswith("_sum"):
            assert g.value == pytest.approx(w.value, rel=1e-12, abs=0), g.name
        else:
            assert (g.value, type(g.value)) == (w.value, type(w.value)), (g.name, g.labels)
        assert g.exemplar == w.exemplar, (g.name, g.labels)
        assert g.timestamp_ms == w.timestamp_ms


def pending(store, names, server):
    """A new store's waiting halves as the oracle's dict items, in order
    (a client half keeps no duration or failure)."""
    out = []
    for p in np.flatnonzero(store.alive[store.lo:store.hi]) + store.lo:
        out.append((store.key[p].tobytes(), names[int(store.svc[p])],
                    float(store.dur[p]) if server else 0.0,
                    bool(store.failed[p]) and server, float(store.ts[p])))
    return out


def oracle_pending(store, server):
    out = []
    for (tid, sid), v in store.items():
        if server:
            out.append((tid + sid, v[0], v[1], bool(v[2]), v[3]))
        else:
            out.append((tid + sid, v[0], 0.0, False, v[1]))
    return out


def assert_same_state(new_reg, new_sg, old_reg, old_sg):
    assert_same_samples(_samples(new_reg), _samples(old_reg))
    assert new_reg.series_dropped == old_reg.series_dropped
    assert new_reg.active_series() == old_reg.active_series()
    assert new_sg.expired == old_sg.expired
    assert new_sg.edges_emitted == old_sg.edges_emitted
    names = new_sg._svc_names
    assert pending(new_sg.pending_clients, names, False) == oracle_pending(old_sg.pending_clients, False)
    assert pending(new_sg.pending_servers, names, True) == oracle_pending(old_sg.pending_servers, True)
    assert len(new_sg.pending_clients) == len(old_sg.pending_clients)
    assert bool(new_sg.pending_servers) == bool(old_sg.pending_servers)
    np.testing.assert_array_equal(np.asarray(new_sg.hll), np.asarray(old_sg.hll))
    np.testing.assert_array_equal(np.asarray(new_sg.cm), np.asarray(old_sg.cm))


# each case: the stream's knobs, and what it must be seen to do
CASES = {
    # halves meet across pushes, client first or server first
    "pairs_across_pushes": dict(pushes=12, n=40, traces=6, span_ids=8, services=6,
                                max_items=10_000, wait_s=60.0, step=1.0,
                                expect=("edges",)),
    # halves left waiting expire as the clock passes wait_s
    "expiry": dict(pushes=14, n=30, traces=40, span_ids=40, services=6,
                   max_items=10_000, wait_s=2.5, step=1.0, expect=("expired",)),
    # a store at a small max_items evicts its oldest, also halves that a
    # later row of the same push would have paired with or overwritten
    "overflow": dict(pushes=16, n=50, traces=4, span_ids=6, services=4,
                     max_items=3, wait_s=60.0, step=0.5, expect=("evicted", "edges")),
    "overflow_one": dict(pushes=20, n=12, traces=2, span_ids=3, services=3,
                         max_items=1, wait_s=60.0, step=0.5, expect=("evicted",)),
    # one service: pairs form and no edge is emitted
    "one_service": dict(pushes=10, n=60, traces=5, span_ids=6, services=1,
                        max_items=10_000, wait_s=60.0, step=1.0, expect=("pairs_no_edge",)),
    # failed server spans feed the failed counter
    "failed_servers": dict(pushes=10, n=60, traces=5, span_ids=6, services=6, error_rate=0.5,
                           max_items=10_000, wait_s=60.0, step=1.0, expect=("failed",)),
    # two halves of one kind and key in one push
    "duplicate_keys": dict(pushes=8, n=80, traces=2, span_ids=3, services=6,
                           kinds=[KIND_CLIENT, KIND_CLIENT, KIND_SERVER],
                           max_items=10_000, wait_s=60.0, step=1.0, expect=("duplicates",)),
    # overwrites in place, then expiry past them
    "duplicates_expire": dict(pushes=16, n=12, traces=3, span_ids=4, services=3,
                              kinds=[KIND_CLIENT, KIND_CLIENT, KIND_SERVER],
                              max_items=8, wait_s=1.2, step=0.5, expect=("expired", "evicted")),
    # the active-series limit refuses series, counted once a refused call
    "max_active_series": dict(pushes=10, n=60, traces=5, span_ids=6, services=6,
                              max_series=23, max_items=10_000, wait_s=3.0, step=1.0,
                              expect=("dropped",)),
    # service names never seen again: the names no waiting half uses are
    # dropped from the processor's table as it grows
    "many_services": dict(pushes=45, n=60, traces=3, span_ids=4, services=30, fresh_services=True,
                          max_items=1, wait_s=60.0, step=0.5, expect=("evicted", "edges")),
    # stale series removed between pushes, then made again
    "remove_stale": dict(pushes=14, n=40, traces=8, span_ids=8, services=6,
                         stale_after_s=2.5, max_items=20, wait_s=3.0, step=1.0,
                         expect=("stale",)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_columnar_generator_equals_the_span_loop(case, seed, monkeypatch):
    p = dict(CASES[case])
    expect = p.pop("expect")
    clock = Clock()
    monkeypatch.setattr(time, "time", clock)
    rng = np.random.default_rng(seed * 7919 + len(case))
    mk = dict(max_active_series=p.get("max_series", 0), stale_after_s=p.get("stale_after_s", 900.0))
    new_reg, old_reg = ManagedRegistry("t", **mk), OracleRegistry("t", **mk)
    new_sm, old_sm = SpanMetricsProcessor(new_reg), OracleSpanMetrics(old_reg)
    new_sg = ServiceGraphsProcessor(new_reg, wait_s=p["wait_s"], max_items=p["max_items"])
    old_sg = OracleServiceGraphs(old_reg, wait_s=p["wait_s"], max_items=p["max_items"])
    seen = {"edges": 0, "pairs": 0, "removed": 0, "dup": 0}
    for i in range(p["pushes"]):
        clock.t += p["step"]
        b = make_push(rng, int(rng.integers(1, p["n"] + 1)), traces=p["traces"],
                      span_ids=p["span_ids"], services=p["services"],
                      error_rate=p.get("error_rate", 0.1), kinds=p.get("kinds"),
                      prefix=f"p{i}-" if p.get("fresh_services") else "")
        ck = b.cols["kind"]
        sel = (ck == KIND_CLIENT) | (ck == KIND_SERVER)
        key = np.where((ck == KIND_SERVER)[:, None], b.cols["parent_span_id"], b.cols["span_id"])
        k = np.concatenate([b.cols["trace_id"], key, ck[:, None].astype(np.uint32)], 1)[sel]
        seen["dup"] += len(k) - len(np.unique(k, axis=0))
        before = old_sg.edges_emitted, len(old_sg.pending_clients) + len(old_sg.pending_servers)
        for proc in (new_sm, new_sg):
            proc.push(b)
        old_sm.push(b)
        old_sg.push(b)
        if "stale" in expect and i % 4 == 3:
            clock.t += 1.0
            got = new_reg.remove_stale()
            assert got == old_reg.remove_stale()
            seen["removed"] += got
        assert_same_state(new_reg, new_sg, old_reg, old_sg)
        seen["edges"] += old_sg.edges_emitted - before[0]
    counts = {s.labels: s.value for s in _samples(old_reg) if s.name == EXPIRED_TOTAL}
    for what in expect:
        if what == "edges":
            assert seen["edges"] > 0
        elif what in ("expired", "evicted"):
            assert any(dict(lab)["reason"] == what for lab in counts), counts
        elif what == "pairs_no_edge":
            assert old_sg.edges_emitted == 0 and old_reg.counters
        elif what == "failed":
            assert any(n == REQ_FAILED for n, _ in old_reg.counters)
        elif what == "duplicates":
            assert seen["dup"] > 0
        elif what == "dropped":
            assert old_reg.series_dropped > 0
        elif what == "stale":
            assert seen["removed"] > 0


def test_a_shuffle_shard_of_two_in_process_generators_equals_the_loop(monkeypatch):
    """The distributor's tee over a shard of two in-process generators:
    each gets its rows of every push as a batch, and publishes what the
    loop publishes for the same rows."""
    from tempo_tpu.modules.distributor import Distributor
    from tempo_tpu.modules.overrides import Limits, Overrides
    from tempo_tpu.modules.ring import MemoryKV, Ring
    from tempo_tpu.ops import hashing

    clock = Clock()
    monkeypatch.setattr(time, "time", clock)
    overrides = Overrides(Limits(metrics_generator_ring_size=2))
    ring = Ring(MemoryKV(), replication_factor=1)
    gens = {}
    for gid in ("generator-0", "generator-1"):
        ring.register(gid)
        gens[gid] = Generator(overrides, instance_id=gid)
    dist = Distributor(Ring(MemoryKV()), {}, overrides, generator_ring=ring,
                       generator_clients=gens)
    targets = ring.shuffle_shard("acme", 2)
    assert len(targets) == 2
    oracles = {}
    for t in targets:
        reg = OracleRegistry("acme")
        oracles[t.instance_id] = (reg, OracleSpanMetrics(reg), OracleServiceGraphs(reg))
    rng = np.random.default_rng(5)
    for _ in range(12):
        clock.t += 1.0
        b = make_push(rng, 80, traces=10, span_ids=6, services=6, error_rate=0.2)
        dist._send_to_generators("acme", b)
        idx = hashing.np_token_for_ids("acme", b.cols["trace_id"]) % np.uint32(2)
        for i, t in enumerate(targets):
            rows = np.flatnonzero(idx == i)
            if len(rows):
                _, sm, sg = oracles[t.instance_id]
                sub = b.select(rows)
                sm.push(sub)
                sg.push(sub)
    for t in targets:
        reg, _, sg = oracles[t.instance_id]
        inst = gens[t.instance_id].instance("acme")
        new_sg = next(p for p in inst.processors if isinstance(p, ServiceGraphsProcessor))
        assert _samples(reg)
        assert_same_state(inst.registry, new_sg, reg, sg)


def test_one_span_push_and_an_instance_default_processors():
    """The smallest push runs the same path: one span, one series each."""
    from tempo_tpu.modules.overrides import Limits, Overrides

    inst = TenantGeneratorInstance("t", Overrides(Limits()))
    b = make_push(np.random.default_rng(0), 1, traces=1, span_ids=1, services=1,
                  error_rate=0.0, kinds=[KIND_CLIENT])
    inst.push_batch(b)
    names = {s.name for s in inst.registry.collect()}
    assert {CALLS, SIZE, LATENCY + "_count"} <= names
    sg = next(p for p in inst.processors if isinstance(p, ServiceGraphsProcessor))
    assert len(sg.pending_clients) == 1 and not sg.pending_servers
