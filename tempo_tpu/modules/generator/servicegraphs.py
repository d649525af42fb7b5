"""Service-graphs processor: client/server span pairing -> edge metrics.

Reference: modules/generator/processor/servicegraphs (servicegraphs.go:60,
consume:140, expiring edge store store/store.go). An edge exists when a
server span's parent is a client span from another service; unpaired
halves wait in an expiring store.

Columnar: a push is paired with one sort-join of its halves' keys
against each other and against the halves still waiting, the waiting
halves are arrays in insertion order, and every edge of a push goes to
the registry as one aggregated update. The outcome is the one a span by
span loop over the push's rows gives (`_plan` says how).

Cardinality accounting uses the device sketches (ops.sketch): HLL for
distinct edge count, count-min for hot-edge estimation — the
BASELINE.json north-star metric for this processor.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

import numpy as np

import jax.numpy as jnp

# edge semantics (pairing rule, failure classification, sketch key) are
# shared with the stored-block trace-graph engine (tempo_tpu/graph) so
# live-generator edges and /api/graph/dependencies cannot drift
from tempo_tpu.graph import edge_hash_limbs, spans_failed
from tempo_tpu.model.trace import KIND_CLIENT, KIND_SERVER
from tempo_tpu.ops import sketch

REQ_TOTAL = "traces_service_graph_request_total"
REQ_FAILED = "traces_service_graph_request_failed_total"
REQ_SECONDS = "traces_service_graph_request_server_seconds"
# spans evicted from the pairing store without ever matching, labeled by
# which half waited (store="client"|"server") and why it left
# (reason="expired"|"evicted") — so stored-vs-live graph discrepancies
# are attributable instead of a single opaque int
EXPIRED_TOTAL = "traces_service_graph_expired_spans_total"

DEFAULT_BOUNDS = [0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 12.8]

# a half's key: trace ID and the client's span ID (a client span's own,
# a server span's parent), as one 24-byte value
_KEY = np.dtype("V24")
CLIENT, SERVER = 0, 1
_STORE_NAMES = ("client", "server")


def _keys(trace_id: np.ndarray, span_id: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.concatenate([trace_id, span_id], axis=1)).view(_KEY).ravel()


def _group(keys: np.ndarray):
    """(distinct keys, each key's group): a sort of the keys' three
    64-bit words, which numpy runs without letting the interpreter go
    for long, unlike a sort or binary search of 24-byte values."""
    w = np.ascontiguousarray(keys).view(np.uint64).reshape(-1, 3)
    order = np.lexsort((w[:, 2], w[:, 1], w[:, 0]))
    ws = w[order]
    first = np.ones(len(keys), bool)
    first[1:] = (ws[1:] != ws[:-1]).any(axis=1)
    grp = np.empty(len(keys), np.int64)
    grp[order] = np.cumsum(first) - 1
    return keys[order[first]], grp


def _halves(batch):
    """The push's client and server spans as halves, in row order: what
    needs no state, worked out before the lock is taken."""
    c = batch.cols
    kinds = c["kind"]
    rows = np.flatnonzero((kinds == KIND_CLIENT) | (kinds == KIND_SERVER))
    if not len(rows):
        return None, []
    is_server = kinds[rows] == KIND_SERVER
    codes, svc_rank = np.unique(c["service"][rows], return_inverse=True)
    return {
        "kind": is_server.astype(np.int64),
        "key": _keys(c["trace_id"][rows],
                     np.where(is_server[:, None], c["parent_span_id"][rows],
                              c["span_id"][rows])),
        "dur": c["duration_nano"][rows].astype(np.float64) / 1e9,
        "failed": spans_failed(c["status_code"][rows]),
        "svc_rank": svc_rank,
    }, [batch.dictionary[int(s)] for s in codes]


class PendingStore:
    """Halves waiting for their partner, in insertion order: a key, the
    service id, a server half's duration and failure, and the time it
    was put. Removal marks an entry dead; the live entries start at
    `lo`, so evicting the oldest and expiring (the times never go down
    while no entry was overwritten in place) are slices at the front.
    A dict from key bytes to position answers lookups: a hash join,
    because numpy's binary search lets the interpreter go, and a writer
    that holds the processor's lock then waits a switch interval for it."""

    def __init__(self):
        cap = 64
        self.key = np.zeros(cap, _KEY)
        self.svc = np.zeros(cap, np.int64)
        self.dur = np.zeros(cap, np.float64)
        self.failed = np.zeros(cap, bool)
        self.ts = np.zeros(cap, np.float64)
        self.alive = np.zeros(cap, bool)
        self.lo = self.hi = 0
        self.index: dict[bytes, int] = {}
        self.ts_sorted = True

    def __len__(self) -> int:
        return len(self.index)

    def find(self, keys: list) -> np.ndarray:
        """Position of each key's (bytes) live entry, -1 where it has none."""
        return np.fromiter(map(self.index.get, keys, itertools.repeat(-1)), np.int64, len(keys))

    def oldest(self, k: int) -> np.ndarray:
        """Positions of the k oldest live entries."""
        return np.flatnonzero(self.alive[self.lo:self.hi])[:k] + self.lo

    def live_before(self, pos: int) -> int:
        return int(np.count_nonzero(self.alive[self.lo:pos]))

    def remove(self, pos: np.ndarray) -> None:
        if not len(pos):
            return
        self.alive[pos] = False
        collections.deque(map(self.index.pop, self.key[pos].tolist()), maxlen=0)
        rest = self.alive[self.lo:self.hi]
        self.lo = self.lo + int(np.argmax(rest)) if self.index else self.hi

    def update(self, pos: np.ndarray, svc, dur, failed, now: float) -> None:
        """Overwrite in place: the entry keeps its place in the order."""
        if not len(pos):
            return
        if (self.ts[pos] != now).any():
            self.ts_sorted = False
        self.svc[pos], self.dur[pos], self.failed[pos], self.ts[pos] = svc, dur, failed, now

    def append(self, keys: np.ndarray, svc, dur, failed, now: float) -> None:
        k = len(keys)
        if not k:
            return
        if self.hi + k > len(self.key):
            self._compact(k)
        if self.index and self.ts[self.hi - 1] > now:
            self.ts_sorted = False
        if not self.index:
            self.lo = self.hi
        sl = slice(self.hi, self.hi + k)
        self.key[sl], self.svc[sl], self.dur[sl], self.failed[sl] = keys, svc, dur, failed
        self.ts[sl] = now
        self.alive[sl] = True
        self.index.update(zip(keys.tolist(), range(self.hi, self.hi + k)))
        self.hi += k

    def _compact(self, extra: int) -> None:
        live = np.flatnonzero(self.alive[: self.hi])
        cap = len(self.key)
        while len(live) + extra > cap // 2:
            cap *= 2
        for name in ("key", "svc", "dur", "failed", "ts", "alive"):
            old = getattr(self, name)
            new = np.zeros(cap, old.dtype)
            new[: len(live)] = old[live]
            setattr(self, name, new)
        self.lo, self.hi = 0, len(live)
        self.index = dict(zip(self.key[: self.hi].tolist(), range(self.hi)))

    def expired(self, now: float, wait_s: float) -> np.ndarray:
        """Positions of the live entries with now - ts > wait_s."""
        if not self.index:
            return np.zeros(0, np.int64)
        if self.ts_sorted:
            # the predicate holds on a prefix: find its end by bisection
            a, b = self.lo, self.hi
            while a < b:
                mid = (a + b) // 2
                if now - self.ts[mid] > wait_s:
                    a = mid + 1
                else:
                    b = mid
            return np.flatnonzero(self.alive[self.lo:a]) + self.lo
        # an entry overwritten in place holds a later time than entries
        # after it: the times are tested one by one until it has left
        # the window [lo, hi), dead entries included
        self.ts_sorted = bool((np.diff(self.ts[self.lo:self.hi]) >= 0).all())
        live = np.flatnonzero(self.alive[self.lo:self.hi]) + self.lo
        return live[now - self.ts[live] > wait_s]


class ServiceGraphsProcessor:
    name = "service-graphs"

    def __init__(self, registry, wait_s: float = 10.0, max_items: int = 10_000,
                 bounds=None):
        self.registry = registry
        self.wait_s = wait_s
        self.max_items = max_items
        self.bounds = bounds or DEFAULT_BOUNDS
        self.pending_clients = PendingStore()
        self.pending_servers = PendingStore()
        self.expired = 0
        self.edges_emitted = 0
        self.hll = sketch.hll_init(sketch.HLLPlan(12))
        self.cm = sketch.cm_init(sketch.CMPlan())
        # service names of the waiting halves, interned
        self._svc_names: list[str] = []
        self._svc_ids: dict[str, int] = {}
        # one lock around push: the pairing stores, the sketches and the
        # counts are read and rewritten by every concurrent pusher
        self._lock = threading.Lock()

    @property
    def _stores(self):
        return (self.pending_clients, self.pending_servers)

    def push(self, batch, now: float | None = None) -> None:
        half, names = _halves(batch)
        with self._lock:
            now = now or time.time()
            if half is not None:
                half["svc"] = self._intern(names)[half.pop("svc_rank")]
                events = self._pair(half, now)
            else:
                events = ([], [[], []])
            self._publish(*events)
            self.expire(now)

    def _intern(self, names: list) -> np.ndarray:
        if len(self._svc_names) + len(names) > 4 * self.max_items + 1024:
            # drop the names no waiting half uses any more
            live = np.unique(np.concatenate(
                [s.svc[s.lo:s.hi][s.alive[s.lo:s.hi]] for s in self._stores]))
            remap = np.zeros(len(self._svc_names), np.int64)
            remap[live] = np.arange(len(live))
            for s in self._stores:
                s.svc[s.lo:s.hi] = remap[s.svc[s.lo:s.hi]]
            self._svc_names = [self._svc_names[i] for i in live.tolist()]
            self._svc_ids = {s: i for i, s in enumerate(self._svc_names)}
        out = np.empty(len(names), np.int64)
        for i, s in enumerate(names):
            j = self._svc_ids.get(s)
            if j is None:
                j = self._svc_ids[s] = len(self._svc_names)
                self._svc_names.append(s)
            out[i] = j
        return out

    # ------------------------------------------------------------------
    def _pair(self, half: dict, now: float):
        """Run the push's halves (in row order) through the stores; return
        the pairs (client svc, server svc, duration, failed, row) and each
        store's eviction rows. A stretch of rows whose evictions could take
        a half that a later row of the stretch touches is cut short where
        that may happen and run again from there, so every stretch's plan
        is exact."""
        pairs, evicted = [], [[], []]
        m, pos = len(half["kind"]), 0
        while pos < m:
            seg = {k: v[pos:] for k, v in half.items()}
            plan = self._plan(seg)
            if isinstance(plan, int):
                # the stretch before the cut plans exactly; a cut before
                # the first row leaves that row alone, which plans exactly
                # but where it evicts its own waiting half
                seg = {k: v[pos:pos + max(plan, 1)] for k, v in half.items()}
                plan = self._plan(seg)
                if isinstance(plan, int):
                    plan = self._self_evicting_put(seg)
            self._apply(plan, now, pos, pairs, evicted)
            pos += plan["rows"]
        return pairs, evicted

    def _plan(self, seg: dict):
        """The plan of a stretch of rows as the loop runs them, or the row
        before which the stretch must be cut.

        The loop takes each row in turn: a half whose key has a waiting
        half of the other kind pairs with it and removes it; otherwise it
        is put in its own store: a key already waiting there is
        overwritten in place (the entry keeps its place, takes the new
        values and time), and a put into a full store, an overwrite too,
        evicts its oldest entry first (when that is the overwritten entry
        itself, the row goes in anew at the end). So within one key, rows (after the half that waited
        before them, if any) run as a chain: a row pairs with the one
        before it exactly when their kinds differ and that one did not
        pair itself; two halves of one kind and key in a row leave one
        entry, the later's values in the earlier's place. Store sizes
        follow a walk that a full store holds at its size."""
        L = len(seg["kind"])
        uniq, grp = _group(seg["key"])
        cs, ss = self._stores
        uniq_bytes = uniq.tolist()
        pos_c, pos_s = cs.find(uniq_bytes), ss.find(uniq_bytes)
        vg = np.flatnonzero((pos_c >= 0) | (pos_s >= 0))  # groups with a waiting half
        v_kind = np.where(pos_c[vg] >= 0, CLIENT, SERVER)
        v_pos = np.where(pos_c[vg] >= 0, pos_c[vg], pos_s[vg])
        # elements: the waiting halves (time -1) and the rows, by key, then time
        e_grp = np.concatenate([vg, grp])
        e_t = np.concatenate([np.full(len(vg), -1), np.arange(L)])
        order = np.lexsort((e_t, e_grp))
        e_grp, e_t = e_grp[order], e_t[order]
        e_kind = np.concatenate([v_kind, seg["kind"]])[order]
        virtual = e_t < 0
        E = len(e_t)
        idx = np.arange(E)
        same = np.zeros(E, bool)
        same[1:] = e_grp[1:] == e_grp[:-1]
        alt = same.copy()
        alt[1:] &= e_kind[1:] != e_kind[:-1]
        start = np.maximum.accumulate(np.where(alt, 0, idx))
        paired = (idx - start) % 2 == 1
        prev_open = np.zeros(E, bool)  # the element before is an unpaired half of the same kind
        prev_open[1:] = same[1:] & (e_kind[1:] == e_kind[:-1]) & ~paired[:-1]
        overwrite = ~virtual & ~paired & prev_open
        new = ~virtual & ~paired & ~prev_open
        occ = np.maximum.accumulate(np.where(virtual | new, idx, -1))
        # chain ends: an unpaired element the next does not overwrite
        next_ow = np.zeros(E, bool)
        next_ow[:-1] = overwrite[1:]
        ends = np.flatnonzero(~paired & ~next_ow)
        popped = np.zeros(len(ends), bool)
        has_next = ends + 1 < E
        popped[has_next] = paired[ends[has_next] + 1]
        # each element's values (a waiting half's from its store)
        src = np.concatenate([np.full(len(vg), -1), np.arange(L)])[order]
        vsrc = np.concatenate([np.arange(len(vg)), np.full(L, -1)])[order]
        e_svc = np.empty(E, np.int64)
        e_dur = np.empty(E)
        e_failed = np.empty(E, bool)
        real = ~virtual
        e_svc[real] = seg["svc"][src[real]]
        e_dur[real] = seg["dur"][src[real]]
        e_failed[real] = seg["failed"][src[real]]
        for kind, store in enumerate(self._stores):
            sel = virtual & (e_kind == kind)
            p = v_pos[vsrc[sel]]
            e_svc[sel], e_dur[sel], e_failed[sel] = store.svc[p], store.dur[p], store.failed[p]

        # store sizes: a put is +1 (an overwrite -1 after it), a pop -1;
        # a put into a full store evicts
        ev_rows, cut = [], L
        for kind, store in enumerate(self._stores):
            put = real & ~paired & (e_kind == kind)
            pop = paired & (e_kind != kind)
            t_put, t_pop = e_t[put], e_t[pop]
            t = np.concatenate([t_put, t_pop])
            o = np.argsort(t)
            t = t[o]
            step1 = np.concatenate([np.ones(len(t_put), np.int64), -np.ones(len(t_pop), np.int64)])[o]
            step2 = -np.concatenate([overwrite[put], np.zeros(len(t_pop), bool)])[o].astype(np.int64)
            walk = len(store) + np.cumsum(np.stack([step1, step2], 1).ravel())
            over = np.maximum.accumulate(np.maximum(walk - self.max_items, 0))
            grew = np.diff(over, prepend=0)[0::2] > 0
            t_ev = t[grew]
            # the oldest entries go first; an entry a row of this stretch
            # touches cannot be evicted by the plan: cut the stretch there
            touched = v_pos[v_kind == kind]
            first_touched = int(touched.min()) if len(touched) else store.hi
            room = store.live_before(first_touched)
            if len(t_ev) > room:
                cut = min(cut, int(t_ev[room]))
            ev_rows.append(t_ev)
        if cut < L:
            return cut
        plan = {"rows": L, "evict_rows": ev_rows}
        # pairs: an element paired with the chain end before it
        j = np.flatnonzero(paired)
        cli = np.where(e_kind[j] == CLIENT, j, j - 1)
        srv = np.where(e_kind[j] == CLIENT, j - 1, j)
        plan["pairs"] = (e_svc[cli], e_svc[srv], e_dur[srv], e_failed[srv], e_t[j])
        # waiting halves that pair or are overwritten
        v_end = ends[virtual[occ[ends]]]
        v_popped = popped[virtual[occ[ends]]]
        vo = occ[v_end]
        plan["pop"] = [v_pos[vsrc[vo[v_popped & (e_kind[vo] == k)]]] for k in (CLIENT, SERVER)]
        upd = ~v_popped & (v_end != vo)
        plan["update"] = [(v_pos[vsrc[vo[upd & (e_kind[vo] == k)]]], v_end[upd & (e_kind[vo] == k)])
                          for k in (CLIENT, SERVER)]
        # new halves still waiting at the end, in the order they were put
        n_end = ends[~virtual[occ[ends]] & ~popped]
        n_end = n_end[np.argsort(e_t[occ[n_end]], kind="stable")]
        plan["append"] = [n_end[e_kind[n_end] == k] for k in (CLIENT, SERVER)]
        plan["values"] = (uniq[e_grp], e_svc, e_dur, e_failed)
        return plan

    def _self_evicting_put(self, seg: dict) -> dict:
        """One row that overwrites its own key's half, which is the oldest
        entry of its full store: the put evicts that very entry, then puts
        the row as a new entry at the end."""
        kind = int(seg["kind"][0])
        return {"rows": 1, "evict_rows": [np.zeros(1, np.int64) if k == kind else
                                          np.zeros(0, np.int64) for k in (CLIENT, SERVER)],
                "pairs": (np.zeros(0, np.int64),) * 2 + (np.zeros(0),
                          np.zeros(0, bool), np.zeros(0, np.int64)),
                "pop": [np.zeros(0, np.int64)] * 2,
                "update": [(np.zeros(0, np.int64), np.zeros(0, np.int64))] * 2,
                "append": [np.zeros(1, np.int64) if k == kind else np.zeros(0, np.int64)
                           for k in (CLIENT, SERVER)],
                "values": (seg["key"], seg["svc"], seg["dur"], seg["failed"])}

    def _apply(self, plan: dict, now: float, offset: int, pairs: list, evicted: list) -> None:
        keys, svc, dur, failed = plan["values"]
        for kind, store in enumerate(self._stores):
            t_ev = plan["evict_rows"][kind]
            store.remove(store.oldest(len(t_ev)))
            evicted[kind].extend((t_ev + offset).tolist())
            store.remove(plan["pop"][kind])
            pos, src = plan["update"][kind]
            store.update(pos, svc[src], dur[src], failed[src], now)
            src = plan["append"][kind]
            store.append(keys[src], svc[src], dur[src], failed[src], now)
        c_svc, s_svc, d, f, t = plan["pairs"]
        if len(t):
            pairs.append((c_svc, s_svc, d, f, t + offset))

    # ------------------------------------------------------------------
    def _publish(self, pairs: list, evicted: list) -> None:
        """Registry updates of one push: a series is touched first where
        the loop's first call for it fell (an edge's total, failed and
        seconds at its first pair, or first failed pair; an eviction
        count at its store's first eviction), and takes all of the push's
        calls for it at once."""
        touches = []  # (row, order within the row, registry call)
        for kind, rows in enumerate(evicted):
            if rows:
                labels = (("store", _STORE_NAMES[kind]), ("reason", "evicted"))
                touches.append((rows[0], 0, (EXPIRED_TOTAL, labels, len(rows))))
                self.expired += len(rows)
        keys = []
        if pairs:
            c_svc, s_svc, dur, failed, t = (np.concatenate(x) for x in zip(*pairs))
            # in the order the pairs were made; pairs inside one service are no edge
            o = np.argsort(t, kind="stable")
            o = o[c_svc[o] != s_svc[o]]
            c_svc, s_svc, dur, failed, t = c_svc[o], s_svc[o], dur[o], failed[o], t[o]
            keys = self._edges(c_svc, s_svc, dur, failed, t, touches)
        now = time.time()
        for _, _, call in sorted(touches, key=lambda x: (x[0], x[1])):
            if call[0] == REQ_SECONDS:
                name, labels, calls, b, s = call
                self.registry.observe_histogram(name, labels, self.bounds, b, s, calls,
                                                now=now, calls=calls)
            else:
                name, labels, calls = call
                self.registry.inc_counter(name, labels, float(calls), now=now, calls=calls)
        if len(keys):
            keys = jnp.asarray(keys)
            self.hll = sketch.hll_update(self.hll, keys, sketch.HLLPlan(12))
            self.cm = sketch.cm_update(self.cm, keys, sketch.CMPlan())

    def _edges(self, c_svc, s_svc, dur, failed, t, touches: list):
        """Add each edge's registry calls of the push to `touches`; return
        the sketch keys, one a pair in the order the pairs were made."""
        if not len(t):
            return []
        self.edges_emitted += len(t)
        uniq, first, inv = np.unique(c_svc << 32 | s_svc, return_index=True, return_inverse=True)
        n = np.bincount(inv)
        n_failed = np.bincount(inv, weights=failed).astype(np.int64)
        sums = np.bincount(inv, weights=dur)
        nb = len(self.bounds) + 1
        bidx = np.searchsorted(np.asarray(self.bounds), dur, side="left")
        buckets = np.bincount(inv * nb + bidx, minlength=len(uniq) * nb).reshape(-1, nb)
        fail_first = np.full(len(uniq), -1)
        fi = np.flatnonzero(failed)
        fu, ff = np.unique(inv[fi], return_index=True)
        fail_first[fu] = t[fi[ff]]
        limbs = []
        for e in range(len(uniq)):
            client = self._svc_names[int(c_svc[first[e]])]
            server = self._svc_names[int(s_svc[first[e]])]
            labels = (("client", client), ("server", server))
            t0 = int(t[first[e]])
            touches.append((t0, 1, (REQ_TOTAL, labels, int(n[e]))))
            if n_failed[e]:
                touches.append((int(fail_first[e]), 2, (REQ_FAILED, labels, int(n_failed[e]))))
            touches.append((t0, 3, (REQ_SECONDS, labels, int(n[e]), buckets[e], sums[e])))
            limbs.append(edge_hash_limbs(client, server))
        return np.stack(limbs)[inv]

    def expire(self, now: float) -> None:
        for kind, store in enumerate(self._stores):
            dead = store.expired(now, self.wait_s)
            if len(dead):
                store.remove(dead)
                self.expired += len(dead)
                self.registry.inc_counter(
                    EXPIRED_TOTAL, (("store", _STORE_NAMES[kind]), ("reason", "expired")),
                    float(len(dead)), calls=len(dead))

    def distinct_edges_estimate(self) -> float:
        return float(sketch.hll_estimate(self.hll, sketch.HLLPlan(12)))
