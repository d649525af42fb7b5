"""The one general load generator. A traffic mix is a data file under
traffic/ (clients, the deck of operations with their shares and the
ranges their literals are drawn from, tenant popularity); this file
reads it and drives closed-loop clients against the server.

Every seed deals the same deck — the same number of each operation in
every `deck_size` requests of a client — in another order and with other
literals, so the seed does not change the work. Literals come from
continuous ranges, so nearly every query is new to the result cache.

The vocabulary of operations is the HTTP API of the `-target=all`
process: find, search_tags, traceql_filter, rate_by_name, rate_total,
rate_by_service, quantiles, push.

A file may split its clients into `roles` (writers beside readers), each
closed-loop over a deck of its own; without the key the file is one role.
A role with `arrival_rate_per_s` is paced instead: k6's
`constant-arrival-rate` executor (`timeUnit` 1 s), its clients the
`preAllocatedVUs`; an arrival that finds every client busy is dropped and
counted, as k6 counts `dropped_iterations`, and one the pacer wakes for
only after the next is due is skipped and counted (`Pacer`).
"""

from __future__ import annotations

import collections
import dataclasses
import http.client
import json
import queue
import threading
import time
import urllib.parse

import numpy as np

import wire
from corpus import SERVICES

STEP_S = 60
FINDS = ("find", "find_acked")  # a stored trace by ID; one a push of this run carried
HIT_LIMIT = 1_000_000  # above every hit set: searches return them whole
QUANTILES = (0.5, 0.99)
PROTOBUF = "application/x-protobuf"


@dataclasses.dataclass
class Request:
    op: str
    tenant: str
    args: tuple  # what the reference needs to answer the same question
    method: str
    path: str
    body: bytes | None = None
    expect: int = 200
    spans: int = 0  # spans carried (pushes)


@dataclasses.dataclass
class Record:
    req: Request
    client: int
    t0: float
    t1: float
    status: int  # 0: no answer (timeout, reset)
    answer: object = None

    @property
    def ok(self) -> bool:
        return self.status == self.req.expect


def deal(deck: list, size: int) -> list:
    """`size` operations with each entry's exact share (largest remainder)."""
    total = sum(e["weight"] for e in deck)
    exact = [e["weight"] * size / total for e in deck]
    counts = [int(x) for x in exact]
    for i in sorted(range(len(deck)), key=lambda i: exact[i] - counts[i],
                    reverse=True)[:size - sum(counts)]:
        counts[i] += 1
    return [e for e, n in zip(deck, counts) for _ in range(n)]


def roles_of(traffic: dict) -> list:
    """The file's roles, `{name, clients, deck, deck_size}` each (and
    `warmup_per_op` or `arrival_rate_per_s`, where a role has its own). A
    file without `roles` is one role made of its top-level keys."""
    if "roles" in traffic:
        return traffic["roles"]
    return [{"name": "clients", **{k: traffic[k] for k in ("clients", "deck", "deck_size")}}]


def client_roles(roles: list) -> list:
    """The role of client 0, 1, ...: clients are numbered through the roles."""
    return [role for role in roles for _ in range(role["clients"])]


def ops_of(traffic: dict) -> set:
    """Every operation some role's deck holds."""
    return {e["op"] for role in roles_of(traffic) for e in role["deck"]}


def range_of(base_s: int) -> dict:
    """The query_range window around spans that start within a second of
    `base_s`: whole steps, the step before theirs to the step after."""
    lo = base_s // STEP_S * STEP_S
    return {"start": lo - STEP_S, "end": lo + 2 * STEP_S, "step": STEP_S}


class Source:
    """What the operations draw from: the tenants, their trace ids, the
    time range of the data and (for pushes) the pool of encoded bodies,
    and the pushes this run has had acknowledged so far."""

    def __init__(self, traffic: dict, tenants: list, multitenant: bool, base_s: int,
                 hexes: dict | None = None, pool: list | None = None):
        self.traffic, self.tenants, self.multitenant = traffic, tenants, multitenant
        self.hexes = hexes or {}
        self.pool = pool or []
        self.range = range_of(base_s)
        self._acked: list = []  # (tenant, body, ids) of every push answered 200, any client's
        self._acked_lock = threading.Lock()
        pop = traffic.get("tenant_popularity", {"dist": "uniform"})
        w = np.ones(len(tenants))
        if pop["dist"] == "zipfian":
            w = 1.0 / np.arange(1, len(tenants) + 1) ** pop["theta"]
        self.tenant_p = w / w.sum()

    def headers(self, tenant: str) -> dict:
        return {"X-Scope-OrgID": tenant} if self.multitenant else {}

    def acknowledged(self, req: "Request") -> None:
        with self._acked_lock:
            self._acked.append((req.tenant, *req.args))

    def draw_acked(self, rng):
        """One acknowledged push, or None while there is none."""
        with self._acked_lock:
            return self._acked[int(rng.integers(0, len(self._acked)))] if self._acked else None


def _us(rng, lo_hi_ms, ms: float | None = None) -> int:
    """A duration literal in whole microseconds: drawn from a range in ms,
    or (warm-up) the one given."""
    if ms is not None:
        return int(ms * 1000)
    return int(rng.integers(int(lo_hi_ms[0] * 1000), int(lo_hi_ms[1] * 1000) + 1))


def warm_literals(entry: dict, per_op: int) -> list:
    """The duration literals (ms) the warm-up asks a deck entry with: its
    `warmup_ms` where it has one, else `per_op` steps through its range, so
    that the window meets no stream length that is not compiled yet. An
    entry without a duration literal is asked `per_op` times as drawn."""
    if "warmup_ms" in entry:
        return list(entry["warmup_ms"])
    key = next((k for k in ("min_duration_ms", "duration_ms") if k in entry), None)
    if key is None:
        return [None] * per_op
    lo, hi = entry[key]
    return [lo + (hi - lo) * i / max(1, per_op - 1) for i in range(per_op)]


def _get(path: str, params: dict) -> str:
    return path + "?" + urllib.parse.urlencode(params)


def build(entry: dict, rng, src: Source, nonce: tuple, ms: float | None = None) -> Request:
    """One request of the deck entry's kind with its literals drawn. The
    warm-up passes its duration literal, `ms` (see warm_literals)."""
    op = entry["op"]
    tenant = src.tenants[int(rng.choice(len(src.tenants), p=src.tenant_p))]
    if op == "find" and entry.get("of") == "acked" and (acked := src.draw_acked(rng)):
        tenant, body, ids = acked
        k = int(rng.integers(0, body.n_traces))
        h = ids[k].tobytes().hex()
        return Request("find_acked", tenant, (h, body.span_sets[k]), "GET", f"/api/traces/{h}")
    if op == "find":
        if rng.random() < entry.get("absent_share", 0.0):
            h = rng.integers(0, 256, 16, dtype=np.uint8).tobytes().hex()
            return Request(op, tenant, (h,), "GET", f"/api/traces/{h}", expect=404)
        ids = src.hexes[tenant]
        h = ids[int(rng.integers(0, len(ids)))]
        return Request(op, tenant, (h,), "GET", f"/api/traces/{h}")
    if op == "push":
        body = src.pool[int(rng.integers(0, len(src.pool)))]
        ids = np.random.default_rng(list(nonce)).integers(
            0, 256, (body.n_traces, 16), dtype=np.uint8)
        return Request(op, tenant, (body, ids), "POST", "/v1/traces",
                       body=body.patched(ids), spans=body.n_spans)
    service = SERVICES[int(rng.integers(0, len(SERVICES)))]
    # `range: "store"`: the search carries the store's time range, as Grafana's Explore does
    window = (src.range["start"], src.range["end"]) if entry.get("range") == "store" else None
    in_range = {"start": window[0], "end": window[1]} if window else {}
    if op == "search_tags":
        us = _us(rng, entry["min_duration_ms"], ms)
        return Request(op, tenant, (service, us * 1000, window), "GET", _get("/api/search", {
            "tags": f"service.name={service}", "minDuration": f"{us}us", "limit": HIT_LIMIT,
            **in_range}))
    us = _us(rng, entry["duration_ms"], ms)
    if op == "traceql_filter":
        status = int(rng.choice(entry["status"]))
        q = f"{{ span.http.status_code = {status} && duration > {us}us }}"
        return Request(op, tenant, (status, us * 1000, window), "GET",
                       _get("/api/search", {"q": q, "limit": HIT_LIMIT, **in_range}))
    sel = f'{{ resource.service.name = "{service}" && duration > {us}us }}'
    q = {
        "rate_by_name": f"{sel} | rate() by (name)",
        "rate_total": f"{sel} | rate()",
        "rate_by_service": f"{sel} | rate() by (resource.service.name)",
        "quantiles": f'{sel} | quantile_over_time(duration, '
                     f'{", ".join(str(x) for x in QUANTILES)})',
    }[op]
    return Request(op, tenant, (service, us * 1000), "GET",
                   _get("/api/metrics/query_range", {"q": q, **src.range}))


# -- reading an answer: what the comparison needs of it, nothing more ---------

_SERIES_LABEL = {"rate_by_name": "name", "rate_total": None,
                 "rate_by_service": "resource.service.name"}


def parse(req: Request, status: int, body: bytes):
    if status != 200:
        return None
    if req.op in FINDS:
        return wire.span_ids(body)
    if req.op == "push":
        return None
    doc = json.loads(body)
    if req.op in ("search_tags", "traceql_filter"):
        ids = [t["traceID"] for t in doc["traces"]]
        return ids if len(ids) != len(set(ids)) else frozenset(ids)  # a list: a trace twice
    result = doc["data"]["result"]
    if req.op == "quantiles":
        out = {}
        for s in result:
            vals = [float(v[1]) for v in s["values"] if float(v[1]) > 0]
            out.setdefault(float(s["metric"]["p"]), []).extend(vals)
        return out
    label = _SERIES_LABEL[req.op]
    counts = {}
    for s in result:  # rate x step, summed over the steps: spans of the series
        total = sum(float(v[1]) for v in s["values"]) * STEP_S
        if total:
            counts[s["metric"].get(label, "") if label else ""] = total
    return counts


# -- the clients ----------------------------------------------------------------


class Client(threading.Thread):
    """One closed-loop client: the next request leaves when the last is
    answered. Runs until `stop_at`, finishing the request in flight."""

    def __init__(self, n: int, seed: int, src: Source, port: int, records: list,
                 hold: threading.Event | None = None, role: dict | None = None):
        super().__init__(daemon=True, name=f"client-{n}")
        self.n, self.seed, self.src, self.port = n, seed, src, port
        self.role = role or roles_of(src.traffic)[0]  # whose deck this client is dealt
        self.records = records  # this client's own list
        self.rng = np.random.default_rng([seed, n, 7])
        self.timeout = float(src.traffic["timeout_s"])
        self.stop_at = 0.0
        self.hold = hold  # traced runs: keep going until the capture is back
        self._hand: list = []
        self._sent = 0
        self._conn = None

    def next_request(self) -> Request:
        if not self._hand:
            self._hand = deal(self.role["deck"], self.role["deck_size"])
            self.rng.shuffle(self._hand)
        self._sent += 1
        return build(self._hand.pop(), self.rng, self.src, (self.seed, self.n, self._sent))

    def exchange(self, req: Request, headers: dict) -> tuple:
        """(status, body) of one request on this client's connection."""
        if self._conn is None:
            self._conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                    timeout=self.timeout)
        self._conn.request(req.method, req.path, body=req.body, headers=headers)
        resp = self._conn.getresponse()
        return resp.status, resp.read()

    def send(self, req: Request) -> Record:
        headers = self.src.headers(req.tenant)
        if req.op in FINDS:
            headers["Accept"] = "application/protobuf"
        if req.body is not None:
            headers["Content-Type"] = PROTOBUF
        t0 = time.perf_counter()
        try:
            status, body = self.exchange(req, headers)
        except (OSError, http.client.HTTPException):
            self._conn.close()
            self._conn = None
            return Record(req, self.n, t0, t0 + self.timeout, 0)
        t1 = time.perf_counter()
        if req.op == "push" and status == req.expect:
            self.src.acknowledged(req)  # before the next request of any client is drawn
        return Record(req, self.n, t0, t1, status, parse(req, status, body))

    def run(self) -> None:
        while time.perf_counter() < self.stop_at or (self.hold and not self.hold.is_set()):
            self.records.append(self.send(self.next_request()))
        if self._conn is not None:
            self._conn.close()


class PacedClient(Client):
    """A client of a paced role: sends the next request of its own deal,
    drawn as a closed-loop client of its number draws it, each time its
    role's Pacer hands it an arrival."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.arrivals: queue.SimpleQueue = queue.SimpleQueue()  # True: an arrival; None: stop
        self.pacer: Pacer | None = None

    def run(self) -> None:
        while self.arrivals.get() is not None:
            self.records.append(self.send(self.next_request()))
            self.pacer.done(self)
        if self._conn is not None:
            self._conn.close()


class Pacer(threading.Thread):
    """One paced role: arrivals at t0 + k / rate for k = 0, 1, ... until
    `stop_at` (or, in a traced run, until `hold` is set), each handed to
    the client that has been free longest. An arrival that finds every
    client busy is dropped and counted (`dropped`); one that the pacer
    itself wakes for only after the next arrival is due, as when the
    interpreter or a capture holds it, is skipped and counted (`missed`).
    So no arrival leaves later than the next one's due time, and no two
    leave in a burst. Starts its clients and, after the last arrival,
    waits for the requests in flight."""

    def __init__(self, clients: list, rate: float, hold: threading.Event | None = None):
        super().__init__(daemon=True, name=f"pacer-{clients[0].role['name']}")
        self.clients, self.rate, self.hold = clients, float(rate), hold
        self.clock, self.sleep = time.perf_counter, time.sleep  # a test hands in fakes
        self.stop_at = 0.0
        self.arrived = self.dropped = self.missed = 0
        self._idle = collections.deque(clients)
        self._lock = threading.Lock()
        for c in clients:
            c.pacer = self

    @property
    def records(self) -> list:
        return [r for c in self.clients for r in c.records]

    def done(self, client: PacedClient) -> None:
        with self._lock:
            self._idle.append(client)

    def run(self) -> None:
        for c in self.clients:
            c.start()
        t0 = self.clock()
        try:
            while True:
                due = t0 + self.arrived / self.rate
                if due >= self.stop_at and (self.hold is None or self.hold.is_set()):
                    return
                wait = due - self.clock()
                if wait > 0:
                    self.sleep(wait)
                self.arrived += 1
                if self.clock() >= t0 + self.arrived / self.rate:  # the next one is due
                    self.missed += 1
                    continue
                with self._lock:
                    client = self._idle.popleft() if self._idle else None
                if client is None:
                    self.dropped += 1
                else:
                    client.arrivals.put(True)
        finally:
            for c in self.clients:
                c.arrivals.put(None)
            for c in self.clients:
                c.join()


def role_threads(roles: list, first: int, seed: int, src: Source, port: int, logs: list,
                 hold: threading.Event | None = None) -> list:
    """The threads that drive the roles' clients, numbered from `first`
    through the roles, client `first + i` appending to `logs[i]`: a
    closed-loop Client a client, or for a role with `arrival_rate_per_s`
    one Pacer over the role's PacedClients. Each is started after its
    `stop_at` is set."""
    out, n = [], first
    for role in roles:
        rate = role.get("arrival_rate_per_s")
        kind = Client if rate is None else PacedClient
        clients = [kind(n + i, seed, src, port, logs[n + i - first], hold, role)
                   for i in range(role["clients"])]
        out += clients if rate is None else [Pacer(clients, rate, hold)]
        n += role["clients"]
    return out


def make_pool(traffic: dict, seed: int, spans: int, base_s: int) -> list:
    """`pool_bodies` pushes of `push_traces` traces, encoded once; their
    spans start within a second of `base_s`."""
    from corpus import encode_push, make_block

    return [wire.PatchableBody(encode_push(
        make_block(traffic["push_traces"], spans, [seed, 99, i], base_s * 10**9)))
        for i in range(traffic["pool_bodies"])]
