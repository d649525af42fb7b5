#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

Drives ONE `python -m tempo_tpu -target=all` process (the only process
that touches JAX, so the only one that holds the chip) from OTLP push to
compacted-block queries and checks every answer against a plain numpy
reference computed here from the generated spans:

  set-up   compile-certify the Pallas scan kernels no engine path calls
           (in_set_scan, u64_range_scan) in a short-lived process that
           exits before the server starts
  boot     start the server, read /status/device: platform must be "tpu"
  ingest   65,536 traces x 16 spans pushed as OTLP/HTTP protobuf in two
           halves (the second re-sends 25% of the first half's traces),
           POST /flush after each -> two blocks of 524,288 spans
  queries  find-by-ID x200, tag search, minDuration search, a TraceQL
           filter, a structural (>>) query, query_range rate() by (name),
           rate() (the compiled tier's fused program) and
           quantile_over_time, /api/graph/critical-path, the standing
           query — with one block (cold + warm), with two blocks (all
           but the structural query), and again (cold + warm) after the
           compactor's own loop merged the two blocks into one
  verdict  the dispatch counters say the chip did the work, the fallback
           and error counters are 0, the log holds no ERROR, the child
           exits 0 through /shutdown

The last stdout line is one JSON object with exactly these keys
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
(the device as the child's JAX reports it; what else the run has to say
is on the `[smoke] summary:` line before it) and the exit code is 0 only
if every phase passed; a failing phase ends the run nonzero with no
result line. Phase wall times are printed as
single observations — they are not metrics. There is no CPU fallback:
without a TPU the run fails after reading the child's backend. The tiny
sandbox dry run is `python chip_smoke.py --cpu-dry-run --traces 256`; it
pins JAX_PLATFORMS=cpu for the child, says "cpu" everywhere and checks
results only.

This process stays off JAX (numpy + stdlib + the repo's numpy-only model
and wire helpers) and asserts so before it exits.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import queue
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import types
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

try:
    from tempo_tpu.model import synth
    from tempo_tpu.model.columnar import SpanBatch
    from tempo_tpu.model.trace import KIND_CLIENT, KIND_SERVER, batch_to_traces
    from tempo_tpu.receivers import otlp
    from tempo_tpu.util import backend
except ImportError as e:  # run from a directory without the checkout
    print(f"chip_smoke.py: needs the tempo_tpu checkout beside it: {e}",
          file=sys.stderr)
    sys.exit(2)

TENANT = "single-tenant"
STEP_S = 60
N_FIND = 200
PUSH_TRACES = 512  # traces per OTLP request (~1.6 MB of protobuf)
HIT_LIMIT = 1_000_000  # above every hit set: searches return them whole
QUANTILE_REL_ERR = 0.125  # metrics_engine/plan.py: 8 sub-buckets/octave

# the query set
Q_TAG = {"tags": "service.name=cart"}
Q_DUR = {"minDuration": "990ms"}
Q_FILTER = {"q": "{ span.http.status_code = 500 && duration > 900ms }"}
Q_STRUCT = {"q": '{ kind = server && name = "render" } >> { kind = client && duration > 900ms }'}
Q_RATE = '{ resource.service.name = "cart" } | rate() by (name)'
Q_RATE_FUSED = '{ resource.service.name = "cart" } | rate()'  # lowers to compiled/
Q_QUANT = "{} | quantile_over_time(duration, 0.5, 0.99)"
Q_STANDING = "{} | rate() by (name)"

# kernels the default one-chip path must reach: any label of a group
# counts. timed_dispatch sites count in dispatches_total; the async
# sketch/mesh-compaction sites only account bytes (count_transfer).
REQUIRED_KERNELS = (
    ("rle_encode", "dbp_encode", "dct_encode"),  # flush page encode
    ("block_sketch", "sketch_accumulate"),  # block write / compaction sketches
    ("seg_bincount", "mesh_bincount"),  # query_range, interpreted by() plan
    ("compiled_metrics",),  # query_range, fused program of the compiled tier
    ("standing_fold",),
    ("graph_critical_path",),
)
REQUIRED_KERNELS_MESH = (("mesh_compaction",), ("mesh_scan", "mesh_rle_scan"),
                         ("mesh_bincount",))


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# data + the plain reference
# ---------------------------------------------------------------------------


def chain_parents(batch: SpanBatch, spans: int) -> None:
    """synth.make_batch draws random parent ids; give every trace one
    real call chain instead (row k's parent is row k-1 of its trace,
    kinds alternate server/client) so the structural query and the
    critical path have something to find. Rows are trace-sorted with
    `spans` rows per trace."""
    c = batch.cols
    k = np.arange(batch.num_spans) % spans
    parent = np.zeros_like(c["span_id"])
    parent[k > 0] = c["span_id"][np.flatnonzero(k > 0) - 1]
    c["parent_span_id"] = parent
    c["kind"] = np.where(k % 2 == 0, KIND_SERVER, KIND_CLIENT).astype(np.uint8)


def make_corpus(seed: int, n_traces: int, spans: int, base_s: int):
    """(first half, second half as pushed, de-duplicated union)."""
    half = n_traces // 2
    dup = half // 4
    base_ns = base_s * 10**9
    a = synth.make_batch(half, spans, seed=2 * seed + 1, base_time_ns=base_ns)
    fresh = synth.make_batch(half - dup, spans, seed=2 * seed + 2, base_time_ns=base_ns)
    chain_parents(a, spans)
    chain_parents(fresh, spans)
    shared = a.select(np.arange(dup * spans))  # a's first 25% of traces
    return a, SpanBatch.concat([shared, fresh]), SpanBatch.concat([a, fresh])


def _tid_hex(rows: np.ndarray) -> list[str]:
    return [r.astype(">u4").tobytes().hex() for r in rows]


class Reference:
    """Answers for one span set, straight from its columns. Additive
    parts (counts, seconds) add across blocks — duplicates included —
    and set parts union, which is how two un-compacted blocks answer."""

    def __init__(self, batch: SpanBatch, spans: int):
        c = batch.cols
        d = np.array(batch.dictionary.entries, dtype=object)
        n = batch.num_spans
        t = n // spans
        tid = c["trace_id"].reshape(t, spans, 4)[:, 0]
        self.trace_hex = _tid_hex(tid)
        self._row_of = {h: i for i, h in enumerate(self.trace_hex)}
        self._span_id = c["span_id"].reshape(t, spans, 2)
        dur = c["duration_nano"].astype(np.int64).reshape(t, spans)
        service = d[c["service"]].reshape(t, spans)
        name = d[c["name"]].reshape(t, spans)
        kind = c["kind"].reshape(t, spans)
        hexes = np.array(self.trace_hex, dtype=object)

        def hit(mask) -> set:
            return set(hexes[mask.any(axis=1)])

        self.tag_hits = hit(service == "cart")
        self.dur_hits = hit(dur >= 990_000_000)
        self.filter_hits = hit((c["http_status"].reshape(t, spans) == 500)
                               & (dur > 900_000_000))
        # chain: span j descends from every span i < j of its trace
        lhs = (kind == KIND_SERVER) & (name == "render")
        rhs = (kind == KIND_CLIENT) & (dur > 900_000_000)
        anc = np.cumsum(lhs, axis=1) - lhs  # matching strict ancestors
        self.struct_hits = hit(rhs & (anc > 0))
        # rate() by (name) over the cart spans; every span of a series
        cart = service == "cart"
        self.rate_counts = {nm: int(((name == nm) & cart).sum())
                            for nm in np.unique(name[cart])}
        self.standing_counts = {nm: int((name == nm).sum()) for nm in np.unique(name)}
        self.durations = dur.ravel()
        # critical path of a chain: self time = own duration minus the
        # one child's, floored at 0; the path's total is their sum
        child = np.concatenate([dur[:, 1:], np.zeros((t, 1), np.int64)], axis=1)
        self_ns = np.maximum(dur - child, 0).sum(axis=1)
        self.cp_traces = t
        self.cp_ns = {s: int(self_ns[service[:, 0] == s].sum())
                      for s in np.unique(service[:, 0])}


    def span_ids(self, trace_hex: str) -> set:
        """The pushed span ids (8 raw bytes each) of one trace."""
        rows = self._span_id[self._row_of[trace_hex]].astype(">u4")
        return {r.tobytes() for r in rows}


class Expect:
    """What the store should answer while it holds `refs` as separate
    blocks (one Reference per block)."""

    def __init__(self, *refs: Reference):
        self.refs = refs

    def _union(self, attr) -> set:
        return set().union(*(getattr(r, attr) for r in self.refs))

    def _sum(self, attr) -> dict:
        out: dict = {}
        for r in self.refs:
            for k, v in getattr(r, attr).items():
                out[k] = out.get(k, 0) + v
        return out

    tag_hits = property(lambda self: self._union("tag_hits"))
    dur_hits = property(lambda self: self._union("dur_hits"))
    filter_hits = property(lambda self: self._union("filter_hits"))
    struct_hits = property(lambda self: self._union("struct_hits"))
    rate_counts = property(lambda self: self._sum("rate_counts"))
    cp_ns = property(lambda self: self._sum("cp_ns"))
    cp_traces = property(lambda self: sum(r.cp_traces for r in self.refs))
    durations = property(lambda self: np.concatenate([r.durations for r in self.refs]))


# ---------------------------------------------------------------------------
# the child server
# ---------------------------------------------------------------------------


CONFIG = """\
target: all
server:
  http_listen_address: 127.0.0.1
  http_listen_port: {port}
storage:
  trace:
    backend: local
    backend_path: {dir}/blocks
    wal_path: {dir}/wal
    compaction:
      cycle_s: {cycle_s}
ingester:
  # no idle cuts: each half is cut once, at /flush, so the standing
  # folds' lengths (and with them the compile cache's keys) do not
  # follow the sweep's timing
  max_trace_idle_s: 3600
overrides:
  defaults:
    # the whole corpus arrives in a minute or two from one client
    max_traces_per_user: 1000000
    ingestion_rate_limit_bytes: 1000000000
    ingestion_burst_size_bytes: 1000000000
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    def __init__(self, workdir: str, cpu_dry_run: bool, cycle_s: float):
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        cfg = os.path.join(workdir, "tempo.yaml")
        with open(cfg, "w") as f:
            f.write(CONFIG.format(port=self.port, dir=workdir, cycle_s=cycle_s))
        self.log_path = os.path.join(workdir, "server.log")
        self._log = open(self.log_path, "w")
        env = dict(os.environ)  # passed through untouched: no platform pin
        if cpu_dry_run:
            env["JAX_PLATFORMS"] = "cpu"  # the one explicit opt-in
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu", "-target=all", f"-config.file={cfg}"],
            stdout=self._log, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
        )

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None, timeout: float = 600.0):
        req = urllib.request.Request(self.url + path, data=body, method=method,
                                     headers=headers or {})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()

    def get_json(self, path: str, params: dict | None = None):
        if params:
            path += "?" + urllib.parse.urlencode(params)
        try:
            status, body = self.request("GET", path)
        except urllib.error.HTTPError as e:  # urlopen raises on 4xx/5xx
            raise SmokeFailure(f"GET {path} -> {e.code}") from e
        check(status == 200, f"GET {path} -> {status}")
        return json.loads(body)

    def wait_ready(self, timeout: float) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited rc={self.proc.returncode} before /ready:\n"
                    + self.log_tail())
            try:
                if self.request("GET", "/ready", timeout=2)[0] == 200:
                    return
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
        raise SmokeFailure(f"server not ready after {timeout:.0f}s:\n" + self.log_tail())

    def metrics(self) -> dict:
        """{'name{labels}': value} of the child's /metrics."""
        out = {}
        for line in self.request("GET", "/metrics")[1].decode().splitlines():
            if line and not line.startswith("#"):
                key, _, val = line.rpartition(" ")
                out[key] = float(val)
        return out

    def log_tail(self, n: int = 40) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def shutdown(self, timeout: float = 120.0) -> int:
        self.request("POST", "/shutdown", b"")
        return self.proc.wait(timeout=timeout)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


# ---------------------------------------------------------------------------
# Pallas certification (runs in its own short-lived process)
# ---------------------------------------------------------------------------

# Must not run in this process: it imports jax. It finishes and exits
# before the server child starts, so the chip only ever has one holder.
CERTIFY = """
import json
import numpy as np, jax, jax.numpy as jnp
from tempo_tpu.ops import pallas_kernels as pk
interpret = jax.default_backend() != "tpu"
rng = np.random.default_rng(0)
n_pad, n = 32768, 30000
cols = [rng.integers(0, 50, n).astype(np.uint32) for _ in range(3)]
sets = [np.array([1, 2, 3], np.uint32), np.array([7], np.uint32),
        np.arange(40, dtype=np.uint32)]
mat = np.full((3, n_pad), pk.NO_MATCH_CODE, np.uint32)
codes = np.full((3, 64), pk.NO_MATCH_CODE, np.uint32)
for c in range(3):
    mat[c, :n] = cols[c]
    codes[c, :len(sets[c])] = sets[c]
got = np.asarray(pk._in_set_call(jnp.asarray(mat), jnp.asarray(codes), interpret))[:n]
want = np.ones(n, bool)
for c in range(3):
    want &= np.asarray(jnp.isin(jnp.asarray(cols[c]), jnp.asarray(sets[c])))
in_set = bool((got.astype(bool) == want).all())
v = rng.integers(0, 2**40, n_pad).astype(np.uint64)
lo_b, hi_b = 2**33, 2**39
hi, lo = (v >> np.uint64(32)).astype(np.uint32), (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
b = np.array([lo_b >> 32, lo_b & 0xFFFFFFFF, hi_b >> 32, hi_b & 0xFFFFFFFF], np.uint32)
got = np.asarray(pk._range_call(jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(b), interpret))
h, l = jnp.asarray(hi), jnp.asarray(lo)
want = np.asarray(((h > b[0]) | ((h == b[0]) & (l >= b[1])))
                  & ((h < b[2]) | ((h == b[2]) & (l <= b[3]))))
rng_ok = bool((got.astype(bool) == want).all())
print(json.dumps({"platform": jax.default_backend(), "interpret": interpret,
                  "in_set_scan": in_set, "u64_range_scan": rng_ok}))
"""


def certify_scan_kernels(cpu_dry_run: bool) -> dict:
    env = dict(os.environ)
    if cpu_dry_run:
        env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", CERTIFY], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    check(out.returncode == 0,
          f"Pallas certification process failed rc={out.returncode}:\n{out.stderr[-3000:]}")
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    backend.check_measurable(doc["platform"], cpu_ok=cpu_dry_run)
    check(doc["in_set_scan"] and doc["u64_range_scan"],
          f"Pallas scan kernel disagrees with its jnp twin: {doc}")
    return doc


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.spans = args.spans_per_trace
        self.phases: list[tuple[str, float]] = []
        self.child: Child | None = None
        now = int(time.time())
        # data sits 10 minutes back on a step boundary: inside retention,
        # inside the standing window, all in one compaction window
        self.base_s = (now // STEP_S) * STEP_S - 600
        self.range = {"start": self.base_s - STEP_S, "end": self.base_s + 2 * STEP_S,
                      "step": STEP_S}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        self.phases.append((name, dt))
        print(f"[smoke] {name}: {dt:.2f}s (single observation)", flush=True)

    def took(self, prefix: str) -> float:
        return sum(dt for name, dt in self.phases if name.startswith(prefix))

    def clear_of_compactor_tick(self, need_s: float) -> None:
        """The compactor's loop ticks every cycle_s from server start
        and merges whatever pair of blocks it finds. Two blocks must be
        queried BEFORE that merge, so the second half only starts when
        `need_s` (its ingest + flush + query pass, estimated from the
        first half's own times) fits before the next tick; otherwise
        that tick — which finds one block and does nothing — is waited
        out first. The merge then happens at the tick after the pass."""
        cycle = self.args.cycle_s
        guard = min(5.0, cycle / 4)
        since = time.time() - self.t_loops
        to_tick = cycle - since % cycle
        fits = need_s + guard <= to_tick
        print(f"[smoke] next compactor tick in {to_tick:.0f}s, second half needs "
              f"~{need_s:.0f}s: {'fits' if fits else 'waiting the tick out'}", flush=True)
        if need_s + 2 * guard > cycle:
            print(f"[smoke]   (needs more than one {cycle:.0f}s cycle: the merge may "
                  "overtake the two-block pass; raise --cycle-s)", flush=True)
        if not fits:
            with self.phase("wait for a compactor tick to pass"):
                time.sleep(to_tick + guard)

    # -- ingest ------------------------------------------------------------
    def push(self, batch: SpanBatch) -> None:
        """POST the batch as OTLP/HTTP protobuf, PUSH_TRACES traces per
        request; requests encode on a helper thread while the previous
        one is in flight."""
        rows = PUSH_TRACES * self.spans
        bodies: queue.Queue = queue.Queue(maxsize=4)

        def encode():
            try:
                for i in range(0, batch.num_spans, rows):
                    part = batch.select(np.arange(i, min(batch.num_spans, i + rows)))
                    bodies.put(otlp.encode_traces_request(batch_to_traces(part)))
                bodies.put(None)
            except BaseException as e:  # surfaces in the consumer
                bodies.put(e)

        t = threading.Thread(target=encode, daemon=True)
        t.start()
        while True:
            body = bodies.get()
            if body is None:
                break
            if isinstance(body, BaseException):
                raise body
            status, _ = self.child.request(
                "POST", "/v1/traces", body,
                {"Content-Type": "application/x-protobuf"})
            check(status == 200, f"push -> {status}")
        t.join()

    def flush(self, want_blocks: int, want_spans: int) -> None:
        status, _ = self.child.request("POST", "/flush", b"")
        check(status == 204, f"/flush -> {status}")
        m = self.child.metrics()
        blocks = m.get(f'tempodb_blocklist_length{{tenant="{TENANT}"}}')
        flushed = m.get(f'tempo_ingester_blocks_flushed_total{{tenant="{TENANT}"}}')
        check(flushed == want_blocks,
              f"{flushed} blocks flushed so far, expected {want_blocks} "
              "(an early cut split a half into several blocks)")
        print(f"[smoke]   blocklist_length={blocks} blocks_flushed={flushed} "
              f"(+{want_spans} spans)", flush=True)

    # -- queries -----------------------------------------------------------
    def compactions(self) -> int:
        return int(self.child.metrics().get(
            f'tempodb_compaction_runs_total{{tenant="{TENANT}"}}', 0))

    def search_hits(self, params: dict) -> set:
        doc = self.child.get_json("/api/search", {**params, "limit": HIT_LIMIT})
        ids = [t["traceID"] for t in doc["traces"]]
        check(len(ids) == len(set(ids)), "search returned a trace twice")
        return set(ids)

    def query_range(self, q: str) -> dict:
        doc = self.child.get_json("/api/metrics/query_range", {"q": q, **self.range})
        check(doc["status"] == "success", f"query_range status {doc['status']}")
        return doc["data"]["result"]

    @staticmethod
    def _counts_by(result: list, label: str) -> dict:
        """rate() series -> spans per label value (rate x step, summed)."""
        out = {}
        for s in result:
            total = sum(float(v[1]) for v in s["values"]) * STEP_S
            check(abs(total - round(total)) < 1e-3, f"non-integral span count {total}")
            if round(total):
                out[s["metric"][label]] = int(round(total))
        return out

    def run_queries(self, tag: str, exp: Expect, find: list, standing: dict,
                    spans_of: dict, structural: bool = True) -> None:
        """One pass over the whole query set; every answer is compared
        with `exp`. The answers that count duplicates (rate, critical
        path) go first: they are the ones a compaction changes, so the
        two-block pass asks them before the merge can finish."""
        with self.phase(f"{tag} query_range rate by name"):
            got = self._counts_by(self.query_range(Q_RATE), "name")
            check(got == exp.rate_counts, f"rate() by (name): {got} != {exp.rate_counts}")
        with self.phase(f"{tag} query_range rate, compiled tier"):
            res = self.query_range(Q_RATE_FUSED)
            got = round(sum(float(v[1]) for s in res for v in s["values"]) * STEP_S, 3)
            check(got == sum(exp.rate_counts.values()),
                  f"rate(): {got} != {sum(exp.rate_counts.values())}")
        with self.phase(f"{tag} query_range quantiles"):
            res = self.query_range(Q_QUANT)
            durs = exp.durations
            for q in (0.5, 0.99):
                vals = [float(v[1]) for s in res for v in s["values"]
                        if float(s["metric"].get("p", -1)) == q and float(v[1]) > 0]
                check(len(vals) == 1, f"quantile {q}: {len(vals)} non-empty steps, expected 1")
                true = float(np.quantile(durs, q)) / 1e9
                check(abs(vals[0] - true) <= QUANTILE_REL_ERR * true,
                      f"quantile {q}: {vals[0]} vs reference {true}")
        with self.phase(f"{tag} critical path"):
            doc = self.child.get_json("/api/graph/critical-path",
                                      {"q": "{}", "by": "service",
                                       "start": self.range["start"], "end": self.range["end"]})
            got = {g["name"]: g["seconds"] for g in doc["groups"]}
            want = {k: round(v / 1e9, 6) for k, v in exp.cp_ns.items()}
            check(doc["traces"] == exp.cp_traces and got == want,
                  f"critical path: traces {doc['traces']} vs {exp.cp_traces}, {got} != {want}")
        with self.phase(f"{tag} standing query"):
            doc = self.child.get_json(f"/api/metrics/standing/{standing['id']}")
            got = self._counts_by(doc["data"]["result"], "name")
            check(got == standing["want"], f"standing: {got} != {standing['want']}")
            state = self.child.get_json(f"/api/metrics/standing/{standing['id']}/state")
            check(state, "standing state is empty")
        for name, params, want in (
            ("tag search", Q_TAG, exp.tag_hits),
            ("minDuration search", Q_DUR, exp.dur_hits),
            ("traceql filter", Q_FILTER, exp.filter_hits),
            ("traceql structural", Q_STRUCT, exp.struct_hits),
        ):
            if name == "traceql structural" and not structural:
                print(f"[smoke] {tag} {name}: NOT ASKED — while a trace sits in two "
                      "blocks the engine re-runs a structural query on the object "
                      "engine over every trace (db.traceql_search), which at this "
                      "size outlasts the 60 s job timeout; asked with one block and "
                      "after compaction", flush=True)
                continue
            with self.phase(f"{tag} {name}"):
                got = self.search_hits(params)
                check(got == want, f"{name}: {len(got)} hits, reference {len(want)} "
                                   f"(missing {len(want - got)}, extra {len(got - want)})")
                check(want, f"{name}: the reference hit set is empty — nothing checked")
        with self.phase(f"{tag} find-by-id x{len(find)}"):
            for h in find:
                status, body = self.child.request(
                    "GET", f"/api/traces/{h}", headers={"Accept": "application/protobuf"})
                check(status == 200, f"find {h} -> {status}")
                got = {s.span_id for t in otlp.decode_traces_request(body)
                       for _, spans in t.batches for s in spans}
                check(got == spans_of[h],
                      f"trace {h}: {len(got)} spans back, {len(spans_of[h])} pushed")

    # -- verdict -----------------------------------------------------------
    def verdict(self, device: dict) -> None:
        m = self.child.metrics()

        def ran(label: str) -> float:
            return (m.get(f'tempo_tpu_device_dispatches_total{{kernel="{label}"}}', 0)
                    + m.get('tempo_tpu_device_transfer_bytes_total'
                            f'{{direction="h2d",kernel="{label}"}}', 0))

        counters = {k: v for k, v in m.items()
                    if k.startswith(("tempo_tpu_device_dispatches_total",
                                     "tempo_tpu_ingest_encode_fallback_total",
                                     "tempo_tpu_compiled_errors_total",
                                     "tempodb_compaction_errors_total"))}
        print(f"[smoke] counters: {json.dumps(counters, sort_keys=True)}", flush=True)
        if device["platform"] == "tpu":
            groups = REQUIRED_KERNELS
            if device["device_count"] > 1:
                groups += REQUIRED_KERNELS_MESH
            for group in groups:
                check(any(ran(k) > 0 for k in group),
                      f"no device dispatch for any of {group}: the chip did not do that work")
        for name in ("tempo_tpu_ingest_encode_fallback_total",
                     "tempo_tpu_compiled_errors_total",
                     "tempodb_compaction_errors_total"):
            bad = {k: v for k, v in m.items() if k.startswith(name) and v}
            check(not bad, f"absorbed device failures: {bad}")
        check("tempo_tpu_compiled_errors_total" in m, "compiled error counter not exposed")

    def check_log(self) -> None:
        with open(self.child.log_path, errors="replace") as f:
            bad = [ln.rstrip() for ln in f
                   if " ERROR " in ln or " CRITICAL " in ln or "Traceback (most recent" in ln]
        check(not bad, "server log holds errors:\n" + "\n".join(bad[:20]))

    # -- main sequence -------------------------------------------------------
    def run(self) -> dict:
        args = self.args
        n = args.traces
        check(n % 8 == 0 and n >= 64, "--traces must be a multiple of 8, >= 64")
        if n != 65536 or self.spans != 16:
            print(f"[smoke] SIZE CUT: {n} traces x {self.spans} spans "
                  "(full size is 65536 x 16)", flush=True)
        with self.phase("generate corpus + reference"):
            a, b, union = make_corpus(args.seed, n, self.spans, self.base_s)
            ref_a, ref_b, ref_u = (Reference(x, self.spans) for x in (a, b, union))
            rng = np.random.default_rng(args.seed)
            # both halves, re-sent traces included (a's first quarter)
            k = min(N_FIND // 2, n // 2)
            find_a = list(rng.choice(ref_a.trace_hex, k, replace=False))
            find_b = list(rng.choice(ref_b.trace_hex, k, replace=False))
            c = types.SimpleNamespace(
                a=a, b=b, ref_a=ref_a, ref_b=ref_b, ref_u=ref_u,
                find_a=find_a, find_b=find_b,
                spans_of={**{h: ref_a.span_ids(h) for h in find_a},
                          **{h: ref_b.span_ids(h) for h in find_b}},
                standing_ab=Expect(ref_a, ref_b)._sum("standing_counts"),
                total=a.num_spans + b.num_spans)
        print(f"[smoke] corpus: {n} traces x {self.spans} spans = {c.total} spans pushed "
              f"({union.num_spans} after de-duplication), seed {args.seed}", flush=True)

        with self.phase("certify pallas scan kernels"):
            cert = certify_scan_kernels(args.cpu_dry_run)
            print(f"[smoke]   {cert}", flush=True)

        workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            return self._serve(workdir, c)
        except Exception:
            if self.child is not None:  # before the scratch dir goes
                print("[smoke] server log tail:\n" + self.child.log_tail(),
                      file=sys.stderr)
            raise
        finally:
            if self.child is not None:
                self.child.kill()
            if args.keep_dir:
                print(f"[smoke] kept {workdir}", flush=True)
            else:
                shutil.rmtree(workdir, ignore_errors=True)

    def _serve(self, workdir: str, c) -> dict:
        args = self.args
        a, b, ref_a, ref_b, ref_u = c.a, c.b, c.ref_a, c.ref_b, c.ref_u
        find_a, find_b, spans_of = c.find_a, c.find_b, c.spans_of
        with self.phase("server start"):
            self.child = Child(workdir, args.cpu_dry_run, args.cycle_s)
            self.child.wait_ready(300)
        self.t_loops = time.time()  # start_loops() follows /ready within ms
        dev = self.child.get_json("/status/device")["backend"]
        print(f"[smoke] backend: platform={dev['platform']} device_kind={dev['device_kind']} "
              f"device_count={dev['device_count']} pallas={dev['pallas']} "
              f"native_codec={dev['native_codec']} default_codec={dev['default_codec']} "
              f"compile_cache_dir={dev['compile_cache_dir'] or '<off>'}", flush=True)
        backend.check_measurable(dev["platform"], cpu_ok=args.cpu_dry_run)
        check(dev["native_codec"] and dev["default_codec"] == "zstd_shuffle",
              "the native codec did not build on this machine: pages would "
              f"silently be {dev['default_codec']}")

        doc = json.loads(self.child.request(
            "POST", "/api/metrics/standing",
            json.dumps({"q": Q_STANDING, "step": STEP_S, "window": 3600}).encode(),
            {"Content-Type": "application/json"})[1])
        standing = {"id": doc["id"], "want": ref_a.standing_counts}

        with self.phase("ingest first half"):
            self.push(a)
        with self.phase("flush first half"):
            self.flush(1, a.num_spans)
        # one block cannot compact: this pass is before compaction by
        # construction, and pays every cold compile
        for temp in ("cold", "warm"):
            self.run_queries(f"[1 block, {temp}]", Expect(ref_a), find_a, standing, spans_of)

        self.clear_of_compactor_tick(
            1.25 * (self.took("ingest first") + self.took("flush first"))
            + 2 * self.took("[1 block, warm]"))
        with self.phase("ingest second half"):
            self.push(b)
        with self.phase("flush second half"):
            self.flush(2, b.num_spans)
        standing["want"] = c.standing_ab
        # the compactor's own loop picks the pair up at its next tick,
        # which clear_of_compactor_tick put after this pass. Should the
        # estimate have been short and the merge swap the blocklist
        # mid-pass, a mismatch is reported as overtaken, not as passed —
        # and only counts as a failure while the counter still reads 0
        overtaken = False
        try:
            self.run_queries("[2 blocks]", Expect(ref_a, ref_b), find_a + find_b,
                             standing, spans_of, structural=False)
        except SmokeFailure as e:
            if not self.compactions():
                raise
            overtaken = True
            print(f"[smoke] two-block pass OVERTAKEN by compaction at: {e}", flush=True)

        with self.phase("compaction (compactor loop, 2 blocks -> 1)"):
            deadline = time.time() + 900
            while True:
                m = self.child.metrics()
                if (m.get(f'tempodb_compaction_runs_total{{tenant="{TENANT}"}}', 0) >= 1
                        and m.get(f'tempodb_blocklist_length{{tenant="{TENANT}"}}') == 1):
                    break
                check(time.time() < deadline, "no compaction within 900s")
                check(self.child.proc.poll() is None, "server died during compaction")
                time.sleep(1.0)
        for temp in ("cold", "warm"):
            self.run_queries(f"[compacted, {temp}]", Expect(ref_u), find_a + find_b,
                             standing, spans_of)

        after = self.child.get_json("/status/device")["backend"]
        mem = [{k: d.get(k) for k in ("id", "bytes_in_use", "peak_bytes_in_use")}
               for d in after["devices"]]
        print(f"[smoke] device memory: {json.dumps(mem)}", flush=True)
        if dev["platform"] == "tpu":
            check(all((d.get("peak_bytes_in_use") or 0) > 0 for d in after["devices"]),
                  f"a device never held a byte: {mem}")
        self.verdict(dev)
        with self.phase("shutdown"):
            rc = self.child.shutdown()
        check(rc == 0, f"server exited rc={rc}")
        self.check_log()

        cache_dir = dev["compile_cache_dir"]
        entries = len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0
        print(f"[smoke] compile cache: {entries} entries in {cache_dir or '<off>'} "
              "(single observation)", flush=True)
        summary = {
            "spans_pushed": c.total,
            "two_block_pass": "overtaken" if overtaken else "checked",
            "cpu_dry_run": bool(args.cpu_dry_run),
            "compile_cache_entries": entries,
        }
        print(f"[smoke] summary: {json.dumps(summary)}", flush=True)
        # the result line: these keys and no others
        return {
            "ok": True,
            "device": {"platform": str(dev["platform"]), "kind": str(dev["device_kind"]),
                       "count": int(dev["device_count"])},
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--traces", type=int, default=65536,
                    help="traces in total over both halves (a cut is printed)")
    ap.add_argument("--spans-per-trace", type=int, default=16)
    ap.add_argument("--cycle-s", type=float, default=120.0,
                    help="compaction.cycle_s of the generated config: longer "
                         "than the second half's ingest + flush + query pass")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="pin the child to JAX_PLATFORMS=cpu and accept it: "
                         "results only, nothing here is a device number")
    ap.add_argument("--keep-dir", action="store_true",
                    help="keep the scratch directory (config, log, blocks)")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    smoke = Smoke(args)
    try:
        result = smoke.run()
        check("jax" not in sys.modules, "the smoke parent imported jax")
    except Exception as e:  # any failed phase: nonzero, and no result line
        print(f"chip_smoke.py: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] total {time.perf_counter() - t0:.1f}s (single observation)", flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
