"""Sustained mixed-workload load test against a real tempo-tpu cluster.

Reference: integration/bench/load_test.go:19 runs k6 against an
all-in-one deployment with scripted thresholds
(smoke_test.js:39-45: write success >99%, read success >90%,
p99 < 1.5s). This is that harness natively, grown into the overload
rig ROADMAP item 5 asked for: it spawns a cluster of
`python -m tempo_tpu` OS processes (distributor + RF=2 ingesters +
query-frontend/querier sharing a ring over the netkv control plane),
sweeps one trace through EVERY ingest protocol, then drives a MIXED
workload — ingest + trace-by-ID find + live-tail search + historical
search + TraceQL metrics query_range — at `--rate` times the seed rate
for --duration seconds, and emits ONE JSON line whose `slo` section is
a machine-checkable gate:

- per-op latency percentiles (p50/p90/p99) vs thresholds,
- per-op error rate vs threshold (sheds are NOT errors),
- every shed response must carry a retry hint (429 + Retry-After) —
  `shed_without_hint` must be 0,
- zero acknowledged-span loss: a sample of acked writes must be
  queryable after the drain,
- bounded RSS: per-process RSS is sampled through the run and the
  final-quarter mean must not exceed `--rss-growth-limit` times the
  second-quarter mean (monotonic growth under sustained load = leak),
- `--vulture`: the continuous-verification prober (tempo_tpu/vulture.py)
  runs beside the workload over real HTTP and the run gates on
  read-after-write correctness at drain (zero notfound / missing /
  incorrect probes) plus the write->searchable freshness SLO.

Exit code is nonzero on any gate breach, so CI can use the rig as-is.

A CPU-ONLY harness: every cluster process is started with
JAX_PLATFORMS=cpu (a chip belongs to one process, and this rig runs
several), the summary line says `"platform": "cpu"`, and no rate it
prints is a device metric. The served path on a chip is driven by
`chip_smoke.py` (one `-target=all` process).

Usage:
  python tools/loadtest.py --duration 120 --rate 10
  python tools/loadtest.py --url http://host:3200 ...   # existing cluster
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cfg(tmp, target, port, instance, kv_url, grpc_port=0, extra="",
         multitenant=False):
    grpc = f"\n  grpc_listen_port: {grpc_port}" if grpc_port else ""
    mt = "multitenancy_enabled: true\n" if multitenant else ""
    return f"""
{mt}target: {target}
server:
  http_listen_address: 127.0.0.1
  http_listen_port: {port}{grpc}
storage:
  trace:
    backend: local
    backend_path: {tmp}/blocks
    wal_path: {tmp}/wal
    blocklist_poll_s: 5
replication_factor: 2
instance_id: {instance}
ring_kv_url: {kv_url}
advertise_addr: http://127.0.0.1:{port}
ring_heartbeat_timeout_s: 10
ingester:
  max_trace_idle_s: 1.0
  flush_check_period_s: 1.0
  max_block_duration_s: 5.0
metrics_generator:
  enabled: false
{extra}
"""


class Proc:
    def __init__(self, tmp, target, name, kv_url, grpc_port=0, extra="",
                 multitenant=False, env_extra=None):
        self.name = name
        self.port = _free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        cfg_path = f"{tmp}/{name}.yaml"
        with open(cfg_path, "w") as f:
            f.write(_cfg(tmp, target, self.port, name, kv_url, grpc_port, extra,
                         multitenant=multitenant))
        self.log = open(f"{tmp}/{name}.log", "w")
        env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu", f"-config.file={cfg_path}"],
            stdout=self.log, stderr=subprocess.STDOUT, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )

    def wait_ready(self, timeout=90):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(f"{self.name} exited rc={self.proc.returncode}")
            try:
                with urllib.request.urlopen(self.url + "/ready", timeout=2) as r:
                    if r.status == 200:
                        return self
            except (urllib.error.URLError, OSError):
                time.sleep(0.3)
        raise TimeoutError(f"{self.name} not ready")

    def terminate(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.log.close()


def start_cluster(tmp: str, grpc_port: int = 0,
                  multitenant: bool = False,
                  extra: str = "",
                  env_extra: dict | None = None) -> tuple[list[Proc], Proc, Proc]:
    """-> (all procs, frontend/query entry, distributor entry).

    The frontend hosts the ring KV service ("local") and every other
    role joins through it — the same bootstrap the multi-process e2e
    test uses. `extra` is appended to every process's config (the --hot
    arm uses it to enable the device-resident tier fleet-wide);
    `env_extra` lands in every process's environment (the
    --ingest-heavy arm arms TEMPO_TPU_DEVICE_ENCODE fleet-wide)."""
    front = Proc(tmp, "query-frontend", "front", kv_url="local",
                 multitenant=multitenant, extra=extra, env_extra=env_extra)
    front.wait_ready()
    kv_url = front.url
    procs = [front]
    procs.append(Proc(tmp, "ingester", "ing-a", kv_url, multitenant=multitenant,
                      extra=extra, env_extra=env_extra))
    procs.append(Proc(tmp, "ingester", "ing-b", kv_url, multitenant=multitenant,
                      extra=extra, env_extra=env_extra))
    dist = Proc(tmp, "distributor", "dist", kv_url, grpc_port=grpc_port,
                multitenant=multitenant, extra=extra, env_extra=env_extra)
    procs.append(dist)
    procs.append(Proc(tmp, "querier", "querier", kv_url,
                      extra=f"frontend_address: {kv_url}\n" + extra,
                      multitenant=multitenant, env_extra=env_extra))
    for p in procs[1:]:
        p.wait_ready()
    time.sleep(1.0)  # let ring heartbeats settle
    return procs, front, dist


# ---------------------------------------------------------------------------
# receiver sweep: one trace through every ingest protocol
# ---------------------------------------------------------------------------


def _post(url, path, body, ct, headers=None):
    req = urllib.request.Request(
        url + path, data=body, method="POST",
        headers={"Content-Type": ct, **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=15) as r:
        return r.status


def receiver_sweep(dist_url: str, query_url: str, grpc_port: int = 0) -> dict:
    """Returns {protocol: 'ok'|'skipped'|error string}; each protocol
    must land a queryable trace (reference: receivers e2e test,
    integration/e2e/receivers_test.go:35)."""
    import random
    import struct

    from tempo_tpu.model import synth
    from tempo_tpu.receivers import jaeger, otlp

    results: dict = {}
    sent: dict[str, bytes] = {}
    seed0 = random.randint(1, 1 << 30)

    def one_trace(i):
        (t,) = synth.make_traces(1, seed=seed0 + i, spans_per_trace=3)
        return t

    # OTLP HTTP protobuf
    t = one_trace(1)
    try:
        _post(dist_url, "/v1/traces", otlp.encode_traces_request([t]), "application/x-protobuf")
        sent["otlp_http_proto"] = t.trace_id
    except Exception as e:
        results["otlp_http_proto"] = f"error: {e}"
    # OTLP HTTP JSON
    t = one_trace(2)
    try:
        _post(dist_url, "/v1/traces", json.dumps(otlp.encode_traces_json([t])).encode(),
              "application/json")
        sent["otlp_http_json"] = t.trace_id
    except Exception as e:
        results["otlp_http_json"] = f"error: {e}"
    # Zipkin JSON (the v2 list-of-spans shape)
    t = one_trace(3)
    try:
        spans_json = []
        for span in t.all_spans():
            spans_json.append({
                "traceId": t.trace_id.hex(),
                "id": span.span_id.hex(),
                "parentId": span.parent_span_id.hex() if span.parent_span_id != b"\x00" * 8 else None,
                "name": span.name,
                "timestamp": span.start_unix_nano // 1000,
                "duration": max(1, span.duration_nano // 1000),
                "localEndpoint": {"serviceName": t.batches[0][0].get("service.name", "svc")},
                "tags": {},
            })
        _post(dist_url, "/api/v2/spans", json.dumps(spans_json).encode(), "application/json")
        sent["zipkin_json"] = t.trace_id
    except Exception as e:
        results["zipkin_json"] = f"error: {e}"
    # Jaeger thrift-binary batch (minimal writer, mirrors the decoder's
    # field ids in receivers/jaeger.py)
    t = one_trace(4)
    try:
        def tstr(out, fid, s):
            b = s.encode()
            out += struct.pack(">bh", jaeger.T_STRING, fid) + struct.pack(">i", len(b)) + b

        def ti64(out, fid, v):
            out += struct.pack(">bhq", jaeger.T_I64, fid, v)

        def tstruct_spans(trace):
            spans_b = bytearray()
            for span in trace.all_spans():
                s = bytearray()
                tid_hi = int.from_bytes(trace.trace_id[:8], "big", signed=False)
                tid_lo = int.from_bytes(trace.trace_id[8:], "big", signed=False)
                ti64(s, 1, tid_lo - (1 << 64) if tid_lo >= 1 << 63 else tid_lo)
                ti64(s, 2, tid_hi - (1 << 64) if tid_hi >= 1 << 63 else tid_hi)
                sid = int.from_bytes(span.span_id, "big", signed=False)
                ti64(s, 3, sid - (1 << 64) if sid >= 1 << 63 else sid)
                pid = int.from_bytes(span.parent_span_id, "big", signed=False)
                ti64(s, 4, pid - (1 << 64) if pid >= 1 << 63 else pid)
                tstr(s, 5, span.name)
                ti64(s, 8, span.start_unix_nano // 1000)
                ti64(s, 9, max(1, span.duration_nano // 1000))
                s.append(jaeger.T_STOP)
                spans_b += s
            return spans_b, sum(1 for _ in trace.all_spans())

        batch = bytearray()
        proc = bytearray()
        tstr(proc, 1, t.batches[0][0].get("service.name", "svc"))
        proc.append(jaeger.T_STOP)
        batch += struct.pack(">bh", jaeger.T_STRUCT, 1) + proc
        spans_b, n = tstruct_spans(t)
        batch += struct.pack(">bh", jaeger.T_LIST, 2)
        batch += struct.pack(">bi", jaeger.T_STRUCT, n)
        batch += spans_b
        batch.append(jaeger.T_STOP)
        _post(dist_url, "/api/traces", bytes(batch), "application/vnd.apache.thrift.binary")
        sent["jaeger_thrift"] = t.trace_id
    except Exception as e:
        results["jaeger_thrift"] = f"error: {e}"

    # gRPC receivers (OTLP unary + OpenCensus stream; Jaeger rides its
    # HTTP thrift form above)
    if grpc_port:
        try:
            import grpc

            from tempo_tpu.receivers.grpc_server import OTLP_EXPORT_METHOD

            chan = grpc.insecure_channel(f"127.0.0.1:{grpc_port}")
            t = one_trace(5)
            chan.unary_unary(OTLP_EXPORT_METHOD,
                             request_serializer=lambda b: b,
                             response_deserializer=lambda b: b)(
                otlp.encode_traces_request([t]), timeout=15)
            sent["otlp_grpc"] = t.trace_id
        except ImportError:
            results["otlp_grpc"] = "skipped"
        except Exception as e:
            results["otlp_grpc"] = f"error: {e}"
        try:
            import grpc

            from tempo_tpu.receivers.grpc_server import OPENCENSUS_EXPORT_METHOD
            from tempo_tpu.receivers import protowire

            # minimal OC request for the sweep
            t = one_trace(7)
            span0 = next(iter(t.all_spans()))
            body = bytearray()
            sp = bytearray()
            protowire.put_bytes_field(sp, 1, span0.trace_id)
            protowire.put_bytes_field(sp, 2, span0.span_id)
            name = bytearray()
            protowire.put_str_field(name, 1, span0.name)
            protowire.put_bytes_field(sp, 4, bytes(name))
            ts = bytearray()
            protowire.put_varint_field(ts, 1, span0.start_unix_nano // 10**9)
            protowire.put_varint_field(ts, 2, span0.start_unix_nano % 10**9)
            protowire.put_bytes_field(sp, 5, bytes(ts))
            te = bytearray()
            end = span0.start_unix_nano + span0.duration_nano
            protowire.put_varint_field(te, 1, end // 10**9)
            protowire.put_varint_field(te, 2, end % 10**9)
            protowire.put_bytes_field(sp, 6, bytes(te))
            protowire.put_bytes_field(body, 2, bytes(sp))
            chan = grpc.insecure_channel(f"127.0.0.1:{grpc_port}")
            call = chan.stream_stream(OPENCENSUS_EXPORT_METHOD,
                                      request_serializer=lambda b: b,
                                      response_deserializer=lambda b: b)
            list(call(iter([bytes(body)])))
            sent["opencensus_grpc"] = span0.trace_id
        except ImportError:
            results["opencensus_grpc"] = "skipped"
        except Exception as e:
            results["opencensus_grpc"] = f"error: {e}"

    # verify every sent trace is queryable
    deadline = time.time() + 30
    pending = dict(sent)
    while pending and time.time() < deadline:
        for proto, tid in list(pending.items()):
            try:
                req = urllib.request.Request(
                    f"{query_url}/api/traces/{tid.hex()}",
                    headers={"Accept": "application/protobuf"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    if r.status == 200:
                        results[proto] = "ok"
                        del pending[proto]
            except (urllib.error.URLError, OSError):
                pass
        if pending:
            time.sleep(0.5)
    for proto in pending:
        results[proto] = "error: not queryable within 30s"
    return results


# ---------------------------------------------------------------------------
# --standing arm: registered queries folded per cut, gated on O(delta),
# zero read dips during handoff, and usage exactness for kind "standing"
# ---------------------------------------------------------------------------


def _http_json(url, method="GET", body=None, tenant=None, timeout=15):
    headers = {"Content-Type": "application/json"}
    if tenant:
        headers["X-Scope-OrgID"] = tenant
    req = urllib.request.Request(
        url, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read()
        return json.loads(raw) if raw else None


class StandingArm:
    """Registers N standing queries across tenants on the ingester
    processes BEFORE the load, samples each one's pinned-window total
    during the run (a dip = a decrease of a cumulative count), and
    gates at drain on:
      (i) O(delta): per-query spansFolded+spansShed == the process's
          cut-delta spans for that tenant (read from /status/standing),
     (ii) zero standing-read dips across every cut/flush/handoff the
          mixed workload provoked,
    (iii) usage exactness: kind "standing" carries positive per-tenant
          cost wherever folds ran.
    """

    def __init__(self, ingester_urls: list, n: int, tenants: list | None):
        self.regs: list[dict] = []  # {url, id, tenant}
        self.dips = 0
        self.samples = 0
        self._last_total: dict[str, float] = {}
        self._stop = threading.Event()
        self._thread = None
        now = int(time.time())
        self.win_start = (now // 60) * 60 - 60
        self.win_end = self.win_start + 3600
        for i in range(n):
            url = ingester_urls[i % len(ingester_urls)]
            tenant = tenants[i % len(tenants)] if tenants else None
            # window far beyond any soak: the accumulator prunes bins
            # older than its window, and a pruned bin inside the PINNED
            # sampling window would read as a dip that never happened
            doc = _http_json(
                f"{url}/api/metrics/standing", method="POST",
                body={"q": "{} | count_over_time()", "step": 60,
                      "window": 7 * 86400}, tenant=tenant)
            self.regs.append({"url": url, "id": doc["id"], "tenant": tenant})

    def _total(self, reg) -> float | None:
        qs = urllib.parse.urlencode({
            "start": self.win_start, "end": self.win_end, "step": 60})
        try:
            doc = _http_json(f"{reg['url']}/api/metrics/standing/"
                             f"{reg['id']}?{qs}", tenant=reg["tenant"])
        except (urllib.error.URLError, OSError, ValueError):
            return None
        return sum(
            float(v) for series in doc["data"]["result"]
            for _, v in series.get("values", []))

    def _run(self):
        while not self._stop.wait(0.5):
            for reg in self.regs:
                total = self._total(reg)
                if total is None:
                    continue
                self.samples += 1
                last = self._last_total.get(reg["id"])
                # cumulative count over a pinned window: any decrease is
                # a read dip (the PR 11 handoff transient, fixed for
                # standing reads)
                if last is not None and total < last - 1e-9:
                    self.dips += 1
                self._last_total[reg["id"]] = total

    def start(self) -> "StandingArm":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def summary(self) -> dict:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
        # one final post-drain sample per query (folds have quiesced)
        for reg in self.regs:
            total = self._total(reg)
            last = self._last_total.get(reg["id"])
            if total is not None and last is not None and total < last - 1e-9:
                self.dips += 1
        # gate (i): O(delta) — per-query folded spans == the engine's
        # cut-delta spans for that tenant on the same process
        odelta_ok, odelta = True, []
        by_url_status: dict[str, dict] = {}
        for reg in self.regs:
            try:
                st = _http_json(f"{reg['url']}/api/metrics/standing/"
                                f"{reg['id']}/state", tenant=reg["tenant"])
                if reg["url"] not in by_url_status:
                    by_url_status[reg["url"]] = _http_json(
                        f"{reg['url']}/status/standing")
                cut = by_url_status[reg["url"]]["cutSpans"].get(
                    reg["tenant"] or "single-tenant", 0)
                folded = st["stats"]["spansFolded"] + st["stats"]["spansShed"]
                ok = folded == cut and st["stats"]["folds"] > 0
                odelta_ok = odelta_ok and ok
                odelta.append({"id": reg["id"], "url": reg["url"],
                               "folded": folded, "cut": cut,
                               "folds": st["stats"]["folds"], "ok": ok})
            except (urllib.error.URLError, OSError, KeyError, ValueError) as e:
                odelta_ok = False
                odelta.append({"id": reg["id"], "url": reg["url"],
                               "error": str(e)})
        # gate (iii): usage exactness for kind "standing" on every
        # ingester that folded
        usage_ok = True
        for url in {r["url"] for r in self.regs}:
            try:
                rep = _http_json(f"{url}/status/usage")
                folded_here = any(o.get("folds", 0) > 0 and o.get("ok")
                                  and o.get("url") == url for o in odelta)
                if folded_here:
                    rows = [
                        kinds.get("standing", {}).get("inspected_bytes", 0)
                        for kinds in (
                            t["kinds"] for t in rep.get("tenants", {}).values())
                    ]
                    usage_ok = usage_ok and any(b > 0 for b in rows)
            except (urllib.error.URLError, OSError, ValueError):
                usage_ok = False
        return {
            "queries": len(self.regs),
            "samples": self.samples,
            "dips": self.dips,
            "odelta": odelta,
            "odelta_ok": odelta_ok,
            "usage_ok": usage_ok,
            "passed": bool(self.dips == 0 and odelta_ok and usage_ok
                           and self.samples > 0),
        }


def query_range_probe(query_url: str, n: int = 10) -> dict:
    """--query-range arm: drive /api/metrics/query_range against the
    freshly-loaded cluster (rate by service over the last 5 minutes,
    1s step) and require every request to return a well-formed matrix.
    Run AFTER the write load so the ingester live/WAL tail has data."""
    import urllib.parse

    end = int(time.time())
    qs = urllib.parse.urlencode({
        "q": "{} | rate() by (resource.service.name)",
        "start": end - 300, "end": end, "step": 1,
    })
    lat, ok, series = [], 0, 0
    for _ in range(n):
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(
                f"{query_url}/api/metrics/query_range?{qs}", timeout=30
            ) as r:
                doc = json.loads(r.read())
            if (r.status == 200 and doc.get("status") == "success"
                    and doc["data"]["resultType"] == "matrix"):
                ok += 1
                series = max(series, len(doc["data"]["result"]))
        except (urllib.error.URLError, OSError, KeyError, ValueError):
            pass
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return {
        "requests": n,
        "ok": ok,
        "series": series,
        "p50_s": round(lat[len(lat) // 2], 3),
        "max_s": round(lat[-1], 3),
        "passed": bool(ok == n and series > 0),
    }


# ---------------------------------------------------------------------------
# mixed-workload rig: ingest + find + live tail + historical search +
# query_range at --rate x the seed rate, with SLO gates
# ---------------------------------------------------------------------------

# seed-rate targets (ops/s at --rate 1); --rate multiplies the lot.
SEED_RATES = {"write": 20.0, "find": 10.0, "search_live": 2.0,
              "search_hist": 1.0, "query_range": 1.0}

# per-op SLO thresholds: (p99 latency s, max error rate). Sheds are not
# errors — they are the control plane working — but every shed MUST
# carry a retry hint, gated separately via shed_without_hint == 0.
DEFAULT_SLO = {
    "write": (1.5, 0.01),
    "find": (1.5, 0.10),  # includes not-yet-flushed races under load
    "search_live": (3.0, 0.05),
    "search_hist": (3.0, 0.05),
    "query_range": (5.0, 0.05),
}


class OpStats:
    def __init__(self):
        import threading

        self.lock = threading.Lock()
        self.lat: dict[str, list] = {}
        self.counts: dict[str, dict] = {}

    def record(self, op: str, outcome: str, dt: float, hint_ok: bool = True):
        """outcome: ok | shed | error. hint_ok=False marks a shed that
        arrived WITHOUT a Retry-After hint (a gate breach)."""
        with self.lock:
            self.lat.setdefault(op, []).append(dt)
            c = self.counts.setdefault(
                op, {"ok": 0, "shed": 0, "error": 0, "shed_without_hint": 0})
            c[outcome] += 1
            if outcome == "shed" and not hint_ok:
                c["shed_without_hint"] += 1

    def summary(self, slo: dict) -> tuple[dict, bool]:
        with self.lock:
            lat = {op: sorted(v) for op, v in self.lat.items()}
            counts = {op: dict(c) for op, c in self.counts.items()}
        out, passed = {}, True
        for op, c in counts.items():
            ls = lat.get(op, [])
            pct = lambda p: round(ls[min(len(ls) - 1, int(len(ls) * p))], 4) if ls else 0.0
            total = c["ok"] + c["shed"] + c["error"]
            err_rate = c["error"] / total if total else 0.0
            p99_limit, err_limit = slo.get(op, (float("inf"), 1.0))
            gates = {
                "p99": pct(0.99) <= p99_limit,
                "error_rate": err_rate <= err_limit,
                "shed_hints": c["shed_without_hint"] == 0,
            }
            passed = passed and all(gates.values())
            out[op] = {
                "total": total, **c,
                "error_rate": round(err_rate, 4),
                "p50_s": pct(0.50), "p90_s": pct(0.90), "p99_s": pct(0.99),
                "gates": gates,
            }
        return out, passed


def _request(url: str, method: str = "GET", body: bytes | None = None,
             ct: str = "", timeout: float = 60.0, headers: dict | None = None):
    """-> (status, headers dict) — 4xx/5xx come back as a status, not an
    exception, so the callers can classify sheds."""
    h = dict(headers or {})
    if ct:
        h["Content-Type"] = ct
    req = urllib.request.Request(url, data=body, method=method, headers=h)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()
            return r.status, dict(r.headers)
    except urllib.error.HTTPError as e:
        e.read()
        return e.code, dict(e.headers)


def _get_json(url: str, timeout: float = 30.0, headers: dict | None = None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _org(tenant: str | None) -> dict:
    return {"X-Scope-OrgID": tenant} if tenant else {}


def run_mixed_load(write_url: str, query_url: str, duration_s: float,
                   rate: float, spans_per_trace: int = 5,
                   slo: dict | None = None, read_lag_s: float = 2.0,
                   seed: int = 1, tenants: list | None = None):
    """Drive the mixed workload; returns (summary dict, acked
    (tenant, trace-id) list) — acked = writes the cluster ACCEPTED
    (HTTP 200), the set the zero-loss gate verifies after the drain.
    `tenants`: multi-tenant mode — every op carries one of these org
    IDs round-robin by rng, and the attribution gate later verifies the
    per-tenant cost split sums to the untagged ingest counters."""
    import random
    import threading
    import urllib.parse

    from tempo_tpu.receivers import otlp
    from tempo_tpu.model import synth

    slo = slo or DEFAULT_SLO
    stats = OpStats()
    acked: list = []  # (monotonic, trace_id)
    acked_lock = threading.Lock()
    stop = threading.Event()

    def classify(status: int, headers: dict) -> tuple[str, bool]:
        if 200 <= status < 300:
            return "ok", True
        if status == 429:
            return "shed", "Retry-After" in headers
        return "error", True

    def paced_loop(op: str, fn, n_threads: int, ops_s: float):
        interval = n_threads / max(ops_s, 0.001)

        def run(tid: int):
            import zlib

            rng = random.Random(seed * 7919 + zlib.crc32(op.encode()) + tid)
            nxt = time.monotonic() + rng.uniform(0, interval)
            while not stop.is_set():
                delay = nxt - time.monotonic()
                if delay > 0 and stop.wait(min(delay, 0.5)):
                    return
                if time.monotonic() < nxt:
                    continue
                nxt += interval
                t0 = time.monotonic()
                try:
                    outcome, hint_ok = fn(rng)
                except Exception:
                    outcome, hint_ok = "error", True
                stats.record(op, outcome, time.monotonic() - t0, hint_ok)

        return [threading.Thread(target=run, args=(i,), daemon=True, name=f"{op}-{i}")
                for i in range(n_threads)]

    seq = [0]
    seq_lock = threading.Lock()

    def pick_tenant(rng):
        return rng.choice(tenants) if tenants else None

    def do_write(rng):
        with seq_lock:
            seq[0] += 1
            i = seq[0]
        tenant = pick_tenant(rng)
        traces = synth.make_traces(2, seed=seed * 1_000_000 + i,
                                   spans_per_trace=spans_per_trace)
        status, headers = _request(
            write_url + "/v1/traces", "POST",
            otlp.encode_traces_request(traces), "application/x-protobuf",
            headers=_org(tenant))
        outcome, hint_ok = classify(status, headers)
        if outcome == "ok":
            with acked_lock:
                for t in traces:
                    acked.append((time.monotonic(), tenant, t.trace_id))
        return outcome, hint_ok

    def pick_acked(rng):
        with acked_lock:
            eligible = len(acked)
            while eligible and time.monotonic() - acked[eligible - 1][0] < read_lag_s:
                eligible -= 1
            if not eligible:
                return None
            _, tenant, tid = acked[rng.randrange(eligible)]
            return tenant, tid

    def do_find(rng):
        picked = pick_acked(rng)
        if picked is None:
            return "ok", True  # nothing acked yet; not a failure
        tenant, tid = picked
        status, headers = _request(f"{query_url}/api/traces/{tid.hex()}",
                                   headers=_org(tenant))
        return classify(status, headers)

    def do_search_live(rng):
        now = int(time.time())
        svc = rng.choice(synth.SERVICES)
        qs = urllib.parse.urlencode({
            "tags": f"service.name={svc}", "start": now - 300, "end": now + 5,
            "limit": 10,
        })
        status, headers = _request(f"{query_url}/api/search?{qs}",
                                   headers=_org(pick_tenant(rng)))
        return classify(status, headers)

    def do_search_hist(rng):
        now = int(time.time())
        svc = rng.choice(synth.SERVICES)
        qs = urllib.parse.urlencode({
            "tags": f"service.name={svc}",
            "start": now - 7200, "end": now - 3600, "limit": 10,
        })
        status, headers = _request(f"{query_url}/api/search?{qs}",
                                   headers=_org(pick_tenant(rng)))
        return classify(status, headers)

    def do_query_range(rng):
        end = int(time.time())
        qs = urllib.parse.urlencode({
            "q": "{} | rate() by (resource.service.name)",
            "start": end - 300, "end": end, "step": 2,
        })
        status, headers = _request(f"{query_url}/api/metrics/query_range?{qs}",
                                   headers=_org(pick_tenant(rng)))
        return classify(status, headers)

    fns = {"write": do_write, "find": do_find, "search_live": do_search_live,
           "search_hist": do_search_hist, "query_range": do_query_range}
    threads = []
    for op, fn in fns.items():
        ops_s = SEED_RATES[op] * rate
        n_threads = max(1, min(32, int(ops_s / 5) + 1))
        threads += paced_loop(op, fn, n_threads, ops_s)
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    ops, slo_pass = stats.summary(slo)
    with acked_lock:
        acked_ids = [(tenant, tid) for _, tenant, tid in acked]
    return {"ops": ops, "slo_pass": slo_pass, "acked_writes": len(acked_ids)}, acked_ids


def verify_acked(query_url: str, acked_ids: list, sample: int = 25,
                 timeout_s: float = 45.0, seed: int = 1) -> dict:
    """Zero-acknowledged-loss gate: a random sample of ACCEPTED writes
    must become queryable once ingest drains (under the tenant that
    wrote them). Anything the cluster shed (429) was never acked and is
    exempt by construction."""
    import random

    rng = random.Random(seed)
    ids = list(dict.fromkeys(acked_ids))
    if len(ids) > sample:
        ids = rng.sample(ids, sample)
    pending = set(ids)
    deadline = time.time() + timeout_s
    while pending and time.time() < deadline:
        for tenant, tid in list(pending):
            try:
                status, _ = _request(f"{query_url}/api/traces/{tid.hex()}",
                                     timeout=10, headers=_org(tenant))
            except Exception:
                # connection-level blip while the cluster drains the
                # backlog: keep polling until the deadline
                continue
            if status == 200:
                pending.discard((tenant, tid))
        if pending:
            time.sleep(0.5)
    return {
        "sampled": len(ids),
        "lost": len(pending),
        "lost_ids": sorted(t.hex() for _, t in pending)[:10],
        "passed": not pending,
    }


# ---------------------------------------------------------------------------
# multi-tenant attribution gate + storage-health summary
# ---------------------------------------------------------------------------

def _parse_counter_series(text: str, family: str) -> dict:
    """{labelstr: value} for one family out of a /metrics exposition."""
    import re

    out = {}
    pat = re.compile(r"^%s\{([^}]*)\}\s+(\S+)$" % re.escape(family))
    for line in text.splitlines():
        m = pat.match(line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def attribution_check(dist_url: str, query_url: str, tenants: list) -> dict:
    """Multi-tenant gate: the per-tenant cost split must be EXACT.

    - At the distributor: sum over tenants of /status/usage ingest
      ingested_bytes == the untagged total of
      tempo_distributor_bytes_received_total on /metrics, and the two
      views agree per tenant (counters and accountant are one number).
    - At the frontend: every driven tenant shows up in /status/usage
      with query-side cost (the worker->frontend usage wire survived a
      real multi-process broker round trip)."""
    import re

    with urllib.request.urlopen(dist_url + "/metrics", timeout=15) as r:
        met = r.read().decode()
    series = _parse_counter_series(met, "tempo_distributor_bytes_received_total")
    by_tenant = {}
    for labels, v in series.items():
        m = re.search(r'tenant="([^"]*)"', labels)
        if m:
            by_tenant[m.group(1)] = by_tenant.get(m.group(1), 0.0) + v
    dist_usage = _get_json(dist_url + "/status/usage")["tenants"]
    usage_by_tenant = {
        t: doc["kinds"].get("ingest", {}).get("ingested_bytes", 0.0)
        for t, doc in dist_usage.items()
    }
    mismatches = {
        t: (by_tenant.get(t, 0.0), usage_by_tenant.get(t, 0.0))
        for t in set(by_tenant) | set(usage_by_tenant)
        if abs(by_tenant.get(t, 0.0) - usage_by_tenant.get(t, 0.0)) > 0.5
    }
    ingest_exact = not mismatches
    sum_exact = abs(sum(by_tenant.values()) - sum(usage_by_tenant.values())) <= 0.5

    front_usage = _get_json(query_url + "/status/usage")["tenants"]
    uncovered = [
        t for t in tenants
        if not front_usage.get(t, {}).get("kinds")
    ]
    return {
        "ingest_bytes_by_tenant": usage_by_tenant,
        "counter_total": sum(by_tenant.values()),
        "attributed_total": sum(usage_by_tenant.values()),
        "mismatches": mismatches,
        "tenants_without_query_usage": uncovered,
        "passed": bool(ingest_exact and sum_exact and not uncovered),
    }


def _device_check_one(url: str) -> dict:
    """One process's device-transfer consistency verdict."""
    try:
        doc = _get_json(url + "/status/device", timeout=30)
        with urllib.request.urlopen(url + "/metrics", timeout=15) as r:
            met = r.read().decode()
    except Exception as e:  # noqa: BLE001 — gate reports, caller decides
        return {"error": str(e), "passed": False, "tracked_pages": 0}
    ship_counter = 0.0
    dispatches = 0.0
    for line in met.splitlines():
        if line.startswith("tempo_tpu_pageheat_ship_bytes_total"):
            ship_counter += float(line.rsplit(" ", 1)[1])
        elif line.startswith("tempo_tpu_device_dispatches_total"):
            dispatches += float(line.rsplit(" ", 1)[1])
    heat = doc.get("pageHeat", {})
    moved = doc.get("transfer", {}).get("totals", {}).get("moved", 0)
    # lifetime totals: eviction-immune, so equality is exact at quiesce
    # no matter how the ledger GC'd during the run
    ledger_total = heat.get("lifetimeMovedBytes", 0)
    ledger_matches = abs(ledger_total - ship_counter) < 0.5
    live = dispatches == 0 or moved > 0
    bounded = heat.get("trackedPages", 0) <= 8192
    curve = doc.get("whatIf", {}).get("curve", [])
    monotone = all(curve[i]["missBytes"] >= curve[i + 1]["missBytes"]
                   for i in range(len(curve) - 1))
    return {
        "ledger_moved_bytes": ledger_total,
        "ship_bytes_counter": ship_counter,
        "device_dispatches": dispatches,
        "transfer_moved_bytes": moved,
        "tracked_pages": heat.get("trackedPages", 0),
        "curve_budgets": len(curve),
        "gates": {
            "ledger_matches_counter": ledger_matches,
            "transfer_live": live,
            "ledger_bounded": bounded,
            "curve_monotone": monotone,
        },
        "passed": bool(ledger_matches and live and bounded and monotone),
    }


def device_transfer_check(urls: list, retries: int = 3) -> dict:
    """Device data-movement gate (ISSUE 14) across every cluster process
    (block reads heat the QUERIER's ledger, not the frontend's):

    - ledger == counters: /status/device lifetimeMovedBytes equals
      tempo_tpu_pageheat_ship_bytes_total on the same process's /metrics
      (they move at the same statement; post-drain they must be equal —
      a mismatch means a touch path bypassed the counter seam).
    - live: some process that served block reads actually recorded page
      heat, and any process with device dispatches shows moved bytes
      (zero under dispatches>0 means the seam is dead code).
    - bounded: trackedPages within the ledger's hard cap, so the RSS
      gate's verdict covers the ledger by construction.
    - the what-if curve each process serves is monotone in budget."""
    last: dict = {}
    for _ in range(max(1, retries)):
        per = {name: _device_check_one(url) for name, url in urls}
        heated = sum(p.get("tracked_pages", 0) for p in per.values())
        last = {
            "procs": per,
            "total_tracked_pages": heated,
            "passed": bool(all(p["passed"] for p in per.values())
                           and heated > 0),
        }
        if last["passed"]:
            return last
        time.sleep(1.0)  # in-flight touches settle, then re-read
    return last


# ---------------------------------------------------------------------------
# --hot arm: repeat-query live-tail/recent-window workload against the
# device-resident tier (ISSUE 16)
# ---------------------------------------------------------------------------

# device-tier config appended to every process config in --hot mode:
# small budget, 1s admission refresh so a short smoke crosses
# min_ships -> candidate -> admitted inside the run.
HOT_TIER_EXTRA = """device_tier:
  budget_mb: 64
  refresh_s: 1.0
  admit_min_ships: 2
"""


def _scrape_hot(urls: list) -> dict:
    """Sum the hot-tier gate's metric families across processes."""
    out = {"h2d_bytes": 0.0, "device_hits": 0.0, "avoided_bytes": 0.0,
           "stage_transfer_s": 0.0, "stage_kernel_s": 0.0, "dispatches": 0.0}
    for _name, url in urls:
        try:
            with urllib.request.urlopen(url + "/metrics", timeout=15) as r:
                met = r.read().decode()
        except Exception:  # noqa: BLE001 — a dead proc fails the gates anyway
            continue
        for line in met.splitlines():
            try:
                val = float(line.rsplit(" ", 1)[1])
            except (ValueError, IndexError):
                continue
            if (line.startswith("tempo_tpu_device_transfer_bytes_total")
                    and 'direction="h2d"' in line):
                out["h2d_bytes"] += val
            elif (line.startswith("tempo_tpu_colcache_hits")
                    and 'tier="device"' in line):
                out["device_hits"] += val
            elif line.startswith("tempo_tpu_device_transfer_bytes_avoided_total"):
                out["avoided_bytes"] += val
            elif line.startswith("tempo_tpu_query_stage_seconds_sum"):
                if 'stage="transfer"' in line:
                    out["stage_transfer_s"] += val
                elif 'stage="kernel"' in line:
                    out["stage_kernel_s"] += val
            elif line.startswith("tempo_tpu_device_dispatches_total"):
                out["dispatches"] += val
    return out


def hot_tier_probe(query_url: str, scrape_urls: list, iters: int = 8,
                   warm_timeout_s: float = 60.0,
                   transfer_frac: float = 0.5) -> dict:
    """Repeat-query arm: fire the SAME recent-window search (identical
    page set) until hot pages are admitted to the device tier, then
    measure a hot window of `iters` repeats and gate on:

    - resident-tier hits climbing while `tempo_tpu_device_transfer_bytes_total`
      (h2d) stays flat — repeats stop re-shipping compressed pages,
    - transfer-stage seconds below `transfer_frac` of kernel-stage
      seconds over the hot window (only gated when the window actually
      dispatched device work),
    - transfer bytes AVOIDED climbing (the ledger credits each resident
      serve with the ship it didn't do).
    """
    from tempo_tpu.model import synth

    # pick a service that actually matches flushed data, then FREEZE the
    # query so every repeat touches the identical page set. synth traces
    # are pinned at a fixed epoch, so the window brackets that epoch —
    # a now-window would miss every flushed block.
    base_s = 1_700_000_000
    qs = None
    for svc in synth.SERVICES:
        cand = urllib.parse.urlencode({
            "tags": f"service.name={svc}",
            "start": base_s - 300, "end": base_s + 300, "limit": 20})
        try:
            doc = _get_json(f"{query_url}/api/search?{cand}", timeout=30)
        except Exception:  # noqa: BLE001
            continue
        if doc.get("traces"):
            qs = cand
            break
    if qs is None:
        return {"error": "no service with searchable traces", "passed": False}

    def fire():
        try:
            _get_json(f"{query_url}/api/search?{qs}", timeout=30)
        except Exception:  # noqa: BLE001 — gates read the counters
            pass

    base = _scrape_hot(scrape_urls)
    # warm phase: repeat until the tier starts serving hits (ship ->
    # heat -> admission needs min_ships repeats + one refresh interval)
    deadline = time.time() + warm_timeout_s
    warm_iters = 0
    while time.time() < deadline:
        fire()
        warm_iters += 1
        if _scrape_hot(scrape_urls)["device_hits"] > base["device_hits"]:
            break
        time.sleep(0.4)
    mid = _scrape_hot(scrape_urls)
    for _ in range(iters):
        fire()
    after = _scrape_hot(scrape_urls)

    hot = {k: after[k] - mid[k] for k in after}
    warm = {k: mid[k] - base[k] for k in mid}
    hits_climb = hot["device_hits"] > 0
    avoided_climb = hot["avoided_bytes"] > 0
    # flat = repeats stopped re-shipping pages: per-dispatch predicate
    # codes (tens of bytes) still ship, so "flat" is a tight per-iter
    # allowance, not literal zero
    h2d_flat = hot["h2d_bytes"] <= max(4096.0 * iters,
                                       0.05 * max(warm["h2d_bytes"], 0.0))
    if hot["dispatches"] > 0:
        # the floor is a millisecond a dispatch: each ships its code set
        # and waits for it, whatever the pages cost (an absolute 5 ms
        # failed one run in three once two pages were resident)
        transfer_ok = hot["stage_transfer_s"] <= max(
            transfer_frac * hot["stage_kernel_s"], 0.001 * hot["dispatches"], 0.005)
    else:
        transfer_ok = False  # hot window never reached the device path
    return {
        "warm_iters": warm_iters,
        "hot_iters": iters,
        "warm": warm,
        "hot": hot,
        "gates": {
            "device_hits_climb": hits_climb,
            "avoided_bytes_climb": avoided_climb,
            "h2d_flat": h2d_flat,
            "transfer_below_kernel": transfer_ok,
        },
        "passed": bool(hits_climb and avoided_climb and h2d_flat
                       and transfer_ok),
    }


# ---------------------------------------------------------------------------
# --shapes arm: literal-rotation query_range workload against the
# compiled-query tier (ISSUE 17)
# ---------------------------------------------------------------------------


def _scrape_compiled(urls: list) -> dict:
    """Sum the compiled-tier gate's counters across processes."""
    out = {"hits": 0.0, "misses": 0.0, "compiles": 0.0, "dispatches": 0.0}
    for _name, url in urls:
        try:
            with urllib.request.urlopen(url + "/metrics", timeout=15) as r:
                met = r.read().decode()
        except Exception:  # noqa: BLE001 — a dead proc fails the gates anyway
            continue
        for line in met.splitlines():
            try:
                val = float(line.rsplit(" ", 1)[1])
            except (ValueError, IndexError):
                continue
            if line.startswith("tempo_tpu_compiled_hits_total"):
                out["hits"] += val
            elif line.startswith("tempo_tpu_compiled_misses_total"):
                out["misses"] += val
            elif line.startswith("tempo_tpu_compiled_compiles_total"):
                out["compiles"] += val
            elif (line.startswith("tempo_tpu_device_dispatches_total")
                    and 'kernel="compiled_metrics"' in line):
                out["dispatches"] += val
    return out


def compiled_shapes_probe(query_url: str, scrape_urls: list,
                          shapes: int = 4) -> dict:
    """Literal-rotation arm: fire /api/metrics/query_range with ONE
    normalized query shape whose literal and window rotate per request
    (a dashboard refresh, distilled). The warm pass lets every querier
    lower the shape and trace the program once; the measured pass
    repeats the same rotation and gates on:

    - ZERO new program traces (`tempo_tpu_compiled_compiles_total`
      flat): literal and window swaps re-enter the cached executable,
    - shape-cache hits climbing while misses stay flat (the shape key
      ignores literals, so the rotation is one shape, not N),
    - the fused path actually dispatching (`kernel="compiled_metrics"`
      climbing — all-fallback would pass the other gates vacuously),
    - every response a well-formed matrix.
    """
    from tempo_tpu.model import synth

    base_s = 1_700_000_000  # synth traces are pinned at a fixed epoch
    lits = [synth.SERVICES[i % len(synth.SERVICES)] for i in range(shapes)]

    def fire(i: int, lit: str) -> bool:
        qs = urllib.parse.urlencode({
            "q": "{ resource.service.name = `%s` } | rate()" % lit,
            "start": base_s - 300 + i, "end": base_s + 300 + i, "step": 10,
        })
        try:
            with urllib.request.urlopen(
                f"{query_url}/api/metrics/query_range?{qs}", timeout=30
            ) as r:
                doc = json.loads(r.read())
            return bool(r.status == 200 and doc.get("status") == "success"
                        and doc["data"]["resultType"] == "matrix")
        except Exception:  # noqa: BLE001 — counted against the ok gate
            return False

    for i, lit in enumerate(lits):  # warm: lower + trace everywhere
        fire(i, lit)
    mid = _scrape_compiled(scrape_urls)
    ok = sum(fire(shapes + i, lit) for i, lit in enumerate(lits))
    after = _scrape_compiled(scrape_urls)

    hot = {k: after[k] - mid[k] for k in after}
    zero_retrace = hot["compiles"] == 0
    hits_climb = hot["hits"] > 0
    misses_flat = hot["misses"] == 0
    fused_ran = hot["dispatches"] > 0
    return {
        "shapes_rotation": shapes,
        "ok": ok,
        "hot": hot,
        "gates": {
            "zero_retrace": zero_retrace,
            "shape_hits_climb": hits_climb,
            "misses_flat": misses_flat,
            "fused_dispatches": fused_ran,
        },
        "passed": bool(ok == shapes and zero_retrace and hits_climb
                       and misses_flat and fused_ran),
    }


# ---------------------------------------------------------------------------
# --repeat arm: repeated identical queries against the result cache
# (ISSUE 19)
# ---------------------------------------------------------------------------


def _scrape_resultcache(urls: list) -> dict:
    """Sum the result-cache gate's families across processes."""
    out = {"hits": 0.0, "misses": 0.0, "negative": 0.0, "stores": 0.0,
           "bytes_saved": 0.0, "inspected_bytes": 0.0}
    for _name, url in urls:
        try:
            with urllib.request.urlopen(url + "/metrics", timeout=15) as r:
                met = r.read().decode()
        except Exception:  # noqa: BLE001 — a dead proc fails the gates anyway
            continue
        for line in met.splitlines():
            try:
                val = float(line.rsplit(" ", 1)[1])
            except (ValueError, IndexError):
                continue
            if line.startswith("tempo_tpu_resultcache_hits_total"):
                out["hits"] += val
            elif line.startswith("tempo_tpu_resultcache_misses_total"):
                out["misses"] += val
            elif line.startswith("tempo_tpu_resultcache_negative_total"):
                out["negative"] += val
            elif line.startswith("tempo_tpu_resultcache_stores_total"):
                out["stores"] += val
            elif line.startswith("tempo_tpu_resultcache_bytes_saved_total"):
                out["bytes_saved"] += val
            elif line.startswith("tempo_tpu_usage_inspected_bytes_total"):
                out["inspected_bytes"] += val
    return out


# ---------------------------------------------------------------------------
# auto-RCA fault campaign (ISSUE 20): seeded backend fault -> exactly
# one attributed machine-written incident; fault-free soak -> zero
# ---------------------------------------------------------------------------

RCA_EXTRA = """vulture:
  enabled: true
  write_backoff_s: 2
  read_backoff_s: 2
slo:
  enabled: true
  eval_interval_s: 1.0
rca:
  enabled: true
"""


def rca_campaign(fault_spec: str = "notfound=1.0,seed=7",
                 soak_s: float = 25.0, deadline_s: float = 90.0) -> dict:
    """Two sequential single-binary clusters, each dogfooding the whole
    trigger loop (in-process vulture -> vulture SLI -> SLO fast burn ->
    RCA engine), the chaos suite as ground-truth generator:

    - faulted arm: TEMPO_TPU_FAULTS armed, so stored probes vanish from
      the read path once they hand off. Gate: at least one incident
      opens, and EVERY unsuppressed incident is attributed
      `backend_fault` (the injected truth) — any other cause is a
      false attribution.
    - clean arm: identical soak, no faults. Gate: zero incidents — the
      typed handoff dip must not page, burn, or open anything.
    """
    out: dict = {}
    for arm, env in (("faulted", {"TEMPO_TPU_FAULTS": fault_spec}),
                     ("clean", None)):
        tmp = tempfile.mkdtemp(prefix=f"tempo-rca-{arm}-")
        proc = Proc(tmp, "all", f"rca-{arm}", kv_url="local",
                    extra=RCA_EXTRA, env_extra=env)
        try:
            proc.wait_ready()
            t0 = time.time()
            incidents: list = []
            budget = deadline_s if arm == "faulted" else soak_s
            while time.time() - t0 < budget:
                time.sleep(2.0)
                try:
                    doc = _get_json(proc.url + "/api/rca")
                except Exception:
                    continue
                incidents = doc.get("incidents", [])
                if arm == "faulted" and incidents:
                    # let the in-flight window settle, then re-read so
                    # the gate sees every incident the burn opened
                    time.sleep(3.0)
                    incidents = _get_json(
                        proc.url + "/api/rca").get("incidents", [])
                    break
            unsuppressed = [i for i in incidents if not i.get("suppressed")]
            misattributed = [i for i in unsuppressed
                             if i.get("cause") != "backend_fault"]
            arm_doc = {
                "incidents": len(incidents),
                "unsuppressed": len(unsuppressed),
                "causes": sorted({i.get("cause") for i in incidents}),
                "elapsed_s": round(time.time() - t0, 1),
            }
            if arm == "faulted":
                arm_doc["passed"] = bool(
                    unsuppressed and not misattributed)
                if incidents:
                    top = incidents[0]
                    arm_doc["first"] = {k: top.get(k) for k in
                                        ("trigger", "cause", "tier")}
            else:
                arm_doc["passed"] = not incidents
            out[arm] = arm_doc
            print(f"[loadtest] rca {arm} arm: {arm_doc}", file=sys.stderr)
        finally:
            proc.terminate()
    out["passed"] = out["faulted"]["passed"] and out["clean"]["passed"]
    return out


def repeat_probe(query_url: str, scrape_urls: list, iters: int = 5) -> dict:
    """Repeated-query arm against the result cache: freeze one search
    and one query_range at the synth epoch (identical block set every
    pass) plus one provably-empty search (a service that never existed
    — the negative-cache probe), fire each once cold, then `iters` warm
    repeats. Gates:

    - every warm response BIT-IDENTICAL to the cold one (content
      compared, not cost stats — those are SUPPOSED to collapse),
    - cache hits climbing while misses stay ~flat (every immutable
      block answers from cache; the blocklist is stable post-drain),
    - per-iter inspected bytes collapsing vs the cold pass and
      bytes-saved climbing (the economy claim, from the counters the
      dashboards read),
    - the negative probe returns ZERO traces on every pass INCLUDING
      the cold unpruned one, while the negative counter climbs — a
      veto is only ever a recomputation skip, never a wrong answer,
    - a deliberately lenient latency backstop (CI wall clocks are
      noisy; inspected-bytes is the deterministic signal).
    """
    from tempo_tpu.model import synth

    base_s = 1_700_000_000  # synth traces are pinned at a fixed epoch
    svc = None
    for cand in synth.SERVICES:
        qs = urllib.parse.urlencode({
            "tags": f"service.name={cand}",
            "start": base_s - 300, "end": base_s + 300, "limit": 50})
        try:
            doc = _get_json(f"{query_url}/api/search?{qs}", timeout=30)
        except Exception:  # noqa: BLE001
            continue
        if doc.get("traces"):
            svc = cand
            break
    if svc is None:
        return {"error": "no service with searchable traces", "passed": False}

    search_qs = urllib.parse.urlencode({
        "tags": f"service.name={svc}",
        "start": base_s - 300, "end": base_s + 300, "limit": 50})
    range_qs = urllib.parse.urlencode({
        "q": "{ resource.service.name = `%s` } | rate()" % svc,
        "start": base_s - 300, "end": base_s + 300, "step": 10})
    neg_qs = urllib.parse.urlencode({
        "tags": "service.name=no-such-svc-rc-probe",
        "start": base_s - 300, "end": base_s + 300, "limit": 50})

    def canon_search(doc):
        return json.dumps(sorted(
            (t.get("traceID"), t.get("startTimeUnixNano"))
            for t in doc.get("traces") or []))

    def canon_range(doc):
        return json.dumps((doc or {}).get("data"), sort_keys=True)

    def fire():
        t0 = time.monotonic()
        try:
            s = _get_json(f"{query_url}/api/search?{search_qs}", timeout=30)
            m = _get_json(f"{query_url}/api/metrics/query_range?{range_qs}",
                          timeout=30)
            n = _get_json(f"{query_url}/api/search?{neg_qs}", timeout=30)
        except Exception:  # noqa: BLE001 — a failed pass breaks identity
            return None, None, None, time.monotonic() - t0
        return (canon_search(s), canon_range(m),
                len(n.get("traces") or []), time.monotonic() - t0)

    base = _scrape_resultcache(scrape_urls)
    cold_search, cold_range, cold_neg, cold_t = fire()
    mid = _scrape_resultcache(scrape_urls)
    identical, neg_always_empty, warm_ts = True, cold_neg == 0, []
    for _ in range(iters):
        w_search, w_range, w_neg, dt = fire()
        warm_ts.append(dt)
        identical = identical and (w_search == cold_search
                                   and w_range == cold_range)
        neg_always_empty = neg_always_empty and w_neg == 0
    after = _scrape_resultcache(scrape_urls)

    cold = {k: mid[k] - base[k] for k in mid}
    warm = {k: after[k] - mid[k] for k in after}
    warm_p50 = sorted(warm_ts)[len(warm_ts) // 2] if warm_ts else 0.0
    cold_touched = cold["misses"] > 0  # the cold pass reached real blocks
    hits_climb = warm["hits"] >= iters
    # a stray miss = a block that appeared mid-probe (compaction); the
    # steady state is zero, the allowance keeps the gate honest not flaky
    misses_flat = warm["misses"] <= max(1.0, 0.1 * warm["hits"])
    negative_climb = warm["negative"] >= iters
    saved_climb = warm["bytes_saved"] > 0
    # warm per-iter read bytes must collapse vs the cold pass; the
    # allowance covers live-segment scans the block cache cannot absorb
    bytes_collapse = (warm["inspected_bytes"] / max(iters, 1)
                      <= 0.6 * cold["inspected_bytes"])
    latency_ok = warm_p50 <= cold_t * 2.0 + 0.25
    return {
        "service": svc,
        "iters": iters,
        "cold": cold,
        "warm": warm,
        "cold_s": round(cold_t, 4),
        "warm_p50_s": round(warm_p50, 4),
        "gates": {
            "cold_touched_blocks": cold_touched,
            "responses_identical": identical,
            "hits_climb": hits_climb,
            "misses_flat": misses_flat,
            "negative_climb": negative_climb,
            "negative_zero_results": neg_always_empty,
            "bytes_saved_climb": saved_climb,
            "inspected_bytes_collapse": bytes_collapse,
            "latency_backstop": latency_ok,
        },
        "passed": bool(cold_touched and identical and hits_climb
                       and misses_flat and negative_climb
                       and neg_always_empty and saved_climb
                       and bytes_collapse and latency_ok),
    }


# ---------------------------------------------------------------------------
# --ingest-heavy arm: write-dominated burst against the device-native
# ingest plane (ISSUE 18)
# ---------------------------------------------------------------------------

# appended to every process config in --ingest-heavy mode: the hot-tier
# budget plus an ingest_tail share so just-cut columns stay resident for
# standing folds and live-tail search; refresh/admission match the --hot
# snippet so both arms can share one cluster.
INGEST_TAIL_EXTRA = """device_tier:
  budget_mb: 64
  ingest_tail_budget_mb: 32
  refresh_s: 1.0
  admit_min_ships: 2
"""

# the two kernels that must evaluate where the cut landed (resident),
# never re-shipping the column payloads they read
INGEST_KERNELS = ("standing_fold", "live_tail_scan")


def _scrape_ingest(urls: list) -> dict:
    """Sum the ingest-plane gate's families across processes."""
    out = {"h2d_bytes": 0.0, "avoided_bytes": 0.0, "dispatches": 0.0,
           "spans_columnar": 0.0, "spans_object": 0.0,
           "device_pages": 0.0, "encode_fallbacks": 0.0,
           "blocks_flushed": 0.0}
    for _name, url in urls:
        try:
            with urllib.request.urlopen(url + "/metrics", timeout=15) as r:
                met = r.read().decode()
        except Exception:  # noqa: BLE001 — a dead proc fails the gates anyway
            continue
        for line in met.splitlines():
            try:
                val = float(line.rsplit(" ", 1)[1])
            except (ValueError, IndexError):
                continue
            resident = any(f'kernel="{k}"' in line for k in INGEST_KERNELS)
            if (line.startswith("tempo_tpu_device_transfer_bytes_total")
                    and 'direction="h2d"' in line and resident):
                out["h2d_bytes"] += val
            elif (line.startswith(
                    "tempo_tpu_device_transfer_bytes_avoided_total")
                    and resident):
                out["avoided_bytes"] += val
            elif (line.startswith("tempo_tpu_device_dispatches_total")
                    and resident):
                out["dispatches"] += val
            elif line.startswith("tempo_tpu_ingest_spans_decoded_total"):
                key = ("spans_columnar" if 'path="columnar"' in line
                       else "spans_object")
                out[key] += val
            elif line.startswith("tempo_tpu_ingest_device_encode_pages_total"):
                out["device_pages"] += val
            elif line.startswith("tempo_tpu_ingest_encode_fallback_total"):
                out["encode_fallbacks"] += val
            elif line.startswith("tempo_ingester_blocks_flushed_total"):
                out["blocks_flushed"] += val
    return out


def ingest_heavy_probe(write_url: str, query_url: str, ing_urls: list,
                       scrape_urls: list, target_spans_s: float,
                       tenant: str | None = None, spans_per_trace: int = 8,
                       burst_s: float = 4.0, writers: int = 4) -> dict:
    """Write-dominated arm (the 100x ingest mix distilled): standing
    queries registered up front, then a full-throttle OTLP burst —
    writers push back-to-back with no pacing — with live-tail searches
    riding beside it, then a drain long enough for every burst trace to
    cut (parking its columnar tail and folding the standing queries
    where it sits). Gates:

    - spans/s >= `target_spans_s` over the burst window (acked spans
      only; sheds are backpressure, not throughput). The cluster procs
      are pinned to the CPU backend: this is a CPU rate, a floor for
      shared-core CI, never a per-chip number.
    - resident evaluation: standing_fold AND live_tail_scan h2d bytes
      stay at dispatch-literal noise (predicate codes / bin edges, a few
      bytes per dispatch) while their avoided-bytes counters climb —
      the folds and tail searches ran where the cut landed, the column
      payloads never re-shipped.
    - the batched columnar decode path carried the burst
      (path="columnar" spans >= the acked burst spans) and the device
      encode arm produced the flushed pages
      (`tempo_tpu_ingest_device_encode_pages_total` climbing, blocks
      actually flushed).
    - zero acked-span loss across the burst, via the same verify_acked
      gate the mixed load uses.
    """
    import random
    import threading

    from tempo_tpu.model import synth
    from tempo_tpu.receivers import otlp

    # standing queries first, so the burst's cuts fold through them;
    # {} | count_over_time() lowers to the resident fold plan
    for url in ing_urls:
        try:
            _http_json(f"{url}/api/metrics/standing", method="POST",
                       body={"q": "{} | count_over_time()", "step": 60,
                             "window": 7 * 86400}, tenant=tenant)
        except Exception as e:  # noqa: BLE001 — gate reports, caller decides
            return {"error": f"standing registration failed: {e}",
                    "passed": False}

    stop_search = threading.Event()
    searches = [0]

    def searcher():
        # now-window: the burst below stamps its spans at the wall clock
        # (unlike the epoch-pinned mixed load) so the searches land on
        # the live/just-cut tail, not on historical blocks
        rng = random.Random(4242)
        while not stop_search.wait(0.25):
            now = int(time.time())
            svc = rng.choice(synth.SERVICES)
            qs = urllib.parse.urlencode({
                "tags": f"service.name={svc}",
                "start": now - 300, "end": now + 5, "limit": 10})
            try:
                _get_json(f"{query_url}/api/search?{qs}", timeout=30,
                          headers=_org(tenant))
                searches[0] += 1
            except Exception:  # noqa: BLE001 — gates read the counters
                pass

    base = _scrape_ingest(scrape_urls)
    s_thread = threading.Thread(target=searcher, daemon=True)
    s_thread.start()

    acked: list = []
    acked_lock = threading.Lock()
    shed = [0]
    seq_lock = threading.Lock()
    seq = [0]
    deadline = time.monotonic() + burst_s

    def blast():
        while time.monotonic() < deadline:
            with seq_lock:
                seq[0] += 1
                i = seq[0]
            # wall-clock timestamps: the standing accumulator prunes
            # bins outside its window, so epoch-pinned spans would never
            # fold — and folds are exactly what this arm gates on
            traces = synth.make_traces(2, seed=31_000_000 + i,
                                       spans_per_trace=spans_per_trace,
                                       base_time_ns=time.time_ns())
            body = otlp.encode_traces_request(traces)
            try:
                status, _ = _request(write_url + "/v1/traces", "POST", body,
                                     "application/x-protobuf", timeout=30,
                                     headers=_org(tenant))
            except Exception:  # noqa: BLE001 — a refused write is not acked
                continue
            if 200 <= status < 300:
                with acked_lock:
                    acked.extend((tenant, t.trace_id) for t in traces)
            elif status == 429:
                shed[0] += 1

    t0 = time.monotonic()
    threads = [threading.Thread(target=blast, daemon=True)
               for _ in range(writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    burst_wall = time.monotonic() - t0

    # drain: max_trace_idle 1s + flush_check 1s -> every burst trace
    # cuts, parking its tail and folding the standing queries; the
    # live-tail searches keep firing over the freshly-parked window
    time.sleep(3.0)
    stop_search.set()
    s_thread.join(timeout=5)
    after = _scrape_ingest(scrape_urls)

    delta = {k: after[k] - base[k] for k in after}
    n_traces = len(acked)
    spans = n_traces * spans_per_trace
    spans_s = spans / max(burst_wall, 1e-9)  # CPU-backend cluster
    # "flat" = dispatch-literal noise only: each resident dispatch still
    # ships O(bytes) of predicate codes / bin edges, never the columns
    h2d_allow = max(64 << 10, 4096.0 * delta["dispatches"])
    loss = verify_acked(query_url, acked)
    gates = {
        "spans_per_s": spans_s >= target_spans_s,
        "h2d_flat": delta["h2d_bytes"] <= h2d_allow,
        "avoided_climb": delta["avoided_bytes"] > 0,
        "resident_dispatches": delta["dispatches"] > 0,
        "columnar_decode": delta["spans_columnar"] >= spans > 0,
        "device_encode_live": delta["device_pages"] > 0,
        "flushed": delta["blocks_flushed"] > 0,
        "zero_acked_loss": loss["passed"],
    }
    return {
        "acked_traces": n_traces,
        "shed_writes": shed[0],
        "spans": spans,
        "burst_s": round(burst_wall, 3),
        "spans_per_s_cpu": round(spans_s, 1),
        "target_spans_s": target_spans_s,
        "live_tail_searches": searches[0],
        "delta": {k: round(v, 1) for k, v in delta.items()},
        "h2d_allowance_bytes": h2d_allow,
        "acked_loss": loss,
        "gates": gates,
        "passed": all(gates.values()),
    }


def storage_summary(query_url: str) -> dict:
    """Fleet storage health from the frontend's /status/storage
    (compression, compaction debt, zone-map coverage), so the rig's
    output carries storage health beside its gates."""
    try:
        doc = _get_json(query_url + "/status/storage?refresh=1", timeout=60)
    except Exception as e:  # noqa: BLE001 — summary is best-effort
        return {"error": str(e)}
    fleet = doc.get("fleet", {})
    return {
        "blocks": fleet.get("blocks"),
        "total_bytes": fleet.get("totalBytes"),
        "compression_ratio": fleet.get("compressionRatio"),
        "zonemap_coverage": fleet.get("zonemapCoverageRatio"),
        "debt_row_groups": fleet.get("compactionDebtRowGroups"),
        "debt_payoff": fleet.get("compactionDebtPayoff"),
    }


def start_vulture(write_url: str, query_url: str, tenant: str | None):
    """--vulture arm: the continuous-verification prober runs BESIDE the
    mixed workload over real HTTP (writes via the distributor, reads via
    the frontend — the sidecar deployment shape), on a compressed tier
    clock so a two-minute run still exercises fresh AND recent tiers."""
    from tempo_tpu.vulture import HTTPClient, Vulture, VultureConfig

    cfg = VultureConfig(
        tenant=tenant or "single-tenant",
        write_backoff_s=2,
        # checks only pick probes >= read_backoff old: under 10-100x
        # load write->readable lag runs seconds, and checking younger
        # probes would just re-measure freshness as phantom notfounds
        read_backoff_s=5,
        search_backoff_s=4,
        metrics_backoff_s=10,
        recent_min_age_s=8,
        aged_min_age_s=30,
        retention_s=600,
        freshness_slo_s=10.0,
        metrics_step_s=5,
    )
    client = HTTPClient(write_url, tenant=tenant, query_url=query_url)
    v = Vulture(client, cfg=cfg)
    v.start()
    return v


def vulture_summary(v, freshness_slo_s: float = 10.0,
                    settle_s: float = 15.0) -> dict:
    """Stop the prober, run the drain-time audit, and gate:
    - zero notfound/missing/incorrect at drain (every probe the cluster
      acked under load must be fully readable once ingest settles),
    - the freshness SLI: p99 write->searchable lag within the SLO.
    The audit polls until clean or settle_s elapses: a probe written
    moments before the stop may still be flushing — a visibility race
    heals across passes, real loss persists."""
    v.stop()
    deadline = time.time() + settle_s
    while True:
        drain = v.verify_written()
        if not drain["failures"] or time.time() >= deadline:
            break
        time.sleep(2.0)
    errors_by_type: dict = {}
    for (type_, tier), n in sorted(v.error_counts.items()):
        errors_by_type[f"{type_}:{tier}"] = n
    lags = sorted(lag for _tier, lag in v.freshness_lags)
    p99 = lags[min(len(lags) - 1, int(len(lags) * 0.99))] if lags else 0.0
    correctness_classes = ("notfound_byid", "notfound_search",
                           "missing_spans", "incorrect_result",
                           "metrics_mismatch")
    drain_bad = sum(drain["failures"].get(c, 0) for c in correctness_classes)
    freshness_ok = not lags or p99 <= freshness_slo_s
    return {
        "writes": len(v.written),
        "checks": sum(v.check_counts.values()),
        "errors": errors_by_type,
        "drain": drain,
        "freshness_p99_s": round(p99, 3),
        "freshness_samples": len(lags),
        "gates": {
            "drain_correctness": drain_bad == 0,
            "freshness_slo": freshness_ok,
        },
        "passed": bool(drain_bad == 0 and freshness_ok),
    }


class RSSSampler:
    """Samples each cluster process's RSS once a second; the gate rejects
    monotonic growth (final-quarter mean vs second-quarter mean)."""

    def __init__(self, procs: list):
        import threading

        self.procs = [(p.name, p.proc.pid) for p in procs]
        self.series: dict[str, list] = {name: [] for name, _ in self.procs}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    @staticmethod
    def _rss(pid: int) -> int:
        from tempo_tpu.util.resource import sample_rss_bytes

        return sample_rss_bytes(pid)

    def _run(self):
        while not self._stop.wait(1.0):
            for name, pid in self.procs:
                v = self._rss(pid)
                if v:
                    self.series[name].append(v)

    def start(self):
        self._thread.start()
        return self

    def stop_and_summary(self, growth_limit: float = 1.5) -> dict:
        self._stop.set()
        self._thread.join(timeout=2)
        out, passed = {}, True
        for name, vals in self.series.items():
            if len(vals) < 8:
                out[name] = {"samples": len(vals), "gate": None}
                continue
            q = len(vals) // 4
            early = sum(vals[q:2 * q]) / q
            late = sum(vals[-q:]) / q
            ratio = late / early if early else 1.0
            ok = ratio <= growth_limit
            passed = passed and ok
            out[name] = {
                "samples": len(vals),
                "rss_mb_early": round(early / 2**20, 1),
                "rss_mb_late": round(late / 2**20, 1),
                "growth_ratio": round(ratio, 3),
                "gate": ok,
            }
        return {"procs": out, "passed": passed}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", help="existing cluster URL (skips spawning)")
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="workload multiplier over the seed rates "
                         "(10-100 = the ROADMAP overload regime)")
    ap.add_argument("--spans-per-trace", type=int, default=5)
    ap.add_argument("--skip-sweep", action="store_true")
    ap.add_argument("--rss-growth-limit", type=float, default=1.5,
                    help="max final/early mean-RSS ratio per process")
    ap.add_argument("--slo-scale", type=float, default=1.0,
                    help="multiply the p99 latency budgets (CI containers "
                         "share cores with the cluster under test; the "
                         "error/shed/loss/RSS gates are never scaled)")
    ap.add_argument("--query-range", action="store_true",
                    help="probe /api/metrics/query_range after the load "
                         "and gate on matrix responses")
    ap.add_argument("--vulture", action="store_true",
                    help="run the continuous-verification prober beside "
                         "the mixed workload and gate on read-after-write "
                         "correctness at drain + the freshness SLO")
    ap.add_argument("--standing", type=int, default=0, metavar="N",
                    help="register N standing queries across tenants on the "
                         "ingesters before the load; gates on (i) per-eval "
                         "inspected spans == cut delta (O(delta)), (ii) zero "
                         "standing-read dips during handoff, (iii) usage "
                         "exactness for kind 'standing'")
    ap.add_argument("--hot", type=int, default=0, metavar="N",
                    help="enable the device-resident hot tier fleet-wide "
                         "and run a repeat-query arm after the drain: the "
                         "same recent-window search repeated until pages "
                         "are admitted, then N hot repeats gated on "
                         "resident hits climbing, h2d transfer bytes flat, "
                         "and transfer-stage time < half of kernel time")
    ap.add_argument("--shapes", type=int, default=0, metavar="N",
                    help="run a compiled-tier arm after the drain: ONE "
                         "query_range shape with N rotating literals/"
                         "windows, gated on zero program retraces across "
                         "the rotation, shape-cache hits climbing, and "
                         "the fused path actually dispatching")
    ap.add_argument("--repeat", type=int, default=0, metavar="N",
                    help="enable the result cache fleet-wide "
                         "(TEMPO_TPU_RESULT_CACHE=force) and run a "
                         "repeated-query arm after the drain: one frozen "
                         "search + query_range + provably-empty search "
                         "fired cold then N warm repeats, gated on "
                         "bit-identical responses, cache hits climbing "
                         "with misses flat, per-iter inspected bytes "
                         "collapsing, and zero incorrect negative vetoes. "
                         "Incompatible with --shapes on the same cluster: "
                         "the cached metrics path answers before the "
                         "compiled tier, so its gates would starve")
    ap.add_argument("--ingest-heavy", action="store_true",
                    help="enable the device-native ingest plane fleet-wide "
                         "(device encode armed, ingest-tail residency on) "
                         "and run a write-dominated burst arm after the "
                         "drain, gated on spans/s >= --ingest-target, "
                         "standing-fold + live-tail h2d flat while avoided "
                         "bytes climb, device-encoded pages flushing, and "
                         "zero acked-span loss")
    ap.add_argument("--ingest-target", type=float, default=300.0,
                    help="spans/s floor for the --ingest-heavy burst "
                         "(sized for shared-core CI on the CPU backend, "
                         "which is all this rig runs)")
    ap.add_argument("--rca", action="store_true",
                    help="run the auto-RCA fault campaign INSTEAD of the "
                         "mixed load: two sequential single-binary "
                         "clusters dogfooding vulture -> SLO burn -> "
                         "incident, gated on a seeded TEMPO_TPU_FAULTS "
                         "backend fault yielding >=1 attributed incident "
                         "with cause backend_fault (and no other "
                         "unsuppressed cause), and a fault-free soak "
                         "yielding zero incidents")
    ap.add_argument("--tenants", type=int, default=1,
                    help=">1 enables multi-tenant mode: the cluster boots "
                         "with multitenancy, every op carries one of N org "
                         "IDs, and the run gates on attribution exactness "
                         "(per-tenant cost vectors == untagged counters)")
    args = ap.parse_args()
    if args.repeat > 0 and args.shapes > 0:
        ap.error("--repeat and --shapes cannot share a cluster: the result "
                 "cache answers metrics queries before the compiled tier, "
                 "so the compiled-shapes gates would never fire")
    multitenant = args.tenants > 1
    tenant_ids = [f"lt-tenant-{i}" for i in range(args.tenants)] if multitenant else None

    if args.rca:
        # the campaign boots its own faulted/clean single-binary clusters;
        # a shared mixed-load cluster would pollute the clean-soak gate
        summary = {"platform": "cpu", "rca": rca_campaign()}
        summary["passed"] = summary["rca"]["passed"]
        print(json.dumps(summary))
        return 0 if summary["passed"] else 1

    procs: list[Proc] = []
    tmpdir = None
    try:
        grpc_port = 0
        try:
            import grpc  # noqa: F401

            grpc_port = _free_port()
        except ImportError:
            pass
        if args.url:
            write_url = query_url = args.url
        else:
            tmpdir = tempfile.mkdtemp(prefix="tempo-loadtest-")
            # INGEST_TAIL_EXTRA is a superset of HOT_TIER_EXTRA (same
            # tier, plus the ingest_tail share), so both arms share it
            extra = (INGEST_TAIL_EXTRA if args.ingest_heavy
                     else HOT_TIER_EXTRA if args.hot > 0 else "")
            env_extra = {}
            if args.ingest_heavy:
                env_extra["TEMPO_TPU_DEVICE_ENCODE"] = "1"
            if args.repeat > 0:
                # result_cache lives under storage.trace; the env force
                # switch enables it fleet-wide without touching `extra`
                env_extra["TEMPO_TPU_RESULT_CACHE"] = "force"
            env_extra = env_extra or None
            procs, front, dist = start_cluster(
                tmpdir, grpc_port=grpc_port, multitenant=multitenant,
                extra=extra, env_extra=env_extra)
            write_url, query_url = dist.url, front.url
            print(f"[loadtest] cluster up: write={write_url} query={query_url}"
                  + (f" tenants={args.tenants}" if multitenant else ""),
                  file=sys.stderr)

        sweep = {}
        if multitenant and not args.skip_sweep:
            # the receiver sweep drives org-less protocol shims; with
            # multitenancy on those are 401 by design — skip it
            args.skip_sweep = True
            print("[loadtest] multi-tenant mode: receiver sweep skipped",
                  file=sys.stderr)
        if not args.skip_sweep:
            sweep = receiver_sweep(write_url, query_url, grpc_port=grpc_port if procs else 0)
            print(f"[loadtest] receiver sweep: {sweep}", file=sys.stderr)
        sweep_ok = all(v in ("ok", "skipped") for v in sweep.values()) if sweep else True

        rss = RSSSampler(procs).start() if procs else None
        standing = None
        if args.standing > 0:
            ing_urls = [p.url for p in procs if p.name.startswith("ing")]
            if not ing_urls:
                ing_urls = [write_url]  # --url mode: single target
            standing = StandingArm(ing_urls, args.standing, tenant_ids).start()
            print(f"[loadtest] standing arm: {args.standing} queries "
                  f"registered across {len(ing_urls)} ingester(s)",
                  file=sys.stderr)
        vulture = None
        if args.vulture:
            vulture = start_vulture(write_url, query_url,
                                    tenant_ids[0] if tenant_ids else None)
            print("[loadtest] vulture prober running beside the workload",
                  file=sys.stderr)
        slo = {op: (p99 * args.slo_scale, err) for op, (p99, err) in DEFAULT_SLO.items()}
        summary, acked_ids = run_mixed_load(
            write_url, query_url, duration_s=args.duration, rate=args.rate,
            spans_per_trace=args.spans_per_trace, slo=slo, tenants=tenant_ids,
        )
        print(f"[loadtest] mixed load done: {summary['acked_writes']} acked writes, "
              f"slo_pass={summary['slo_pass']}", file=sys.stderr)

        loss = verify_acked(query_url, acked_ids)
        summary["acked_loss"] = loss
        print(f"[loadtest] acked-loss check: {loss}", file=sys.stderr)

        standing_ok = True
        if standing is not None:
            summary["standing"] = standing.summary()
            standing_ok = summary["standing"]["passed"]
            print(f"[loadtest] standing gate: {summary['standing']}",
                  file=sys.stderr)

        vulture_ok = True
        if vulture is not None:
            summary["vulture"] = vulture_summary(vulture)
            vulture_ok = summary["vulture"]["passed"]
            print(f"[loadtest] vulture gate: {summary['vulture']}", file=sys.stderr)

        if rss is not None:
            summary["rss"] = rss.stop_and_summary(args.rss_growth_limit)
            print(f"[loadtest] rss: {summary['rss']}", file=sys.stderr)

        summary["receiver_sweep"] = sweep
        summary["rate"] = args.rate
        if args.query_range:
            qr = query_range_probe(query_url)
            print(f"[loadtest] query_range probe: {qr}", file=sys.stderr)
            summary["query_range"] = qr
            sweep_ok = sweep_ok and qr["passed"]
        attribution_ok = True
        if multitenant:
            attr = attribution_check(write_url, query_url, tenant_ids)
            summary["attribution"] = attr
            attribution_ok = attr["passed"]
            print(f"[loadtest] attribution gate: {attr}", file=sys.stderr)
        summary["storage"] = storage_summary(query_url)
        print(f"[loadtest] storage health: {summary['storage']}", file=sys.stderr)
        # post-drain (workload stopped, vulture stopped): the transfer
        # ledger and its counters must agree exactly at quiesce — on
        # every process (queriers do the block reads, not the frontend)
        check_urls = ([(p.name, p.url) for p in procs] if procs
                      else [("target", query_url)])
        summary["device_transfer"] = device_transfer_check(check_urls)
        device_ok = summary["device_transfer"]["passed"]
        print(f"[loadtest] device-transfer gate: {summary['device_transfer']}",
              file=sys.stderr)
        hot_ok = True
        if args.hot > 0:
            summary["hot_tier"] = hot_tier_probe(query_url, check_urls,
                                                 iters=args.hot)
            hot_ok = summary["hot_tier"]["passed"]
            print(f"[loadtest] hot-tier gate: {summary['hot_tier']}",
                  file=sys.stderr)
        ingest_ok = True
        if args.ingest_heavy:
            ing_urls = [p.url for p in procs if p.name.startswith("ing")]
            if not ing_urls:
                ing_urls = [write_url]  # --url mode: single target
            summary["ingest_heavy"] = ingest_heavy_probe(
                write_url, query_url, ing_urls, check_urls,
                target_spans_s=args.ingest_target,
                tenant=tenant_ids[0] if tenant_ids else None,
                spans_per_trace=max(args.spans_per_trace, 8))
            ingest_ok = summary["ingest_heavy"]["passed"]
            print(f"[loadtest] ingest-heavy gate: {summary['ingest_heavy']}",
                  file=sys.stderr)
        shapes_ok = True
        if args.shapes > 0:
            summary["compiled_shapes"] = compiled_shapes_probe(
                query_url, check_urls, shapes=args.shapes)
            shapes_ok = summary["compiled_shapes"]["passed"]
            print(f"[loadtest] compiled-shapes gate: "
                  f"{summary['compiled_shapes']}", file=sys.stderr)
        repeat_ok = True
        if args.repeat > 0:
            summary["result_cache"] = repeat_probe(
                query_url, check_urls, iters=args.repeat)
            repeat_ok = summary["result_cache"]["passed"]
            print(f"[loadtest] result-cache gate: {summary['result_cache']}",
                  file=sys.stderr)
        summary["passed"] = bool(
            summary["slo_pass"]
            and loss["passed"]
            and sweep_ok
            and attribution_ok
            and vulture_ok
            and standing_ok
            and device_ok
            and hot_ok
            and ingest_ok
            and shapes_ok
            and repeat_ok
            and (rss is None or summary["rss"]["passed"])
        )
        # every process this rig spawns is CPU-pinned; --url targets
        # somebody else's cluster, whose backend this rig cannot see
        summary["platform"] = "external" if args.url else "cpu"
        print(json.dumps(summary))
        return 0 if summary["passed"] else 1
    finally:
        for p in procs:
            p.terminate()


if __name__ == "__main__":
    sys.exit(main())
