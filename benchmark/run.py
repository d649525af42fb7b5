#!/usr/bin/env python3
"""One run of one benchmark cell against the served path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ../BENCHMARK.json's `workloads`: a configuration
(configs/<name>.json: the server's settings, the store's size, the
guarantees) under a traffic mix (traffic/<name>.json). One run starts ONE
`python -m tempo_tpu -target=all` process, loads the store through
`POST /v1/traces` + `POST /flush` from --seed, warms every operation of
the mix, lets the mix's closed-loop clients run for --seconds, compares
every answer of the window with the plain reference and prints one JSON
result line last on stdout. Everything before the window is `setup_s`.
A mix may hold writers beside readers (`roles` in its file): one window
then yields the write and the read metrics side by side.

There is no CPU fallback: unless the server reports a TPU the run fails
with no result line. `--cpu-dry-run` is the one explicit rehearsal (tiny
store, server pinned to the CPU backend, "cpu" in every device field, no
device metric). This process stays off JAX and asserts so.
"""

from __future__ import annotations

import argparse
import atexit
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import numpy as np  # noqa: E402

try:
    import tempo_tpu  # noqa: E402,F401  (the system under test, beside this directory)
except ImportError as e:
    print(f"benchmark/run.py: needs the tempo_tpu checkout beside it: {e}", file=sys.stderr)
    sys.exit(2)

import check  # noqa: E402
import corpus  # noqa: E402
import layers  # noqa: E402
import traffic as tr  # noqa: E402
from reference import Reference, partitions  # noqa: E402
from server import BenchFailure, Child, merge  # noqa: E402

PUSH_TRACES = 512  # traces per OTLP request while the store is loaded
READBACK_TRACES = 200  # acknowledged traces a write cell reads back


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def tenants_of(config: dict) -> list:
    if not config["server"].get("multitenancy_enabled"):
        return ["single-tenant"]
    return [f"tenant-{i}" for i in range(config["tenants"])]


def store_data(config: dict, tenants: list, dry_traces: int = 0) -> dict:
    """What corpus.make_store needs of the configuration; a rehearsal
    (`dry_traces` in the whole store) cuts the blocks, never their number."""
    data = {k: config[k] for k in ("blocks_per_tenant", "traces_per_block",
                                   "spans_per_trace", "resend_fraction")}
    data["tenants"] = tenants
    if dry_traces:
        data["traces_per_block"] = max(16, dry_traces // (len(tenants) * data["blocks_per_tenant"]))
    return data


def merged(scrape: dict, tenant: str) -> bool:
    """Whether a compaction job of the tenant had ended by this scrape of /metrics."""
    return any(v > 0 for k, v in scrape.items() if f'tenant="{tenant}"' in k
               and k.startswith("tempodb_compaction_blocks_compacted_total"))


def percentile(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class Run:
    def __init__(self, args, bench, cell, config, traffic):
        self.args, self.bench, self.cell = args, bench, cell
        self.config, self.traffic = config, traffic
        self.dry = args.cpu_dry_run
        self.child: Child | None = None
        self.workdir = tempfile.mkdtemp(prefix="tempo_bench_")
        self.tenants = tenants_of(config)
        self.data = store_data(config, self.tenants, args.dry_traces if self.dry else 0)
        if self.dry:  # a rehearsal: results only; pushes keep their size
            traffic["pool_bodies"] = min(traffic.get("pool_bodies", 0), 8)
        # data sits 10 minutes back on a step boundary: inside every retention window
        self.base_s = (int(time.time()) // tr.STEP_S) * tr.STEP_S - 600
        self.preload = traffic.get("preload", True)
        self.trace_dir = None
        self.roles, self.ops = tr.roles_of(traffic), tr.ops_of(traffic)
        self.client_roles = tr.client_roles(self.roles)
        if len(self.client_roles) > 8:
            raise BenchFailure("more than 8 clients: the warm-up's are numbered 90 to 97")
        # `push_age_s`: the pushes' spans are that old at the run's start, not the store's age
        self.push_base_s = (int(time.time()) - traffic["push_age_s"]
                            if "push_age_s" in traffic else self.base_s)
        if (self.preload and "push_age_s" in traffic
                and tr.range_of(self.push_base_s)["start"] < tr.range_of(self.base_s)["end"]):
            raise BenchFailure(f"push_age_s {traffic['push_age_s']}: the pushes' spans would "
                               "fall into the store's query_range window")
        if config.get("compaction_in_run"):
            partitions(config["blocks_per_tenant"])  # more than the reference enumerates: refused

    # -- set-up ---------------------------------------------------------------
    def start_server(self) -> None:
        server_cfg = merge(self.config["server"], self.traffic.get("server_overlay", {}))
        self.child = Child(self.workdir, server_cfg, self.dry)
        atexit.register(self.child.kill)

    def check_backend(self) -> dict:
        dev = self.child.get_json("/status/device")["backend"]
        say(f"backend: platform={dev['platform']} device_kind={dev['device_kind']} "
            f"device_count={dev['device_count']} pallas={dev['pallas']} "
            f"native_codec={dev['native_codec']} "
            f"compile_cache_dir={dev['compile_cache_dir'] or '<off>'}")
        if dev["platform"] != "tpu" and not (self.dry and dev["platform"] == "cpu"):
            raise BenchFailure(
                f"the server runs on {dev['platform']!r}, not 'tpu': refusing to measure "
                "(no CPU fallback; --cpu-dry-run is the explicit rehearsal)")
        if not self.dry and dev["device_count"] < self.cell["chips"]:
            raise BenchFailure(f"{dev['device_count']} chips found, the cell asks for "
                               f"{self.cell['chips']}")
        if not dev["native_codec"]:
            raise BenchFailure("the native codec did not build on this machine")
        return dev

    def push_block(self, block: corpus.Block, tenant: str) -> None:
        """POST the block PUSH_TRACES traces a request; the next request
        encodes on a helper thread while one is in flight."""
        bodies: queue.Queue = queue.Queue(maxsize=4)

        def encode():
            try:
                for lo in range(0, block.n_traces, PUSH_TRACES):
                    part = block.slice_traces(lo, min(block.n_traces, lo + PUSH_TRACES))
                    bodies.put(corpus.encode_push(part))
                bodies.put(None)
            except BaseException as e:  # surfaces in the consumer
                bodies.put(e)
                raise

        t = threading.Thread(target=encode, daemon=True)
        t.start()
        headers = {"Content-Type": tr.PROTOBUF, **self.src.headers(tenant)}
        while (body := bodies.get()) is not None:
            if isinstance(body, BaseException):
                raise body
            status, _ = self.child.request("POST", "/v1/traces", body, headers)
            if status != 200:
                raise BenchFailure(f"push while loading -> {status}")
        t.join()

    def flush(self) -> None:
        status, _ = self.child.request("POST", "/flush", b"")
        if status != 204:
            raise BenchFailure(f"/flush -> {status}")

    def load_store(self, store: dict) -> None:
        """Round r pushes every tenant's block r, then one /flush cuts them."""
        t0 = time.perf_counter()
        for r in range(self.data["blocks_per_tenant"]):
            for tenant in self.tenants:
                self.push_block(store[tenant][r], tenant)
            self.flush()
        m = self.child.metrics()
        for tenant in self.tenants:
            n = m.get(f'tempo_ingester_blocks_flushed_total{{tenant="{tenant}"}}')
            if n != self.data["blocks_per_tenant"]:
                raise BenchFailure(f"{tenant}: {n} blocks flushed, expected "
                                   f"{self.data['blocks_per_tenant']} (an early cut split one)")
        spans = sum(b.num_spans for bl in store.values() for b in bl)
        dt = time.perf_counter() - t0
        say(f"store loaded: {spans} spans in {len(self.tenants)} tenant(s) x "
            f"{self.data['blocks_per_tenant']} block(s), {dt:.1f}s ({spans / dt:.0f} spans/s)")

    def warm_up(self) -> None:
        """Every operation of every role's deck, one client: `warmup_per_op` times or
        with each of its `warmup_ms`; then all clients of all roles at once for
        `warmup_burst_s`."""
        t0 = time.perf_counter()
        c = tr.Client(99, self.args.seed, self.src, self.child.port, [])
        n = 0
        for role in self.roles:
            per_op = role.get("warmup_per_op", self.traffic.get("warmup_per_op", 2))
            for entry in role["deck"]:
                for ms in tr.warm_literals(entry, per_op):
                    rec = c.send(tr.build(entry, c.rng, self.src, (self.args.seed, 99, n), ms=ms))
                    n += 1
                    if not rec.ok:
                        raise BenchFailure(f"warm-up {entry['op']} -> status {rec.status}")
                    if entry["op"] == "push":
                        self.warm_spans += rec.req.spans
        # then all clients at once: concurrent queries are folded in batches, whose
        # shapes a single client never meets
        burst = [tr.Client(90 + i, self.args.seed, self.src, self.child.port, [], role=role)
                 for i, role in enumerate(self.client_roles)]
        for b in burst:
            b.stop_at = time.perf_counter() + self.traffic.get("warmup_burst_s", 0)
            b.start()
        for b in burst:
            b.join()
            n += len(b.records)
            self.warm_spans += sum(r.req.spans for r in b.records if r.ok)
            for r in b.records:
                if r.ok:
                    continue
                if r.req.op != "find_acked":
                    raise BenchFailure(f"a request of the warm-up burst failed: {r.req.op} -> "
                                       f"{r.status}: {r.req.path[:120]}")
                # an acknowledged trace that is not there is the program's answer, and wrong:
                # the run goes on and says so in `readback_wrong`
                say(f"warm-up burst: find_acked -> {r.status}: {r.req.path}")
                self.warm_readback_wrong += 1
        if not self.preload:
            self.flush()
        say(f"warm-up: {n} requests, {time.perf_counter() - t0:.1f}s")

    # -- the window -------------------------------------------------------------
    def cache_files(self) -> set:
        d = self.cache_dir
        return set(os.listdir(d)) if d and os.path.isdir(d) else set()

    def flusher(self, t0: float, every: float, stop_at: float, hold, out: list) -> None:
        """`flush_every_s` of the traffic: POST /flush at t0 + every, + 2 every, ...
        for as long as the clients run, in a timed and in a traced run alike."""
        k = 1
        while True:
            due = t0 + k * every
            if due >= stop_at and (hold is None or hold.is_set()):
                return
            time.sleep(min(0.2, max(0.0, due - time.perf_counter())))
            if time.perf_counter() < due:
                continue
            try:
                a = time.perf_counter()
                self.flush()
                out.append(time.perf_counter() - a)
            except Exception as e:  # judged after the window: a flush that failed is a failure
                out.append(e)
            k += 1

    def capture(self, at: float, done: threading.Event, out: dict) -> None:
        """--trace 1: ask the process that holds the chip for a profiler
        capture once, `at` seconds into the window."""
        try:
            time.sleep(max(0.0, at - time.perf_counter()))
            secs = self.traffic["trace_seconds"]
            t0 = time.perf_counter()
            out["reply"] = self.child.get_json("/status/profile/device", {"seconds": secs},
                                               timeout=240)
            out["took_s"] = time.perf_counter() - t0
        except Exception as e:  # reported by the caller: a traced run must not die silently
            out["error"] = f"{type(e).__name__}: {e}"
        finally:
            done.set()

    def window(self) -> dict:
        seconds = self.args.seconds
        traced = bool(self.args.trace)
        done = threading.Event() if traced else None
        logs = [[] for _ in self.client_roles]
        clients = [tr.Client(i, self.args.seed, self.src, self.child.port, logs[i], done, role)
                   for i, role in enumerate(self.client_roles)]
        cap: dict = {}
        before, files_before = self.child.metrics(), self.cache_files()
        t0 = time.perf_counter()
        for c in clients:
            c.stop_at = t0 + seconds
            c.start()
        if traced:
            threading.Thread(target=self.capture, args=(t0 + seconds / 3, done, cap),
                             daemon=True).start()
        flushes, flusher = [], None
        if self.traffic.get("flush_every_s"):
            flusher = threading.Thread(target=self.flusher, daemon=True, args=(
                t0, float(self.traffic["flush_every_s"]), t0 + seconds, done, flushes))
            flusher.start()
        for c in clients:
            c.join(timeout=seconds + 300)
            if c.is_alive():
                raise BenchFailure(f"{c.name} did not stop")
        if flusher is not None:
            flusher.join(timeout=300)
            bad = [f for f in flushes if isinstance(f, Exception)]
            if bad or flusher.is_alive():
                raise BenchFailure(f"a /flush of the window failed: {bad or 'still running'}")
            say(f"{len(flushes)} /flush calls in the window, "
                + ", ".join(f"{f:.2f}s" for f in flushes))
        t_end = max(t0 + seconds, max((r.t1 for log in logs for r in log), default=t0))
        after, files_after = self.child.metrics(), self.cache_files()
        if traced and "error" in cap:
            raise BenchFailure(f"the profiler capture failed: {cap['error']}")
        records = sorted((r for log in logs for r in log), key=lambda r: r.t0)
        for r in records:
            r.req.body = None  # the bytes are on the server now
        return {"records": records, "t0": t0, "seconds": seconds, "t_end": t_end,
                "scrapes": (before, after), "cache_files": (files_before, files_after),
                "capture": cap}

    # -- after the window -----------------------------------------------------
    def readback(self, records: list) -> dict:
        """Cells that push: /flush, then read a seeded sample of acknowledged
        traces from every part of the window back by id, and count the
        spans the store holds in the pushes' own time range against the
        acknowledged ones."""
        self.flush()
        acked = [r for r in records if r.req.op == "push" and r.ok]
        rng = np.random.default_rng([self.args.seed, 5])
        picks = sorted({0, len(acked) - 1,
                        *np.linspace(0, len(acked) - 1, READBACK_TRACES // 2).astype(int)})
        wrong, asked = self.warm_readback_wrong, 0
        c = tr.Client(98, self.args.seed, self.src, self.child.port, [])
        for i in picks if acked else []:
            body, ids = acked[i].req.args
            for k in rng.choice(body.n_traces, 2, replace=False):
                h = ids[k].tobytes().hex()
                rec = c.send(tr.Request("find", acked[i].req.tenant, (h,), "GET",
                                        f"/api/traces/{h}"))
                asked += 1
                wrong += not (rec.ok and rec.answer == body.span_sets[k])
        acked_spans = sum(r.req.spans for r in acked) + self.warm_spans
        unsure = sum(r.req.spans for r in records if r.req.op == "push" and not r.ok)
        # by (name): the interpreted plan; the fused rate() would compile a
        # program for this run's own number of blocks (16 s, my chip run)
        doc = self.child.get_json("/api/metrics/query_range",
                                  {"q": "{} | rate() by (name)", **tr.range_of(self.push_base_s)})
        got = round(sum(float(v[1]) for s in doc["data"]["result"] for v in s["values"])
                    * tr.STEP_S)
        gap = max(acked_spans - got, got - acked_spans - unsure, 0)
        say(f"readback: {asked} traces asked, {wrong} wrong; store counts {got} spans, "
            f"{acked_spans} acknowledged (+{unsure} unsure)")
        return {"readback_wrong": wrong, "span_count_gap": gap}

    def reduce_trace(self, cap: dict) -> dict | None:
        reply = cap["reply"]
        say(f"profiler capture: supported={reply.get('supported')} dir={reply.get('dir')} "
            f"files={reply.get('files')} took {cap['took_s']:.1f}s")
        if not reply.get("supported"):
            raise BenchFailure(f"the server could not trace its device: {reply.get('error')}")
        self.trace_dir = reply["dir"]
        size = sum(os.path.getsize(os.path.join(dp, f))
                   for dp, _, fs in os.walk(self.trace_dir) for f in fs)
        t0 = time.perf_counter()
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "xplane.py"), self.trace_dir]
            + ([self.args.dump_planes] if self.args.dump_planes else []),
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
            timeout=200)
        say(f"trace: {size} bytes on disk, reduced in {time.perf_counter() - t0:.1f}s")
        try:
            doc = json.loads(out.stdout.strip().splitlines()[-1])
        except (IndexError, ValueError):
            raise BenchFailure(f"the trace reducer failed rc={out.returncode}:\n"
                               f"{out.stderr[-2000:]}") from None
        if "error" in doc:
            if self.dry:
                say(f"trace (cpu dry run, no device plane expected): {doc['error']}")
                return None
            raise BenchFailure(f"no device trace: {doc['error']}")
        for d in doc["devices"]:
            say(f"trace plane {d['plane']}: lines {d['lines']}, busy {d['busy_s']:.4f}s")
        say(f"traced window {doc['window_s']:.3f}s by the trace's own start and stop "
            f"({reply.get('seconds')}s asked for)")
        return doc

    # -- the sequence -------------------------------------------------------------
    def go(self) -> dict:
        args, traffic = self.args, self.traffic
        self.start_server()
        # while the server boots: the data, drawn from the seed
        t0 = time.perf_counter()
        store = corpus.make_store(args.seed, self.data, self.base_s) if self.preload else {}
        pool = tr.make_pool(traffic, args.seed, self.data["spans_per_trace"],
                            self.push_base_s) if "pool_bodies" in traffic else []
        multitenant = bool(self.config["server"].get("multitenancy_enabled"))
        self.src = tr.Source(traffic, self.tenants, multitenant, self.base_s,
                             {t: np.array([h for b in bl for h in corpus.trace_hex(b)],
                                          dtype=object) for t, bl in store.items()}, pool)
        say(f"data drawn in {time.perf_counter() - t0:.1f}s"
            + (f"; pool of {len(pool)} bodies x {pool[0].n_spans} spans" if pool else ""))
        self.warm_spans = self.warm_readback_wrong = 0
        self.child.wait_ready(300)
        say(f"server ready {time.perf_counter() - T_PROCESS:.1f}s after process start")
        dev = self.check_backend()
        self.cache_dir = dev["compile_cache_dir"]
        if self.preload:
            self.load_store(store)
        if pool:
            self.dry_send(pool)
        self.warm_up()
        setup_s = time.perf_counter() - T_PROCESS
        say(f"set-up {setup_s:.1f}s; window of {args.seconds}s, "
            + " + ".join(f"{role['clients']} {role['name']}" for role in self.roles)
            + f", closed-loop, trace={args.trace}")

        w = self.window()
        records = w["records"]
        for r in [r for r in records if not r.ok][:10]:  # beside the server's log, they say why
            say(f"not as expected: {r.req.op} -> {r.status} (expected {r.req.expect}) "
                f"{r.t0 - w['t0']:.2f}s into the window, after {r.t1 - r.t0:.2f}s: {r.req.path[:120]}")
        status = self.child.get_json("/status/device")["backend"]
        peak = max((d.get("peak_bytes_in_use") or 0 for d in status["devices"]), default=0)
        say(f"device memory peak: {peak} bytes")
        numbers = {}
        if "push" in self.ops:
            numbers.update(self.readback(records))
        numbers["server_exit"] = self.child.shutdown()
        errors = self.child.log_errors()
        numbers["log_errors"] = len(errors)
        say(f"server exit {numbers['server_exit']}; {len(errors)} ERROR lines in its log"
            + "".join("\n[bench]   log: " + e[:300] for e in errors[:5]))

        # the reference, once the window has closed and the server is gone
        t0 = time.perf_counter()
        # a tenant whose blocks no job has touched when the last answer is in has one state
        refs = {t: Reference(bl, bool(self.config.get("compaction_in_run"))
                             and merged(w["scrapes"][1], t)) for t, bl in store.items()}
        for k, v in check.compare(records, refs).items():
            # an acknowledged trace read back wrong, inside the window or after it: one number
            numbers[k] = numbers.get(k, 0) + v if k == "readback_wrong" else v
        say(f"reference: {numbers['_compared_items']} items compared in "
            f"{time.perf_counter() - t0:.1f}s")
        correct, compared = check.verdict(numbers)
        if not correct:  # the answers that moved a number, for whoever looks into the run
            moved = [r for r in records if r.ok and not check.verdict(
                check.compare([r], refs))[0]]
            for r in moved[:10]:
                say(f"  wrong: {r.req.op} {r.req.path[:110]} answered " + (
                    repr(r.answer)[:200] if isinstance(r.answer, dict)
                    else f"{len(r.answer or ())} ids"))

        trace = self.reduce_trace(w["capture"]) if args.trace else None
        metrics = self.metrics(w, setup_s, trace)
        device = {"platform": str(dev["platform"]), "kind": str(dev["device_kind"]),
                  "count": int(dev["device_count"]), "memory_peak_bytes": int(peak)}
        result = {"correct": correct, "attempted": len(records),
                  "failed": sum(1 for r in records if not r.ok), "metrics": metrics,
                  "device": device}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {  # an operation's name is its whole HLO line: cut it
                "device_ops": [[n[:160], v] for n, v in trace["device_ops"]],
                "idle_gaps": [[n[:160], v] for n, v in trace["idle_gaps"]]}
        result["compared"] = compared
        return result

    def dry_send(self, pool: list) -> None:
        """The generator's ceiling: bodies made new a second, nothing sent."""
        rng = np.random.default_rng(0)
        t0, n = time.perf_counter(), 0
        while time.perf_counter() - t0 < 0.5:
            body = pool[n % len(pool)]
            body.patched(rng.integers(0, 256, (body.n_traces, 16), dtype=np.uint8))
            n += 1
        dt = time.perf_counter() - t0
        say(f"generator ceiling: {n * pool[0].n_spans / dt:.0f} spans/s patched by one "
            "thread, nothing sent")

    def metrics(self, w: dict, setup_s: float, trace) -> dict:
        records, t0, seconds = w["records"], w["t0"], w["seconds"]
        in_window = [r for r in records if r.ok and r.t1 <= t0 + seconds]
        for role in self.roles:
            if not any(self.client_roles[r.client] is role for r in in_window):
                raise BenchFailure(f"role {role['name']!r} got no answer inside the window")
        values = {"setup_s": setup_s}
        if "push" in self.ops:
            # back-to-back writers hold the server at capacity: the rate is the metric, the
            # pushes' tail swings with where the flushes fall ("per operation" prints it)
            values["ingest_spans_per_s"] = sum(
                r.req.spans for r in in_window if r.req.op == "push") / seconds
        if self.ops - {"push"}:  # beside the writers or alone: over the records that are no push
            values["queries_per_s"] = sum(r.req.op != "push" for r in in_window) / seconds
            values["query_p95_ms"] = percentile(
                [(r.t1 - r.t0) * 1000.0 for r in records if r.req.op != "push"], 95)
        by_op: dict = {}
        for r in records:
            by_op.setdefault(r.req.op, []).append((r.t1 - r.t0) * 1000.0)
        say("per operation: " + "; ".join(
            f"{op} n={len(v)} p50={percentile(v, 50):.1f}ms p95={percentile(v, 95):.1f}ms"
            for op, v in sorted(by_op.items())))
        say("end to end: " + ", ".join(f"{k}={v:.4f}" for k, v in values.items()))
        before, after = w["scrapes"]
        grew = {k: after[k] - before.get(k, 0.0) for k in after
                if k.startswith(("tempo_tpu_device_dispatches_total",
                                 "tempo_ingester_blocks_flushed_total"))}
        say("device dispatches and blocks flushed in the window: "
            + json.dumps({k: v for k, v in sorted(grew.items()) if v}))
        compactor = {k: (after[k] - before.get(k, 0.0), after[k]) for k in sorted(after)
                     if k.startswith("tempodb_compaction_") and after[k]}
        say("compactor in the window (tempodb_compaction_*): "
            + json.dumps({k: d for k, (d, _) in compactor.items() if d})
            + "; since the server's start: " + json.dumps({k: v for k, (_, v) in compactor.items()}))
        new_files = w["cache_files"][1] - w["cache_files"][0]
        if new_files:
            say("compiled inside the window: " + ", ".join(sorted(new_files)))
        if not self.args.trace:  # the end-to-end metrics BENCHMARK.json lists for this cell
            return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                    for m in self.bench["end_to_end"]
                    if self.cell["name"] in m.get("workloads", [self.cell["name"]])}

        done = [r for r in records if r.ok and r.t1 <= w["t_end"]]
        pushes = sum(r.req.op == "push" for r in done)
        facts = layers.Facts(
            scrapes=w["scrapes"], counts={"pushes": pushes, "queries": len(done) - pushes},
            new_cache_files=len(new_files), trace=trace)
        out = {}
        for m in self.bench["per_layer"]:
            if self.cell["name"] not in m.get("workloads", [self.cell["name"]]):
                continue
            v = layers.evaluate(layers.load_reader(m["name"]), facts)
            if v is None:
                say(f"per layer: {m['name']} found nothing to read"
                    + (" (cpu dry run)" if self.dry else ""))
                continue
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        say("per layer: " + ", ".join(f"{k}={v['value']:.6g}{v['unit']}" for k, v in out.items()))
        return out

    def close(self) -> None:
        if self.child is not None:
            self.child.kill()
        for d in (self.workdir, self.trace_dir):
            if d and not self.args.keep_dir:
                shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="rehearsal: tiny store, server on the CPU backend, results only")
    ap.add_argument("--dry-traces", type=int, default=256,
                    help="traces in the whole store of a --cpu-dry-run")
    ap.add_argument("--dump-planes", metavar="FILE",
                    help="--trace 1: also write the trace's device planes as JSON")
    ap.add_argument("--keep-dir", action="store_true",
                    help="keep the work directory (config, log, blocks)")
    args = ap.parse_args(argv)
    if threading.current_thread() is threading.main_thread():
        # a run that is cut short still takes its server down (finally + atexit)
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    run = None
    try:
        run = Run(args, *load_cell(args.workload))
        result = run.go()
        if "jax" in sys.modules:
            raise BenchFailure("the benchmark's parent process imported jax")
    except Exception as e:  # any failure: nonzero, and no result line
        if run is not None and run.child is not None:
            print("[bench] server log tail:\n" + run.child.log_tail(), file=sys.stderr)
        print(f"benchmark/run.py: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    finally:
        if run is not None:
            run.close()
    for name, e in result["compared"].items():
        print(f"[bench] compared {name}: {e['value']} (limit {e['limit']})", file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
