"""In-process sampling profiler for the /status/profile endpoint.

Reference analog: the reference serves net/http/pprof and exposes mutex
profiling flags (cmd/tempo/main.go:57,90). The Python equivalent here
samples every live thread's stack via sys._current_frames() at a fixed
rate for a bounded window and aggregates frame hit counts — the same
shape of answer a pprof CPU profile gives ("where is time going right
now"), with no interpreter-wide tracing overhead while idle.

Two output formats:
- text (default): human-readable hottest frames + hottest stacks;
- collapsed: one `frame;frame;...;frame count` line per distinct stack
  (Brendan Gregg's folded format), so the output pipes straight into
  flamegraph.pl / speedscope / inferno without any conversion.

capture_device_profile() is the accelerator-side analog: a bounded
jax.profiler trace window for the /status/profile/device endpoint.
While it runs, the program's three timing seams (stagetimings.stage,
devicetiming.timed_dispatch, tracing.span) write their intervals into
the profiler's own trace through annotation(), so host spans and device
operations share one clock; when it stops, the capture reduces its own
xplane (reduce_capture) into the reply's `summary` and into the
tempo_tpu_profile_* counters.
"""

from __future__ import annotations

import bisect
import contextlib
import contextvars
import glob
import itertools
import json
import logging
import os
import re
import sys
import tempfile
import threading
import time
from collections import Counter

from tempo_tpu.util import metrics

log = logging.getLogger(__name__)

_STACK_DEPTH = 64


def _sample(seconds: float, hz: int):
    """(frame_hits, stack_hits, samples): stack_hits keys are FULL
    root->leaf semicolon-joined stacks (collapsed format needs the whole
    stack; the text report truncates for display)."""
    seconds = max(0.1, min(float(seconds), 60.0))
    interval = 1.0 / max(1, min(int(hz), 1000))
    me = threading.get_ident()
    frame_hits: Counter = Counter()
    stack_hits: Counter = Counter()
    samples = 0
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < _STACK_DEPTH:
                co = f.f_code
                entry = f"{co.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno}:{co.co_name}"
                stack.append(entry)
                f = f.f_back
            if not stack:
                continue
            frame_hits[stack[0]] += 1
            stack_hits[";".join(reversed(stack))] += 1
            samples += 1
        time.sleep(interval)
    return frame_hits, stack_hits, samples


def sample_profile(seconds: float = 5.0, hz: int = 100, top: int = 40,
                   fmt: str = "text") -> str:
    """Sample all thread stacks for `seconds`.

    fmt="text": report of the hottest frames and hottest whole stacks.
    fmt="collapsed": semicolon-folded stacks with sample counts, one
    line each — standard flamegraph input."""
    frame_hits, stack_hits, samples = _sample(seconds, hz)
    if fmt == "collapsed":
        lines = [f"{stack} {n}" for stack, n in sorted(stack_hits.items())]
        return "\n".join(lines) + ("\n" if lines else "")
    lines = [f"# sampling profile: {seconds:.1f}s @ {hz}Hz, {samples} thread-samples"]
    lines.append("\n## hottest frames (leaf)")
    for entry, n in frame_hits.most_common(top):
        lines.append(f"{n:6d}  {entry}")
    lines.append("\n## hottest stacks (root->leaf, truncated)")
    for stack, n in stack_hits.most_common(10):
        parts = stack.split(";")
        shown = ";".join(parts[:10])
        lines.append(f"{n:6d}  {shown}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# annotations: the program's seams on the profiler's clock
# ---------------------------------------------------------------------------

# true only between start_trace and stop_trace of capture_device_profile:
# with no capture running a seam pays one read of this global
capturing = False
# shared no-op context (reentrant + shareable); `with ... as s` binds None
NULL_CONTEXT = contextlib.nullcontext()
_annotation_type = None
# id shared by every annotation of one request: set at the request's root
# (request_scope), inherited by pool threads with the context, and carried
# to the worker's thread in the job descriptor
_req: contextvars.ContextVar = contextvars.ContextVar("tempo_profile_req", default=0)
_req_ids = itertools.count(1)


def _load_annotation_type():
    import jax.profiler

    class Annotation(jax.profiler.TraceAnnotation):
        def __enter__(self):  # binds None, like the disabled tracer's null context
            super().__enter__()

    return Annotation


def annotation(name: str, **ids):
    """`name` as an interval in the running capture's host plane, tagged
    with the request's `req` id; the shared null context when no capture
    runs. A parent is the enclosing annotation on the same thread."""
    if not capturing:
        return NULL_CONTEXT
    return _annotation_type(name, req=_req.get(), **ids)


def current_req() -> int:
    """The active request's annotation id (0: no capture, or no request)."""
    return _req.get() if capturing else 0


@contextlib.contextmanager
def _request_scope(req: int):
    token = _req.set(req or next(_req_ids))
    try:
        yield
    finally:
        _req.reset(token)


def request_scope(req: int = 0):
    """Root of one request's annotations: mints its `req` id, or adopts
    the one a job descriptor carried across the queue."""
    if not capturing:
        return NULL_CONTEXT
    return _request_scope(req)


# ---------------------------------------------------------------------------
# the reduction of one capture
# ---------------------------------------------------------------------------

_HASH = re.compile(r"\(\d+\)$")  # `jit_fn(2646591877435955813)` -> `jit_fn`
ALIGN_NS = 1e6  # alignment counts a run that starts this long after a dispatch closed


def _union(intervals: list) -> list:
    """Sorted disjoint [(start, end)] covering the same instants."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        elif b > a:
            out.append((a, b))
    return out


def _innermost(events: list) -> list:
    """One thread's (name, start, end) annotations -> disjoint
    (start, end, name) pieces naming the innermost open one."""
    out: list = []
    stack: list = []  # (end, name) of the open annotations, outermost first
    t = 0.0

    def emit(until):
        nonlocal t
        if stack and until > t:
            out.append((t, until, stack[-1][1]))
        t = max(t, until)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(a)
        stack.append((b, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def _partition_idle(window_ns: float, busy: list, by_thread: dict) -> dict:
    """Idle nanoseconds by annotation name; see reduce_capture."""
    points = []  # (t, order, what): a piece closes (0) or opens (1), idling starts or ends (2)
    edge = 0.0
    for a, b in busy:
        points += [(edge, 2, True), (a, 2, False)]
        edge = b
    points += [(edge, 2, True), (window_ns, 2, False)]
    for events in by_thread.values():
        for a, b, name in _innermost(events):
            points += [(a, 1, name), (b, 0, name)]
    points.sort(key=lambda p: p[:2])
    idle: dict = {"none": 0.0}
    work: Counter = Counter()  # name -> threads in it, `*/wait` apart
    wait: Counter = Counter()
    idling, last = False, 0.0
    for t, order, what in points:
        if idling and t > last:
            pool = work or wait
            if pool:
                n = sum(pool.values())
                for name, k in pool.items():
                    idle[name] = idle.get(name, 0.0) + (t - last) * k / n
            else:
                idle["none"] += t - last
        last = t
        if order == 2:
            idling = what
            continue
        idle.setdefault(what, 0.0)
        pool = wait if what.endswith("/wait") else work
        if order == 1:
            pool[what] += 1
        else:  # a thread's pieces are disjoint: this closes the one it opened
            pool[what] -= 1
            if not pool[what]:
                del pool[what]
    return idle


def reduce_capture(window_ns: float, annotations: list, programs: list | None,
                   ops: list | None) -> dict:
    """One capture as numbers. Pure: plain tuples in, a dict out.

    window_ns    what is reduced: the interval in which the seams were armed
                 (read_capture). Every time below counts from its start, and
                 whatever lies outside it is clipped away
    annotations  [(thread, name, start_ns, duration_ns)] of the host plane:
                 the intervals annotation() wrote
    programs     [(device, name, start_ns, duration_ns)], one per program run
                 (the device planes' `XLA Modules` lines; `device` is the
                 plane's name); None without a device plane
    ops          [(device, start_ns, duration_ns)], one per operation (`XLA Ops`)

    device    busy seconds (the union of `ops`, over every device plane: some
              device ran) and idle seconds of the window; `per_device`, the
              busy seconds of each plane by itself; and per program (trailing
              hash cut) its runs and device seconds, split by whether the run
              overlaps a `dispatch/*` annotation. None without a device
              plane, and then so are `idle` and `alignment`.
    dispatch  per kernel: count, wall seconds (`dispatch/<kernel>`), transfer
              seconds (`transfer/<kernel>`), and device seconds: each run is
              given to the one dispatch it overlaps most and counted only
              where it lies inside that dispatch, and runs of several devices
              that overlap count once, so device seconds never pass the wall.
              `skew_s`, only where a dispatch ran on several devices (a mesh
              program): the latest minus the earliest, over its devices, of
              the end of each device's last run inside it: how long the
              first shard to finish was done before the last.
    idle      the complement of busy inside the window, partitioned; the
              labels sum to the idle seconds exactly. At every idle instant
              the time is split equally among the threads whose innermost
              open annotation is not a `*/wait`; if there is none, among the
              waiting ones; if no thread is inside an annotation, it goes to
              `none`. A label is the innermost annotation's name, so it is a
              span's self time that is blamed. One interpreter runs one of
              those threads at a time: k threads inside annotations at once
              are k candidates for the work that kept the chip waiting (one
              of them ran, or all sat in a lock or a socket), not k cores, and
              the equal split says only that the trace cannot tell them apart.
    alignment of `runs` program runs, `aligned` overlap a `dispatch/*`
              annotation or start within 1 ms after one closed (`share`):
              device work launched through timed_dispatch, seen on one
              clock. `early` more start within 1 ms before a dispatch
              opens. A launch cannot precede the call that makes it: those
              are the device plane's stamps leading the host plane's, by an
              offset that is constant within a capture (on a v5e 0.22, 0.80
              and 1.19 ms in three captures, each to within 0.05 ms,
              against the runtime's own DoEnqueueProgram host events). What
              is in neither count ran outside any timed_dispatch.
    """
    by_thread: dict = {}
    dispatches = []  # (start, end, kernel)
    table: dict = {}
    for thread, name, a, d in annotations:
        a, b = max(0.0, a), min(window_ns, a + d)
        if b <= a:
            continue
        by_thread.setdefault(thread, []).append((name, a, b))
        seam, _, kernel = name.partition("/")
        if seam in ("dispatch", "transfer"):
            row = table.setdefault(kernel, {"count": 0, "wall_s": 0.0,
                                            "transfer_s": 0.0, "device_s": 0.0})
            if seam == "transfer":
                row["transfer_s"] += (b - a) / 1e9
            else:
                row["count"] += 1
                row["wall_s"] += (b - a) / 1e9
                dispatches.append((a, b, kernel))
    summary = {"window_s": window_ns / 1e9, "annotations": len(annotations),
               "device": None, "dispatch": table, "idle": None, "alignment": None}
    if programs is None:
        return summary

    dispatches.sort()
    starts = [a for a, _, _ in dispatches]
    longest = max((b - a for a, b, _ in dispatches), default=0.0)
    given: dict = {}  # dispatch index -> [(start, end, device)] of its runs, clipped
    per_program: dict = {}
    counted = aligned = early = 0
    for device, name, a, d in programs:
        a, b = max(0.0, a), min(window_ns, a + d)
        if b <= a:
            continue
        best, best_overlap, after, before = None, 0.0, False, False
        lo = bisect.bisect_left(starts, a - longest - ALIGN_NS)
        for i in range(lo, bisect.bisect_right(starts, b + ALIGN_NS)):
            da, db, _ = dispatches[i]
            after = after or da < b and a <= db + ALIGN_NS
            before = before or 0 < da - a <= ALIGN_NS
            overlap = min(b, db) - max(a, da)
            if overlap > 0 and overlap >= best_overlap:
                best, best_overlap = i, overlap  # ties: the later start, the inner one
        counted += 1
        aligned += after
        early += before and not after
        inside = "dispatch" if best is not None else "none"
        row = per_program.setdefault((_HASH.sub("", name), inside),
                                     {"runs": 0, "device_s": 0.0})
        row["runs"] += 1
        row["device_s"] += (b - a) / 1e9
        if best is not None:
            da, db, _ = dispatches[best]
            given.setdefault(best, []).append((max(a, da), min(b, db), device))
    for i, runs in given.items():
        row = table[dispatches[i][2]]
        row["device_s"] += sum(b - a for a, b in _union([r[:2] for r in runs])) / 1e9
        last: dict = {}  # device -> the end of its last run inside this dispatch
        for _, b, device in runs:
            last[device] = max(b, last.get(device, b))
        if len(last) > 1:
            row["skew_s"] = (row.get("skew_s", 0.0)
                             + (max(last.values()) - min(last.values())) / 1e9)

    by_device: dict = {}
    for device, a, d in ops or []:
        by_device.setdefault(device, []).append((max(0.0, a), min(window_ns, a + d)))
    busy = _union([iv for ivs in by_device.values() for iv in ivs])
    busy_ns = sum(b - a for a, b in busy)
    idle = _partition_idle(window_ns, busy, by_thread)
    summary["device"] = {
        "busy_s": busy_ns / 1e9,
        "idle_s": (window_ns - busy_ns) / 1e9,
        "per_device": [{"device": device,
                        "busy_s": sum(b - a for a, b in _union(ivs)) / 1e9}
                       for device, ivs in sorted(by_device.items())],
        "programs": [{"program": p, "inside": i, **row}
                     for (p, i), row in sorted(per_program.items())],
    }
    summary["idle"] = {name: ns / 1e9 for name, ns in sorted(idle.items())}
    summary["alignment"] = {"runs": counted, "aligned": aligned, "early": early,
                            "share": aligned / counted if counted else None}
    return summary


DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:CPU"
ARMED = "capture/armed"  # the capture's own annotation around its sleep


def read_capture(out_dir: str) -> tuple:
    """reduce_capture's arguments from the newest .xplane.pb under
    `out_dir`. An annotation of ours is a host event that carries `req`.
    The window is the capture's own `capture/armed`, and times count from
    its start: the trace is longer by the 0.4 s in which the profiler
    starts and stops, where no seam could write."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    profile = ProfileData.from_file(path)
    annotations, programs, ops, armed = [], None, None, None
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == ARMED:
                        armed = (float(e.start_ns), float(e.duration_ns))
                    elif "/" in e.name and any(k == "req" for k, _ in e.stats):
                        annotations.append((thread, e.name, float(e.start_ns),
                                            float(e.duration_ns)))
        elif plane.name.startswith(DEVICE_PLANE):
            programs, ops = programs or [], ops or []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs += [(plane.name, e.name, float(e.start_ns), float(e.duration_ns))
                                 for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(plane.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
    if armed is None:
        raise ValueError(f"{path} holds no {ARMED}")
    t0, window_ns = armed
    return (window_ns,
            [(thread, name, a - t0, d) for thread, name, a, d in annotations],
            None if programs is None else [(dev, name, a - t0, d)
                                           for dev, name, a, d in programs],
            None if ops is None else [(dev, a - t0, d) for dev, a, d in ops])


# the capture's counters: every tempo_tpu_profile_* family grows only when a
# capture ends, and only where the trace holds a device plane (but
# dispatch_bytes_total, which timed_dispatch adds to as an annotated
# dispatch returns: the trace does not carry a dispatch's bytes)
device_idle_seconds_total = metrics.counter(
    "tempo_tpu_profile_device_idle_seconds_total",
    "Seconds inside captures in which no device operation ran, by what the "
    "host was doing: the innermost open annotation (host) and its part "
    "before the slash (seam); host=\"none\": no thread was inside one",
)
dispatch_wall_seconds_total = metrics.counter(
    "tempo_tpu_profile_dispatch_wall_seconds_total",
    "Wall seconds of dispatch/* annotations inside captures, by kernel",
)
dispatch_device_seconds_total = metrics.counter(
    "tempo_tpu_profile_dispatch_device_seconds_total",
    "Device seconds of program runs inside their dispatch/* annotation, by "
    "kernel (never more than the dispatch's wall)",
)
dispatches_total = metrics.counter(
    "tempo_tpu_profile_dispatches_total",
    "dispatch/* annotations inside captures, by kernel",
)
dispatch_bytes_total = metrics.counter(
    "tempo_tpu_profile_dispatch_bytes_total",
    "Bytes the dispatches that wrote a dispatch/* annotation took as "
    "arguments (shipped or resident) and gave as results, by kernel: "
    "tempo_tpu_device_transfer_bytes_total's count, held to the dispatches "
    "a capture saw, so that it divides by their device seconds",
)
mesh_skew_seconds_total = metrics.counter(
    "tempo_tpu_profile_mesh_skew_seconds_total",
    "Shard skew of the dispatches inside captures that ran on several "
    "devices, by kernel: latest minus earliest end, over the devices, of each "
    "device's last program run inside the dispatch",
)


def _count(summary: dict) -> None:
    if summary["device"] is None:  # no device plane: nothing of a device to count
        return
    for host, s in summary["idle"].items():
        device_idle_seconds_total.inc(s, host=host, seam=host.partition("/")[0])
    for kernel, row in summary["dispatch"].items():
        dispatch_wall_seconds_total.inc(row["wall_s"], kernel=kernel)
        dispatch_device_seconds_total.inc(row["device_s"], kernel=kernel)
        dispatches_total.inc(row["count"], kernel=kernel)
        if "skew_s" in row:  # only a dispatch that ran on several devices has one
            mesh_skew_seconds_total.inc(row["skew_s"], kernel=kernel)


# ---------------------------------------------------------------------------
# the capture
# ---------------------------------------------------------------------------

_DEVICE_PROFILE_PREFIX = "tempo-tpu-device-profile-"
_DEVICE_PROFILE_KEEP = 3


def _prune_device_profiles(keep: int = _DEVICE_PROFILE_KEEP) -> None:
    """Captures are per-request artifacts on a long-lived server: keep
    only the newest few so a dashboard probe hammering the endpoint
    can't fill the disk with profiler traces."""
    root = tempfile.gettempdir()
    try:
        dirs = sorted(
            (os.path.join(root, n) for n in os.listdir(root)
             if n.startswith(_DEVICE_PROFILE_PREFIX)),
            key=lambda p: os.path.getmtime(p),
        )
    except OSError:
        return
    import shutil

    for stale in dirs[:-keep] if keep else dirs:
        shutil.rmtree(stale, ignore_errors=True)


def _ledger_window(mark: int) -> dict:
    """Transfer-ledger view of the capture window: which (block, column)
    pages shipped while the profiler ran, so the kernel trace and the
    data movement it paid for are ONE correlated artifact."""
    try:
        from tempo_tpu.util import pageheat

        return pageheat.LEDGER.window_report(mark)
    except Exception as e:  # noqa: BLE001 — the link must not kill the capture
        return {"error": str(e)}


def capture_device_profile(seconds: float = 1.0, out_dir: str | None = None) -> dict:
    """Bounded jax.profiler capture: traces whatever device work runs in
    the window into a TensorBoard-loadable directory. Degrades honestly —
    {"supported": False, "error": ...} when the backend/profiler can't —
    because an admin endpoint that 500s under the exact conditions it
    exists to debug is worse than useless.

    The Python tracer is off (a traced run should be the run it explains:
    with it on a 5 s capture weighed 40 MB and slowed the server by a
    quarter); the host tracer stays on and keeps the annotations the
    program's seams write while `capturing` is set. /status/profile is the
    Python-level view.

    "summary" is reduce_capture() over the trace just written (or
    {"error": ...} if it could not be read), also added to the
    tempo_tpu_profile_* counters.

    Every response (including degraded ones) carries "transferLedger":
    the page-heat accesses recorded over the SAME window, keyed off a
    ledger sequence mark taken before the trace starts."""
    global capturing, _annotation_type
    seconds = max(0.1, min(float(seconds), 30.0))
    from tempo_tpu.util import pageheat

    mark = pageheat.LEDGER.mark()
    try:
        import jax
        import jax.profiler

        if _annotation_type is None:
            _annotation_type = _load_annotation_type()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
    except Exception as e:  # pragma: no cover - jax is baked in
        return {"supported": False, "error": f"jax unavailable: {e}",
                "transferLedger": _ledger_window(mark)}
    if out_dir is None:
        # mkdtemp: unique under rapid successive captures (a wall-clock
        # suffix collides within one second); old captures are pruned
        out_dir = tempfile.mkdtemp(prefix=_DEVICE_PROFILE_PREFIX)
        _prune_device_profiles()
    try:
        # a second capture while one runs fails here, before the flag moves
        jax.profiler.start_trace(out_dir, profiler_options=options)
    except Exception as e:
        return {"supported": False, "error": f"profiler start failed: {e}",
                "transferLedger": _ledger_window(mark)}
    capturing = True
    try:
        with annotation(ARMED):  # when the seams were armed, on the profiler's clock
            time.sleep(seconds)
    finally:
        capturing = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            return {"supported": False, "error": f"profiler stop failed: {e}",
                    "dir": out_dir, "transferLedger": _ledger_window(mark)}
    files = []
    for root, _dirs, names in os.walk(out_dir):
        for n in names:
            files.append(os.path.relpath(os.path.join(root, n), out_dir))
    try:
        summary = reduce_capture(*read_capture(out_dir))
        _count(summary)
        log.info("device profile summary: %s", json.dumps(summary))
    except Exception as e:  # noqa: BLE001 — the trace is on disk either way
        summary = {"error": f"{type(e).__name__}: {e}"}
    return {
        "supported": True,
        "seconds": seconds,
        "dir": out_dir,
        "files": sorted(files)[:200],
        "summary": summary,
        "transferLedger": _ledger_window(mark),
    }
