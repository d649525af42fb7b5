"""Producer/consumer overlap utilities for the hot data paths.

SURVEY.md 7.4 names host<->device bandwidth + serial decode->kernel->
encode chains as the 10x-killer; the reference overlaps these stages
with async page prefetch (pkg/parquetquery/iters.go:246,
tempodb/encoding/v2/iterator_prefetch.go) and N flush queues. Python
equivalents work because the heavy stages release the GIL: native codec
calls are ctypes (GIL dropped for the C call), device dispatch blocks in
XLA, and file IO blocks in the OS.
"""

from __future__ import annotations

import os
import queue
import threading
from concurrent.futures import ThreadPoolExecutor

from tempo_tpu.util import usage

_SENTINEL = object()


def overlap_enabled() -> bool:
    """Whether producer/consumer threading can actually overlap work.

    On a single-core host the GIL-released C calls still cannot run
    concurrently with Python (one core), so background threads only add
    context switches. TEMPO_TPU_OVERLAP=0/1 overrides the auto-detect."""
    env = os.environ.get("TEMPO_TPU_OVERLAP")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no")
    try:
        # affinity-aware: a pinned/cgroup-limited process on a big node
        # still only has the cpuset it was given
        usable = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):  # pragma: no cover
        usable = os.cpu_count() or 1
    return usable > 1


def prefetch_iter(iterable, depth: int = 2, join_timeout_s: float = 60.0):
    """Run `iterable` on a background thread, buffering up to `depth`
    items ahead of the consumer. Exceptions re-raise at the consumer.
    Closing the returned generator (or abandoning it) stops the producer
    thread, so a consumer that fails mid-stream never leaks a thread
    blocked on a full queue.

    BLOCKING-CLOSE CONTRACT: close() joins the producer for up to
    `join_timeout_s` (default 60s) so the caller's cleanup cannot race a
    producer still inside the source. A producer wedged in an
    uncancellable call therefore stalls close() for the full timeout —
    acceptable on the compactor (today's only caller, documented there);
    latency-sensitive callers must pass a small join_timeout_s and
    accept the leaked daemon thread instead."""
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def run():
        try:
            for item in iterable:
                if not _put(item):
                    return
        except BaseException as e:  # propagate into the consuming thread
            _put((_SENTINEL, e))
        else:
            _put((_SENTINEL, None))
        finally:
            # close the source ON the producer thread: the generator is
            # guaranteed not to be executing here, so this cannot race a
            # cross-thread close() (ValueError: generator already
            # executing) the way a consumer-side close would
            close = getattr(iterable, "close", None)
            if close is not None:
                close()

    t = threading.Thread(target=run, daemon=True, name="prefetch-iter")
    t.start()
    try:
        while True:
            item = q.get()
            if isinstance(item, tuple) and len(item) == 2 and item[0] is _SENTINEL:
                if item[1] is not None:
                    raise item[1]
                return
            yield item
    finally:
        stop.set()
        # quiesce before returning control: the caller's cleanup (closing
        # block streams under the producer) is only safe once the
        # producer has actually exited. Bounded join: a producer stuck in
        # an untimed backend read must not convert a failed job into a
        # hung daemon — leak the (daemon) thread with a warning instead,
        # which is the pre-join behavior for exactly that pathology.
        t.join(timeout=join_timeout_s)
        if t.is_alive():  # pragma: no cover - needs a wedged source
            import logging

            logging.getLogger(__name__).warning(
                "prefetch producer did not quiesce within %.0fs; leaking daemon thread",
                join_timeout_s,
            )


def _item_nbytes(item) -> int:
    """Best-effort size of a prefetched item (dict of numpy arrays,
    one array, or bytes) — feeds the wasted-bytes counter."""
    if isinstance(item, dict):
        return sum(getattr(v, "nbytes", len(v) if isinstance(v, (bytes, bytearray)) else 0)
                   for v in item.values())
    return getattr(item, "nbytes", len(item) if isinstance(item, (bytes, bytearray)) else 0)


def _under_pressure() -> bool:
    """Prefetch gate: lookahead trades memory for latency, exactly the
    wrong trade while the process is under memory pressure — new
    ReadAhead instances run without the background slot until the
    governor (util/resource) reports OK again."""
    from tempo_tpu.util import resource

    return resource.governor().level() >= resource.LEVEL_PRESSURE


class ReadAhead:
    """One-slot lookahead for a pull-based loader: while the consumer
    works on item i, a worker thread loads item i+1.

    Observability: process-wide counters (through the register_collector
    seam in util/metrics, like the column-cache gauges) expose whether
    the lookahead actually lands — `tempodb_search_prefetch_hits_total`
    (get() served by a completed prefetch), `..._misses_total` (cold or
    out-of-order loads paid inline), and `..._wasted_bytes_total`
    (prefetched items abandoned at close, e.g. a search that hit its
    limit early — bytes loaded for nothing).
    """

    # class-level aggregates; the metrics collector snapshots them at
    # every exposition (values only grow, counter semantics hold)
    _totals_lock = threading.Lock()
    _totals = {"hits": 0, "misses": 0, "wasted_bytes": 0}
    _metrics_registered = False

    def __init__(self, load, n_items: int):
        self._load = load
        # the prefetch thread loads bytes FOR the request that created
        # this ReadAhead: carry its cost vector (and only that — stage
        # timings stay per-thread so overlapped IO never double-counts
        # wall-clock buckets) into the background loads
        self._usage_vec = usage.active()
        self._n = n_items
        self._next = 0
        self._future = None
        self._pool = (
            ThreadPoolExecutor(max_workers=1)
            if n_items > 1 and overlap_enabled() and not _under_pressure()
            else None
        )
        self._register_metrics()

    @classmethod
    def _bump(cls, key: str, amount: int = 1) -> None:
        with cls._totals_lock:
            cls._totals[key] += amount

    @classmethod
    def _register_metrics(cls) -> None:
        if cls._metrics_registered:
            return
        cls._metrics_registered = True
        from tempo_tpu.util import metrics

        gauges = {
            "hits": metrics.counter(
                "tempodb_search_prefetch_hits_total",
                "ReadAhead gets served by a completed prefetch"),
            "misses": metrics.counter(
                "tempodb_search_prefetch_misses_total",
                "ReadAhead cold/out-of-order loads paid inline"),
            "wasted_bytes": metrics.counter(
                "tempodb_search_prefetch_wasted_bytes_total",
                "Bytes prefetched but abandoned at close (early exit)"),
        }

        def collect():
            with cls._totals_lock:
                snap = dict(cls._totals)
            for key, c in gauges.items():
                # counters only move forward: publish the delta since
                # the last exposition
                delta = snap[key] - c.value()
                if delta > 0:
                    c.inc(delta)

        metrics.register_collector(collect)

    def _schedule(self):
        if self._pool is not None and self._next < self._n:
            i = self._next
            self._future = self._pool.submit(
                usage.run_with, self._usage_vec, self._load, i)

    def get(self, i: int):
        """Items must be requested in order 0..n-1."""
        if self._future is not None and self._next == i:
            fut, self._future = self._future, None
            self._next += 1
            self._schedule()
            self._bump("hits")
            return fut.result()
        # cold path (first call or out-of-order): load inline, then look ahead
        item = self._load(i)
        self._next = i + 1
        self._schedule()
        self._bump("misses")
        return item

    def close(self):
        fut, self._future = self._future, None
        if fut is not None and fut.done() and fut.exception() is None:
            # loaded but never consumed: the lookahead overshot (early
            # exit on limit) — account the bytes it cost
            self._bump("wasted_bytes", _item_nbytes(fut.result()))
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
