"""TSDB-lite metric registry.

Reference: modules/generator/registry (registry.go:56 ManagedRegistry,
counter.go, histogram.go, hash.go — counters/histograms keyed by label
hash, staleness removal, active-series limiting, periodic collect into
a Prometheus appender).

Columnar: each family (one metric name) keeps its series as rows of
parallel arrays, so a processor adds a whole push's groups with one
fancy-indexed add a column. A series is created, or refused by
`max_active_series`, the first time it is touched; `collect()` lists
counters, then histograms, each in the order their series were created.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

from tempo_tpu.encoding.vtpu import format as fmt


@dataclass
class Sample:
    name: str
    labels: tuple  # ((k, v), ...)
    value: float
    timestamp_ms: int = 0
    exemplar: "Exemplar | None" = None


@dataclass
class Exemplar:
    """One trace-ID exemplar attached to a series (reference:
    registry histogram exemplars — the (trace_id, value, ts) triple a
    Grafana heatmap uses to jump from a bucket to the trace). The same
    struct travels in query_range responses (metrics_engine), so both
    metric surfaces speak one exemplar shape."""

    trace_id: str  # hex
    value: float
    timestamp_ms: int = 0

    def to_dict(self) -> dict:
        return {"traceID": self.trace_id, "value": self.value,
                "timestamp": self.timestamp_ms}


COUNTER, HISTOGRAM = "counter", "histogram"


class _Family:
    """The series of one metric name: labels -> row, and one growable
    array a field, rows in creation order."""

    def __init__(self, kind: str, width: int = 0):
        self.labels: list[tuple] = []
        self.index: dict[tuple, int] = {}
        spec = {"seq": (np.int64, ()), "last": (np.float64, ())}
        if kind == COUNTER:
            spec["value"] = (np.float64, ())
        else:
            spec.update(buckets=(np.int64, (width,)), sum=(np.float64, ()),
                        count=(np.int64, ()), ex_has=(np.bool_, ()),
                        ex_tid=(np.uint32, (4,)), ex_val=(np.float64, ()),
                        ex_ts=(np.int64, ()))
        self.cols = {k: np.zeros((8,) + tail, dt) for k, (dt, tail) in spec.items()}

    def __len__(self) -> int:
        return len(self.labels)

    def add_row(self, labels: tuple, seq: int, now: float) -> int:
        row = len(self.labels)
        if row == len(self.cols["seq"]):
            self.cols = {k: np.concatenate([v, np.zeros_like(v)]) for k, v in self.cols.items()}
        self.labels.append(labels)
        self.index[labels] = row
        self.cols["seq"][row] = seq
        self.cols["last"][row] = now
        return row

    def keep(self, mask: np.ndarray) -> None:
        n = len(self.labels)
        self.cols = {k: np.concatenate([v[:n][mask], np.zeros_like(v[: max(8, n)])])
                     for k, v in self.cols.items()}
        self.labels = [lab for lab, k in zip(self.labels, mask.tolist()) if k]
        self.index = {lab: i for i, lab in enumerate(self.labels)}


class ManagedRegistry:
    def __init__(self, tenant: str, max_active_series: int = 0,
                 stale_after_s: float = 900.0):
        self.tenant = tenant
        self.max_active_series = max_active_series
        self.stale_after_s = stale_after_s
        self.lock = threading.Lock()
        self._families: dict[tuple, _Family] = {}  # (kind, name) -> family
        self._n_series = 0
        self._seq = 0
        self.bucket_bounds: dict[str, list] = {}
        self.series_dropped = 0
        # bumped when rows move (remove_stale): a caller that cached rows
        # across pushes resolves them again
        self.generation = 0

    # ------------------------------------------------------------------
    # under self.lock: what a processor that adds a whole push at once uses
    def row_locked(self, kind: str, name: str, labels: tuple, now: float,
                   calls: int = 1, width: int = 0) -> int:
        """The series' row, created if the limit has room; -1 and
        `calls` refusals counted if not (each call it stands for would
        have been refused on its own)."""
        fam = self._families.get((kind, name))
        if fam is not None:
            row = fam.index.get(labels)
            if row is not None:
                return row
        if self.max_active_series and self._n_series >= self.max_active_series:
            self.series_dropped += calls
            return -1
        if fam is None:
            fam = self._families[(kind, name)] = _Family(kind, width)
        self._n_series += 1
        self._seq += 1
        return fam.add_row(labels, self._seq, now)

    def add_counters_locked(self, name: str, rows: np.ndarray, deltas: np.ndarray,
                            now: float) -> None:
        """rows of `name` (row_locked); deltas added, a row that repeats
        once for each time it is given."""
        if len(rows):
            cols = self._families[(COUNTER, name)].cols
            np.add.at(cols["value"], rows, deltas)
            cols["last"][rows] = now

    def add_histograms_locked(self, name: str, bounds: list, rows: np.ndarray,
                              buckets: np.ndarray, sums: np.ndarray, counts: np.ndarray,
                              now: float, exemplars=None) -> None:
        """rows of `name`, as add_counters_locked's; buckets (rows,
        len(bounds) + 1). exemplars: (trace-ID limbs (rows, 4), values,
        timestamps ms) that replace each row's exemplar (the last given
        for a row that repeats), or None to keep them."""
        self.bucket_bounds[name] = list(bounds)
        if not len(rows):
            return
        cols = self._families[(HISTOGRAM, name)].cols
        np.add.at(cols["buckets"], rows, buckets)
        np.add.at(cols["sum"], rows, sums)
        np.add.at(cols["count"], rows, counts)
        cols["last"][rows] = now
        if exemplars is not None:
            tid, val, ts = exemplars
            cols["ex_has"][rows] = True
            cols["ex_tid"][rows] = tid
            cols["ex_val"][rows] = val
            cols["ex_ts"][rows] = ts

    # ------------------------------------------------------------------
    def inc_counter(self, name: str, labels: tuple, delta: float,
                    now: float | None = None, calls: int = 1) -> None:
        """`calls` increments folded into one `delta`: refused as one
        series, counted as `calls` refusals."""
        now = now or time.time()
        with self.lock:
            row = self.row_locked(COUNTER, name, labels, now, calls)
            if row >= 0:
                self.add_counters_locked(name, np.array([row]), np.array([float(delta)]), now)

    def observe_histogram(self, name: str, labels: tuple, bounds: list,
                          bucket_counts, total_sum: float, total_count: int,
                          now: float | None = None, calls: int = 1) -> None:
        """Batch-observe: pre-aggregated bucket counts from a vectorized
        pass (the processors hand whole batches, not single points);
        `calls` as inc_counter's."""
        now = now or time.time()
        with self.lock:
            row = self.row_locked(HISTOGRAM, name, labels, now, calls, len(bounds) + 1)
            self.add_histograms_locked(
                name, bounds, np.array([row]) if row >= 0 else np.zeros(0, np.int64),
                np.asarray(bucket_counts, np.int64)[None], np.array([float(total_sum)]),
                np.array([int(total_count)]), now)

    # ------------------------------------------------------------------
    def remove_stale(self, now: float | None = None) -> int:
        now = now or time.time()
        removed = 0
        with self.lock:
            for fam in self._families.values():
                stale = now - fam.cols["last"][: len(fam)] > self.stale_after_s
                if stale.any():
                    removed += int(stale.sum())
                    fam.keep(~stale)
            if removed:
                self._n_series -= removed
                self.generation += 1
        return removed

    def active_series(self) -> int:
        with self.lock:
            return self._n_series

    def _in_creation_order(self, kind: str) -> list:
        """(family name, family, row) of every `kind` series, oldest first."""
        fams = [(name, f) for (k, name), f in self._families.items() if k == kind and len(f)]
        if not fams:
            return []
        seq = np.concatenate([f.cols["seq"][: len(f)] for _, f in fams])
        which = np.repeat(np.arange(len(fams)), [len(f) for _, f in fams])
        row = np.concatenate([np.arange(len(f)) for _, f in fams])
        order = np.argsort(seq, kind="stable")
        return [(fams[w][0], fams[w][1], r)
                for w, r in zip(which[order].tolist(), row[order].tolist())]

    def collect(self, now_ms: int | None = None) -> list:
        now_ms = now_ms or int(time.time() * 1000)
        out: list[Sample] = []
        with self.lock:
            for name, fam, r in self._in_creation_order(COUNTER):
                out.append(Sample(name, fam.labels[r], float(fam.cols["value"][r]), now_ms))
            for name, fam, r in self._in_creation_order(HISTOGRAM):
                labels, c = fam.labels[r], fam.cols
                bounds = self.bucket_bounds.get(name, [])
                cum = np.cumsum(c["buckets"][r]).tolist()
                for i, b in enumerate(bounds):
                    out.append(Sample(f"{name}_bucket", labels + (("le", str(b)),), cum[i], now_ms))
                ex = None
                if c["ex_has"][r]:
                    ex = Exemplar(fmt.id_to_hex(c["ex_tid"][r]), float(c["ex_val"][r]),
                                  int(c["ex_ts"][r]))
                # exemplar rides the +Inf bucket (contains every value),
                # the OpenMetrics exposition convention
                out.append(Sample(f"{name}_bucket", labels + (("le", "+Inf"),), cum[-1],
                                  now_ms, exemplar=ex))
                out.append(Sample(f"{name}_sum", labels, float(c["sum"][r]), now_ms))
                out.append(Sample(f"{name}_count", labels, int(c["count"][r]), now_ms))
        return out

    def prometheus_text(self) -> str:
        lines = []
        for s in self.collect():
            labels = list(s.labels) + [("tenant", self.tenant)]
            lbl = ",".join(f'{k}="{v}"' for k, v in labels)
            line = f"{s.name}{{{lbl}}} {s.value}"
            if s.exemplar is not None:
                # OpenMetrics exemplar suffix: `# {labels} value timestamp`
                ex = s.exemplar
                line += (f' # {{trace_id="{ex.trace_id}"}} {ex.value}'
                         f" {ex.timestamp_ms / 1000:.3f}")
            lines.append(line)
        return "\n".join(lines) + ("\n" if lines else "")
