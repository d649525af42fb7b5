"""Span-metrics processor: RED metrics per (service, span_name, kind, status).

Reference: modules/generator/processor/spanmetrics (spanmetrics.go:25,
aggregateMetrics:86 — traces_spanmetrics_{calls_total,latency,size_total}
with intrinsic dimensions).

Vectorized: one np.unique group-by over a packed uint64 key per batch,
one searchsorted histogramming pass, and one add a column into the
registry's series arrays — no per-span or per-series python loop. A
group's series rows are resolved once and kept across pushes; only a
group seen for the first time builds its label strings.
"""

from __future__ import annotations

import time

import numpy as np

from tempo_tpu.modules.generator.registry import COUNTER, HISTOGRAM

# seconds buckets matching the reference's default latency histogram
DEFAULT_BOUNDS = [0.002, 0.004, 0.008, 0.016, 0.032, 0.064, 0.128, 0.256, 0.512, 1.02, 2.05, 4.10]

CALLS = "traces_spanmetrics_calls_total"
LATENCY = "traces_spanmetrics_latency"
SIZE = "traces_spanmetrics_size_total"

KIND_NAMES = {0: "SPAN_KIND_UNSPECIFIED", 1: "SPAN_KIND_INTERNAL", 2: "SPAN_KIND_SERVER",
              3: "SPAN_KIND_CLIENT", 4: "SPAN_KIND_PRODUCER", 5: "SPAN_KIND_CONSUMER"}
STATUS_NAMES = {0: "STATUS_CODE_UNSET", 1: "STATUS_CODE_OK", 2: "STATUS_CODE_ERROR"}

# a group's key across pushes: service id << 40 | name id << 16 | kind
# << 8 | status, ids interned by this processor below 2**24
_ID_LIMIT = 1 << 24


class SpanMetricsProcessor:
    name = "span-metrics"

    def __init__(self, registry, bounds=None):
        self.registry = registry
        self.bounds = bounds or DEFAULT_BOUNDS
        self.spans_processed = 0
        self._reset_cache(registry.generation)

    def _reset_cache(self, generation: int) -> None:
        self._generation = generation
        self._ids: dict[str, int] = {}  # service and span names -> id
        self._keys = np.zeros(0, np.uint64)  # sorted group keys whose three series exist
        self._rows = np.zeros((0, 3), np.int64)  # their CALLS, SIZE, LATENCY rows

    def _id(self, s: str) -> int:
        i = self._ids.get(s)
        if i is None:
            i = self._ids[s] = len(self._ids)
        return i

    def push(self, batch) -> None:
        n = batch.num_spans
        if n == 0:
            return
        self.spans_processed += n
        c = batch.cols
        d = batch.dictionary
        # composite group key: service | name | kind | status, the codes
        # replaced by their ranks so the key packs into 64 bits and sorts
        # as the codes do
        svc_codes, svc_rank = np.unique(c["service"], return_inverse=True)
        name_codes, name_rank = np.unique(c["name"], return_inverse=True)
        n_names = np.uint64(len(name_codes))
        keys = ((svc_rank.astype(np.uint64) * n_names + name_rank.astype(np.uint64)) << np.uint64(16)
                | c["kind"].astype(np.uint64) << np.uint64(8) | c["status_code"].astype(np.uint64))
        uniq, first_row, inverse = np.unique(keys, return_index=True, return_inverse=True)
        g = len(uniq)
        counts = np.bincount(inverse, minlength=g)
        secs = c["duration_nano"].astype(np.float64) / 1e9
        sums = np.bincount(inverse, weights=secs, minlength=g)
        sizes = np.bincount(inverse, weights=np.full(n, batch.nbytes() / max(n, 1)), minlength=g)
        # histogram: bucket index per span (searchsorted), then 2D bincount
        nb = len(self.bounds) + 1
        bidx = np.searchsorted(np.asarray(self.bounds), secs, side="left")
        bucket_counts = np.bincount(inverse * nb + bidx, minlength=g * nb).reshape(g, nb)
        g_svc = ((uniq >> np.uint64(16)) // n_names).astype(np.int64)  # ranks
        g_name = ((uniq >> np.uint64(16)) % n_names).astype(np.int64)
        g_kind_status = uniq & np.uint64(0xFFFF)
        ex = (c["trace_id"][first_row], secs[first_row],
              (c["start_unix_nano"][first_row] // 10**6).astype(np.int64))

        reg = self.registry
        with reg.lock:
            now = time.time()
            if (reg.generation != self._generation
                    or len(self._ids) + len(svc_codes) + len(name_codes) >= _ID_LIMIT):
                self._reset_cache(reg.generation)
            svc_ids = np.array([self._id(d[int(s)]) for s in svc_codes], np.uint64)
            name_ids = np.array([self._id(d[int(s)]) for s in name_codes], np.uint64)
            gkeys = (svc_ids[g_svc] << np.uint64(40) | name_ids[g_name] << np.uint64(16)
                     | g_kind_status)
            at = np.minimum(np.searchsorted(self._keys, gkeys), max(len(self._keys) - 1, 0))
            hit = (self._keys[at] == gkeys) if len(self._keys) else np.zeros(g, bool)
            rows = np.full((g, 3), -1, np.int64)
            rows[hit] = self._rows[at[hit]]
            # groups new to the cache, in key order: each series is made
            # (or refused) the first time it is touched, CALLS, SIZE,
            # LATENCY a group, as one call a series would
            for i in np.flatnonzero(~hit).tolist():
                kind, status = int(g_kind_status[i]) >> 8, int(g_kind_status[i]) & 0xFF
                labels = (
                    ("service", d[int(svc_codes[g_svc[i]])]),
                    ("span_name", d[int(name_codes[g_name[i]])]),
                    ("span_kind", KIND_NAMES.get(kind, str(kind))),
                    ("status_code", STATUS_NAMES.get(status, str(status))),
                )
                rows[i] = (reg.row_locked(COUNTER, CALLS, labels, now),
                           reg.row_locked(COUNTER, SIZE, labels, now),
                           reg.row_locked(HISTOGRAM, LATENCY, labels, now, width=nb))
            made = ~hit & (rows >= 0).all(axis=1)
            if made.any():
                keys_all = np.concatenate([self._keys, gkeys[made]])
                order = np.argsort(keys_all, kind="stable")
                self._keys = keys_all[order]
                self._rows = np.concatenate([self._rows, rows[made]])[order]
            ok = rows[:, 0] >= 0
            reg.add_counters_locked(CALLS, rows[ok, 0], counts[ok].astype(np.float64), now)
            ok = rows[:, 1] >= 0
            reg.add_counters_locked(SIZE, rows[ok, 1], sizes[ok], now)
            ok = rows[:, 2] >= 0
            # one representative span of the group as the trace exemplar
            reg.add_histograms_locked(
                LATENCY, self.bounds, rows[ok, 2], bucket_counts[ok], sums[ok], counts[ok],
                now, exemplars=(ex[0][ok], ex[1][ok], ex[2][ok]))

