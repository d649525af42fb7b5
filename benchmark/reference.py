"""The plain reference: what a trace store holding these blocks has to
answer, straight from the generated columns. numpy only; it imports
nothing of the program and takes nothing the program made.

One Reference covers one tenant's blocks as separate, un-compacted
blocks: additive answers (counts, quantile samples) add across blocks,
re-sent traces included; set answers (hits, a trace's spans) union.
Rows of all blocks are laid end to end, which gives both for free.

A configuration that says `compaction_in_run` lets the compactor merge
blocks while the window runs, and a merge combines the copies of a span
that its inputs hold (`trace_id`, `span_id` equal). What the store then
rightly counts depends on which blocks have been merged so far: on a
partition of the loaded blocks into compaction outputs, within a group a
span counted once, across groups once a group. `Reference.states` holds
one view of the reference per partition (one, every copy counted, without
the key), and check.py accepts an additive answer that one of them gives
whole. Set answers do not depend on the partition.

`Bf16Counts`, `CoarseQuantiles`, `LeakyTenants` and `HalfCombined` are
the controls: the same reference with one stated guarantee broken, put in
the program's place to show that the comparison fails it (see ../PERF.md,
section 2).
"""

from __future__ import annotations

import copy

import numpy as np

from corpus import OP_NAMES, SERVICES, Block

PAGE_ROWS = 32768  # rows of one page / row group of a flushed block
MAX_PARTITION_BLOCKS = 4  # 15 partitions; 5 blocks would be 52, 8 blocks 4,140


def partitions(n: int) -> list:
    """Every partition of blocks 0..n-1 into groups: 1, 2, 5, 15 for n = 1..4."""
    if n > MAX_PARTITION_BLOCKS:
        raise ValueError(
            f"compaction_in_run with {n} blocks a tenant: the reference enumerates the "
            f"partitions of at most {MAX_PARTITION_BLOCKS} blocks into compaction outputs")
    out = [[]]
    for b in range(n):
        out = [p[:i] + [p[i] + [b]] + p[i + 1:] for p in out for i in range(len(p))] \
            + [p + [[b]] for p in out]
    return out


class Reference:
    def __init__(self, blocks: list, compaction_in_run: bool = False):
        b = Block.concat(blocks)
        c, s = b.cols, b.spans
        t = b.n_traces
        tid = c["trace_id"].reshape(t, s, 4)[:, 0]
        self.hexes = np.array([r.astype(">u4").tobytes().hex() for r in tid], dtype=object)
        self._row_of = {}
        for i, h in enumerate(self.hexes):  # first copy of a re-sent trace
            self._row_of.setdefault(h, i)
        self._span_id = c["span_id"].reshape(t, s, 2)
        self.dur = c["duration_nano"].astype(np.int64).reshape(t, s)
        self.service = c["service"].reshape(t, s)
        self.name = c["name"].reshape(t, s)
        self.http_status = c["http_status"].reshape(t, s)
        self._start = c["start_unix_nano"].astype(np.int64).reshape(t, s)
        self._keep = None  # the rows an additive answer counts; None: every copy
        self.states, self.merges = [self], 0
        if compaction_in_run:
            block_of = np.repeat(np.arange(len(blocks)), [x.num_spans for x in blocks])
            _, span_key = np.unique(np.concatenate([c["trace_id"], c["span_id"]], axis=1),
                                    axis=0, return_inverse=True)
            self.states = [
                self._counting(_first_copies(span_key.ravel(), block_of, p), len(blocks) - len(p))
                for p in reversed(partitions(len(blocks)))]  # un-compacted first

    def _counting(self, keep: np.ndarray, merges: int) -> "Reference":
        """This reference, its additive answers taken over the rows of `keep`.
        `merges`: the loaded blocks less the groups, which jobs only raise."""
        state = copy.copy(self)
        state._keep = keep.reshape(self.dur.shape)
        state.states, state.merges = [state], merges
        return state

    # -- sets ---------------------------------------------------------------
    def find(self, trace_hex: str):
        """The pushed span ids (8 raw bytes each) of a trace, or None."""
        row = self._row_of.get(trace_hex)
        if row is None:
            return None
        return {r.tobytes() for r in self._span_id[row].astype(">u4")}

    def _hits(self, mask) -> frozenset:
        return frozenset(self.hexes[mask.any(axis=1)])

    def _in(self, window):
        """start=<s>&end=<s> of a search, upstream's meaning and the
        program's: a span counts where it overlaps the range."""
        if window is None:
            return True
        return ((self._start + self.dur >= window[0] * 10**9)
                & (self._start <= window[1] * 10**9))

    def search_tags(self, service: str, min_duration_ns: int, window=None) -> frozenset:
        """tags=service.name=<service>&minDuration=<d>: traces of the
        service with a span at least that long."""
        return self._hits((self.service == SERVICES.index(service))
                          & (self.dur >= min_duration_ns) & self._in(window))

    def traceql_filter(self, status: int, duration_ns: int, window=None) -> frozenset:
        """{ span.http.status_code = <status> && duration > <d> }: one
        span has to meet both."""
        return self._hits((self.http_status == status) & (self.dur > duration_ns)
                          & self._in(window))

    # -- counts -------------------------------------------------------------
    def _matching(self, service: str, duration_ns: int):
        m = (self.service == SERVICES.index(service)) & (self.dur > duration_ns)
        return m if self._keep is None else m & self._keep

    def _count(self, mask) -> int:
        return int(mask.sum())

    def rate_by_name(self, service: str, duration_ns: int) -> dict:
        m = self._matching(service, duration_ns)
        out = {nm: self._count(m & (self.name == i)) for i, nm in enumerate(OP_NAMES)}
        return {k: v for k, v in out.items() if v}

    def rate_total(self, service: str, duration_ns: int) -> dict:
        n = self._count(self._matching(service, duration_ns))
        return {"": n} if n else {}

    def rate_by_service(self, service: str, duration_ns: int) -> dict:
        """One series holds every matching span, so the program's slot
        stream is one run a page: the weighted-count path."""
        n = self._count(self._matching(service, duration_ns))
        return {service: n} if n else {}

    def quantiles(self, service: str, duration_ns: int, qs) -> dict:
        """{q: (lower, upper)} in seconds: the two order statistics the
        quantile lies between (they differ only where spans are few)."""
        d = self.dur[self._matching(service, duration_ns)]
        return {q: (float(np.quantile(d, q, method="lower")) / 1e9,
                    float(np.quantile(d, q, method="higher")) / 1e9) for q in qs}


def _first_copies(span_key: np.ndarray, block_of: np.ndarray, partition: list) -> np.ndarray:
    """The rows a store in this state counts: within each group of the
    partition the first copy of a span, whichever block of it holds it."""
    keep = np.zeros(span_key.shape[0], bool)
    for group in partition:
        rows = np.flatnonzero(np.isin(block_of, group))
        keep[rows[np.unique(span_key[rows], return_index=True)[1]]] = True
    return keep


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even) -> float32."""
    u = x.astype(np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


class Bf16Counts(Reference):
    """Control for the cells that ask query_range: span counts summed as
    the MXU sums them at default precision. The program folds a page's
    slot stream as (slot, run length) pairs through a matmul; at default
    precision the run lengths are rounded to bfloat16, and one above 256
    does not survive that (PERF.md, PR 22). Here every page's count is
    rounded so before the pages are added."""

    def _count(self, mask) -> int:
        flat = mask.ravel()
        pad = (-flat.shape[0]) % PAGE_ROWS
        pages = np.concatenate([flat, np.zeros(pad, bool)]).reshape(-1, PAGE_ROWS)
        return int(_round_bf16(pages.sum(axis=1)).sum())


class CoarseQuantiles(Reference):
    """Control for the cells that ask quantile_over_time: the quantile read
    from a sketch of 4 sub-buckets an octave, half the 8 the configuration
    states (one bit less of a duration's mantissa). As the program's sketch
    does, it answers with the upper edge of the bucket that holds the
    order statistic; its error can reach 25 %, the stated limit is 12.5 %."""

    SUB = 4

    def quantiles(self, service, duration_ns, qs) -> dict:
        out = {}
        for q, (lo, _) in super().quantiles(service, duration_ns, qs).items():
            ns = lo * 1e9
            octave = 2.0 ** np.floor(np.log2(ns))
            sub = np.floor((ns / octave - 1.0) * self.SUB)
            edge = float(octave * (1.0 + (sub + 1) / self.SUB)) / 1e9
            out[q] = (edge, edge)
        return out


class HalfCombined(Reference):
    """Control for a configuration that compacts inside a run: a merge
    that combines the copies of every other re-sent trace and keeps both
    copies of the rest. No partition of the blocks gives its counts: a
    merge combines every copy its inputs hold, or it has not run."""

    def __init__(self, blocks: list):
        super().__init__(blocks)
        first = np.zeros(len(self.hexes), bool)
        first[list(self._row_of.values())] = True
        resent = np.flatnonzero(~first)  # the second copies, in store order
        keep = np.ones(self.dur.shape, bool)
        keep[resent[::2]] = False
        self._keep = keep


class LeakyTenants(Reference):
    """Control for the multi-tenant cells: searches see every tenant's
    blocks, which the configuration says a tenant never does."""

    def __init__(self, blocks: list, others: list):
        super().__init__(blocks)
        self._all = Reference(blocks + others)

    def search_tags(self, *args):
        return self._all.search_tags(*args)

    def traceql_filter(self, *args):
        return self._all.traceql_filter(*args)
