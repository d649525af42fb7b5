"""The plain reference: what a trace store holding these blocks has to
answer, straight from the generated columns. numpy only; it imports
nothing of the program and takes nothing the program made.

One Reference covers one tenant's blocks as separate, un-compacted
blocks: additive answers (counts, quantile samples) add across blocks,
re-sent traces included; set answers (hits, a trace's spans) union.
Rows of all blocks are laid end to end, which gives both for free.

`Bf16Counts`, `CoarseQuantiles` and `LeakyTenants` are the controls: the
same reference with one stated guarantee broken, put in the program's
place to show that the comparison fails it (see ../PERF.md, section 2).
"""

from __future__ import annotations

import numpy as np

from corpus import OP_NAMES, SERVICES, Block

PAGE_ROWS = 32768  # rows of one page / row group of a flushed block


class Reference:
    def __init__(self, blocks: list):
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

    # -- sets ---------------------------------------------------------------
    def find(self, trace_hex: str):
        """The pushed span ids (8 raw bytes each) of a trace, or None."""
        row = self._row_of.get(trace_hex)
        if row is None:
            return None
        return {r.tobytes() for r in self._span_id[row].astype(">u4")}

    def _hits(self, mask) -> frozenset:
        return frozenset(self.hexes[mask.any(axis=1)])

    def search_tags(self, service: str, min_duration_ns: int) -> frozenset:
        """tags=service.name=<service>&minDuration=<d>: traces of the
        service with a span at least that long."""
        return self._hits((self.service == SERVICES.index(service))
                          & (self.dur >= min_duration_ns))

    def traceql_filter(self, status: int, duration_ns: int) -> frozenset:
        """{ span.http.status_code = <status> && duration > <d> }: one
        span has to meet both."""
        return self._hits((self.http_status == status) & (self.dur > duration_ns))

    # -- counts -------------------------------------------------------------
    def _matching(self, service: str, duration_ns: int):
        return (self.service == SERVICES.index(service)) & (self.dur > duration_ns)

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


class LeakyTenants(Reference):
    """Control for the multi-tenant cells: searches see every tenant's
    blocks, which the configuration says a tenant never does."""

    def __init__(self, blocks: list, others: list):
        super().__init__(blocks)
        self._all = Reference(blocks + others)

    def search_tags(self, service, min_duration_ns):
        return self._all.search_tags(service, min_duration_ns)

    def traceql_filter(self, status, duration_ns):
        return self._all.traceql_filter(status, duration_ns)
