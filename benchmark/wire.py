"""Just enough protobuf to read span ids out of a trace and to find the
trace-id bytes in an encoded push, without the program's decoder.

TracesData / ExportTraceServiceRequest / tempopb.Trace all share the
layout: field 1 = repeated ResourceSpans; there field 2 = repeated
ScopeSpans; there field 2 = repeated Span; in a Span field 1 = trace_id
(16 bytes) and field 2 = span_id (8 bytes).
"""

from __future__ import annotations

import numpy as np


def _varint(buf, i: int):
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, wire type, value start, value end) of one message."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 2:
            n, i = _varint(buf, i)
            yield key >> 3, wt, i, i + n
            i += n
        elif wt == 0:
            j = i
            _, i = _varint(buf, i)
            yield key >> 3, wt, j, i
        elif wt == 1:
            yield key >> 3, wt, i, i + 8
            i += 8
        elif wt == 5:
            yield key >> 3, wt, i, i + 4
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")


def _spans(buf):
    """(start, end) of every Span message in the buffer."""
    for f, wt, a, b in _fields(buf, 0, len(buf)):
        if f != 1 or wt != 2:
            continue
        for f2, wt2, a2, b2 in _fields(buf, a, b):
            if f2 != 2 or wt2 != 2:
                continue
            for f3, wt3, a3, b3 in _fields(buf, a2, b2):
                if f3 == 2 and wt3 == 2:
                    yield a3, b3


def span_ids(buf: bytes) -> set:
    """The span ids (8 raw bytes each) of every span in the message."""
    out = set()
    for a, b in _spans(buf):
        for f, wt, va, vb in _fields(buf, a, b):
            if f == 2 and wt == 2:
                out.add(bytes(buf[va:vb]))
                break
    return out


def trace_id_offsets(buf: bytes):
    """(offsets, which, ids, span_sets): the byte offset of every span's
    trace_id in the message; the distinct trace ids in order of first
    appearance; `which` maps each offset to its trace's place in `ids`;
    span_sets[k] holds the span ids (8 raw bytes) of trace ids[k]."""
    offsets, which, ids, seen, span_sets = [], [], [], {}, []
    for a, b in _spans(buf):
        k = None
        for f, wt, va, vb in _fields(buf, a, b):
            if f == 1 and wt == 2:
                if vb - va != 16:
                    raise ValueError("trace_id is not 16 bytes")
                tid = bytes(buf[va:vb])
                k = seen.setdefault(tid, len(ids))
                if k == len(ids):
                    ids.append(tid)
                    span_sets.append(set())
                offsets.append(va)
                which.append(k)
            elif f == 2 and wt == 2:  # span_id follows trace_id
                span_sets[k].add(bytes(buf[va:vb]))
                break
    return np.array(offsets, np.int64), np.array(which, np.int64), ids, span_sets


class PatchableBody:
    """An encoded push whose trace ids can be overwritten in place, so a
    pool of bodies encoded once during set-up makes every send new."""

    def __init__(self, body: bytes):
        self.buf = np.frombuffer(bytearray(body), dtype=np.uint8)
        off, which, self.ids, self.span_sets = trace_id_offsets(body)
        self._idx = off[:, None] + np.arange(16)
        self._which = which
        self.n_traces = len(self.ids)
        self.n_spans = len(off)

    def patched(self, new_ids: np.ndarray) -> bytes:
        """The body with trace k's id replaced by new_ids[k] ((n, 16) u8)."""
        buf = self.buf.copy()
        buf[self._idx] = new_ids[self._which]
        return buf.tobytes()
