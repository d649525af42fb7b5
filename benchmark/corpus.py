"""The benchmark's own data: spans drawn from a seed, cut into tenants
and blocks as a configuration file says.

This is the generator the yardstick rests on, so it lives here and is
numpy only. The span and attribute shape is `tempo_tpu/model/synth.py`'s
`make_batch` (8 services, 6 operation names, http status / method / url,
two attributes a span) with `chip_smoke.py`'s chained parents; the draws
are this file's own. Only `to_span_batch` touches the program: it wraps
the arrays in the program's SpanBatch so that its OTLP encoder can put
them on the wire, as any client's encoder would.
"""

from __future__ import annotations

import dataclasses

import numpy as np

SERVICES = ("frontend", "cart", "checkout", "currency", "shipping", "payment", "email", "ads")
OP_NAMES = ("GET /api/products", "POST /api/cart", "oteldemo.Checkout/Place", "db.query",
            "cache.get", "render")
HTTP_METHODS = ("GET", "POST", "PUT", "DELETE")
HTTP_STATUS = (200, 200, 404, 500)
ATTR_KEYS = ("k8s.pod.name", "region", "customer.id", "retry.count", "db.statement")
N_URLS, N_ATTR_VALUES, ATTRS_PER_SPAN = 64, 256, 2
KIND_CLIENT, KIND_SERVER = 3, 2  # OTLP SpanKind
VT_STR, VT_INT = 0, 1

# one string table for every block: a column holds indices into it
STRINGS = (SERVICES + OP_NAMES + HTTP_METHODS + tuple(f"http://svc/{i}" for i in range(N_URLS))
           + ATTR_KEYS + tuple(f"v{i}" for i in range(N_ATTR_VALUES)))
_OFF = {}
_at = 0
for _name, _n in (("service", len(SERVICES)), ("name", len(OP_NAMES)),
                  ("http_method", len(HTTP_METHODS)), ("http_url", N_URLS),
                  ("attr_key", len(ATTR_KEYS)), ("attr_str", N_ATTR_VALUES)):
    _OFF[_name] = _at
    _at += _n
STRING_COLUMNS = ("service", "name", "http_method", "http_url")


@dataclasses.dataclass
class Block:
    """One block's rows as pushed: `cols[...]` has n_traces*spans rows,
    the rows of a trace adjacent; `attrs[...]` has ATTRS_PER_SPAN rows a
    span in span order. String columns hold indices into STRINGS."""

    cols: dict
    attrs: dict
    spans: int

    @property
    def num_spans(self) -> int:
        return int(self.cols["trace_id"].shape[0])

    @property
    def n_traces(self) -> int:
        return self.num_spans // self.spans

    def head(self, n_traces: int) -> "Block":
        n = n_traces * self.spans
        return Block({k: v[:n] for k, v in self.cols.items()},
                     {k: v[:n * ATTRS_PER_SPAN] for k, v in self.attrs.items()}, self.spans)

    def slice_traces(self, lo: int, hi: int) -> "Block":
        a, b = lo * self.spans, hi * self.spans
        attrs = {k: v[a * ATTRS_PER_SPAN:b * ATTRS_PER_SPAN] for k, v in self.attrs.items()}
        attrs["attr_span"] = attrs["attr_span"] - np.uint32(a)
        return Block({k: v[a:b] for k, v in self.cols.items()}, attrs, self.spans)

    @staticmethod
    def concat(blocks: list) -> "Block":
        cols = {k: np.concatenate([b.cols[k] for b in blocks]) for k in blocks[0].cols}
        attrs = {k: np.concatenate([b.attrs[k] for b in blocks]) for k in blocks[0].attrs}
        off = np.cumsum([0] + [b.num_spans for b in blocks[:-1]])
        attrs["attr_span"] = np.concatenate(
            [b.attrs["attr_span"] + np.uint32(o) for b, o in zip(blocks, off)])
        return Block(cols, attrs, blocks[0].spans)


def make_block(n_traces: int, spans: int, seed, base_ns: int) -> Block:
    """n_traces traces of `spans` spans each. Every trace is one call
    chain (row k's parent is row k-1, kinds alternate server/client) and
    carries one service; everything else is drawn per span."""
    rng = np.random.default_rng(seed)
    n = n_traces * spans
    tid = rng.integers(0, 2**32, size=(n_traces, 4), dtype=np.uint32)
    span_id = rng.integers(1, 2**32, size=(n, 2), dtype=np.uint32)
    k = np.arange(n) % spans
    parent = np.zeros_like(span_id)
    parent[k > 0] = span_id[np.flatnonzero(k > 0) - 1]
    cols = {
        "trace_id": np.repeat(tid, spans, axis=0),
        "span_id": span_id,
        "parent_span_id": parent,
        "start_unix_nano": (base_ns + rng.integers(0, 10**9, size=n)).astype(np.uint64),
        "duration_nano": rng.integers(10**5, 10**9, size=n).astype(np.uint64),
        "kind": np.where(k % 2 == 0, KIND_SERVER, KIND_CLIENT).astype(np.uint8),
        "status_code": rng.choice([0, 0, 0, 2], size=n).astype(np.uint8),
        "name": rng.integers(0, len(OP_NAMES), size=n).astype(np.uint32),
        "service": np.repeat(rng.integers(0, len(SERVICES), size=n_traces), spans).astype(np.uint32),
        "http_status": rng.choice(HTTP_STATUS, size=n).astype(np.uint16),
        "http_method": rng.integers(0, len(HTTP_METHODS), size=n).astype(np.uint32),
        "http_url": rng.integers(0, N_URLS, size=n).astype(np.uint32),
    }
    m = n * ATTRS_PER_SPAN
    attrs = {
        "attr_span": np.repeat(np.arange(n, dtype=np.uint32), ATTRS_PER_SPAN),
        "attr_key": rng.integers(0, len(ATTR_KEYS), size=m).astype(np.uint32),
        "attr_vtype": rng.choice([VT_STR, VT_INT], size=m).astype(np.uint8),
        "attr_str": rng.integers(0, N_ATTR_VALUES, size=m).astype(np.uint32),
        "attr_num": rng.integers(0, 1000, size=m).astype(np.float64),
    }
    return Block(cols, attrs, spans)


def make_store(seed: int, data: dict, base_s: int) -> dict:
    """{tenant: [Block, ...]} as the configuration's `data` says. Block r
    of a tenant (r >= 1) re-sends the first `resend_fraction` of a block's
    worth of traces from block r-1's fresh part and fills up with fresh
    traces: the replication-factor duplicates of a store that has not
    been compacted yet. A trace sits in at most two blocks."""
    spans, per_block = data["spans_per_trace"], data["traces_per_block"]
    dup = int(per_block * data["resend_fraction"])
    store = {}
    for t, tenant in enumerate(data["tenants"]):
        blocks, prev_fresh = [], None
        for r in range(data["blocks_per_tenant"]):
            n_fresh = per_block - (dup if r else 0)
            fresh = make_block(n_fresh, spans, [seed, t, r], base_s * 10**9)
            blocks.append(Block.concat([prev_fresh.head(dup), fresh]) if r else fresh)
            prev_fresh = fresh
        store[tenant] = blocks
    return store


def trace_hex(block: Block) -> list:
    """The block's trace ids as the API spells them (32 hex digits)."""
    tid = block.cols["trace_id"][::block.spans]
    return [r.astype(">u4").tobytes().hex() for r in tid]


def to_span_batch(block: Block):
    """The block as the program's SpanBatch, for its OTLP encoder."""
    from tempo_tpu.model.columnar import SCOPE_SPAN, Dictionary, SpanBatch

    d = Dictionary()
    codes = np.array([d.add(s) for s in STRINGS], dtype=np.uint32)
    cols = dict(block.cols)
    for name in STRING_COLUMNS:
        cols[name] = codes[block.cols[name] + _OFF[name]]
    a = block.attrs
    attrs = {
        "attr_span": a["attr_span"],
        "attr_scope": np.full(a["attr_span"].shape[0], SCOPE_SPAN, dtype=np.uint8),
        "attr_key": codes[a["attr_key"] + _OFF["attr_key"]],
        "attr_vtype": a["attr_vtype"],
        "attr_str": np.where(a["attr_vtype"] == VT_STR,
                             codes[a["attr_str"] + _OFF["attr_str"]], 0).astype(np.uint32),
        "attr_num": a["attr_num"],
    }
    return SpanBatch(cols=cols, attrs=attrs, dictionary=d)


def encode_push(block: Block) -> bytes:
    """One OTLP/HTTP protobuf ExportTraceServiceRequest for the block."""
    from tempo_tpu.model.trace import batch_to_traces
    from tempo_tpu.receivers import otlp

    return otlp.encode_traces_request(batch_to_traces(to_span_batch(block)))
