"""Receiver codec tests: OTLP proto round-trip, OTLP/JSON, Zipkin v2,
Jaeger thrift-binary (payload built with a minimal thrift writer), and
the HTTP shim dispatch. Mirrors the reference's receiver coverage
(integration/e2e/receivers_test.go exercises every protocol)."""

import functools
import gzip
import json
import struct

import numpy as np
import pytest

from tempo_tpu import receivers
from tempo_tpu.model.synth import make_trace
from tempo_tpu.model.trace import (
    KIND_CLIENT,
    KIND_SERVER,
    STATUS_ERROR,
    Span,
    Trace,
)
from tempo_tpu.receivers import jaeger, otlp, zipkin
from tempo_tpu.receivers import protowire as pw


def _span_index(traces):
    out = {}
    for t in traces:
        for resource, spans in t.batches:
            for s in spans:
                out[s.span_id] = (resource, s)
    return out


class TestOTLPProto:
    def test_round_trip(self):
        traces = [make_trace(seed=i, n_spans=5) for i in range(3)]
        buf = otlp.encode_traces_request(traces)
        back = otlp.decode_traces_request(buf)
        assert {t.trace_id for t in back} == {t.trace_id for t in traces}
        want = _span_index(traces)
        got = _span_index(back)
        assert set(got) == set(want)
        for sid, (resource, s) in want.items():
            r2, s2 = got[sid]
            assert r2.get("service.name") == resource.get("service.name")
            assert s2.name == s.name
            assert s2.start_unix_nano == s.start_unix_nano
            assert s2.duration_nano == s.duration_nano
            assert s2.kind == s.kind
            assert s2.status_code == s.status_code
            assert s2.attributes == {k: v for k, v in s.attributes.items()}

    def test_attr_types_round_trip(self):
        s = Span(
            trace_id=b"\x01" * 16,
            span_id=b"\x02" * 8,
            name="op",
            start_unix_nano=10,
            duration_nano=5,
            attributes={
                "s": "x",
                "i": -42,
                "b": True,
                "f": 2.5,
                "arr": ["a", 1],
                "kv": {"inner": "y"},
            },
        )
        t = Trace(trace_id=s.trace_id, batches=[({"service.name": "svc"}, [s])])
        back = otlp.decode_traces_request(otlp.encode_traces_request([t]))
        s2 = list(back[0].all_spans())[0]
        assert s2.attributes == s.attributes

    def test_spans_regrouped_by_trace_id(self):
        # one ResourceSpans carrying spans of two traces must split
        a = Span(trace_id=b"\xaa" * 16, span_id=b"\x01" * 8, name="a")
        b = Span(trace_id=b"\xbb" * 16, span_id=b"\x02" * 8, name="b")
        t = Trace(trace_id=a.trace_id, batches=[({"service.name": "s"}, [a, b])])
        back = otlp.decode_traces_request(otlp.encode_traces_request([t]))
        assert {x.trace_id for x in back} == {a.trace_id, b.trace_id}

    def test_truncated_rejected(self):
        buf = otlp.encode_traces_request([make_trace(seed=0, n_spans=3)])
        with pytest.raises(ValueError):
            otlp.decode_traces_request(buf[: len(buf) - 3])


class TestOTLPJson:
    def test_decode(self):
        doc = {
            "resourceSpans": [
                {
                    "resource": {
                        "attributes": [
                            {"key": "service.name", "value": {"stringValue": "shop"}}
                        ]
                    },
                    "scopeSpans": [
                        {
                            "spans": [
                                {
                                    "traceId": "0102030405060708090a0b0c0d0e0f10",
                                    "spanId": "0102030405060708",
                                    "name": "GET /",
                                    "kind": "SPAN_KIND_SERVER",
                                    "startTimeUnixNano": "1000",
                                    "endTimeUnixNano": "3000",
                                    "status": {"code": "STATUS_CODE_ERROR"},
                                    "attributes": [
                                        {"key": "http.method", "value": {"stringValue": "GET"}},
                                        {"key": "retries", "value": {"intValue": "3"}},
                                    ],
                                }
                            ]
                        }
                    ],
                }
            ]
        }
        traces = otlp.decode_traces_json(doc)
        assert len(traces) == 1
        (resource, spans) = traces[0].batches[0]
        assert resource["service.name"] == "shop"
        s = spans[0]
        assert s.trace_id == bytes(range(1, 17))
        assert s.name == "GET /"
        assert s.kind == KIND_SERVER
        assert s.duration_nano == 2000
        assert s.status_code == STATUS_ERROR
        assert s.attributes == {"http.method": "GET", "retries": 3}


class TestZipkin:
    def test_decode(self):
        spans = [
            {
                "traceId": "000000000000000000000000000000aa",
                "id": "00000000000000bb",
                "name": "get",
                "kind": "CLIENT",
                "timestamp": 1_000_000,
                "duration": 2_000,
                "localEndpoint": {"serviceName": "frontend"},
                "tags": {"http.path": "/x", "error": "boom"},
            },
            {
                "traceId": "aa",  # short hex form of the same id
                "id": "cc",
                "name": "child",
                "localEndpoint": {"serviceName": "backend"},
            },
        ]
        traces = zipkin.decode_spans_json(spans)
        assert len(traces) == 1
        t = traces[0]
        assert t.span_count() == 2
        services = {r["service.name"] for r, _ in t.batches}
        assert services == {"frontend", "backend"}
        idx = _span_index(traces)
        s = idx[b"\x00" * 7 + b"\xbb"][1]
        assert s.kind == KIND_CLIENT
        assert s.start_unix_nano == 1_000_000_000
        assert s.duration_nano == 2_000_000
        assert s.status_code == STATUS_ERROR


# --- minimal thrift-binary writer, test-side only ---


def _tstr(out, fid, s):
    b = s.encode() if isinstance(s, str) else s
    out += struct.pack(">bh", jaeger.T_STRING, fid) + struct.pack(">i", len(b)) + b


def _ti64(out, fid, v):
    out += struct.pack(">bhq", jaeger.T_I64, fid, v)


def _ti32(out, fid, v):
    out += struct.pack(">bhi", jaeger.T_I32, fid, v)


def _tag(key, vtype, **vals):
    out = bytearray()
    _tstr(out, 1, key)
    _ti32(out, 2, vtype)
    if "s" in vals:
        _tstr(out, 3, vals["s"])
    if "d" in vals:
        out += struct.pack(">bhd", jaeger.T_DOUBLE, 4, vals["d"])
    if "b" in vals:
        out += struct.pack(">bhb", jaeger.T_BOOL, 5, 1 if vals["b"] else 0)
    if "l" in vals:
        _ti64(out, 6, vals["l"])
    out.append(jaeger.T_STOP)
    return bytes(out)


def _tlist(out, fid, elems):
    out += struct.pack(">bh", jaeger.T_LIST, fid)
    out += struct.pack(">bi", jaeger.T_STRUCT, len(elems))
    for e in elems:
        out += e


def _jaeger_span(tid_high, tid_low, span_id, parent, name, start_us, dur_us, tags):
    out = bytearray()
    _ti64(out, 1, tid_low)
    _ti64(out, 2, tid_high)
    _ti64(out, 3, span_id)
    _ti64(out, 4, parent)
    _tstr(out, 5, name)
    _ti64(out, 8, start_us)
    _ti64(out, 9, dur_us)
    _tlist(out, 10, tags)
    out.append(jaeger.T_STOP)
    return bytes(out)


def _jaeger_batch(service, spans):
    out = bytearray()
    proc = bytearray()
    _tstr(proc, 1, service)
    proc.append(jaeger.T_STOP)
    out += struct.pack(">bh", jaeger.T_STRUCT, 1) + proc
    _tlist(out, 2, spans)
    out.append(jaeger.T_STOP)
    return bytes(out)


class TestJaeger:
    def test_decode_batch(self):
        spans = [
            _jaeger_span(
                0xAA,
                0xBB,
                0x01,
                0,
                "root",
                5_000_000,
                250_000,
                [
                    _tag("span.kind", 0, s="server"),
                    _tag("http.status_code", 3, l=500),
                    _tag("error", 2, b=True),
                    _tag("ratio", 1, d=0.5),
                ],
            ),
            _jaeger_span(0xAA, 0xBB, 0x02, 0x01, "child", 5_100_000, 50_000, []),
        ]
        traces = jaeger.decode_batch(_jaeger_batch("payments", spans))
        assert len(traces) == 1
        t = traces[0]
        assert t.trace_id == struct.pack(">QQ", 0xAA, 0xBB)
        resource, decoded = t.batches[0]
        assert resource["service.name"] == "payments"
        assert len(decoded) == 2
        root = next(s for s in decoded if s.name == "root")
        assert root.kind == KIND_SERVER
        assert root.status_code == STATUS_ERROR
        assert root.start_unix_nano == 5_000_000_000
        assert root.duration_nano == 250_000_000
        assert root.attributes["http.status_code"] == 500
        assert root.attributes["ratio"] == 0.5
        assert "span.kind" not in root.attributes
        child = next(s for s in decoded if s.name == "child")
        assert child.parent_span_id == struct.pack(">Q", 0x01)

    def test_truncated_rejected(self):
        buf = _jaeger_batch("svc", [_jaeger_span(1, 2, 3, 0, "x", 0, 0, [])])
        with pytest.raises(ValueError):
            jaeger.decode_batch(buf[:-5])


class TestShim:
    def test_dispatch_otlp_proto(self):
        traces = [make_trace(seed=7, n_spans=4)]
        body = otlp.encode_traces_request(traces)
        got = receivers.decode_http("/v1/traces", "application/x-protobuf", body)
        assert {t.trace_id for t in got} == {traces[0].trace_id}

    def test_dispatch_otlp_json(self):
        body = json.dumps({"resourceSpans": []}).encode()
        assert receivers.decode_http("/v1/traces", "application/json", body) == []

    def test_dispatch_zipkin(self):
        body = json.dumps([{"traceId": "ab", "id": "01", "name": "z"}]).encode()
        got = receivers.decode_http("/api/v2/spans", "application/json", body)
        assert len(got) == 1

    def test_dispatch_jaeger(self):
        body = _jaeger_batch("svc", [_jaeger_span(1, 2, 3, 0, "x", 0, 0, [])])
        got = receivers.decode_http("/api/traces", "application/vnd.apache.thrift.binary", body)
        assert len(got) == 1

    def test_unknown_path(self):
        with pytest.raises(receivers.UnsupportedPayload):
            receivers.decode_http("/nope", "", b"")

    def test_gzip_body(self):
        raw = otlp.encode_traces_request([make_trace(seed=1, n_spans=2)])
        assert receivers.decompress_body(gzip.compress(raw), "gzip") == raw
        with pytest.raises(receivers.UnsupportedPayload):
            receivers.decompress_body(raw, "br")


class TestColumnarDecode:
    """The batched columnar fast path must be invisible to everything
    downstream: the SpanBatch it builds straight off the wire carries
    the same spans, field for field, as the object decode would have."""

    def _assert_same(self, batch, want_traces):
        from tempo_tpu.model import trace as tr

        assert batch.num_spans == sum(t.span_count() for t in want_traces)
        want = _span_index(want_traces)
        got = _span_index(tr.batch_to_traces(batch))
        assert set(got) == set(want)
        for sid, (resource, s) in want.items():
            r2, s2 = got[sid]
            assert r2 == resource
            assert s2.name == s.name
            assert s2.trace_id == s.trace_id
            assert s2.parent_span_id == s.parent_span_id
            assert s2.start_unix_nano == s.start_unix_nano
            assert s2.duration_nano == s.duration_nano
            assert s2.kind == s.kind
            assert s2.status_code == s.status_code
            assert s2.attributes == s.attributes

    def test_proto_parity_with_object_decode(self):
        traces = [make_trace(seed=i, n_spans=5) for i in range(4)]
        body = otlp.encode_traces_request(traces)
        batch = receivers.decode_http_columnar(
            "/v1/traces", "application/x-protobuf", body)
        assert batch is not None
        self._assert_same(batch, receivers.decode_http(
            "/v1/traces", "application/x-protobuf", body))

    def test_json_parity_with_object_decode(self):
        body = json.dumps({
            "resourceSpans": [{
                "resource": {"attributes": [
                    {"key": "service.name",
                     "value": {"stringValue": "shop"}}]},
                "scopeSpans": [{"spans": [
                    {"traceId": "0102030405060708090a0b0c0d0e0f10",
                     "spanId": "0102030405060708",
                     "name": "GET /",
                     "kind": "SPAN_KIND_SERVER",
                     "startTimeUnixNano": "1000",
                     "endTimeUnixNano": "3000",
                     "status": {"code": "STATUS_CODE_ERROR"},
                     "attributes": [
                         {"key": "http.method",
                          "value": {"stringValue": "GET"}},
                         {"key": "retries", "value": {"intValue": "3"}},
                     ]},
                    {"traceId": "0102030405060708090a0b0c0d0e0f10",
                     "spanId": "1112131415161718",
                     "parentSpanId": "0102030405060708",
                     "name": "db query",
                     "startTimeUnixNano": "1500",
                     "endTimeUnixNano": "2500"},
                ]}],
            }]
        }).encode()
        batch = receivers.decode_http_columnar(
            "/v1/traces", "application/json", body)
        assert batch is not None
        self._assert_same(batch, receivers.decode_http(
            "/v1/traces", "application/json", body))

    def test_non_otlp_declines_to_object_path(self):
        body = json.dumps([{"traceId": "ab", "id": "01", "name": "z"}]).encode()
        assert receivers.decode_http_columnar(
            "/api/v2/spans", "application/json", body) is None

    def test_decode_path_counter_splits_arms(self):
        body = otlp.encode_traces_request([make_trace(seed=9, n_spans=3)])
        col0 = receivers.spans_decoded_total.value(path="columnar")
        obj0 = receivers.spans_decoded_total.value(path="object")
        receivers.decode_http_columnar(
            "/v1/traces", "application/x-protobuf", body)
        assert receivers.spans_decoded_total.value(path="columnar") == col0 + 3
        receivers.decode_http("/v1/traces", "application/x-protobuf", body)
        assert receivers.spans_decoded_total.value(path="object") == obj0 + 3


# --- the two OTLP/HTTP protobuf scanners ------------------------------------
#
# receivers/otlp.py's Python scanner is the definition; native/codec.cc's
# scan answers only where its answer is certain to be the same. Each case
# below is one body, with the reason the native scan must decline it for
# ("" where it must not).


def _kv(key, any_value: bytes) -> bytes:
    out = bytearray()
    pw.put_bytes_field(out, 1, key if isinstance(key, bytes) else key.encode())
    pw.put_bytes_field(out, 2, any_value)
    return bytes(out)


def _any(field: int, value) -> bytes:
    """An AnyValue with one field set: 1 string, 2 bool, 3 int, 4 double,
    5 array, 6 kvlist, 7 bytes."""
    out = bytearray()
    if field in (2, 3):
        pw.put_varint_field(out, field, value)
    elif field == 4:
        pw.put_double_field(out, field, value)
    else:
        pw.put_bytes_field(out, field, value)
    return bytes(out)


def _span(tid=b"\x01" * 16, sid=b"\x02" * 8, pid=None, name=b"op", kind=2,
          start=1_000, end=3_000, attrs=(), status=None, extra=b"") -> bytes:
    out = bytearray()
    if tid is not None:
        pw.put_bytes_field(out, 1, tid)
    if sid is not None:
        pw.put_bytes_field(out, 2, sid)
    if pid is not None:
        pw.put_bytes_field(out, 4, pid)
    pw.put_bytes_field(out, 5, name)
    pw.put_varint_field(out, 6, kind)
    pw.put_fixed64_field(out, 7, start)
    pw.put_fixed64_field(out, 8, end)
    for a in attrs:
        pw.put_bytes_field(out, 9, a)
    if status is not None:
        st = bytearray()
        pw.put_varint_field(st, 3, status)
        pw.put_bytes_field(out, 15, bytes(st))
    return bytes(out) + extra


def _resource_spans(spans=(), resource_attrs=None, extra=b"",
                    resource_last=False) -> bytes:
    """One ResourceSpans field of a request; resource_attrs None leaves
    the Resource out, a list of lists writes several Resource messages."""
    parts = []
    groups = ([] if resource_attrs is None else
              resource_attrs if resource_attrs and isinstance(resource_attrs[0], list)
              else [resource_attrs])
    for group in groups:
        res = bytearray()
        for a in group:
            pw.put_bytes_field(res, 1, a)
        part = bytearray()
        pw.put_bytes_field(part, 1, bytes(res))
        parts.append(bytes(part))
    ss = bytearray()
    for sp in spans:
        pw.put_bytes_field(ss, 2, sp)
    scope = bytearray()
    pw.put_bytes_field(scope, 2, bytes(ss))
    parts.insert(0 if resource_last else len(parts), bytes(scope))
    out = bytearray()
    pw.put_bytes_field(out, 1, b"".join(parts) + extra)
    return bytes(out)


_SVC = _kv("service.name", _any(1, b"shop"))
_UNKNOWN = (b"\xa0\x06\x07"  # field 100, varint
            b"\xa1\x06" + b"\x01" * 8 +  # fixed64
            b"\xa2\x06\x03abc"  # length-delimited
            b"\xa5\x06" + b"\x02" * 4)  # fixed32


@functools.cache
def _w_shape(n_traces, spans):
    from tempo_tpu.model import synth
    from tempo_tpu.model.trace import batch_to_traces

    return otlp.encode_traces_request(
        batch_to_traces(synth.make_batch(n_traces, spans, seed=3)))


def _one(*attrs, **kw) -> bytes:
    """A request of one span under service `shop` with these attributes."""
    return _resource_spans([_span(attrs=attrs, **kw)], [_SVC])


_SCAN_CASES = {
    "w_shape_64x16": (lambda: _w_shape(64, 16), ""),
    "empty_body": (lambda: b"", ""),
    "several_resources": (lambda: b"".join(
        _resource_spans(
            [_span(sid=bytes([i, j] * 4), name=b"op%d" % j,
                   attrs=[_kv("k", _any(1, b"v%d" % j))]) for j in range(3)],
            [_kv("service.name", _any(1, b"svc%d" % i)),
             _kv("zone", _any(1, b"z%d" % (i % 2))), _kv("replicas", _any(3, i))])
        for i in range(4)), ""),
    "resource_without_service_name": (lambda: _resource_spans(
        [_span()], [_kv("zone", _any(1, b"a"))]), ""),
    "no_resource_at_all": (lambda: _resource_spans([_span()], None), ""),
    "resource_after_its_spans": (lambda: _resource_spans(
        [_span(), _span(sid=b"\x03" * 8)],
        [_SVC, _kv("zone", _any(1, b"a"))], resource_last=True), ""),
    "two_resource_messages": (lambda: _resource_spans(
        [_span()], [[_SVC], [_kv("zone", _any(1, b"a"))]]), ""),
    "group_without_spans": (lambda: _resource_spans(
        [], [_kv("service.name", _any(1, b"idle")), _kv("zone", _any(1, b"q"))])
        + _resource_spans([_span()], [_SVC]), ""),
    "service_name_as_span_attr": (lambda: _one(
        _kv("service.name", _any(1, b"inner"))), ""),
    "http_keys_on_the_resource": (lambda: _resource_spans(
        [_span()], [_SVC, _kv("http.method", _any(3, 5))]), ""),
    "value_string": (lambda: _one(_kv("k", _any(1, b"v"))), ""),
    "value_empty_string": (lambda: _one(_kv("k", _any(1, b""))), ""),
    "value_bool": (lambda: _one(_kv("t", _any(2, 1)), _kv("f", _any(2, 0))), ""),
    "value_negative_int": (lambda: _one(_kv("k", _any(3, -7))), ""),
    "value_int_beyond_double": (lambda: _one(_kv("k", _any(3, 2**63 - 1))), ""),
    "value_double": (lambda: _one(_kv("k", _any(4, -2.5)),
                                  _kv("n", _any(4, float("nan")))), ""),
    "value_array": (lambda: _one(_kv("k", _any(5, b"\x0a\x03\x0a\x01x"))),
                    "value_type"),
    "value_kvlist": (lambda: _one(_kv("k", _any(6, b"\x0a" + bytes([len(
        _kv("in", _any(3, 1)))]) + _kv("in", _any(3, 1))))), "value_type"),
    "value_bytes": (lambda: _one(_kv("k", _any(7, b"\x00\xff"))), "value_type"),
    "value_absent": (lambda: _one(_kv("k", b"")), "value_type"),
    "value_field_missing": (lambda: _one(b"\x0a\x01k"), "value_type"),
    "value_array_on_a_resource": (lambda: _resource_spans(
        [_span()], [_SVC, _kv("k", _any(5, b""))]), "value_type"),
    "promoted_values": (lambda: _one(
        _kv("http.status_code", _any(3, 503)), _kv("http.method", _any(1, b"GET")),
        _kv("http.url", _any(1, b"http://a/b")), _kv("k", _any(1, b"v"))), ""),
    "http_status_code_as_string": (lambda: _one(
        _kv("http.status_code", _any(1, b"200"))), "promoted_type"),
    "http_status_code_70000": (lambda: _one(
        _kv("http.status_code", _any(3, 70_000))), "promoted_type"),
    "http_status_code_negative": (lambda: _one(
        _kv("http.status_code", _any(3, -1))), "promoted_type"),
    "http_method_as_int": (lambda: _one(
        _kv("http.method", _any(3, 1))), "promoted_type"),
    "http_url_as_bool": (lambda: _one(
        _kv("http.url", _any(2, 1))), "promoted_type"),
    "service_name_as_int": (lambda: _resource_spans(
        [_span()], [_kv("service.name", _any(3, 9))]), "promoted_type"),
    "repeated_key": (lambda: _one(
        _kv("k", _any(1, b"a")), _kv("j", _any(3, 1)), _kv("k", _any(1, b"b"))),
        "duplicate_key"),
    "repeated_promoted_key": (lambda: _one(
        _kv("http.method", _any(1, b"GET")), _kv("http.method", _any(1, b"PUT"))),
        "duplicate_key"),
    "repeated_resource_key": (lambda: _resource_spans(
        [_span()], [[_SVC, _kv("zone", _any(1, b"a"))],
                    [_kv("zone", _any(1, b"b"))]]), "duplicate_key"),
    "same_key_in_two_spans": (lambda: _resource_spans(
        [_span(attrs=[_kv("k", _any(1, b"a"))]),
         _span(sid=b"\x03" * 8, attrs=[_kv("k", _any(1, b"b"))])],
        [_SVC, _kv("k", _any(1, b"r"))]), ""),
    "empty_key": (lambda: _one(_kv("", _any(1, b"v")), _kv("k", _any(3, 1))),
                  "empty_key"),
    "empty_resource_key": (lambda: _resource_spans(
        [_span()], [_SVC, _kv("", _any(1, b"v"))]), "empty_key"),
    "name_not_utf8": (lambda: _one(name=b"caf\xe9 \xff\xfe"), ""),
    "value_not_utf8": (lambda: _one(_kv("k", _any(1, b"\xc3(")),
                                    _kv("j", _any(1, b"\xef\xbf\xbd("))), ""),
    "key_utf8": (lambda: _one(_kv("clé", _any(1, b"v")),
                              _kv("鍵\U0001f511", _any(3, 1))), ""),
    "key_not_utf8": (lambda: _one(_kv(b"\xff", _any(1, b"a")),
                                  _kv(b"\xfe", _any(1, b"b"))), "key_encoding"),
    "key_utf8_surrogate": (lambda: _one(_kv(b"\xed\xa0\x80", _any(1, b"a"))),
                           "key_encoding"),
    "key_utf8_overlong": (lambda: _one(_kv(b"\xc0\xaf", _any(1, b"a"))),
                          "key_encoding"),
    "ids_absent": (lambda: _one(tid=None, sid=None), ""),
    "ids_0_bytes": (lambda: _one(tid=b"", sid=b"", pid=b""), ""),
    "ids_4_bytes": (lambda: _one(tid=b"\x01\x02\x03\x04", sid=b"\x05\x06\x07\x08",
                                 pid=b"\x09\x0a\x0b\x0c"), ""),
    "ids_full": (lambda: _one(tid=bytes(range(16)), sid=bytes(range(8)),
                              pid=bytes(range(8, 16))), ""),
    "trace_id_20_bytes": (lambda: _one(tid=bytes(range(20))), "id_length"),
    "span_id_9_bytes": (lambda: _one(sid=bytes(range(9))), "id_length"),
    "parent_id_16_bytes": (lambda: _one(pid=bytes(range(16))), "id_length"),
    "end_before_start": (lambda: _one(start=5_000, end=4_000), ""),
    "times_at_u64_max": (lambda: _one(start=0, end=2**64 - 1), ""),
    "times_as_varints": (lambda: _one(
        extra=b"\x38\x90\x4e" b"\x40\xa0\x9c\x01"), ""),
    "status_codes": (lambda: _resource_spans(
        [_span(status=2), _span(sid=b"\x03" * 8, status=1, kind=5)], [_SVC]), ""),
    "kind_300": (lambda: _one(kind=300), "out_of_range"),
    "status_256": (lambda: _one(status=256), "out_of_range"),
    "name_repeated_last_wins": (lambda: _one(extra=b"\x2a\x03two"), ""),
    "unknown_fields_everywhere": (lambda: _UNKNOWN + _resource_spans(
        [_span(attrs=[_kv("k", _UNKNOWN + _any(1, b"v")) + _UNKNOWN],
               extra=_UNKNOWN, status=2)],
        [_SVC + _UNKNOWN], extra=_UNKNOWN) + _UNKNOWN, ""),
    "junk_after_the_value_is_not_read": (lambda: _one(
        _kv("k", _any(3, 4) + b"\xff\xff")), ""),
    "group_wire_types": (lambda: _one(extra=b"\xa3\x06"), "malformed"),
    "wire_type_7": (lambda: b"\xa7\x06" + _one(), "malformed"),
    "name_as_varint": (lambda: _one(extra=b"\x28\x05"), "malformed"),
    "trace_id_as_varint": (lambda: _one(extra=b"\x08\x05"), "malformed"),
    "kind_as_bytes": (lambda: _one(extra=b"\x32\x011"), "malformed"),
    "span_as_varint": (lambda: b"\x0a\x04\x12\x02\x10\x01", "malformed"),
    "resource_spans_as_fixed32": (lambda: b"\x0d\x00\x00\x00\x00", "malformed"),
    "int_value_as_fixed64": (lambda: _one(
        _kv("k", b"\x19" + b"\x01" * 8)), "malformed"),
    "varint_of_eleven_bytes": (lambda: _one(
        extra=b"\xa0\x06" + b"\xff" * 10 + b"\x01"), "malformed"),
    "varint_past_64_bits": (lambda: _one(
        extra=b"\xa0\x06" + b"\xff" * 9 + b"\x7f"), "malformed"),
    "length_past_the_end": (lambda: _one() + b"\x0a\x7fabc", "malformed"),
    "inner_length_past_its_message": (lambda: _resource_spans(
        [_span(extra=b"\x2a\x7f")], [_SVC]), "malformed"),
}


def _resolved(batch):
    """The batch with every code column resolved to its strings."""
    d = batch.dictionary.entries
    out = {}
    for cols, coded in ((batch.cols, ("name", "service", "http_method", "http_url")),
                        (batch.attrs, ("attr_key", "attr_str"))):
        for k, v in cols.items():
            out[k] = [d[c] for c in v.tolist()] if k in coded else v
    return out


def _assert_same_batch(got, want):
    a, b = _resolved(got), _resolved(want)
    assert a.keys() == b.keys()
    for k in a:
        if isinstance(a[k], list):
            assert a[k] == b[k], k
        else:
            assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
            assert np.array_equal(a[k], b[k], equal_nan=k == "attr_num"), k
    assert set(got.dictionary.entries) == set(want.dictionary.entries)


def _outcome(fn):
    try:
        return fn()
    except Exception as e:  # the handler's 400 or 500 is decided by the class
        return type(e)


def _assert_same_outcome(body, expect=None):
    """decode_traces_request_columnar answers `body` as the Python scanner
    does, the batch or the exception class, and heard `expect` (scanner,
    reason) where given."""
    said = []
    got = _outcome(lambda: otlp.decode_traces_request_columnar(
        body, scanned=lambda scanner, reason: said.append((scanner, reason))))
    want = _outcome(lambda: otlp._scan_columnar(body))
    assert len(said) == 1
    if expect is not None:
        assert said[0] == expect
    if isinstance(want, type):
        assert got is want and said[0][0] == "python"
    else:
        _assert_same_batch(got, want)
    return said[0]


@pytest.fixture(params=["library", "no_library"])
def scanner(request, monkeypatch):
    """Both arms of decode_traces_request_columnar: the native scan where
    it built, and the Python scanner alone where native.lib() is None."""
    from tempo_tpu import native

    if request.param == "no_library":
        monkeypatch.setattr(native, "lib", lambda: None)
    else:
        assert native.lib() is not None, "native codec library failed to build"
    return request.param


class TestScannerEquivalence:
    @pytest.mark.parametrize("case", sorted(_SCAN_CASES))
    def test_native_scan_is_the_python_scan_or_declines(self, scanner, case):
        make, reason = _SCAN_CASES[case]
        expect = (("python", "no_library") if scanner == "no_library" else
                  ("python", reason) if reason else ("native", ""))
        _assert_same_outcome(make(), expect)

    @pytest.mark.parametrize("chunk", range(4))
    def test_truncated_bodies(self, scanner, chunk):
        body = _w_shape(64, 16)
        step = len(body) // 100
        for cut in range(chunk * 25 * step + 1, (chunk + 1) * 25 * step, step):
            _assert_same_outcome(body[:cut])

    @pytest.mark.parametrize("chunk", range(4))
    def test_flipped_bytes(self, scanner, chunk):
        body = _w_shape(64, 16)
        rng = np.random.default_rng(chunk)
        native_answers = 0
        for at in rng.integers(0, len(body), 25).tolist():
            flipped = bytearray(body)
            flipped[at] ^= 1 << int(rng.integers(0, 8))
            native_answers += _assert_same_outcome(bytes(flipped))[0] == "native"
        # most flips land in a string or an id and leave the wire intact
        assert (native_answers > 0) == (scanner == "library")

    def test_callers_dictionary_is_honoured(self, scanner):
        from tempo_tpu.model.columnar import Dictionary

        d = Dictionary(["", "already", "shop"])
        batch = otlp.decode_traces_request_columnar(_one(), dictionary=d)
        assert batch.dictionary is d
        assert d.entries[:3] == ["", "already", "shop"]
        assert batch.cols["service"].tolist() == [2]
        _assert_same_batch(batch, otlp._scan_columnar(
            _one(), Dictionary(["", "already", "shop"])))

    def test_generated_traces(self, scanner):
        from hypothesis import given, settings
        from hypothesis import strategies as st

        scalars = st.one_of(
            st.text(max_size=6), st.booleans(),
            st.integers(-(2**63), 2**63 - 1), st.floats(allow_nan=False))
        values = st.one_of(scalars, st.lists(scalars, max_size=2),
                           st.dictionaries(st.text(max_size=3), scalars, max_size=2))
        attrs = st.dictionaries(
            st.one_of(st.text(max_size=4), st.sampled_from(
                ["http.method", "http.url", "http.status_code", "service.name"])),
            values, max_size=4)
        spans = st.builds(
            Span, trace_id=st.binary(min_size=16, max_size=16),
            span_id=st.binary(min_size=8, max_size=8),
            parent_span_id=st.binary(min_size=8, max_size=8),
            name=st.text(max_size=8), kind=st.integers(0, 5),
            start_unix_nano=st.integers(0, 2**62),
            duration_nano=st.integers(0, 2**40),
            status_code=st.integers(0, 2), attributes=attrs)
        traces = st.lists(st.builds(
            lambda batches: Trace(trace_id=b"\0" * 16, batches=batches),
            st.lists(st.tuples(attrs, st.lists(spans, max_size=3)), max_size=3)),
            max_size=3)

        heard = set()

        @settings(max_examples=150, deadline=None, database=None, derandomize=True)
        @given(traces)
        def run(ts):
            heard.add(_assert_same_outcome(otlp.encode_traces_request(ts)))

        run()
        if scanner == "library":  # the strategy reaches both answers
            assert {("native", ""), ("python", "value_type")} <= heard


class TestScannerCounter:
    def test_native_body_counts_once_and_its_spans_as_columnar(self):
        body = _w_shape(2, 3)
        n0 = receivers.decode_requests_total.value(scanner="native", reason="")
        col0 = receivers.spans_decoded_total.value(path="columnar")
        receivers.decode_http_columnar("/v1/traces", "application/x-protobuf", body)
        assert receivers.decode_requests_total.value(
            scanner="native", reason="") == n0 + 1
        assert receivers.spans_decoded_total.value(path="columnar") == col0 + 6

    def test_declined_body_counts_under_its_reason(self):
        body = _SCAN_CASES["value_kvlist"][0]()
        p0 = receivers.decode_requests_total.value(
            scanner="python", reason="value_type")
        col0 = receivers.spans_decoded_total.value(path="columnar")
        batch = receivers.decode_http_columnar(
            "/v1/traces", "application/x-protobuf", body)
        assert batch.num_spans == 1
        assert receivers.decode_requests_total.value(
            scanner="python", reason="value_type") == p0 + 1
        assert receivers.spans_decoded_total.value(path="columnar") == col0 + 1

    def test_malformed_body_counts_before_it_is_refused(self):
        body = _w_shape(1, 2)[:-3]
        p0 = receivers.decode_requests_total.value(
            scanner="python", reason="malformed")
        with pytest.raises(pw.WireError):
            receivers.decode_http_columnar(
                "/v1/traces", "application/x-protobuf", body)
        assert receivers.decode_requests_total.value(
            scanner="python", reason="malformed") == p0 + 1

    def test_every_series_is_there_from_import(self):
        from tempo_tpu import native

        have = {(s["scanner"], s["reason"])
                for s, _ in receivers.decode_requests_total.series()}
        assert have >= {("native", ""), ("python", "no_library")} | {
            ("python", r) for r in native.OTLP_DECLINED.values()}

    def test_json_bodies_do_not_count(self):
        before = sum(v for _, v in receivers.decode_requests_total.series())
        receivers.decode_http_columnar("/v1/traces", "application/json", b"{}")
        assert sum(v for _, v in receivers.decode_requests_total.series()) == before


# --- zipkin v1 thrift ------------------------------------------------------


def _zk_endpoint(service):
    out = bytearray()
    _ti32(out, 1, 0)
    out += struct.pack(">bhh", 6, 2, 0)  # port i16
    _tstr(out, 3, service)
    out.append(jaeger.T_STOP)
    return bytes(out)


def _zk_annotation(value, service):
    out = bytearray()
    _ti64(out, 1, 1)  # timestamp
    _tstr(out, 2, value)
    out += struct.pack(">bh", jaeger.T_STRUCT, 3) + _zk_endpoint(service)
    out.append(jaeger.T_STOP)
    return bytes(out)


def _zk_binary_annotation(key, value, service=None):
    out = bytearray()
    _tstr(out, 1, key)
    _tstr(out, 2, value)
    _ti32(out, 3, 6)  # STRING
    if service:
        out += struct.pack(">bh", jaeger.T_STRUCT, 4) + _zk_endpoint(service)
    out.append(jaeger.T_STOP)
    return bytes(out)


def _signed64(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _zk_span(tid_hi, tid_lo, sid, pid, name, ts_us, dur_us, annos=(), bannos=()):
    tid_hi, tid_lo, sid, pid = (_signed64(x) for x in (tid_hi, tid_lo, sid, pid))
    out = bytearray()
    _ti64(out, 1, tid_lo)
    _tstr(out, 3, name)
    _ti64(out, 4, sid)
    if pid:
        _ti64(out, 5, pid)
    if annos:
        out += struct.pack(">bh", jaeger.T_LIST, 6)
        out += struct.pack(">bi", jaeger.T_STRUCT, len(annos))
        for a in annos:
            out += a
    if bannos:
        out += struct.pack(">bh", jaeger.T_LIST, 8)
        out += struct.pack(">bi", jaeger.T_STRUCT, len(bannos))
        for b in bannos:
            out += b
    _ti64(out, 10, ts_us)
    _ti64(out, 11, dur_us)
    _ti64(out, 12, tid_hi)
    out.append(jaeger.T_STOP)
    return bytes(out)


class TestZipkinThrift:
    def _payload(self, spans):
        out = bytearray()
        out += struct.pack(">bi", jaeger.T_STRUCT, len(spans))
        for s in spans:
            out += s
        return bytes(out)

    def test_decode_v1_thrift(self):
        spans = [
            _zk_span(0x1122334455667788, 0x99AABBCCDDEEFF00, 0x1, 0, "root",
                     1_700_000_000_000_000, 5000,
                     annos=[_zk_annotation("sr", "web")],
                     bannos=[_zk_binary_annotation("http.path", "/x")]),
            _zk_span(0x1122334455667788, 0x99AABBCCDDEEFF00, 0x2, 0x1, "call",
                     1_700_000_000_000_100, 300,
                     annos=[_zk_annotation("cs", "web")]),
        ]
        (trace,) = zipkin.decode_spans_thrift(self._payload(spans))
        assert trace.trace_id == bytes.fromhex("112233445566778899aabbccddeeff00")
        by_name = {s.name: s for s in trace.all_spans()}
        root, call = by_name["root"], by_name["call"]
        from tempo_tpu.model.trace import KIND_CLIENT, KIND_SERVER

        assert root.kind == KIND_SERVER and call.kind == KIND_CLIENT
        assert root.start_unix_nano == 1_700_000_000_000_000_000
        assert root.duration_nano == 5_000_000
        assert root.attributes == {"http.path": "/x"}
        assert call.parent_span_id == (0x1).to_bytes(8, "big")
        assert trace.batches[0][0]["service.name"] == "web"

    def test_http_route_v1_and_v2_paths(self):
        from tempo_tpu import receivers as rx

        spans = [_zk_span(0, 0x42, 0x7, 0, "op", 10, 5,
                          annos=[_zk_annotation("ss", "svc")])]
        body = self._payload(spans)
        for path in (rx.ZIPKIN_V1_PATH, rx.ZIPKIN_PATH):
            traces = rx.decode_http(path, "application/x-thrift", body)
            assert traces and traces[0].trace_id.endswith(b"\x42")

    def test_v1_json_rejected(self):
        from tempo_tpu import receivers as rx

        with pytest.raises(rx.UnsupportedPayload):
            rx.decode_http(rx.ZIPKIN_V1_PATH, "application/json", b"[]")

    def test_truncated_thrift_rejected(self):
        spans = [_zk_span(0, 1, 2, 0, "op", 10, 5)]
        body = self._payload(spans)[:-4]
        with pytest.raises(Exception):
            zipkin.decode_spans_thrift(body)


class TestJaegerAgentUDP:
    """Agent-mode UDP ports (reference shim.go:111 hosts thrift_compact
    6831 / thrift_binary 6832 — how most legacy jaeger clients ship)."""

    def _spans(self, n=3):
        from tempo_tpu.model.trace import KIND_CLIENT, Span

        tid = bytes(range(16))
        return [
            Span(
                trace_id=tid,
                span_id=bytes([9, i] * 4),
                parent_span_id=b"\x00" * 8 if i == 0 else bytes([9, 0] * 4),
                name=f"udp-op-{i}",
                start_unix_nano=1_700_000_000_000_000_000 + i * 1000,
                duration_nano=5_000_000 + i,
                kind=KIND_CLIENT,
                status_code=2 if i == 2 else 0,
                attributes={"idx": i, "ratio": 1.5, "ok": True, "tag": f"v{i}"},
            )
            for i in range(n)
        ]

    def test_compact_datagram_roundtrip(self):
        from tempo_tpu.receivers import jaeger

        spans = self._spans()
        buf = jaeger.encode_agent_batch_compact(
            "svc-udp", spans, process_tags={"host": "h1"})
        traces = jaeger.decode_agent_datagram(buf)
        assert len(traces) == 1
        t = traces[0]
        res, got = t.batches[0]
        assert res["service.name"] == "svc-udp" and res["host"] == "h1"
        assert [s.name for s in got] == [s.name for s in spans]
        for orig, dec in zip(spans, got):
            assert dec.trace_id == orig.trace_id
            assert dec.span_id == orig.span_id
            assert dec.parent_span_id == orig.parent_span_id
            assert dec.start_unix_nano == orig.start_unix_nano
            # microsecond wire precision
            assert abs(dec.duration_nano - orig.duration_nano) < 1000
            assert dec.kind == orig.kind
            assert dec.status_code == orig.status_code
            assert dec.attributes["idx"] == orig.attributes["idx"]
            assert dec.attributes["ratio"] == 1.5
            assert dec.attributes["ok"] is True

    def test_udp_server_end_to_end(self):
        import socket
        import time

        from tempo_tpu.receivers import jaeger
        from tempo_tpu.receivers.udp import UDPAgentServer

        got = []
        srv = UDPAgentServer(lambda traces, org_id=None: got.extend(traces),
                             compact_port=0, binary_port=0).start()
        try:
            buf = jaeger.encode_agent_batch_compact("svc", self._spans(2))
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.sendto(buf, ("127.0.0.1", srv.compact_port))
            deadline = time.time() + 5
            while not got and time.time() < deadline:
                time.sleep(0.02)
            assert got and got[0].span_count() == 2
            assert srv.batches == 1 and srv.spans == 2
        finally:
            srv.stop()

    def test_binary_datagram(self):
        """A strict-binary emitBatch envelope (port 6832 dialect) decodes
        through the same entry point."""
        import struct

        from tempo_tpu.receivers import jaeger

        # build binary envelope around a binary-encoded Batch by reusing
        # the HTTP collector encoder if present; hand-roll otherwise
        spans = self._spans(1)
        # binary Batch: {1: Process{1: str}, 2: [Span{1..9}]}
        def _str_b(s):
            b = s.encode()
            return struct.pack(">i", len(b)) + b

        def field(fid, ftype):
            return struct.pack(">bh", ftype, fid)

        sp = spans[0]
        tid_high, tid_low = struct.unpack(">QQ", sp.trace_id)
        (sid,) = struct.unpack(">Q", sp.span_id)

        def i64f(fid, v):
            if v >= 1 << 63:
                v -= 1 << 64
            return field(fid, 10) + struct.pack(">q", v)

        span_struct = (
            i64f(1, tid_low) + i64f(2, tid_high) + i64f(3, sid) + i64f(4, 0)
            + field(5, 11) + _str_b(sp.name)
            + i64f(8, sp.start_unix_nano // 1000)
            + i64f(9, sp.duration_nano // 1000)
            + b"\x00"
        )
        process = field(1, 11) + _str_b("bin-svc") + b"\x00"
        batch = field(1, 12) + process + field(2, 15) + struct.pack(">bi", 12, 1) + span_struct + b"\x00"
        args = field(1, 12) + batch + b"\x00"
        msg = struct.pack(">I", 0x80010004) + _str_b("emitBatch") + struct.pack(">i", 7) + args
        traces = jaeger.decode_agent_datagram(msg)
        assert len(traces) == 1
        res, got = traces[0].batches[0]
        assert res["service.name"] == "bin-svc"
        assert got[0].name == sp.name

    def test_malformed_datagram_counted_not_fatal(self):
        from tempo_tpu.receivers.udp import UDPAgentServer

        srv = UDPAgentServer(lambda *a, **k: None, compact_port=0, binary_port=None)
        assert srv.handle_datagram(b"\x82\x81garbage") == 0
        assert srv.handle_datagram(b"") == 0
        assert srv.errors == 2
        for s in srv._socks:
            s.close()

    def test_stop_before_start_closes_sockets(self):
        """Regression: stop() on a never-started server raised
        AttributeError (self._stop only existed after start()) and
        leaked the bound sockets."""
        from tempo_tpu.receivers.udp import UDPAgentServer

        srv = UDPAgentServer(lambda *a, **k: None, compact_port=0, binary_port=0)
        assert srv._socks
        srv.stop()  # must not raise
        for s in srv._socks:
            assert s.fileno() == -1  # closed, not leaked
