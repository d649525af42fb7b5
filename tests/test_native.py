"""Native C++ codec library tests.

The library must build in this image (g++ + system zlib/zstd are baked
in), so these tests do NOT skip when the build fails — a broken native
path is a real regression.
"""

import zlib

import numpy as np
import pytest

from tempo_tpu import native
from tempo_tpu.encoding.vtpu import codec


@pytest.fixture(scope="module")
def lib():
    b = native.lib()
    assert b is not None, "native codec library failed to build"
    return b


def test_crc32_matches_stdlib(lib):
    data = b"span batch payload" * 100
    assert lib.crc32(data) == zlib.crc32(data)


def test_hash64_stable_and_seeded(lib):
    d = b"trace-id-0123456789abcdef"
    assert lib.hash64(d) == lib.hash64(d)
    assert lib.hash64(d, 1) != lib.hash64(d, 2)
    assert lib.hash64(d) != lib.hash64(d[:-1])


@pytest.mark.parametrize("codec_name", ["zstd", "zlib"])
def test_compress_roundtrip(lib, codec_name):
    rng = np.random.default_rng(0)
    # compressible: sorted small deltas
    raw = np.sort(rng.integers(0, 1000, 50_000).astype(np.uint64)).tobytes()
    comp = lib.compress(raw, codec_name)
    assert len(comp) < len(raw)
    assert lib.decompress(comp, len(raw), codec_name) == raw


def test_decompress_corrupt_raises(lib):
    comp = bytearray(lib.compress(b"x" * 1000, "zstd"))
    comp[5] ^= 0xFF
    with pytest.raises(native.NativeError):
        lib.decompress(bytes(comp), 1000, "zstd")


def test_varint_roundtrip(lib):
    rng = np.random.default_rng(1)
    vals = np.cumsum(rng.integers(-(2**20), 2**20, 10_000)).astype(np.int64)
    vals[0] = -(2**62)  # extremes
    vals[1] = 2**62
    enc = lib.varint_encode(vals)
    # delta+varint beats 8 bytes/elem on small deltas despite extremes
    assert len(enc) < vals.size * 8
    out = lib.varint_decode(enc, vals.size)
    np.testing.assert_array_equal(out, vals)


def test_varint_corrupt_raises(lib):
    enc = bytearray(lib.varint_encode(np.arange(100, dtype=np.int64)))
    with pytest.raises(native.NativeError):
        lib.varint_decode(bytes(enc[:-1] + b"\xff"), 100)  # dangling continuation


@pytest.mark.parametrize("codec_name", ["none", "zlib", "zstd"])
def test_page_roundtrip(lib, codec_name):
    raw = np.arange(10_000, dtype=np.uint32).tobytes()
    page = lib.page_encode(raw, codec_name)
    assert lib.page_decode(page) == raw


def test_page_crc_detects_flip(lib):
    raw = b"z" * 4096
    page = bytearray(lib.page_encode(raw, "none"))
    page[-1] ^= 0x01
    with pytest.raises(native.NativeError):
        lib.page_decode(bytes(page))


def test_kway_merge_orders_and_flags_dups(lib):
    # 3 sorted streams with a shared key
    hi = [np.array([1, 5, 9], np.uint64), np.array([2, 5], np.uint64), np.array([0], np.uint64)]
    lo = [np.array([0, 0, 0], np.uint64), np.array([0, 0], np.uint64), np.array([7], np.uint64)]
    s, r, dup = lib.kway_merge_u128(hi, lo)
    keys = [(int(hi[si][ri]), int(lo[si][ri])) for si, ri in zip(s, r)]
    assert keys == sorted(keys)
    assert dup.sum() == 1  # the second (5,0)
    assert len(s) == 6


def test_kway_merge_large_random(lib):
    rng = np.random.default_rng(2)
    streams_hi, streams_lo = [], []
    for _ in range(5):
        n = int(rng.integers(100, 500))
        h = np.sort(rng.integers(0, 1000, n).astype(np.uint64))
        streams_hi.append(h)
        streams_lo.append(np.zeros(n, np.uint64))
    s, r, dup = lib.kway_merge_u128(streams_hi, streams_lo)
    merged = np.concatenate(streams_hi)
    merged.sort()
    got = np.array([streams_hi[si][ri] for si, ri in zip(s, r)])
    np.testing.assert_array_equal(got, merged)
    # dup flags mark every repeat of the previous key
    np.testing.assert_array_equal(dup[1:], got[1:] == got[:-1])
    assert not dup[0]


# -- integration with the page codec ---------------------------------------


def test_codec_zstd_roundtrip_via_native():
    arr = np.arange(5000, dtype=np.int64).reshape(100, 50)
    page, crc = codec.encode(arr, "zstd")
    out = codec.decode(page, arr.dtype.str, arr.shape, "zstd", crc)
    np.testing.assert_array_equal(out, arr)


def test_codec_auto_resolves_to_zstd_shuffle():
    assert codec.best_codec() == "zstd_shuffle"
    assert codec.resolve_codec("auto") == "zstd_shuffle"
    assert codec.resolve_codec("zlib") == "zlib"


def test_codec_zstd_shuffle_roundtrip_all_widths():
    rng = np.random.default_rng(3)
    cases = [
        rng.integers(0, 2**32, (128, 4)).astype(np.uint32),  # id limbs
        rng.integers(0, 2**63, 1000).astype(np.uint64),
        rng.standard_normal(777),  # float64
        rng.integers(0, 255, 513).astype(np.uint8),  # width 1: no shuffle
        rng.integers(0, 2**16, 42).astype(np.uint16),
        np.empty((0,), np.uint32),
    ]
    for arr in cases:
        page, crc = codec.encode(arr, "zstd_shuffle")
        out = codec.decode(page, arr.dtype.str, arr.shape, "zstd_shuffle", crc)
        np.testing.assert_array_equal(out, arr)


def test_codec_zstd_shuffle_corruption_detected():
    arr = np.arange(4096, dtype=np.uint64)
    page, crc = codec.encode(arr, "zstd_shuffle")
    bad = bytearray(page)
    bad[len(bad) // 2] ^= 0xFF
    with pytest.raises(codec.CorruptPage):
        codec.decode(bytes(bad), arr.dtype.str, arr.shape, "zstd_shuffle", crc)


def test_codec_crc_mismatch_raises():
    arr = np.ones(100, np.uint32)
    page, crc = codec.encode(arr, "zstd")
    with pytest.raises(codec.CorruptPage):
        codec.decode(page, arr.dtype.str, arr.shape, "zstd", crc ^ 1)


def test_kway_merge_u192_orders_and_dedupes(lib):
    rng = np.random.default_rng(5)
    streams = []
    for _ in range(4):
        n = int(rng.integers(50, 200))
        keys = rng.integers(0, 40, (n, 3)).astype(np.uint64)
        keys = keys[np.lexsort((keys[:, 2], keys[:, 1], keys[:, 0]))]
        streams.append(keys)
    s, r, dup = lib.kway_merge_u192(
        [k[:, 0] for k in streams], [k[:, 1] for k in streams], [k[:, 2] for k in streams]
    )
    got = np.stack([streams[si][ri] for si, ri in zip(s, r)])
    want = np.concatenate(streams)
    want = want[np.lexsort((want[:, 2], want[:, 1], want[:, 0]))]
    np.testing.assert_array_equal(got, want)
    # dup iff exact 192-bit repeat of previous
    np.testing.assert_array_equal(dup[1:], (got[1:] == got[:-1]).all(axis=1))
    # surviving keys are exactly the distinct set
    surv = got[~dup]
    np.testing.assert_array_equal(surv, np.unique(want, axis=0))


def test_compactor_native_merge_matches_device_plan(tmp_path, lib, monkeypatch):
    """The native k-way merge plan and the device lexsort plan must
    produce identical compacted blocks."""
    from tempo_tpu.backend import TypedBackend
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.encoding.common import BlockConfig, CompactionOptions
    from tempo_tpu.encoding.vtpu.block import VtpuBackendBlock
    from tempo_tpu.encoding.vtpu.compactor import VtpuCompactor
    from tempo_tpu.encoding.vtpu.create import write_block
    from tempo_tpu.model import synth
    from tempo_tpu.model import trace as tr

    def build(root):
        be = TypedBackend(LocalBackend(str(root)))
        cfg = BlockConfig(codec="zlib")  # decodable with native disabled
        traces = synth.make_traces(30, seed=11)
        metas = []
        # two blocks with an overlapping half: real dedupe work
        for chunk in (traces[:20], traces[10:]):
            b = tr.traces_to_batch(chunk).sorted_by_trace()
            metas.append(write_block([b], "t", be, cfg))
        return be, cfg, metas

    be1, cfg, metas1 = build(tmp_path / "native")
    comp = VtpuCompactor(CompactionOptions(block_config=cfg))
    out_native = comp.compact(metas1, "t", be1)

    import tempo_tpu.native as native_mod

    be2, cfg2, metas2 = build(tmp_path / "device")
    monkeypatch.setattr(native_mod, "_lib", None)
    monkeypatch.setattr(native_mod, "_tried", True)  # force fallback path
    out_dev = VtpuCompactor(CompactionOptions(block_config=cfg2)).compact(metas2, "t", be2)
    monkeypatch.undo()

    assert len(out_native) == len(out_dev) == 1
    assert out_native[0].total_objects == out_dev[0].total_objects
    b1 = VtpuBackendBlock(out_native[0], be1, cfg)
    b2 = VtpuBackendBlock(out_dev[0], be2, cfg2)
    rows1 = np.concatenate([b1.read_columns(rg, ["trace_id"])["trace_id"] for rg in b1.index().row_groups])
    rows2 = np.concatenate([b2.read_columns(rg, ["trace_id"])["trace_id"] for rg in b2.index().row_groups])
    np.testing.assert_array_equal(rows1, rows2)


# -- the OTLP scan -----------------------------------------------------------
#
# What the scan answers is held to the Python scanner in
# tests/test_receivers.py; these are the binding's own obligations.


def _otlp_body(n_traces, seed, n_spans=4):
    from tempo_tpu.model.synth import make_trace
    from tempo_tpu.receivers import otlp

    return otlp.encode_traces_request(
        [make_trace(seed=seed * 100 + i, n_spans=n_spans) for i in range(n_traces)])


def _scans_equal(a, b):
    for x, y in ((a.cols, b.cols), (a.attrs, b.attrs)):
        assert x.keys() == y.keys()
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    for x, y in zip(a[2:], b[2:]):
        np.testing.assert_array_equal(x, y)


def _in_thread(fn):
    """fn's result from a thread of its own: the scan's arrays are per
    thread and only grow, so a new thread starts with none."""
    import threading

    out = []
    t = threading.Thread(target=lambda: out.append(fn()))
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and out
    return out[0]


def test_otlp_scan_returns_columns_at_their_counts(lib):
    from tempo_tpu.model.columnar import ATTR_COLUMNS, SPAN_COLUMNS

    scan = lib.otlp_scan(_otlp_body(3, seed=1))
    assert set(scan.cols) == set(SPAN_COLUMNS) and set(scan.attrs) == set(ATTR_COLUMNS)
    for name, (dtype, width) in SPAN_COLUMNS.items():
        assert scan.cols[name].dtype == dtype
        assert scan.cols[name].shape == ((12, width) if width else (12,))
    m = scan.attrs["attr_span"].shape[0]
    assert m > 0
    for name, (dtype, _) in ATTR_COLUMNS.items():
        assert scan.attrs[name].dtype == dtype and scan.attrs[name].shape == (m,)
    k = scan.str_off.shape[0]
    assert scan.str_len.shape == scan.str_used.shape == (k,)
    assert (scan.str_off[0], scan.str_len[0]) == (0, 0)  # "" is entry 0
    for col in ("name", "service", "http_method", "http_url"):
        assert scan.cols[col].max() < k
    assert scan.attrs["attr_key"].max() < k and scan.attrs["attr_str"].max() < k
    # the arrays are the caller's own, not views of the thread's scratch
    again = lib.otlp_scan(_otlp_body(3, seed=2))
    assert not np.array_equal(again.cols["trace_id"], scan.cols["trace_id"])


def test_otlp_scan_empty_body(lib):
    scan = lib.otlp_scan(b"")
    assert scan.cols["trace_id"].shape == (0, 4)
    assert scan.attrs["attr_num"].shape == (0,)
    assert scan.str_off.shape == (1,)


def test_otlp_scan_declines_with_a_listed_reason(lib):
    assert lib.otlp_scan(b"\x0a\x7f") == "malformed"
    assert lib.otlp_scan(b"\x0b") in native.OTLP_DECLINED.values()


@pytest.mark.parametrize("caps", [[1, 1, 1, 1], [1, 4096, 64, 4096],
                                  [4096, 1, 64, 4096], [4096, 4096, 1, 4096],
                                  [4096, 4096, 64, 2]])
def test_otlp_scan_asks_again_where_its_arrays_were_too_small(lib, caps):
    """Each capacity in turn too small for the body: the second call, at
    the counts the first returned, gives the answer a roomy call gives."""
    from tempo_tpu.receivers import protowire as pw

    body = bytearray(_otlp_body(6, seed=3))
    # a resource with two attrs beside service.name, so that capacity counts
    res, kv = bytearray(), bytearray()
    for key in (b"zone", b"rack"):
        kv.clear()
        pw.put_bytes_field(kv, 1, key)
        pw.put_bytes_field(kv, 2, b"\x0a\x01a")
        pw.put_bytes_field(res, 1, bytes(kv))
    rs = bytearray()
    pw.put_bytes_field(rs, 1, bytes(res))
    pw.put_bytes_field(rs, 2, b"\x12\x04\x2a\x02op")
    pw.put_bytes_field(body, 1, bytes(rs))
    body = bytes(body)
    want = lib.otlp_scan(body)
    assert not isinstance(want, str) and want.cols["trace_id"].shape[0] == 25
    _scans_equal(_in_thread(lambda: lib.otlp_scan(body, caps=caps)), want)


def test_otlp_scan_threads_each_get_their_own_answer(lib):
    """Four threads scanning different bodies at once, over and over."""
    from concurrent.futures import ThreadPoolExecutor

    bodies = [_otlp_body(4 + 3 * i, seed=10 + i, n_spans=3 + i) for i in range(4)]
    want = [lib.otlp_scan(b) for b in bodies]

    def work(i):
        for _ in range(50):
            _scans_equal(lib.otlp_scan(bodies[i]), want[i])
        return True

    with ThreadPoolExecutor(4) as pool:
        assert all(f.result(timeout=120) for f in [pool.submit(work, i) for i in range(4)])


def test_otlp_scan_reads_nothing_past_the_body(lib):
    """Truncated and bit-flipped bodies, each laid so that its last byte is
    the last byte before a page no one may read: an overrun kills the
    process instead of passing unseen."""
    import ctypes
    import mmap

    body = _otlp_body(16, seed=4, n_spans=8)
    page = mmap.PAGESIZE
    pages = len(body) // page + 1
    mm = mmap.mmap(-1, (pages + 1) * page)
    hold = ctypes.c_char.from_buffer(mm)
    base = ctypes.addressof(hold)
    libc = ctypes.CDLL(None, use_errno=True)
    libc.mprotect.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
    assert libc.mprotect(base + pages * page, page, 0) == 0  # PROT_NONE
    try:
        def at_the_end(data: bytes):
            view = np.frombuffer(mm, np.uint8, len(data), pages * page - len(data))
            view[:] = np.frombuffer(data, np.uint8)
            return view

        rng = np.random.default_rng(5)
        answered = declined = 0
        for cut in range(0, len(body), max(1, len(body) // 150)):
            got = lib.otlp_scan(at_the_end(body[:cut]))
            declined += isinstance(got, str)
        for at in rng.integers(0, len(body), 150).tolist():
            flipped = bytearray(body)
            flipped[at] ^= 1 << int(rng.integers(0, 8))
            got = lib.otlp_scan(at_the_end(bytes(flipped)))
            answered += not isinstance(got, str)
        assert declined > 100 and answered > 0
        whole = lib.otlp_scan(at_the_end(body))
        _scans_equal(whole, lib.otlp_scan(body))
    finally:
        libc.mprotect(base + pages * page, page, mmap.PROT_READ | mmap.PROT_WRITE)
        del hold
        mm.close()
