"""ctypes bindings for the native C++ codec library.

The library is compiled on demand with g++ (cached next to the source,
keyed by source hash AND build host: -march=native output only runs on
the CPU it was built for, so a binary copied in from another machine is
never loaded) and loaded via ctypes — no pybind11 in this image.
All entry points hold no Python state and release the GIL for the
duration of the C call (ctypes does this for us), so page encode/decode,
k-way merge planning and the OTLP receiver's scan of a push run
concurrently with device work and with each other.

`lib()` returns the loaded binding or None when no compiler/headers are
available; callers fall back: encoding/vtpu/codec.py to stdlib paths,
receivers/otlp.py to its Python scanner.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import NamedTuple

import numpy as np

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "codec.cc")

_lock = threading.Lock()
_lib = None
_tried = False


class NativeError(Exception):
    pass


ERR = {-1: "destination too small", -2: "corrupt input", -3: "bad argument"}


def _check(r: int) -> int:
    if r < 0:
        raise NativeError(ERR.get(r, f"native error {r}"))
    return r


# why ttpu_otlp_scan declines a body (codec.cc's OTLP_* enum): the
# closed list behind tempo_tpu_ingest_decode_requests_total's `reason`
OTLP_DECLINED = {
    1: "malformed", 2: "value_type", 3: "promoted_type", 4: "duplicate_key",
    5: "empty_key", 6: "key_encoding", 7: "id_length", 8: "out_of_range",
    9: "too_large",
}

_SPANS, _ATTRS, _RES, _STRS, _SLOTS = range(5)  # codec.cc's C_* enum
# the arrays ttpu_otlp_scan fills, in codec.cc's A_* order: (name, dtype,
# width, which capacity sizes it). Names with "_" stay inside the scan.
_OTLP_ARRAYS = (
    ("start_unix_nano", np.uint64, 1, _SPANS),
    ("duration_nano", np.uint64, 1, _SPANS),
    ("trace_id", np.uint32, 4, _SPANS),
    ("span_id", np.uint32, 2, _SPANS),
    ("parent_span_id", np.uint32, 2, _SPANS),
    ("name", np.uint32, 1, _SPANS),
    ("http_method", np.uint32, 1, _SPANS),
    ("http_url", np.uint32, 1, _SPANS),
    ("service", np.uint32, 1, _SPANS),
    ("http_status", np.uint16, 1, _SPANS),
    ("kind", np.uint8, 1, _SPANS),
    ("status_code", np.uint8, 1, _SPANS),
    ("attr_num", np.float64, 1, _ATTRS),
    ("attr_span", np.uint32, 1, _ATTRS),
    ("attr_key", np.uint32, 1, _ATTRS),
    ("attr_str", np.uint32, 1, _ATTRS),
    ("attr_scope", np.uint8, 1, _ATTRS),
    ("attr_vtype", np.uint8, 1, _ATTRS),
    ("_res_num", np.float64, 1, _RES),
    ("_res_key", np.uint32, 1, _RES),
    ("_res_str", np.uint32, 1, _RES),
    ("_res_vtype", np.uint8, 1, _RES),
    ("str_off", np.uint32, 1, _STRS),
    ("str_len", np.uint32, 1, _STRS),
    ("_str_stamp", np.uint32, 1, _STRS),
    ("str_used", np.uint8, 1, _STRS),
    ("_slots", np.uint32, 1, _SLOTS),
)


class OtlpColumns(NamedTuple):
    """What one ttpu_otlp_scan found: a SpanBatch's columns, with every
    string column (name, service, http_method, http_url, attr_key,
    attr_str) holding a local code into the table of unique slices
    `(str_off, str_len)` of the body; entry 0 is the empty string, and
    `str_used` marks the entries some row refers to."""

    cols: dict
    attrs: dict
    str_off: np.ndarray
    str_len: np.ndarray
    str_used: np.ndarray


def _host_tag() -> str:
    """Identity of the machine a -march=native build is valid on: the
    architecture plus the CPU feature flags the compiler targets."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return hashlib.sha256(f"{platform.machine()}|{flags}".encode()).hexdigest()[:8]


def _build() -> str | None:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    host = _host_tag()
    so = os.path.join(_DIR, f"_codec_{tag}_{host}.so")
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"  # pid-suffixed: concurrent first-use
    # builds from sibling processes must not interleave into one file
    base = ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
            _SRC, "-o", tmp, "-lz"]
    # images without the libzstd dev symlink still carry the runtime;
    # -l:libzstd.so.1 links it directly (codec.cc declares the ABI)
    err: Exception | None = None
    for zstd_flag in ("-lzstd", "-l:libzstd.so.1"):
        try:
            subprocess.run(base + [zstd_flag], check=True,
                           capture_output=True, timeout=120)
            break
        except (OSError, subprocess.SubprocessError) as e:
            err = e
    else:
        if os.path.exists(so):  # a sibling may have won
            return so
        detail = getattr(err, "stderr", b"") or b""
        log.warning("native codec build failed (%s %s): the default page "
                    "codec degrades from zstd_shuffle to zlib, and OTLP "
                    "decode to the Python scanner", err,
                    detail.decode("utf-8", "replace")[-500:])
        return None
    os.replace(tmp, so)
    # drop this host's stale builds (another host's stay: a shared
    # checkout must not make two machines evict each other's binary)
    for f in os.listdir(_DIR):
        if (f.startswith("_codec_") and f.endswith(f"_{host}.so")
                and f != os.path.basename(so)):
            try:
                os.unlink(os.path.join(_DIR, f))
            except OSError:
                pass
    return so


class _Binding:
    def __init__(self, so_path: str):
        self.path = so_path
        self._tls = threading.local()
        lib = ctypes.CDLL(so_path)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        self._crc32 = lib.ttpu_crc32
        self._crc32.restype = ctypes.c_uint32
        self._crc32.argtypes = [u8p, ctypes.c_size_t]
        self._hash64 = lib.ttpu_hash64
        self._hash64.restype = ctypes.c_uint64
        self._hash64.argtypes = [u8p, ctypes.c_size_t, ctypes.c_uint64]
        self._zstd_bound = lib.ttpu_zstd_bound
        self._zstd_bound.restype = ctypes.c_size_t
        self._zstd_bound.argtypes = [ctypes.c_size_t]
        for name in ("zstd_compress", "zlib_compress"):
            fn = getattr(lib, f"ttpu_{name}")
            fn.restype = ctypes.c_longlong
            fn.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t, ctypes.c_int]
            setattr(self, f"_{name}", fn)
        for name in ("zstd_decompress", "zlib_decompress"):
            fn = getattr(lib, f"ttpu_{name}")
            fn.restype = ctypes.c_longlong
            fn.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
            setattr(self, f"_{name}", fn)
        self._zlib_bound = lib.ttpu_zlib_bound
        self._zlib_bound.restype = ctypes.c_size_t
        self._zlib_bound.argtypes = [ctypes.c_size_t]
        i64p = ctypes.POINTER(ctypes.c_int64)
        self._venc = lib.ttpu_varint_encode_i64
        self._venc.restype = ctypes.c_longlong
        self._venc.argtypes = [i64p, ctypes.c_size_t, u8p, ctypes.c_size_t]
        self._vdec = lib.ttpu_varint_decode_i64
        self._vdec.restype = ctypes.c_longlong
        self._vdec.argtypes = [u8p, ctypes.c_size_t, i64p, ctypes.c_size_t]
        u32p = ctypes.POINTER(ctypes.c_uint32)
        self._cenc = lib.ttpu_col_encode
        self._cenc.restype = ctypes.c_longlong
        self._cenc.argtypes = [u8p, ctypes.c_size_t, ctypes.c_size_t,
                               ctypes.c_int, ctypes.c_int, u8p,
                               ctypes.c_size_t, u32p]
        self._cdec = lib.ttpu_col_decode
        self._cdec.restype = ctypes.c_longlong
        self._cdec.argtypes = [u8p, ctypes.c_size_t, ctypes.c_int,
                               ctypes.c_size_t, u8p, ctypes.c_size_t, u32p]
        self._penc = lib.ttpu_page_encode
        self._penc.restype = ctypes.c_longlong
        self._penc.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t,
                               ctypes.c_int, ctypes.c_int]
        self._praw = lib.ttpu_page_raw_len
        self._praw.restype = ctypes.c_longlong
        self._praw.argtypes = [u8p, ctypes.c_size_t]
        self._pdec = lib.ttpu_page_decode
        self._pdec.restype = ctypes.c_longlong
        self._pdec.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
        u64pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64))
        self._kway = lib.ttpu_kway_merge_u128
        self._kway.restype = ctypes.c_longlong
        self._kway.argtypes = [u64pp, u64pp, ctypes.POINTER(ctypes.c_size_t),
                               ctypes.c_size_t,
                               ctypes.POINTER(ctypes.c_uint32),
                               ctypes.POINTER(ctypes.c_uint32),
                               u8p, ctypes.c_size_t]
        self._kway3 = lib.ttpu_kway_merge_u192
        self._kway3.restype = ctypes.c_longlong
        self._kway3.argtypes = [u64pp, u64pp, u64pp,
                                ctypes.POINTER(ctypes.c_size_t),
                                ctypes.c_size_t,
                                ctypes.POINTER(ctypes.c_uint32),
                                ctypes.POINTER(ctypes.c_uint32),
                                u8p, ctypes.c_size_t]
        self._oscan = lib.ttpu_otlp_scan
        self._oscan.restype = ctypes.c_longlong
        self._oscan.argtypes = [u8p, ctypes.c_size_t,
                                ctypes.POINTER(ctypes.c_void_p),
                                ctypes.POINTER(ctypes.c_uint64),
                                ctypes.POINTER(ctypes.c_uint64)]
        self._u8p = u8p

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _buf(b) -> tuple:
        arr = np.frombuffer(b, np.uint8) if not isinstance(b, np.ndarray) else b
        return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), arr.size

    def crc32(self, data: bytes) -> int:
        p, n = self._buf(data)
        return int(self._crc32(p, n))

    def hash64(self, data: bytes, seed: int = 0) -> int:
        p, n = self._buf(data)
        return int(self._hash64(p, n, seed))

    def compress(self, data: bytes, codec: str = "zstd", level: int = 3) -> bytes:
        p, n = self._buf(data)
        if codec == "zstd":
            cap = int(self._zstd_bound(n))
            out = np.empty(cap, np.uint8)
            r = _check(self._zstd_compress(p, n, out.ctypes.data_as(self._u8p), cap, level))
        elif codec == "zlib":
            cap = int(self._zlib_bound(n))
            out = np.empty(cap, np.uint8)
            r = _check(self._zlib_compress(p, n, out.ctypes.data_as(self._u8p), cap, level))
        else:
            raise ValueError(codec)
        return out[:r].tobytes()

    def decompress(self, data: bytes, raw_len: int, codec: str = "zstd") -> bytes:
        p, n = self._buf(data)
        out = np.empty(raw_len, np.uint8)
        fn = self._zstd_decompress if codec == "zstd" else self._zlib_decompress
        r = _check(fn(p, n, out.ctypes.data_as(self._u8p), raw_len))
        return out[:r].tobytes()

    def varint_encode(self, vals: np.ndarray) -> bytes:
        vals = np.ascontiguousarray(vals, np.int64)
        cap = vals.size * 10 + 16
        out = np.empty(cap, np.uint8)
        r = _check(self._venc(vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                              vals.size, out.ctypes.data_as(self._u8p), cap))
        return out[:r].tobytes()

    def varint_decode(self, data: bytes, n_elems: int) -> np.ndarray:
        p, n = self._buf(data)
        out = np.empty(n_elems, np.int64)
        r = _check(self._vdec(p, n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                              n_elems))
        if r != n_elems:
            raise NativeError(f"decoded {r} elems, expected {n_elems}")
        return out

    def _otlp_work(self, want: list) -> tuple:
        """This thread's arrays for ttpu_otlp_scan, each holding at least
        the rows `want` asks for (C_* order, slots apart): (capacities,
        views in A_* order, the pointer array). They only grow, so a
        thread carves them anew only for a body larger than any before."""
        work = getattr(self._tls, "otlp", None)
        if work is not None and all(h >= w for h, w in zip(work[0], want)):
            return work
        caps = [max(w, h) for w, h in zip(want, work[0])] if work else list(want)
        caps.append(1 << (2 * caps[_STRS] - 1).bit_length())  # hash slots
        views = [np.empty(caps[per] * width, dt)
                 for _, dt, width, per in _OTLP_ARRAYS]
        ptrs = (ctypes.c_void_p * len(views))(*[v.ctypes.data for v in views])
        work = self._tls.otlp = (caps, views, ptrs)
        return work

    def otlp_scan(self, body, caps: list | None = None) -> OtlpColumns | str:
        """One pass over an OTLP ExportTraceServiceRequest body with the
        interpreter lock released: the columns it fills, or the reason it
        declines (a value of OTLP_DECLINED) where the Python scanner of
        receivers/otlp.py could answer otherwise. `caps` overrides the
        first guess of (spans, attr rows, resource attrs, unique strings);
        arrays that prove too short are carved again at the counts the
        scan returns and the body is scanned once more."""
        p, n = self._buf(body)
        want = caps or [n // 32 + 16, n // 8 + 16, 64, n // 32 + 64]
        counts = (ctypes.c_uint64 * 4)()
        for _ in range(2):
            have, views, ptrs = self._otlp_work(want)
            r = self._oscan(p, n, ptrs, (ctypes.c_uint64 * 5)(*have), counts)
            if r != -1:
                break
            want = list(counts)
        if r > 0:
            return OTLP_DECLINED[r]
        _check(r)
        out = {}
        for (name, _, width, per), v in zip(_OTLP_ARRAYS, views):
            if not name.startswith("_"):
                a = v[:counts[per] * width].copy()
                out[name] = a.reshape(-1, width) if width > 1 else a
        strs = [out.pop(k) for k in ("str_off", "str_len", "str_used")]
        attrs = {k: out.pop(k) for k in list(out) if k.startswith("attr_")}
        return OtlpColumns(out, attrs, *strs)

    PAGE_CODECS = {"none": 0, "zlib": 1, "zstd": 2, "zstd_shuffle": 3}

    def _scratch(self, cap: int) -> np.ndarray:
        """Per-thread reusable output buffer (page encodes run hot: a
        fresh np.empty per page costs allocation + page faults)."""
        buf = getattr(self._tls, "scratch", None)
        if buf is None or buf.size < cap:
            buf = np.empty(max(cap, 1 << 20), np.uint8)
            self._tls.scratch = buf
        return buf

    def col_encode(self, arr: np.ndarray, codec: str, level: int = 1) -> tuple[bytes, int]:
        """Fixed-width column -> (page bytes, crc of raw). ONE C call:
        crc + byte-shuffle + compression, no intermediate Python copies."""
        arr = np.ascontiguousarray(arr)
        n = arr.nbytes
        width = arr.dtype.itemsize
        cap = int(self._zstd_bound(n)) + 64
        out = self._scratch(cap)
        crc = ctypes.c_uint32(0)
        src = arr.view(np.uint8).reshape(-1) if n else np.empty(0, np.uint8)
        r = _check(self._cenc(src.ctypes.data_as(self._u8p), n, width,
                              self.PAGE_CODECS[codec], level,
                              out.ctypes.data_as(self._u8p), out.size,
                              ctypes.byref(crc)))
        return out[:r].tobytes(), int(crc.value)

    def col_decode(self, page: bytes, dtype: str, shape: tuple, codec: str) -> tuple[np.ndarray, int]:
        """Page bytes -> (array, crc of raw); decompress + unshuffle +
        crc in one C call, writing straight into the result buffer."""
        dt = np.dtype(dtype)
        out = np.empty(shape, dt)
        n = out.nbytes
        p, plen = self._buf(page)
        crc = ctypes.c_uint32(0)
        dst = out.view(np.uint8).reshape(-1) if n else np.empty(0, np.uint8)
        _check(self._cdec(p, plen, self.PAGE_CODECS[codec], dt.itemsize,
                          dst.ctypes.data_as(self._u8p), n, ctypes.byref(crc)))
        return out, int(crc.value)

    def page_encode(self, raw: bytes, codec: str = "zstd", level: int = 3) -> bytes:
        p, n = self._buf(raw)
        cap = int(self._zstd_bound(n)) + 64
        out = np.empty(cap, np.uint8)
        r = _check(self._penc(p, n, out.ctypes.data_as(self._u8p), cap,
                              self.PAGE_CODECS[codec], level))
        return out[:r].tobytes()

    def page_decode(self, page: bytes) -> bytes:
        p, n = self._buf(page)
        raw_len = _check(self._praw(p, n))
        out = np.empty(max(raw_len, 1), np.uint8)
        r = _check(self._pdec(p, n, out.ctypes.data_as(self._u8p), raw_len))
        return out[:r].tobytes()

    def kway_merge_u128(self, keys_hi: list[np.ndarray], keys_lo: list[np.ndarray]):
        """Merge k sorted u128 streams -> (stream_idx, row_idx, dup_mask)."""
        k = len(keys_hi)
        his = [np.ascontiguousarray(h, np.uint64) for h in keys_hi]
        los = [np.ascontiguousarray(l, np.uint64) for l in keys_lo]
        lens = (ctypes.c_size_t * k)(*[h.size for h in his])
        u64p = ctypes.POINTER(ctypes.c_uint64)
        hp = (u64p * k)(*[h.ctypes.data_as(u64p) for h in his])
        lp = (u64p * k)(*[l.ctypes.data_as(u64p) for l in los])
        total = int(sum(h.size for h in his))
        os_ = np.empty(total, np.uint32)
        orow = np.empty(total, np.uint32)
        odup = np.empty(total, np.uint8)
        r = _check(self._kway(hp, lp, lens, k,
                              os_.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                              orow.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                              odup.ctypes.data_as(self._u8p), total))
        return os_[:r], orow[:r], odup[:r].astype(bool)

    def kway_merge_u192(self, keys_hi: list[np.ndarray], keys_mid: list[np.ndarray],
                        keys_lo: list[np.ndarray]):
        """Merge k sorted u192 streams (traceID hi/lo + spanID lanes) ->
        (stream_idx, row_idx, dup_mask). Streams must each be sorted by
        (hi, mid, lo); dup flags exact 192-bit repeats of the previous key."""
        k = len(keys_hi)
        his = [np.ascontiguousarray(h, np.uint64) for h in keys_hi]
        mids = [np.ascontiguousarray(m, np.uint64) for m in keys_mid]
        los = [np.ascontiguousarray(l, np.uint64) for l in keys_lo]
        lens = (ctypes.c_size_t * k)(*[h.size for h in his])
        u64p = ctypes.POINTER(ctypes.c_uint64)
        hp = (u64p * k)(*[h.ctypes.data_as(u64p) for h in his])
        mp = (u64p * k)(*[m.ctypes.data_as(u64p) for m in mids])
        lp = (u64p * k)(*[l.ctypes.data_as(u64p) for l in los])
        total = int(sum(h.size for h in his))
        os_ = np.empty(total, np.uint32)
        orow = np.empty(total, np.uint32)
        odup = np.empty(total, np.uint8)
        r = _check(self._kway3(hp, mp, lp, lens, k,
                               os_.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                               orow.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                               odup.ctypes.data_as(self._u8p), total))
        return os_[:r], orow[:r], odup[:r].astype(bool)


def lib() -> _Binding | None:
    """The process-wide binding, building the .so on first use."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            so = _build()
            if so is not None:
                try:
                    _lib = _Binding(so)
                except OSError:
                    _lib = None
            _tried = True
    return _lib
