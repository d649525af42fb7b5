"""Device-side page encoders: the write-path inverse of the resident
decode formulas.

The lightweight tier (encoding/vtpu/lightweight.py) was built so the
READ path could evaluate pages without expanding them; this module runs
the same arithmetic in reverse so the WRITE path's cut/flush encode is a
batched device kernel instead of a per-column host loop. Pages are
bit-identical to the host encoders — same header, same body CRC, same
np.packbits(bitorder="little") stream layout — so readers (host decode,
device-resident decode, gather) cannot tell which arm produced a block
(tests/test_device_encode.py holds the two arms to byte equality).

Division of labor per codec (one timed_dispatch per page, so the flush
waterfall shows encode as `transfer` (column ship) + `kernel` stages):

- rle  — the device computes the row-change mask (the O(n*k) compare);
  the host turns the (n-1)-byte mask into firsts/lengths and gathers
  run values. d2h is the mask, not the column.
- dbp  — per-column delta + zigzag runs on device in two u32 limbs
  (x64 is disabled: 64-bit numpy inputs would silently truncate, so
  64-bit arithmetic is explicit limb math, mirroring dbp_decode_device's
  limb prefix scan), followed by the static-width bitpack. Widths come
  from the host probe formulas (identical arithmetic), so the kernel is
  shape-static and the jit cache is keyed by (widths, item bits).
  d2h is the packed streams — i.e. the page body itself.
- dct  — the page dictionary (np.unique) stays host (it is a sort);
  the device packs the index stream at the static width.

Padding: rows are padded to a power of two by REPEATING the last row,
which contributes zero change-marks (rle) and zero deltas -> zero
zigzag bits (dbp), so slicing the exact host byte count off the device
result reproduces np.packbits' zero-padding bit-exactly.

`TEMPO_TPU_DEVICE_ENCODE=0` is the kill switch; unset, the arm follows
the accelerator (util/backend: on for a TPU, off for CPU tier-1 runs).
Any kernel failure falls back to the host encoder per column and counts
in tempo_tpu_ingest_encode_fallback_total — ingest never stalls on the
device plane.
"""

from __future__ import annotations

import functools
import logging
import os
import struct
import zlib

import numpy as np

from tempo_tpu.encoding.vtpu import lightweight as lw
from tempo_tpu.util import metrics
from tempo_tpu.util.devicetiming import timed_dispatch

log = logging.getLogger(__name__)

device_encode_pages_total = metrics.counter(
    "tempo_tpu_ingest_device_encode_pages_total",
    "Pages encoded by the device encode kernels, by codec",
)
encode_fallback_total = metrics.counter(
    "tempo_tpu_ingest_encode_fallback_total",
    "Lightweight pages that fell back to the host encoder (device kernel "
    "error), by codec",
)

_BYTE_WEIGHTS = (1, 2, 4, 8, 16, 32, 64, 128)


def device_encode_enabled() -> bool:
    """TEMPO_TPU_DEVICE_ENCODE: 0 kills, 1 forces; unset follows the
    accelerator (same convention as the metrics device accumulator) so
    CPU-only tier-1 runs keep the host arm without any env setup."""
    env = os.environ.get("TEMPO_TPU_DEVICE_ENCODE", "").strip().lower()
    if env in ("0", "false", "no"):
        return False
    if env in ("1", "true", "yes", "force"):
        return True
    from tempo_tpu.util import backend

    return backend.on_accelerator()


def _pow2(n: int) -> int:
    p = 8
    while p < n:
        p <<= 1
    return p


def _unsigned_2d(arr: np.ndarray) -> np.ndarray:
    """(n, k) view of the column as unsigned lanes the device can carry:
    same-width unsigned for <=4-byte dtypes, u32 limb pairs (lo, hi
    interleaved, little-endian) for 8-byte ones. Pure bit reinterpret —
    row equality and modular arithmetic are preserved exactly."""
    a2 = lw._as_2d(arr)
    item = a2.dtype.itemsize
    u = np.ascontiguousarray(a2).view(f"<u{item}")
    if item == 8:
        u = u.view("<u4").reshape(a2.shape[0], a2.shape[1] * 2)
    return u


def _pad_rows(u: np.ndarray, n_pad: int) -> np.ndarray:
    """Pad axis 0 to n_pad by repeating the last row (zero deltas, zero
    change marks — see module docstring)."""
    n = u.shape[0]
    if n_pad == n:
        return u
    out = np.empty((n_pad,) + u.shape[1:], u.dtype)
    out[:n] = u
    out[n:] = u[n - 1]
    return out


# ---------------------------------------------------------------------------
# kernels (built lazily so host-only processes never import jax)
# ---------------------------------------------------------------------------


def _pack_lanes(jnp, z, w: int):
    """Bitpack (m,) u32 values at static width w (m*w must divide 8 —
    callers pad m to a power of two >= 8). Matches
    np.packbits(bitorder="little") on the zigzag/index stream: value i
    occupies bits [i*w, (i+1)*w), LSB first within the byte."""
    bits = ((z[:, None] >> jnp.arange(w, dtype=jnp.uint32)) & jnp.uint32(1))
    by = bits.reshape(-1, 8).astype(jnp.uint32)
    weights = jnp.asarray(_BYTE_WEIGHTS, jnp.uint32)
    return (by * weights[None, :]).sum(axis=1).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _rle_kernel():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def page_encode_rle_change_mask(a2):
        return (a2[1:] != a2[:-1]).any(axis=1)

    return page_encode_rle_change_mask


@functools.lru_cache(maxsize=None)
def _dbp_kernel(widths: tuple, item_bits: int):
    """Per-page dbp encode: columns arrive as (k, n_pad) u32 lo/hi limb
    planes; returns one packed u8 stream per sub-column. The zigzag of
    the 64-bit wrapped delta is computed entirely in u32 limbs; since
    widths are capped at 32, the packed stream only needs the low limb
    (the high limb of any in-cap zigzag value is zero by construction).
    """
    import jax
    import jax.numpy as jnp

    one = jnp.uint32(1)
    zero = jnp.uint32(0)

    @jax.jit
    def page_encode_dbp(lo_p, hi_p):
        outs = []
        for c, w in enumerate(widths):
            lo, hi = lo_p[c], hi_p[c]
            if item_bits == 64:
                d_lo = lo[1:] - lo[:-1]
                borrow = (lo[1:] < lo[:-1]).astype(jnp.uint32)
                d_hi = hi[1:] - hi[:-1] - borrow
            elif item_bits == 32:
                d_lo = lo[1:] - lo[:-1]
                d_hi = zero - (d_lo >> 31)
            else:
                mask_w = jnp.uint32((1 << item_bits) - 1)
                d_w = (lo[1:] - lo[:-1]) & mask_w
                sign = (d_w >> (item_bits - 1)) & one
                ext = jnp.uint32(0xFFFFFFFF & ~((1 << item_bits) - 1))
                d_lo = d_w | (sign * ext)
                d_hi = zero - sign
            # zigzag in limbs: z = (s << 1) ^ (s >> 63); low limb only
            neg_mask = zero - (d_hi >> 31)
            z_lo = (d_lo << 1) ^ neg_mask
            if w == 0:
                outs.append(jnp.zeros(0, jnp.uint8))
                continue
            z = jnp.concatenate([z_lo, jnp.zeros(1, jnp.uint32)])
            outs.append(_pack_lanes(jnp, z, w))
        return tuple(outs)

    return page_encode_dbp


@functools.lru_cache(maxsize=None)
def _pack_kernel(w: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def page_encode_dct_pack(idx):
        return _pack_lanes(jnp, idx, w)

    return page_encode_dct_pack


# ---------------------------------------------------------------------------
# per-codec device encode (bit-identical to the lightweight.py arm)
# ---------------------------------------------------------------------------


def _rle_device(arr: np.ndarray) -> bytes | None:
    n = arr.shape[0]
    if n < 2:
        return None
    u = _unsigned_2d(arr)
    up = _pad_rows(u, _pow2(n))
    change = timed_dispatch("rle_encode", _rle_kernel(), up)
    d = np.asarray(change)[: n - 1]
    firsts = np.concatenate([[0], np.flatnonzero(d) + 1])
    lengths = np.diff(np.concatenate([firsts, [n]])).astype(np.uint32)
    values = np.ascontiguousarray(arr[firsts])
    body = values.tobytes() + lengths.tobytes()
    return struct.pack("<II", len(firsts), zlib.crc32(body)) + body


def _dbp_device(arr: np.ndarray) -> bytes | None:
    n = arr.shape[0]
    if n < 2:
        return None
    a2 = lw._as_2d(arr)
    k = a2.shape[1]
    # widths from the host probe arithmetic — the kernel's static shape
    widths = []
    for c in range(k):
        w = lw._dbp_width(lw._zigzag(lw._deltas_s64(a2[:, c])))
        if w > lw.DBP_MAX_WIDTH:
            raise ValueError(f"dbp: delta width {w} exceeds cap {lw.DBP_MAX_WIDTH}")
        widths.append(w)
    item = a2.dtype.itemsize
    n_pad = _pow2(n)
    u = _unsigned_2d(arr)  # (n, k) or (n, 2k) limb-interleaved
    if item == 8:
        limbs = u.reshape(n, k, 2)
        lo = np.ascontiguousarray(limbs[:, :, 0].T)
        hi = np.ascontiguousarray(limbs[:, :, 1].T)
    else:
        lo = np.ascontiguousarray(u.T.astype(np.uint32))
        hi = np.zeros_like(lo)
    lo = _pad_rows(lo.T, n_pad).T
    hi = _pad_rows(hi.T, n_pad).T
    streams = timed_dispatch(
        "dbp_encode",
        _dbp_kernel(tuple(widths), item * 8),
        np.ascontiguousarray(lo),
        np.ascontiguousarray(hi),
    )
    uu = a2.astype(np.uint64)
    na = lw._n_anchors(n)
    anchor_rows = (np.arange(na, dtype=np.int64) + 1) * lw.DBP_MINIBLOCK
    parts = [uu[0].astype("<u8").tobytes()]
    for c in range(k):
        a = uu[anchor_rows, c] if na else np.zeros(0, np.uint64)
        parts.append(a.astype("<u8").tobytes())
    for c, w in enumerate(widths):
        nb = ((n - 1) * w + 7) // 8
        parts.append(np.asarray(streams[c])[:nb].tobytes())
    body = b"".join(parts)
    return (
        struct.pack("<BB", 1, k)
        + bytes(widths)
        + struct.pack("<I", zlib.crc32(body))
        + body
    )


def _dct_device(arr: np.ndarray) -> bytes | None:
    n = arr.shape[0]
    if n < 2:
        return None
    a2 = lw._as_2d(arr)
    uniq, inv = np.unique(a2, axis=0, return_inverse=True)
    d = uniq.shape[0]
    w = max(d - 1, 0).bit_length()
    if w > lw.DBP_MAX_WIDTH:
        raise ValueError(f"dct: index width {w} exceeds cap {lw.DBP_MAX_WIDTH}")
    if w == 0:
        stream = b""
    else:
        inv_p = np.zeros(_pow2(n), np.uint32)
        inv_p[:n] = inv.reshape(-1).astype(np.uint32)
        packed = timed_dispatch("dct_encode", _pack_kernel(w), inv_p)
        stream = np.asarray(packed)[: (n * w + 7) // 8].tobytes()
    body = np.ascontiguousarray(uniq).tobytes() + stream
    return struct.pack("<BBII", 1, w, d, zlib.crc32(body)) + body


_DEVICE_ENC = {"rle": _rle_device, "dbp": _dbp_device, "dct": _dct_device}


def encode_page_device(arr: np.ndarray, codec: str) -> bytes | None:
    """Device-encode one column page; None -> caller uses the host arm.

    ValueError (width over the device cap) propagates — it is the same
    contract the host encoder enforces, not a device failure. Everything
    else is a device failure: logged, counted, and absorbed into a host
    fallback so a broken kernel degrades throughput, never ingest.
    """
    fn = _DEVICE_ENC.get(codec)
    if fn is None:
        return None
    try:
        page = fn(arr)
    except ValueError:
        raise
    except Exception:
        encode_fallback_total.inc(codec=codec)
        log.exception("device %s encode failed; falling back to host", codec)
        return None
    if page is not None:
        device_encode_pages_total.inc(codec=codec)
    return page
