"""Device page encoders (ISSUE 18): the write-path inverse of the
resident decode formulas.

The contract under test is BIT identity: a page produced by
ops/encode's device arm must be byte-for-byte the page the host
encoders in encoding/vtpu/lightweight.py would have written — header,
widths, CRC, packbits padding, everything — so readers cannot tell
which arm produced a block. Each codec round-trips through BOTH
decoders (host numpy and, for dbp, the device-resident limb scan) and
the routing layer (codec.encode) is exercised with the
TEMPO_TPU_DEVICE_ENCODE kill switch in every position, plus the
host-fallback path when a kernel dies mid-encode.

Runs on the CPU backend: the kernels are plain jit (no pallas), so
tier-1 covers the exact arithmetic that ships on the TPU.
"""

import numpy as np
import pytest

from tempo_tpu.encoding.vtpu import codec, lightweight as lw
from tempo_tpu.ops import encode as dev
from tempo_tpu.ops import pallas_kernels

ABSENT = np.uint32(0xFFFFFFFF)


def _corpus():
    """Named (array, codecs) cases covering every dtype/shape/edge the
    write path produces: dictionary codes with the 0xFFFFFFFF absent
    sentinel, u64 timestamp/duration columns (limb arithmetic), 2-D
    trace-id limbs, negative deltas, sub-byte widths, and lengths on
    either side of the pow2 padding boundary."""
    rng = np.random.default_rng(7)
    ts = (np.uint64(1_700_000_000_000_000_000)
          + np.cumsum(rng.integers(0, 1 << 20, 1000).astype(np.uint64)))
    down = ts[::-1].copy()  # every delta negative
    codes = rng.integers(0, 5, 777).astype(np.uint32)
    codes[rng.random(777) < 0.1] = ABSENT  # absent sentinel rows
    runs = np.repeat(np.arange(9, dtype=np.uint32), 64)
    tid = rng.integers(0, 1 << 32, (300, 4), dtype=np.uint64).astype(np.uint32)
    return [
        ("codes_with_absent", codes, ("rle", "dct", "dbp")),
        ("long_runs_u32", runs, ("rle", "dct", "dbp")),
        ("timestamps_u64", ts, ("dbp", "rle")),
        ("descending_u64", down, ("dbp",)),
        ("trace_id_2d_u32", tid, ("rle", "dct", "dbp")),
        ("constant_u64", np.full(257, 42, np.uint64), ("rle", "dct", "dbp")),
        ("two_rows", np.array([7, ABSENT], np.uint32), ("rle", "dct", "dbp")),
        ("pow2_exact", rng.integers(0, 3, 256).astype(np.uint32),
         ("rle", "dct", "dbp")),
        ("pow2_plus_one", rng.integers(0, 3, 257).astype(np.uint32),
         ("rle", "dct", "dbp")),
        ("status_i32", rng.integers(0, 3, 100).astype(np.int32),
         ("rle", "dct")),
    ]


HOST_ENC = {"rle": lw.rle_encode, "dbp": lw.dbp_encode, "dct": lw.dct_encode}
HOST_DEC = {"rle": lw.rle_decode, "dbp": lw.dbp_decode, "dct": lw.dct_decode}

CASES = [pytest.param(arr, c, id=f"{name}-{c}")
         for name, arr, cs in _corpus() for c in cs]


class TestBitIdentity:
    @pytest.mark.parametrize("arr,cdc", CASES)
    def test_device_page_equals_host_page(self, arr, cdc):
        page = dev.encode_page_device(arr, cdc)
        assert page is not None, "device arm declined an encodable column"
        assert page == HOST_ENC[cdc](arr)

    @pytest.mark.parametrize("arr,cdc", CASES)
    def test_host_decode_round_trip(self, arr, cdc):
        page = dev.encode_page_device(arr, cdc)
        out = HOST_DEC[cdc](page, arr.dtype.str, arr.shape)
        assert out.dtype == arr.dtype
        np.testing.assert_array_equal(out, arr)

    def test_resident_decode_round_trip_u64_dbp(self):
        """device-encoded dbp page -> device-resident limb-scan decode:
        the zero-host-codec read path must see the exact column."""
        rng = np.random.default_rng(3)
        arr = (np.uint64(1 << 60)
               + np.cumsum(rng.integers(0, 1 << 16, 500).astype(np.uint64)))
        page = dev.encode_page_device(arr, "dbp")
        out = pallas_kernels.dbp_decode_device(page, arr.dtype.str, arr.shape)
        np.testing.assert_array_equal(out, arr)

    def test_tiny_and_empty_columns_decline_to_host(self):
        """n < 2 rows: the device arm returns None (nothing to batch)
        and the routing layer must fall through to host bytes."""
        for arr in (np.zeros(0, np.uint32), np.array([9], np.uint64)):
            for cdc in ("rle", "dct", "dbp"):
                assert dev.encode_page_device(arr, cdc) is None

    def test_dbp_width_cap_raises_like_host(self):
        """A delta wider than the 32-bit cap is a caller contract
        violation on BOTH arms, not a device failure — no fallback."""
        arr = np.array([0, 1 << 40, 0, 1 << 40], np.uint64)
        before = dev.encode_fallback_total.value(codec="dbp")
        with pytest.raises(ValueError):
            lw.dbp_encode(arr)
        with pytest.raises(ValueError):
            dev.encode_page_device(arr, "dbp")
        assert dev.encode_fallback_total.value(codec="dbp") == before


class TestRouting:
    def test_codec_encode_bytes_identical_across_switch(self, monkeypatch):
        arr = np.repeat(np.arange(5, dtype=np.uint32), 50)
        monkeypatch.setenv("TEMPO_TPU_DEVICE_ENCODE", "0")
        host_page, host_crc = codec.encode(arr, "rle")
        monkeypatch.setenv("TEMPO_TPU_DEVICE_ENCODE", "1")
        dev_page, dev_crc = codec.encode(arr, "rle")
        assert (dev_page, dev_crc) == (host_page, host_crc)

    def test_kill_switch_keeps_device_arm_cold(self, monkeypatch):
        arr = np.repeat(np.arange(4, dtype=np.uint32), 40)
        monkeypatch.setenv("TEMPO_TPU_DEVICE_ENCODE", "0")
        before = dev.device_encode_pages_total.value(codec="rle")
        codec.encode(arr, "rle")
        assert dev.device_encode_pages_total.value(codec="rle") == before
        monkeypatch.setenv("TEMPO_TPU_DEVICE_ENCODE", "1")
        codec.encode(arr, "rle")
        assert dev.device_encode_pages_total.value(codec="rle") == before + 1

    def test_kernel_failure_falls_back_to_host_page(self, monkeypatch):
        """A dying kernel degrades to host encode — same bytes out, the
        fallback counter moves, ingest never sees the exception."""
        def boom(arr):
            raise RuntimeError("injected kernel failure")

        monkeypatch.setitem(dev._DEVICE_ENC, "dct", boom)
        monkeypatch.setenv("TEMPO_TPU_DEVICE_ENCODE", "1")
        arr = np.array([3, 1, 2, 1, 3, 3], np.uint32)
        before = dev.encode_fallback_total.value(codec="dct")
        page, crc = codec.encode(arr, "dct")
        assert page == lw.dct_encode(arr)
        assert dev.encode_fallback_total.value(codec="dct") == before + 1
        np.testing.assert_array_equal(
            codec.decode(page, arr.dtype.str, arr.shape, "dct", crc), arr)
