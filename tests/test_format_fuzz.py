"""Seeded fuzz for the page-encoding tier (lightweight + entropy).

Tier-1 contract for every codec that can appear in PageMeta:
- round trips are exact for random dtypes/shapes/run structures,
  including empty and single-element pages;
- the stored crc (of the DECODED payload) verifies, and a flipped crc
  is detected;
- truncating the page at any boundary raises CorruptPage — never a
  silently wrong array (PR 6: corruption is never served);
- run-/dict-/gather-space reads agree with the full decode.

Seeds are fixed so failures replay bit-identically.
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.encoding.vtpu import codec, format as fmt, lightweight as lw

SEEDS = (0, 1, 2)


def _random_array(rng, kind: str):
    """Arrays shaped like real column pages, per target codec."""
    n = int(rng.choice([0, 1, 2, 7, 127, 128, 129, 1000, 4096]))
    if kind == "rle":
        # run-heavy, sometimes 2-D (trace-ID limb rows)
        if rng.random() < 0.5:
            vals = rng.integers(0, 50, max(n // max(int(rng.integers(1, 9)), 1), 1))
            arr = np.repeat(vals, rng.integers(1, 9, len(vals)))[:n].astype(np.uint32)
            if len(arr) < n:
                arr = np.concatenate([arr, np.zeros(n - len(arr), np.uint32)])
        else:
            base = rng.integers(0, 2**32, (max(n // 4, 1), 4)).astype(np.uint32)
            arr = np.repeat(base, 4, axis=0)[:n]
        return arr
    if kind == "dbp":
        dt = rng.choice([np.uint32, np.uint64])
        if rng.random() < 0.3:
            return np.sort(rng.integers(0, 2**30, (n, 4)).astype(np.uint32), axis=0)
        deltas = rng.integers(-(2**20), 2**20, n)
        return (np.int64(2**40) + np.cumsum(deltas)).astype(dt)
    if kind == "dct":
        d = int(rng.choice([1, 2, 17, 200]))
        if rng.random() < 0.5:
            return rng.integers(0, max(d, 1), n).astype(np.uint32)
        pool = rng.integers(0, 2**32, (max(d, 1), 2)).astype(np.uint32)
        return pool[rng.integers(0, len(pool), n)]
    # entropy tier: anything integral
    dt = rng.choice([np.uint8, np.uint32, np.uint64])
    return rng.integers(0, 2**31, n).astype(dt)


def _codecs_under_test():
    out = ["none", "zlib", "rle", "dbp", "dct"]
    from tempo_tpu import native

    if native.lib() is not None:
        out += ["zstd", "zstd_shuffle"]
    return out


class TestRoundTripFuzz:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_every_codec_round_trips(self, seed):
        rng = np.random.default_rng(seed)
        for c in _codecs_under_test():
            kind = c if c in ("rle", "dbp", "dct") else "entropy"
            for _ in range(12):
                arr = _random_array(rng, kind)
                page, crc = codec.encode(arr, c)
                out = codec.decode(page, arr.dtype.str, arr.shape, c, crc)
                assert out.dtype == arr.dtype and out.shape == arr.shape
                assert (out == arr).all(), (c, arr.shape, arr.dtype)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_crc_flip_detected(self, seed):
        rng = np.random.default_rng(100 + seed)
        for c in _codecs_under_test():
            kind = c if c in ("rle", "dbp", "dct") else "entropy"
            arr = _random_array(rng, kind)
            while arr.size == 0:
                arr = _random_array(rng, kind)
            page, crc = codec.encode(arr, c)
            with pytest.raises(codec.CorruptPage):
                codec.decode(page, arr.dtype.str, arr.shape, c, crc ^ 0xDEAD)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_truncation_raises_not_garbage(self, seed):
        rng = np.random.default_rng(200 + seed)
        for c in _codecs_under_test():
            kind = c if c in ("rle", "dbp", "dct") else "entropy"
            arr = _random_array(rng, kind)
            while arr.size < 16:
                arr = _random_array(rng, kind)
            page, crc = codec.encode(arr, c)
            cuts = sorted({0, 1, 3, len(page) // 4, len(page) // 2, len(page) - 1})
            for cut in cuts:
                with pytest.raises(codec.CorruptPage):
                    codec.decode(page[:cut], arr.dtype.str, arr.shape, c, crc)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_mangled_body_raises(self, seed):
        """Bit flips inside the page body must be caught by the body or
        payload crc, for the run-space reads too."""
        rng = np.random.default_rng(300 + seed)
        for c in ("rle", "dbp", "dct"):
            arr = _random_array(rng, c)
            while arr.size < 64:
                arr = _random_array(rng, c)
            page, crc = codec.encode(arr, c)
            flip = bytearray(page)
            pos = int(rng.integers(8, len(flip)))
            flip[pos] ^= 0x40
            with pytest.raises(codec.CorruptPage):
                codec.decode(bytes(flip), arr.dtype.str, arr.shape, c, crc)


class TestEncodedSpaceReads:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_rle_runs_and_gather_match_decode(self, seed):
        rng = np.random.default_rng(400 + seed)
        for _ in range(8):
            arr = _random_array(rng, "rle")
            page, crc = codec.encode(arr, "rle")
            full = codec.decode(page, arr.dtype.str, arr.shape, "rle", crc)
            values, lengths = lw.rle_decode_runs(page, arr.dtype.str, arr.shape)
            assert (np.repeat(values, lengths, axis=0) == full).all()
            if arr.shape[0]:
                rows = np.sort(rng.choice(arr.shape[0], min(13, arr.shape[0]), replace=False))
                assert (lw.rle_gather(values, lengths, rows) == full[rows]).all()

    @pytest.mark.parametrize("seed", SEEDS)
    def test_dbp_gather_matches_decode(self, seed):
        rng = np.random.default_rng(500 + seed)
        for _ in range(8):
            arr = _random_array(rng, "dbp")
            page, crc = codec.encode(arr, "dbp")
            full = codec.decode(page, arr.dtype.str, arr.shape, "dbp", crc)
            if arr.shape[0]:
                rows = np.sort(rng.choice(arr.shape[0], min(29, arr.shape[0]), replace=False))
                got, touched = lw.dbp_gather(page, arr.dtype.str, arr.shape, rows)
                assert (got == full[rows]).all()
                assert touched <= arr.shape[0] + lw.DBP_MINIBLOCK

    @pytest.mark.parametrize("seed", SEEDS)
    def test_dct_indices_match_decode(self, seed):
        rng = np.random.default_rng(600 + seed)
        for _ in range(8):
            arr = _random_array(rng, "dct")
            page, crc = codec.encode(arr, "dct")
            full = codec.decode(page, arr.dtype.str, arr.shape, "dct", crc)
            values, idx = lw.dct_indices(page, arr.dtype.str, arr.shape)
            if arr.shape[0]:
                assert (values[idx].reshape(arr.shape) == full).all()

    @pytest.mark.parametrize("seed", SEEDS)
    def test_device_decode_parity(self, seed):
        """The device dbp decode (two-limb prefix scan) and rle expand
        are bit-identical to the host decode."""
        from tempo_tpu.ops import pallas_kernels as pk

        rng = np.random.default_rng(700 + seed)
        for _ in range(4):
            arr = _random_array(rng, "dbp")
            page, crc = codec.encode(arr, "dbp")
            host = codec.decode(page, arr.dtype.str, arr.shape, "dbp", crc)
            dev = pk.dbp_decode_device(page, arr.dtype.str, arr.shape)
            assert (host == dev).all()
        arr = _random_array(rng, "rle")
        while arr.ndim != 1 or arr.size == 0:
            arr = _random_array(rng, "rle")
        page, crc = codec.encode(arr, "rle")
        values, lengths = lw.rle_decode_runs(page, arr.dtype.str, arr.shape)
        dev = np.asarray(pk.rle_expand_device(
            values.astype(np.uint32), lengths.astype(np.int32), arr.shape[0]))
        assert (dev == arr.astype(np.uint32)).all()


class TestFusedKernels:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_fused_rle_in_set_matches_host(self, seed):
        """The batched fused decode+predicate program equals per-row
        np.isin over the expanded columns."""
        from tempo_tpu.ops import pallas_kernels as pk

        rng = np.random.default_rng(800 + seed)
        U, C, K, R, n = 3, 2, 4, 32, 256
        values = rng.integers(0, 10, (U, C, R)).astype(np.uint32)
        lengths = np.zeros((U, C, R), np.int32)
        for u in range(U):
            for c in range(C):
                lengths[u, c] = rng.multinomial(n, np.ones(R) / R)
        codes = np.full((U, C, K), 0xFFFFFFFF, np.uint32)
        codes[:, :, :2] = rng.integers(0, 10, (U, C, 2))
        masks = pk.fused_rle_in_set(values, lengths, codes, n)
        for u in range(U):
            want = np.ones(n, bool)
            for c in range(C):
                col = np.repeat(values[u, c], lengths[u, c])
                want &= np.isin(col, codes[u, c][codes[u, c] != 0xFFFFFFFF])
            assert (masks[u] == want).all()

    def test_unshuffle_device_inverts_byte_shuffle(self):
        from tempo_tpu.ops import pallas_kernels as pk

        rng = np.random.default_rng(5)
        x = rng.integers(0, 2**32, 4096).astype(np.uint32)
        planes = x.view(np.uint8).reshape(-1, 4).T.copy()  # blosc shuffle
        assert (np.asarray(pk.unshuffle_device(planes, 4)) == x).all()


class TestChooser:
    def test_chooser_deterministic_and_bounded(self):
        rng = np.random.default_rng(9)
        svc = np.repeat(rng.integers(0, 5, 512).astype(np.uint32), 8)
        assert lw.choose_codec("service", svc, "zlib") == "rle"
        assert lw.choose_codec("service", svc, "zlib") == "rle"  # stable
        # high-entropy column refuses every lightweight codec
        rnd = rng.integers(0, 2**63, 4096).astype(np.uint64)
        assert lw.choose_codec("duration_nano", rnd, "zlib") == "zlib"
        # kill switch
        import os

        os.environ["TEMPO_TPU_LIGHTWEIGHT"] = "0"
        try:
            assert lw.choose_codec("service", svc, "zlib") == "zlib"
        finally:
            os.environ.pop("TEMPO_TPU_LIGHTWEIGHT")

    def test_tiny_pages_stay_on_default(self):
        arr = np.zeros(8, np.uint32)
        assert lw.choose_codec("service", arr, "zlib") == "zlib"


class TestPlanPageRuns:
    def test_shuffled_pages_dict_plans_by_offset(self):
        """plan_page_runs must sort by OFFSET, not dict order: after
        relocation/reencode mixes the pages dict can interleave
        arbitrarily vs the byte layout (the regression this pins)."""
        import random

        names = [f"c{i}" for i in range(8)]
        pages = {}
        off = 0
        metas = []
        for n in names:
            ln = 100 + 10 * len(metas)
            metas.append((n, off, ln))
            off += ln + 50  # 50-byte gaps, below any sane max_gap
        random.Random(7).shuffle(metas)
        for n, o, ln in metas:
            pages[n] = fmt.PageMeta(offset=o, length=ln, dtype="<u4",
                                    shape=(25,), codec="none", crc=0)
        rg = fmt.RowGroupMeta(n_spans=25, n_attrs=0, min_id="0", max_id="f",
                              start_s=0, end_s=1, pages=pages)
        runs = fmt.plan_page_runs(rg, list(pages), max_gap=64)
        # one run (gaps all 50 <= 64), covering the true byte span
        assert len(runs) == 1
        lo, hi, run_names = runs[0]
        assert lo == min(o for _, o, _ in metas)
        assert hi == max(o + ln for _, o, ln in metas)
        assert sorted(run_names) == sorted(names)
        # and with zero tolerance, one run per page, offset-ordered
        runs = fmt.plan_page_runs(rg, list(pages), max_gap=0)
        offs = [lo for lo, _, _ in runs]
        assert offs == sorted(offs) and len(runs) == len(names)
