"""Pallas kernel tests: parity between the fused kernels (interpret mode
on CPU), the jnp fallback, and a numpy oracle."""

import numpy as np
import pytest

from tempo_tpu.ops import pallas_kernels as pk


def _oracle_in_set(cols, code_sets, n_pad):
    n = cols[0].shape[0]
    out = np.zeros(n_pad, bool)
    m = np.ones(n, bool)
    for col, cs in zip(cols, code_sets):
        m &= np.isin(col.astype(np.uint32), cs.astype(np.uint32))
    out[:n] = m
    return out

class TestInSetScan:
    @pytest.mark.parametrize("n,c,s", [(1024, 1, 1), (1024, 3, 4), (2048, 2, 7), (4096, 4, 1)])
    def test_matches_oracle(self, n, c, s):
        rng = np.random.default_rng(n + c + s)
        cols = [rng.integers(0, 50, n).astype(np.uint32) for _ in range(c)]
        sets_ = [rng.choice(50, size=s, replace=False).astype(np.uint32) for _ in range(c)]
        got = np.asarray(pk.in_set_scan(cols, sets_, n))
        np.testing.assert_array_equal(got, _oracle_in_set(cols, sets_, n))

    def test_partial_fill_pads_false(self):
        n, pad = 700, 1024
        col = np.zeros(n, np.uint32)  # all match code 0
        got = np.asarray(pk.in_set_scan([col], [np.array([0], np.uint32)], pad))
        assert got[:n].all() and not got[n:].any()

    def test_no_match_sentinel_set(self):
        col = np.arange(1024, dtype=np.uint32)
        got = np.asarray(pk.in_set_scan([col], [np.array([pk.NO_MATCH_CODE])], 1024))
        assert not got.any()

    def test_uint16_column(self):
        col = np.full(1024, 500, np.uint16)  # http_status style
        got = np.asarray(pk.in_set_scan([col], [np.array([500], np.uint32)], 1024))
        assert got.all()

    def test_fallback_matches_kernel(self, monkeypatch):
        rng = np.random.default_rng(9)
        cols = [rng.integers(0, 20, 2048).astype(np.uint32) for _ in range(2)]
        sets_ = [np.array([3, 7], np.uint32), np.array([11], np.uint32)]
        kern = np.asarray(pk.in_set_scan(cols, sets_, 2048))
        monkeypatch.setenv("TEMPO_TPU_NO_PALLAS", "1")
        fall = np.asarray(pk.in_set_scan(cols, sets_, 2048))
        np.testing.assert_array_equal(kern, fall)


class TestU64RangeScan:
    @pytest.mark.parametrize("lo,hi", [(0, 2**64 - 1), (10**9, 5 * 10**9), (0, 10**6), (2**40, 2**63)])
    def test_matches_oracle(self, lo, hi):
        rng = np.random.default_rng(int(lo % 97))
        v = rng.integers(0, 2**63, 2048).astype(np.uint64)
        v[:10] = [0, 1, lo, max(lo - 1, 0), lo + 1, hi, hi - 1, min(hi + 1, 2**64 - 1), 2**32, 2**32 - 1]
        got = np.asarray(pk.u64_range_scan(v, lo, hi, 2048))
        want = (v >= lo) & (v <= hi)
        np.testing.assert_array_equal(got, want)

    def test_pad_rows_masked_even_when_zero_in_range(self):
        v = np.full(100, 5, np.uint64)
        got = np.asarray(pk.u64_range_scan(v, 0, 10, 1024))
        assert got[:100].all() and not got[100:].any()


class TestBincountKernel:
    """seg_bincount only takes the Pallas road on a TPU, so tier-1 never
    ran `_bincount_kernel` at all: interpret it here, against
    np.bincount, at the narrowest, a middle and the widest slot vector
    the kernel is allowed (`_BC_MAX_SLOTS`). Weights above 256 are the
    case a bf16-rounded MXU pass got wrong on the chip."""

    @pytest.mark.parametrize("n_slots", [128, 4096, 32768])
    @pytest.mark.parametrize("weighted", [False, True])
    def test_matches_np_bincount(self, n_slots, weighted):
        import jax.numpy as jnp

        assert n_slots <= pk._BC_MAX_SLOTS
        rng = np.random.default_rng(n_slots + weighted)
        n = 4 * pk._BC_ROWS
        slots = rng.integers(-2, n_slots, n).astype(np.int32)  # <0 = dropped
        w = (rng.integers(1, 100_000, n) if weighted else np.ones(n)).astype(np.int32)
        got = np.asarray(pk._bincount_call(
            jnp.asarray(slots), jnp.asarray(w), n_slots, interpret=True))
        live = slots >= 0
        want = np.bincount(slots[live], weights=w[live], minlength=n_slots)
        np.testing.assert_array_equal(got.astype(np.int64), want.astype(np.int64))

    def test_device_road_pads_rows_to_pow2(self, monkeypatch):
        """seg_bincount's accelerator branches pad the row count to a
        power of two (pad rows are dropped slots), so stream lengths
        that follow an ingest cut share one executable. Driven through
        the XLA scatter branch, the one a non-TPU accelerator takes."""
        monkeypatch.setattr(pk.backend, "platform", lambda: "gpu")
        rng = np.random.default_rng(7)
        before = pk._bincount_xla._cache_size()
        for n in (300, 401, 512):  # all land on 512 rows
            slots = rng.integers(-1, 200, n).astype(np.int32)
            w = rng.integers(1, 5000, n).astype(np.int32)
            got = pk.seg_bincount(slots, 200, weights=w)
            live = slots >= 0
            want = np.bincount(slots[live], weights=w[live], minlength=200)
            np.testing.assert_array_equal(got, want.astype(np.int64))
            got = pk.seg_bincount(slots, 200)
            np.testing.assert_array_equal(
                got, np.bincount(slots[live], minlength=200))
        assert pk._bincount_xla._cache_size() == before + 1
