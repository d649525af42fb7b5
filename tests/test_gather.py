"""A gather reads a page it has already parsed.

`lightweight.dbp_gather_rows` unpacks every touched miniblock in one
pass and must return what a full decode holds at those rows, and the
miniblock rows the old per-miniblock loop touched. `EncodedColumn.gather`
takes the page's parsed form from the column cache when the block has
one (checksummed once, at the fill) and parses at every call when it has
none; a page that fails its CRC raises either way and is never cached.
`tempodb_gathers_total{codec, source}` says which of the two a call took.
"""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.backend import MockBackend, TypedBackend
from tempo_tpu.encoding import from_version
from tempo_tpu.encoding.common import BlockConfig, SearchRequest
from tempo_tpu.encoding.vtpu import block as vblock
from tempo_tpu.encoding.vtpu import lightweight as lw
from tempo_tpu.encoding.vtpu.block import EncodedColumn, VtpuBackendBlock
from tempo_tpu.encoding.vtpu.codec import CorruptPage
from tempo_tpu.encoding.vtpu.colcache import ColumnCache, shared_cache

from test_runspace import _corpus, _env, _hit_tuples

ENC = from_version("vtpu1")
A = lw.DBP_MINIBLOCK


def _column(n: int, k: int, dtype=np.uint64, seed: int = 0) -> np.ndarray:
    """A near-sorted (n, k) column (1-D for k = 1) whose sub-columns
    want different bit widths."""
    rng = np.random.default_rng(seed)
    steps = rng.integers(0, 1 << 20, (n, k)) >> (np.arange(k) * 5)
    col = (np.cumsum(steps, axis=0) + 1_700_000_000).astype(dtype)
    return col[:, 0] if k == 1 else col


def _rows(pattern: str, n: int) -> np.ndarray:
    rng = np.random.default_rng(n)
    last_lo = (n - 1) // A * A
    if pattern == "first_miniblock":
        return np.arange(min(n, A))[::3]
    if pattern == "last_miniblock":
        return np.arange(last_lo, n)
    if pattern == "unsorted":
        return rng.permutation(n)[:61]
    if pattern == "repeated":
        return np.repeat(rng.integers(0, n, 9), 3)
    raise AssertionError(pattern)


def _touched_by_loop(rows: np.ndarray, n: int) -> int:
    """What the per-miniblock loop counted: the rows of every miniblock
    a requested row lands in, the page's last one short."""
    return sum(min(lo + A, n) - lo for lo in {int(r) // A * A for r in rows})


def _assert_gathers(arr: np.ndarray, rows: np.ndarray) -> None:
    page = lw.dbp_encode(arr)
    full = lw.dbp_decode(page, arr.dtype.str, arr.shape)
    assert (full == arr).all()
    got, touched = lw.dbp_gather(page, arr.dtype.str, arr.shape, rows)
    assert got.dtype == arr.dtype and got.shape == (len(rows),) + arr.shape[1:]
    assert (got == full[rows]).all()
    assert touched == _touched_by_loop(rows, arr.shape[0])
    # the cached path's entry: the same rows from parts parsed once
    parts = lw.dbp_gather_parts(page, arr.dtype.str, arr.shape)
    again, touched_again = lw.dbp_gather_rows(*parts, arr.dtype.str, arr.shape, rows)
    assert (again == got).all() and touched_again == touched


class TestOnePassUnpacker:
    @pytest.mark.parametrize("pattern", ["first_miniblock", "last_miniblock",
                                         "unsorted", "repeated"])
    @pytest.mark.parametrize("n", [1, 127, 128, 129, 1000, 32768])
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_equals_decoded_rows(self, k, n, pattern):
        _assert_gathers(_column(n, k, seed=k), _rows(pattern, n))

    @pytest.mark.parametrize("n", [1, 128, 300])
    @pytest.mark.parametrize("k", [1, 2])
    def test_width_zero_streams(self, k, n):
        """A constant sub-column packs to an empty stream: its rows are
        the block bases, and the stream after it starts where it would."""
        arr = _column(n, 2, seed=5)
        arr[:, 0] = 7
        if k == 1:
            arr = np.ascontiguousarray(arr[:, 0])
        assert lw.dbp_probe(arr)[1][0] == 0
        _assert_gathers(arr, _rows("unsorted", n))

    @pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.uint16])
    def test_narrow_columns_that_wrap(self, dtype):
        """Deltas are modular in the column's own width: a u32 column
        that passes 2^32 comes back exact, anchors included."""
        bits = np.dtype(dtype).itemsize * 8
        arr = (np.arange(700, dtype=np.uint64) * ((1 << bits) // 300 + 1)).astype(dtype)
        assert (np.diff(arr.astype(np.int64)) < 0).any()
        _assert_gathers(arr, _rows("unsorted", 700))

    def test_rows_out_of_range_raise(self):
        arr = _column(300, 1)
        page = lw.dbp_encode(arr)
        for rows in ([300], [-1]):
            with pytest.raises(IndexError):
                lw.dbp_gather(page, arr.dtype.str, arr.shape, np.array(rows))

    def test_no_rows_and_no_page_rows(self):
        arr = _column(300, 2)
        got, touched = lw.dbp_gather(lw.dbp_encode(arr), arr.dtype.str, arr.shape,
                                     np.array([], np.int64))
        assert got.shape == (0, 2) and touched == 0
        empty = arr[:0]
        got, touched = lw.dbp_gather(lw.dbp_encode(empty), empty.dtype.str, empty.shape,
                                     np.array([], np.int64))
        assert got.shape == (0, 2) and touched == 0


# ---------------------------------------------------------------------------
# EncodedColumn.gather over a block
# ---------------------------------------------------------------------------

# (column, its codec in test_runspace's corpus)
COLUMNS = [("service", "rle"), ("name", "dct"), ("start_unix_nano", "dbp"),
           ("attr_span", "dbp")]


def _gathers(codec: str) -> dict:
    return {src: vblock.gathers_total.value(codec=codec, source=src)
            for src in ("cached", "parsed")}


def _since(before: dict, codec: str) -> dict:
    return {src: v - before[src] for src, v in _gathers(codec).items()}


@pytest.fixture
def corpus():
    backend = TypedBackend(MockBackend())
    cfg = BlockConfig(row_group_spans=128)
    return backend, cfg, _corpus(backend, cfg, n_blocks=1)[0]


def _open(corpus, column_cache):
    backend, cfg, meta = corpus
    return VtpuBackendBlock(meta, backend, cfg, column_cache=column_cache)


class TestEncodedColumnGather:
    @pytest.mark.parametrize("cached", [True, False], ids=["cache", "no_cache"])
    @pytest.mark.parametrize("name,codec", COLUMNS)
    def test_equals_decoded_rows(self, corpus, name, codec, cached):
        """Cold, then warm: with a cache the second call finds the
        parsed form and the first filled it; without one both parse."""
        blk = _open(corpus, ColumnCache(1 << 24) if cached else None)
        rg = blk.index().row_groups[1]
        assert rg.pages[name].codec == codec
        full = _open(corpus, None).read_columns(rg, [name])[name]
        rows = np.array([5, 90, 3, 90, len(full) - 1])
        for want in ("parsed", "cached" if cached else "parsed"):
            before = _gathers(codec)
            got = blk.encoded_column(rg, name).gather(rows)
            assert got.dtype == full.dtype and (got == full[rows]).all()
            assert _since(before, codec) == {"cached": 0, "parsed": 0, want: 1}

    @pytest.mark.parametrize("name,codec", COLUMNS[1:])
    def test_a_warm_gather_never_reaches_the_page(self, corpus, name, codec, monkeypatch):
        blk = _open(corpus, ColumnCache(1 << 24))
        rg = blk.index().row_groups[0]
        rows = np.arange(0, 100, 7)
        cold = blk.encoded_column(rg, name).gather(rows)
        monkeypatch.setattr(EncodedColumn, "_page",
                            lambda self: pytest.fail("a warm gather read its page"))
        monkeypatch.setattr(lw.zlib, "crc32",
                            lambda *a: pytest.fail("a warm gather checksummed"))
        assert (blk.encoded_column(rg, name).gather(rows) == cold).all()

    @pytest.mark.parametrize("name,codec", COLUMNS[1:])
    def test_decoded_bytes_do_not_depend_on_the_cache(self, corpus, name, codec):
        """A dbp gather counts the miniblock rows it touched times the
        item size, a dct gather its output: cold, warm or cacheless."""
        rows = np.array([1, 2, 3])
        counted = []
        for blk in (_open(corpus, None), _open(corpus, ColumnCache(1 << 24))):
            rg = blk.index().row_groups[0]
            for _ in range(2):
                d0 = blk.decoded_bytes
                out = blk.encoded_column(rg, name).gather(rows)
                counted.append(blk.decoded_bytes - d0)
        n = rg.pages[name].shape[0]
        want = out.nbytes if codec == "dct" else min(A, n) * out.dtype.itemsize
        assert counted == [want] * 4

    def test_an_evicted_part_means_a_reparse(self, corpus):
        """Eviction takes parts one by one: a form with a part missing is
        parsed again, from the page, and is whole afterwards."""
        cache = ColumnCache(1 << 24)
        blk = _open(corpus, cache)
        rg = blk.index().row_groups[0]
        rows = np.array([0, 50, 127])
        cold = blk.encoded_column(rg, "start_unix_nano").gather(rows)
        key = (blk.meta.block_id, "start_unix_nano", rg.pages["start_unix_nano"].offset, "dbps")
        with cache._lock:
            cache._bytes -= cache._lru.pop(key).nbytes
        before = _gathers("dbp")
        assert (blk.encoded_column(rg, "start_unix_nano").gather(rows) == cold).all()
        assert _since(before, "dbp") == {"cached": 0, "parsed": 1}
        assert cache.get(key) is not None


# ---------------------------------------------------------------------------
# the guarantee: a corrupt page raises, and is never served from a cache
# ---------------------------------------------------------------------------


def _flip_body_byte(backend, meta, pm) -> None:
    """One flipped byte in the middle of the page's packed streams."""
    key = (meta.tenant_id, meta.block_id, "data.bin")
    data = bytearray(backend.raw.objects[key])
    data[pm.offset + pm.length // 2] ^= 0x10
    backend.raw.objects[key] = bytes(data)


def test_every_series_is_exposed_before_the_first_gather():
    """The cached share's reader takes a window whose cache served
    nothing as 0, not as nothing to read: both sources of every codec
    are on /metrics from the start."""
    lines = vblock.gathers_total.expose()
    for codec in ("rle", "dct", "dbp"):
        for source in ("cached", "parsed"):
            assert any(f'codec="{codec}"' in ln and f'source="{source}"' in ln
                       for ln in lines)


class TestCorruptPage:
    @pytest.mark.parametrize("cached", [True, False], ids=["cache", "no_cache"])
    @pytest.mark.parametrize("name", ["start_unix_nano", "name"])
    def test_a_flipped_byte_raises_and_is_never_cached(self, corpus, name, cached):
        backend, _cfg, meta = corpus
        cache = ColumnCache(1 << 24) if cached else None
        blk = _open(corpus, cache)
        rg = blk.index().row_groups[0]
        pm = rg.pages[name]
        _flip_body_byte(backend, meta, pm)
        for _ in range(2):  # the second call raises too: nothing was kept
            with pytest.raises(CorruptPage):
                blk.encoded_column(rg, name).gather(np.array([0, 1]))
        if cached:
            kinds = {k[3] for k in cache._lru if k[1] == name and len(k) == 4}
            assert kinds <= {"page"}  # the raw bytes as fetched, no parsed form


# ---------------------------------------------------------------------------
# a block search over a cached block
# ---------------------------------------------------------------------------


class TestSearchGathers:
    REQ = SearchRequest(tags={"service": "needle-svc"},
                        start_seconds=1, end_seconds=2 * 10**9, limit=0)

    def test_second_search_parses_nothing_and_hits_agree(self, corpus):
        backend, cfg, meta = corpus
        shared_cache().clear()
        codecs = ("rle", "dct", "dbp")
        before = {c: _gathers(c) for c in codecs}
        cold = ENC.open_block(meta, backend, cfg).search(self.REQ)
        first = {c: _since(before[c], c) for c in codecs}
        assert first["dbp"]["parsed"] > 0 and first["dct"]["parsed"] > 0
        before = {c: _gathers(c) for c in codecs}
        warm = ENC.open_block(meta, backend, cfg).search(self.REQ)
        second = {c: _since(before[c], c) for c in codecs}
        assert all(second[c]["parsed"] == 0 for c in codecs)
        assert {c: sum(second[c].values()) for c in codecs} == \
            {c: sum(first[c].values()) for c in codecs}
        with _env(TEMPO_TPU_RUNSPACE="0"):
            shared_cache().clear()
            rows = ENC.open_block(meta, backend, cfg).search(self.REQ)
        assert _hit_tuples(cold) == _hit_tuples(warm) == _hit_tuples(rows) and cold.traces

    def test_cold_decoded_bytes_are_the_parents(self):
        """decodedBytes of a cold search over test_runspace's corpus, as
        the tree before the parsed forms counted them (PR 36 read them
        there): a gather counts what it did before, whatever it reads."""
        backend = TypedBackend(MockBackend())
        cfg = BlockConfig(row_group_spans=128)
        metas = _corpus(backend, cfg)
        want = {"tags": 7800, "window": 10872}
        reqs = {"tags": SearchRequest(tags={"service": "needle-svc"}, limit=0),
                "window": self.REQ}
        for which, req in reqs.items():
            shared_cache().clear()
            cold = sum(ENC.open_block(m, backend, cfg).search(req).decoded_bytes
                       for m in metas)
            assert cold == want[which]
