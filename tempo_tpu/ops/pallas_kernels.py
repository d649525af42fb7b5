"""Pallas TPU kernels for the scan hot paths.

The column predicate scan is the innermost loop of tag search and
TraceQL fetch (reference hot loop: vparquet/block_search.go:95,297 and
the parquetquery iterator tree). The jnp path in ops/scan.py leaves
fusion to XLA; the pallas kernels here fuse an entire predicate set
into ONE VMEM pass over the stacked column tile — no (N,) bool
intermediates ever materialize in HBM, and the candidate code sets sit
in SMEM next to the scalar unit.

Kernels run compiled on TPU and in interpreter mode elsewhere (CPU
tests), selected automatically; set TEMPO_TPU_NO_PALLAS=1 to force the
jnp fallback everywhere.

Geometry: column tiles are (C, TILE) with TILE=1024 — a multiple of the
(8, 128) f32/u32 VPU tile, and the engine's minimum row-group pad
(BlockConfig.min_device_bucket) — so blocks always divide evenly.
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tempo_tpu.util import backend

TILE = 1024
NO_MATCH_CODE = np.uint32(0xFFFFFFFF)  # sentinel code: matches no dictionary entry


def _use_pallas() -> bool:
    return os.environ.get("TEMPO_TPU_NO_PALLAS", "") != "1"


def _interpret() -> bool:
    # compiled Mosaic kernels need a real TPU; everywhere else (CPU test
    # meshes) use the interpreter
    return not backend.on_accelerator()


# ---------------------------------------------------------------------------
# fused multi-column in-set scan
# ---------------------------------------------------------------------------


_SUBLANES = 8  # f32/u32 VPU sublane count; rows of the (8, n/8) layout


def _in_set_kernel(codes_ref, cols_ref, out_ref):
    """AND over predicates of (col_c in codes_c), one tile.

    codes_ref: (C, S) uint32 in SMEM — candidate dictionary codes per
    predicate column, padded with NO_MATCH_CODE.
    cols_ref: (C, 8, t) uint32 in VMEM — rows pre-reshaped to fill all 8
    VPU sublanes. out_ref: (8, t) uint32.
    """
    C, S = codes_ref.shape
    mask = jnp.ones(out_ref.shape, jnp.uint32)
    for c in range(C):
        col = cols_ref[c]
        hit = jnp.zeros_like(mask)
        for s in range(S):
            code = codes_ref[c, s]
            hit = hit | (col == code).astype(jnp.uint32)
        mask = mask & hit
    out_ref[...] = mask


def _tile_for(n8: int) -> int:
    """Largest power-of-two lane tile <= 8Ki that divides n8 (= n/8, a
    pow2 multiple of TILE/8). Small grids amortize per-program overhead;
    VMEM stays bounded at C * 256 KiB per block."""
    t = TILE // _SUBLANES
    while t < (1 << 13) and n8 % (t << 1) == 0:
        t <<= 1
    return min(t, n8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _in_set_call(cols_mat: jnp.ndarray, codes_mat: jnp.ndarray, interpret: bool):
    """cols_mat: (C, N) uint32 -> (N,) uint32 match mask."""
    C, N = cols_mat.shape
    n8 = N // _SUBLANES
    tile = _tile_for(n8)
    out = pl.pallas_call(
        _in_set_kernel,
        out_shape=jax.ShapeDtypeStruct((_SUBLANES, n8), jnp.uint32),
        grid=(n8 // tile,),
        in_specs=[
            pl.BlockSpec((C, codes_mat.shape[1]), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((C, _SUBLANES, tile), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="scan_in_set",
    )(codes_mat, cols_mat.reshape(C, _SUBLANES, n8))
    return out.reshape(N)


def in_set_scan(cols: list[np.ndarray], code_sets: list[np.ndarray], n_pad: int) -> jnp.ndarray:
    """Fused AND-of-in-set scan: span row matches iff for every predicate
    c, cols[c][row] is in code_sets[c].

    cols: C arrays of (n,) integer dictionary codes (any uint dtype).
    code_sets: C arrays of candidate codes (ragged; padded to one width).
    n_pad: static padded row count (multiple of TILE — the engine's
    bucket_for guarantees this).
    Returns a (n_pad,) bool device array; rows past len(cols[c]) are False.
    """
    C = len(cols)
    assert C == len(code_sets) and C > 0
    assert n_pad % TILE == 0, n_pad
    n = cols[0].shape[0]
    mat = np.full((C, n_pad), NO_MATCH_CODE, dtype=np.uint32)  # pad rows never match
    for c, col in enumerate(cols):
        mat[c, :n] = col.astype(np.uint32)
    s_pad = 1
    while s_pad < max(cs.shape[0] for cs in code_sets):
        s_pad <<= 1  # pow2 widths bound the jit cache
    codes = np.full((C, s_pad), NO_MATCH_CODE, dtype=np.uint32)
    for c, cs in enumerate(code_sets):
        codes[c, : cs.shape[0]] = cs.astype(np.uint32)
    if not _use_pallas():
        from tempo_tpu.ops import scan  # one canonical in-set implementation

        mask = jnp.ones(n_pad, bool)
        dmat = jnp.asarray(mat)
        for c in range(C):
            mask = mask & scan.in_set(dmat[c], jnp.asarray(codes[c]))
    else:
        mask = _in_set_call(jnp.asarray(mat), jnp.asarray(codes), _interpret()).astype(bool)
    if n < n_pad:
        # pad rows hold NO_MATCH_CODE, but so does the code-set padding —
        # they'd compare equal; mask pads explicitly
        mask = mask & (jnp.arange(n_pad) < n)
    return mask


# ---------------------------------------------------------------------------
# fused duration-range scan (uint64 as two uint32 lanes)
# ---------------------------------------------------------------------------


def _range_kernel(bounds_ref, hi_ref, lo_ref, out_ref):
    """lo_bound <= (hi,lo) <= hi_bound on a 64-bit value split into two
    uint32 lanes (no x64 on device). bounds_ref (SMEM): (4,) uint32 =
    [min_hi, min_lo, max_hi, max_lo]."""
    h = hi_ref[...]
    l = lo_ref[...]
    min_h, min_l, max_h, max_l = (bounds_ref[i] for i in range(4))
    ge = (h > min_h) | ((h == min_h) & (l >= min_l))
    le = (h < max_h) | ((h == max_h) & (l <= max_l))
    out_ref[...] = (ge & le).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _range_call(hi: jnp.ndarray, lo: jnp.ndarray, bounds: jnp.ndarray, interpret: bool):
    """hi/lo: (N,) uint32 limb arrays -> (N,) uint32 match mask."""
    N = hi.shape[0]
    n8 = N // _SUBLANES
    tile = _tile_for(n8)
    out = pl.pallas_call(
        _range_kernel,
        out_shape=jax.ShapeDtypeStruct((_SUBLANES, n8), jnp.uint32),
        grid=(n8 // tile,),
        in_specs=[
            pl.BlockSpec((4,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((_SUBLANES, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((_SUBLANES, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        interpret=interpret,
        name="scan_range",
    )(bounds, hi.reshape(_SUBLANES, n8), lo.reshape(_SUBLANES, n8))
    return out.reshape(N)


# ---------------------------------------------------------------------------
# segmented bincount (the TraceQL metrics reduction)
# ---------------------------------------------------------------------------

_BC_ROWS = 256  # span rows folded per grid step (bounds the one-hot tile)
_BC_MAX_SLOTS = 1 << 15  # widest slot vector the VMEM one-hot tile carries
# (256 x 32768 f32 = 32 MiB streamed tile-by-tile; wider falls back to host)


def _bincount_kernel(slots_ref, w_ref, out_ref):
    """Accumulate one row tile into the slot counts.

    slots_ref: (_BC_ROWS, 1) int32 in VMEM — combined slot index per
    span row ((series*bins + bin) [*buckets + bucket]); negative = drop.
    w_ref: (_BC_ROWS, 1) f32 — per-entry weight (1 for raw rows; the
    run length for run-compressed slot streams).
    out_ref: (1, S) f32 — running counts, same block every grid step
    (the TPU grid is sequential, so += accumulation is well-defined).

    The histogram is computed as a one-hot matmul: rows compare against
    a lane iota to build the (rows, S) one-hot tile, and a (1, rows) x
    (rows, S) dot folds it — scatter-free, which is the shape the MXU
    wants (SQL-on-compressed-data aggregates reduce the same way).
    Weighted entries just scale the reducing vector: the matmul does
    the multiply-by-run-length for free.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    slots = slots_ref[...]  # (R, 1) int32
    S = out_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    one_hot = (slots == iota).astype(jnp.float32)  # (R, S); negatives match nothing
    w = w_ref[...].reshape(1, slots.shape[0])
    # HIGHEST: the MXU's default f32 matmul rounds its operands to
    # bf16, which is exact for the 0/1 one-hot but not for a run length
    # above 256 — weighted counts came back wrong on the chip without it
    out_ref[...] += jax.lax.dot_general(
        w, one_hot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("n_slots_pad", "interpret"))
def _bincount_call(slots: jnp.ndarray, weights: jnp.ndarray, n_slots_pad: int,
                   interpret: bool):
    """slots/weights: (N,) int32, N a multiple of _BC_ROWS ->
    (n_slots_pad,) f32."""
    N = slots.shape[0]
    out = pl.pallas_call(
        _bincount_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_slots_pad), jnp.float32),
        grid=(N // _BC_ROWS,),
        in_specs=[
            pl.BlockSpec((_BC_ROWS, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_BC_ROWS, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n_slots_pad), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="seg_bincount",
    )(slots.reshape(N, 1), weights.astype(jnp.float32).reshape(N, 1))
    return out.reshape(n_slots_pad)


@functools.partial(jax.jit, static_argnames=("n_slots_pad",))
def _bincount_xla(slots: jnp.ndarray, weights: jnp.ndarray, n_slots_pad: int):
    """Compiled scatter-add bincount — the device reduction on compiled
    non-TPU backends (GPU), where Mosaic kernels can't build but scatter
    is native. Integer adds: bit-identical to every other home."""
    idx = jnp.where(slots >= 0, slots, n_slots_pad)  # OOB + drop mode
    return jnp.zeros(n_slots_pad, jnp.int32).at[idx].add(weights, mode="drop")


def compress_slot_runs(slots: np.ndarray, max_fraction: float = 0.75):
    """Run-compress a slot stream: consecutive equal slot ids (spans of
    one trace share series and usually time bin) collapse to one
    (slot, weight) pair — the reduction then consumes the run form,
    shrinking both the H2D transfer and the scatter width. Exact: the
    weighted counts sum to precisely the per-row counts.

    Streams that barely compress (every span in its own bucket — the
    quantile shape) return (slots_i32, None): shipping raw beats paying
    for weights that are all 1. max_fraction is the runs/rows ratio
    above which compression is declined."""
    n = len(slots)
    if n == 0:
        return slots.astype(np.int32), np.zeros(0, np.int32)
    if n > 512:
        # cheap prefix probe before paying the full boundary pass: a
        # stream whose first 256 entries barely repeat won't compress
        head = int(np.count_nonzero(slots[1:257] != slots[:256]))
        if head > 256 * max_fraction:
            return slots, None
    new = np.ones(n, bool)
    new[1:] = slots[1:] != slots[:-1]
    r = int(np.count_nonzero(new))
    if r > n * max_fraction:
        # no copy on decline: the raw stream ships as-is (the i32 cast
        # only pays for itself when there is an H2D transfer to shrink)
        return slots, None
    firsts = np.flatnonzero(new)
    weights = np.diff(np.append(firsts, n)).astype(np.int32)
    return slots[firsts].astype(np.int32), weights


def seg_bincount(slots: np.ndarray, n_slots: int,
                 weights: np.ndarray | None = None) -> np.ndarray:
    """Count occurrences of each slot id in [0, n_slots): the device
    reduction behind `| rate()` / `| quantile_over_time()` — span rows
    carry a combined (series, time-bin[, histogram-bucket]) slot index
    and the counts vector IS the range-vector partial (mergeable by
    addition, so mesh shards psum it). Negative slot ids are dropped
    (masked spans / out-of-window bins). Returns (n_slots,) int64.

    weights: optional per-slot-entry counts (the run-compressed form
    from compress_slot_runs) — the MXU one-hot matmul folds them by
    scaling the reducing vector, the XLA path scatter-adds them.

    Reduction home by backend: the Pallas one-hot-matmul kernel on real
    TPUs, a compiled XLA scatter-add on other COMPILED accelerator
    backends (GPU), and the numpy fold when only a CPU is attached —
    interpret-mode pallas is an interpreter, not a device path (it lost
    3.7x to host numpy on the unselective quantile), and XLA-CPU's
    serial scatter loses ~25x to np.bincount, so on a CPU host the
    device road's win is the ARCHITECTURE (batched buffering + run
    compression + one fold), not the fold's instruction set.
    TEMPO_TPU_NO_PALLAS=1 also forces the numpy fold. Counts are exact
    below 2**24 per slot (f32 accumulation); one dispatch covers at
    most a few million spans, far inside that bound.
    """
    n = slots.shape[0]
    if n == 0:
        # a zero-step grid never runs _init, leaving out_ref undefined
        return np.zeros(n_slots, np.int64)
    s_pad = 128
    while s_pad < n_slots:
        s_pad <<= 1  # pow2 widths bound the jit cache
    n_pad = _BC_ROWS
    while n_pad < n:
        n_pad <<= 1  # so do pow2 row counts: a standing fold's length
        # follows the ingest cut, and every new length was a compile

    def padded():
        """(slots, weights) at n_pad rows; pad rows are dropped slots."""
        s_p = np.full(n_pad, -1, np.int32)
        s_p[:n] = slots.astype(np.int32)
        w_p = np.zeros(n_pad, np.int32)
        w_p[:n] = 1 if weights is None else np.asarray(weights, np.int32)
        return jnp.asarray(s_p), jnp.asarray(w_p)

    on_tpu = _use_pallas() and not _interpret()
    if on_tpu and s_pad <= _BC_MAX_SLOTS:
        out = np.asarray(_bincount_call(*padded(), s_pad, False)).astype(np.int64)
        return out[:n_slots]
    if _use_pallas() and backend.platform() != "cpu":
        # compiled accelerator without Mosaic (or a slot space too wide
        # for the VMEM one-hot tile): native scatter-add
        out = np.asarray(_bincount_xla(*padded(), s_pad)).astype(np.int64)
        return out[:n_slots]
    # CPU-only (or pallas disabled): the exact numpy mirror — negative
    # ids would wrap under jnp indexing; mask then integer scatter-add
    # (np.add.at stays in int64, no float64 weighted-bincount detour)
    live = slots >= 0
    out = np.zeros(n_slots, np.int64)
    if weights is None:
        out[:] = np.bincount(slots[live], minlength=n_slots)[:n_slots]
    else:
        np.add.at(out, slots[live], np.asarray(weights, np.int64)[live])
    return out


# ---------------------------------------------------------------------------
# device decode of the lightweight page encodings (zero-decode read path)
# ---------------------------------------------------------------------------
#
# The lightweight tier (encoding/vtpu/lightweight.py) exists so pages
# can travel to the compute unit STILL ENCODED and decode next to the
# predicate math instead of on the host codec: rle expansion is one
# repeat, dbp is bit-window extraction + a two-limb prefix scan, and
# the byte-shuffle transform inverts as shifts+ors. Everything here is
# one jitted program per shape — compiled by XLA on whatever backend is
# attached, fused with the predicate compare that follows (pallas
# interpret mode is an interpreter, not a device path; see seg_bincount).
# u64 values ride as (hi, lo) u32 limb pairs (no x64 on device); the
# limb adder below is EXACT u64 addition, so device decode is
# bit-identical to the host cumsum.


def _limb_add(a, b):
    """(hi, lo) + (hi, lo) mod 2^64 — associative (it IS u64 addition),
    so lax.associative_scan turns delta streams into absolute values."""
    ah, al = a
    bh, bl = b
    lo = al + bl
    carry = (lo < bl).astype(jnp.uint32)
    return ah + bh + carry, lo


@functools.partial(jax.jit, static_argnames=("n",))
def _dbp_decode_jit(words: jnp.ndarray, first_hi, first_lo, width, n: int):
    """Packed zigzag deltas -> (hi, lo) absolute values, one sub-column.

    words: (W,) uint32 — the packed stream as little-endian u32 words
    (padded with one extra word). width: traced scalar <= 32, so every
    value spans at most two words: two gathers + shifts extract it.
    """
    w = width.astype(jnp.uint32)
    i = jnp.arange(n - 1, dtype=jnp.int32)
    off = i.astype(jnp.uint32) * w
    word_i = (off >> 5).astype(jnp.int32)
    rem = off & jnp.uint32(31)
    lo_w = words[word_i]
    hi_w = words[word_i + 1]
    # shift counts stay < 32 ((32-rem)&31 with the rem==0 case masked
    # out by the where) — no UB shifts on any backend
    hi_part = jnp.where(rem == 0, jnp.uint32(0),
                        hi_w << ((jnp.uint32(32) - rem) & jnp.uint32(31)))
    mask = jnp.where(w >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << (w & jnp.uint32(31))) - jnp.uint32(1))
    z = ((lo_w >> rem) | hi_part) & mask
    # unzigzag in 32-bit two's complement, sign-extended to limbs —
    # equal to the host's u64 unzigzag because |delta| < 2^31 (w <= 32)
    d = (z >> jnp.uint32(1)) ^ (jnp.uint32(0) - (z & jnp.uint32(1)))
    dh = jnp.where((d >> jnp.uint32(31)) != 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    hs = jnp.concatenate([first_hi.reshape(1), dh])
    ls = jnp.concatenate([first_lo.reshape(1), d])
    return jax.lax.associative_scan(_limb_add, (hs, ls))


# Public seam: the compiled query tier's fused metrics program composes
# this decode inline (vmapped over stacked units), so compiled-vs-host
# bit identity on dbp columns reduces to this single definition.
dbp_decode_limbs = _dbp_decode_jit


def dbp_decode_device(page: bytes, dtype: str, shape: tuple) -> np.ndarray:
    """Decode one dbp page ON DEVICE (the host only reinterprets the
    packed bytes as u32 words — no codec work). Bit-identical to
    lightweight.dbp_decode; the jit below is what the fused mesh scan
    inlines next to its predicate compare."""
    from tempo_tpu.encoding.vtpu import lightweight as lw
    from tempo_tpu.util.devicetiming import timed_dispatch

    first, _anchors, widths, streams, n = lw.dbp_parts(page, dtype, shape)
    dt = np.dtype(dtype)
    if n == 0:
        return np.empty(shape, dt)
    k = len(widths)
    out = np.empty((n, k), np.uint64)
    for c in range(k):
        raw = bytes(streams[c])
        pad = (-len(raw)) % 4 + 4  # round to words + one guard word
        words = np.frombuffer(raw + b"\x00" * pad, "<u4")
        # the packed words go in raw: the dispatch seam ships them, so
        # the decode kernel's h2d (the ENCODED size — the whole point of
        # device decode) and d2h (the expanded limbs) are both measured
        hi, lo = timed_dispatch(
            "dbp_decode", _dbp_decode_jit,
            words,
            jnp.uint32(first[c] >> np.uint64(32)),
            jnp.uint32(first[c] & np.uint64(0xFFFFFFFF)),
            jnp.int32(widths[c]),
            n,
        )
        out[:, c] = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(lo)
    return np.ascontiguousarray(out.astype(dt, copy=False).reshape(shape))


@functools.partial(jax.jit, static_argnames=("n",))
def rle_expand_device(values: jnp.ndarray, lengths: jnp.ndarray, n: int) -> jnp.ndarray:
    """Run values + lengths -> (n,) rows: RLE expansion is native on
    device (one repeat — a cumsum + gather under the hood)."""
    return jnp.repeat(values, lengths, total_repeat_length=n)


@functools.partial(jax.jit, static_argnames=("itemsize",))
def unshuffle_device(planes: jnp.ndarray, itemsize: int) -> jnp.ndarray:
    """Invert the blosc-style byte shuffle on device: planes (itemsize,
    N) uint8 — plane j holds byte j of every element — recombine as
    shifts+ors into (N,) uint32/uint64-as-limbs. itemsize <= 4 returns
    uint32. The host then only pays the entropy decode (zstd), and the
    transpose that used to follow it lands next to the predicate math."""
    out = jnp.zeros(planes.shape[1], jnp.uint32)
    for j in range(min(itemsize, 4)):
        out = out | (planes[j].astype(jnp.uint32) << jnp.uint32(8 * j))
    return out


# ---------------------------------------------------------------------------
# fused RLE decode + predicate scan (batched across row-group units)
# ---------------------------------------------------------------------------


def rle_cols_hit(values: jnp.ndarray, lengths: jnp.ndarray,
                 codes: jnp.ndarray, n: int, hit: jnp.ndarray) -> jnp.ndarray:
    """ONE unit's fused RLE decode+predicate: values/lengths (C, R),
    codes (C, K) — the in-set verdict is computed per RUN, expanded
    with one repeat, and AND-folded into `hit` (n,). The single shared
    body behind fused_rle_in_set and the mesh's make_sharded_rle_scan,
    so the two fused-scan homes cannot drift."""
    C, K = codes.shape
    for c in range(C):
        run_hit = jnp.zeros(values.shape[1], bool)
        for k in range(K):
            code = codes[c, k]
            run_hit = run_hit | ((values[c] == code)
                                 & (code != jnp.uint32(0xFFFFFFFF)))
        hit = hit & jnp.repeat(run_hit, lengths[c], total_repeat_length=n)
    return hit


def rle_cols_hit_live(values: jnp.ndarray, lengths: jnp.ndarray,
                      codes: jnp.ndarray, live: jnp.ndarray,
                      n: int, hit: jnp.ndarray) -> jnp.ndarray:
    """rle_cols_hit with a per-column participation flag: `live` (C,)
    bool — a column this query did not constrain contributes accept-all
    instead of its verdict. The multi-query body: one run payload, Q
    different (codes, live) pairs vmapped over it, so N concurrent
    queries with overlapping page sets pay ONE decode+scan launch."""
    C, K = codes.shape
    for c in range(C):
        run_hit = jnp.zeros(values.shape[1], bool)
        for k in range(K):
            code = codes[c, k]
            run_hit = run_hit | ((values[c] == code)
                                 & (code != jnp.uint32(0xFFFFFFFF)))
        row_hit = jnp.repeat(run_hit, lengths[c], total_repeat_length=n)
        hit = hit & (row_hit | ~live[c])
    return hit


@functools.partial(jax.jit, static_argnames=("n",))
def _batched_rle_in_set_jit(values: jnp.ndarray, lengths: jnp.ndarray,
                            codes: jnp.ndarray, live: jnp.ndarray,
                            valid: jnp.ndarray, n: int) -> jnp.ndarray:
    """values/lengths (C, R) — ONE unit's run payload; codes (Q, C, K),
    live (Q, C), valid (n,) -> (Q, n) bool. The single-device batched
    multi-query scan: the payload is traced once and every query's
    verdict reuses it in-register."""

    def one(cd, lv):
        return rle_cols_hit_live(values, lengths, cd, lv, n, valid)

    return jax.vmap(one)(codes, live)


def batched_rle_in_set(values, lengths, codes: np.ndarray, live: np.ndarray,
                       valid: np.ndarray, n: int) -> np.ndarray:
    """Host wrapper for the batched multi-query scan. values/lengths may
    be numpy (shipped, counted h2d) OR device arrays from the resident
    hot tier (counted resident, zero movement) — the batching and the
    hot tier compose: N queries x 1 scan x 0 bytes shipped."""
    from tempo_tpu.util.devicetiming import timed_dispatch

    if isinstance(values, np.ndarray):
        values = values.astype(np.uint32)
    if isinstance(lengths, np.ndarray):
        lengths = lengths.astype(np.int32)
    return np.asarray(timed_dispatch(
        "batched_rle_scan", _batched_rle_in_set_jit,
        values, lengths, codes.astype(np.uint32),
        live.astype(bool), valid.astype(bool), n,
    ))


@functools.partial(jax.jit, static_argnames=("n",))
def _fused_rle_in_set_jit(values: jnp.ndarray, lengths: jnp.ndarray,
                          codes: jnp.ndarray, n: int) -> jnp.ndarray:
    """values/lengths (U, C, R), codes (U, C, K) -> (U, n) bool masks,
    batched over U (block, row-group) units so the dispatch tax is
    paid once per batch, not per row group."""

    def unit(v, l, cd):
        return rle_cols_hit(v, l, cd, n, jnp.ones((n,), bool))

    return jax.vmap(unit)(values, lengths, codes)


def fused_rle_in_set(values: np.ndarray, lengths: np.ndarray,
                     codes: np.ndarray, n: int) -> np.ndarray:
    """Host wrapper for the fused batched scan (the single-device analog
    of parallel/search.make_sharded_rle_scan). Rows past a unit's true
    span count must be masked by the caller's valid mask. Runs under the
    dispatch seam: the run-form h2d bytes vs the (U, n) mask d2h are
    exactly the zero-decode economy the transfer plane exists to show."""
    from tempo_tpu.util.devicetiming import timed_dispatch

    return np.asarray(timed_dispatch(
        "fused_rle_scan", _fused_rle_in_set_jit,
        values.astype(np.uint32),
        lengths.astype(np.int32),
        codes.astype(np.uint32),
        n,
    ))


def u64_range_scan(values: np.ndarray, lo_bound: int, hi_bound: int, n_pad: int) -> jnp.ndarray:
    """lo_bound <= values <= hi_bound over uint64 values, evaluated on
    device as paired uint32 limbs (duration predicates; reference:
    parquetquery IntBetweenPredicate). Rows past len(values) are False."""
    assert n_pad % TILE == 0
    n = values.shape[0]
    hi = np.zeros(n_pad, np.uint32)
    lo = np.zeros(n_pad, np.uint32)
    v = values.astype(np.uint64)
    hi[:n] = (v >> np.uint64(32)).astype(np.uint32)
    lo[:n] = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    bounds = np.array(
        [lo_bound >> 32, lo_bound & 0xFFFFFFFF, hi_bound >> 32, hi_bound & 0xFFFFFFFF],
        dtype=np.uint32,
    )
    if not _use_pallas():
        h, l = jnp.asarray(hi), jnp.asarray(lo)
        ge = (h > bounds[0]) | ((h == bounds[0]) & (l >= bounds[1]))
        le = (h < bounds[2]) | ((h == bounds[2]) & (l <= bounds[3]))
        out = ge & le
    else:
        out = _range_call(jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(bounds), _interpret()).astype(bool)
    if n < n_pad:
        out = out & (jnp.arange(n_pad) < n)  # pad rows are (0,0): mask them
    return out
