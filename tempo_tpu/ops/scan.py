"""Column predicate scans — the search/TraceQL fetch kernels.

Role-equivalent to the reference's parquetquery predicate pushdown
(pkg/parquetquery/predicates.go:13-446 and the iterator trees built in
tempodb/encoding/vparquet/block_traceql.go): evaluate per-span predicates
against columnar data, then roll span-level hits up to trace level.

TPU-first shape: a row group is a set of fixed-length column arrays on
device. String predicates are resolved host-side against the row group's
dictionary (the reference's dictionary-pruning trick,
pkg/parquetquery/predicates.go:446) into a small set of matching codes;
the device kernel is then pure integer compares — eq / in-set / range —
fused by the XLA elementwise fuser into a single pass over the columns.

Trace-level rollup uses segment reductions over the span->trace segment
index that block encoding stores per row group.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

NO_MATCH_CODE = np.uint32(0xFFFFFFFF)  # dictionary code guaranteed unused


def eq(col: jnp.ndarray, value) -> jnp.ndarray:
    return col == jnp.asarray(value, col.dtype)


def in_set(col: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """col (N,) in values (S,) -> (N,) bool. S is small and static.

    An empty candidate set is encoded by passing [NO_MATCH_CODE].
    """
    if values.shape[0] == 0:
        return jnp.zeros(col.shape, bool)
    return jnp.any(col[:, None] == values[None, :].astype(col.dtype), axis=1)


def between(col: jnp.ndarray, lo, hi) -> jnp.ndarray:
    """lo <= col <= hi (inclusive both ends, matching parquetquery's
    IntBetweenPredicate semantics)."""
    c = col
    return (c >= jnp.asarray(lo, c.dtype)) & (c <= jnp.asarray(hi, c.dtype))


def time_overlap(start: jnp.ndarray, end: jnp.ndarray, req_start, req_end) -> jnp.ndarray:
    """Span/trace [start,end] intersects request window [req_start,req_end]."""
    return (end >= jnp.asarray(req_start, end.dtype)) & (start <= jnp.asarray(req_end, start.dtype))


def spans_to_traces_any(span_mask: jnp.ndarray, trace_seg: jnp.ndarray,
                        num_traces: int) -> jnp.ndarray:
    """Trace matches if ANY of its spans matched (tag-search semantics,
    reference: vparquet/block_search.go pipeline)."""
    return jax.ops.segment_max(span_mask.astype(jnp.int32), trace_seg,
                               num_segments=num_traces) > 0


def spans_to_traces_count(span_mask: jnp.ndarray, trace_seg: jnp.ndarray,
                          num_traces: int) -> jnp.ndarray:
    """Matching-span count per trace (for TraceQL `| count() > n`)."""
    return jax.ops.segment_sum(span_mask.astype(jnp.int32), trace_seg,
                               num_segments=num_traces)


def segment_reduce(values: jnp.ndarray, span_mask: jnp.ndarray,
                   trace_seg: jnp.ndarray, num_traces: int, op: str):
    """Per-trace reduction over matching spans' values.

    op in {sum, min, max}: backs TraceQL spanset aggregates
    (avg = sum/count at the call site).
    Non-matching spans contribute the op identity.
    """
    v = values.astype(jnp.float32)
    if op == "sum":
        v = jnp.where(span_mask, v, 0.0)
        return jax.ops.segment_sum(v, trace_seg, num_segments=num_traces)
    if op == "min":
        v = jnp.where(span_mask, v, jnp.inf)
        return jax.ops.segment_min(v, trace_seg, num_segments=num_traces)
    if op == "max":
        v = jnp.where(span_mask, v, -jnp.inf)
        return jax.ops.segment_max(v, trace_seg, num_segments=num_traces)
    raise ValueError(f"unknown op {op!r}")


def find_ids(trace_limbs: jnp.ndarray, target: jnp.ndarray) -> jnp.ndarray:
    """Rows whose 128-bit trace ID equals target (4,) -> (N,) bool.

    The trace-by-ID row-group scan after bloom says 'maybe'
    (reference: vparquet/block_findtracebyid.go binary search; here a
    vectorized compare is cheaper than branching on device).
    """
    return jnp.all(trace_limbs == target[None, :].astype(trace_limbs.dtype), axis=1)


# ---------------------------------------------------------------------------
# run-space predicate evaluation (host numpy)
# ---------------------------------------------------------------------------
#
# The row-space scans above compare one value per ROW; for RLE pages the
# same predicates compare one value per RUN — cost proportional to the
# encoded form, not the row count — and the boolean verdict expands with
# a single repeat (which is also the shape the device expansion kernel
# wants, ops/pallas_kernels.rle_expand_device). These are the eq /
# in_set / between of the zero-decode read path.


def in_set_runs(run_values: np.ndarray, codes: np.ndarray,
                invert: bool = False) -> np.ndarray:
    """Per-RUN in-set verdict: (n_runs,) bool. Row semantics match
    np.isin(expanded, codes, invert=...) exactly — every row of a run
    holds the run's value, so the run verdict IS the row verdict."""
    return np.isin(run_values, codes, invert=invert)


def between_runs(run_values: np.ndarray, lo, hi) -> np.ndarray:
    """Per-run lo <= v <= hi (inclusive both ends, like `between`)."""
    v = run_values
    return (v >= np.asarray(lo, v.dtype)) & (v <= np.asarray(hi, v.dtype))


def expand_run_mask(run_mask: np.ndarray, run_lengths: np.ndarray,
                    n: int) -> np.ndarray:
    """Run verdicts -> (n,) row mask. A plain repeat: one bool per row,
    never the VALUES — unselected runs are never expanded."""
    if len(run_mask) == 0:
        return np.zeros(n, bool)
    out = np.repeat(run_mask, run_lengths)
    assert len(out) == n, (len(out), n)
    return out


def runs_firsts_seg(run_lengths: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(firsts, seg) row segmentation implied by run lengths: firsts[r]
    = first row of run r, seg[i] = run of row i. For an RLE trace-ID
    column the runs ARE the traces (trace-sorted rows make equal IDs
    maximal stretches), so this replaces trace_segmentation without
    decoding a single ID."""
    lens = np.asarray(run_lengths, np.int64)
    firsts = np.zeros(len(lens), np.int64)
    if len(lens):
        np.cumsum(lens[:-1], out=firsts[1:])
    seg = np.repeat(np.arange(len(lens), dtype=np.int64), lens)
    return firsts, seg


# ---------------------------------------------------------------------------
# resident-tier fused scans (device-resident COMPRESSED pages)
# ---------------------------------------------------------------------------
#
# The hot tier (encoding/vtpu/colcache.DeviceTier) parks encoded page
# forms — rle runs, dct dictionary+indices, dbp packed words — as device
# arrays. A scan that hits the tier never touches fetch/decode/h2d: the
# kernels below fuse the (bit-exact) device decode into the predicate
# compare, and the only bytes that ship per query are the predicate's
# code set / bounds (a few hundred bytes). Run semantics mirror the
# run-space host helpers above EXACTLY — code-set padding repeats a real
# code instead of a sentinel, so device membership is np.isin
# bit-for-bit even against pathological column values.
#
# Each program takes a TUPLE of pages of one shape bucket and answers
# them in one dispatch, (k, n) bool: a dispatch costs milliseconds of
# waiting for the interpreter around microseconds of device, so a scan
# over a block's row groups pays it once a bucket, not once a page.


@functools.partial(jax.jit, static_argnames=("n", "invert"))
def _rle_in_set_resident_jit(values, lengths, codes, n: int, invert: bool):
    """values/lengths k x (R,) resident; codes (K,) shipped -> (k, n) bool."""
    def page(v, l):
        run_hit = jnp.any(v[:, None] == codes[None, :].astype(v.dtype), axis=1)
        if invert:
            run_hit = ~run_hit
        return jnp.repeat(run_hit, l, total_repeat_length=n)

    return jax.vmap(page)(jnp.stack(values), jnp.stack(lengths))


@functools.partial(jax.jit, static_argnames=("n",))
def _rle_between_resident_jit(values, lengths, lo, hi, n: int):
    def page(v, l):
        run_hit = (v >= lo.astype(v.dtype)) & (v <= hi.astype(v.dtype))
        return jnp.repeat(run_hit, l, total_repeat_length=n)

    return jax.vmap(page)(jnp.stack(values), jnp.stack(lengths))


@functools.partial(jax.jit, static_argnames=("invert",))
def _dct_in_set_resident_jit(dvals, idx, codes, invert: bool):
    """dvals k x (V,) page dictionaries + idx k x (n,) resident -> (k, n)
    bool: the verdict is computed once per dictionary ENTRY and gathered
    by the resident index — the dct analog of the per-run verdict."""
    def page(dv, ix):
        hit = jnp.any(dv[:, None] == codes[None, :].astype(dv.dtype), axis=1)
        if invert:
            hit = ~hit
        return hit[ix]

    return jax.vmap(page)(jnp.stack(dvals), jnp.stack(idx))


@jax.jit
def _dct_between_resident_jit(dvals, idx, lo, hi):
    def page(dv, ix):
        hit = (dv >= lo.astype(dv.dtype)) & (dv <= hi.astype(dv.dtype))
        return hit[ix]

    return jax.vmap(page)(jnp.stack(dvals), jnp.stack(idx))


@functools.partial(jax.jit, static_argnames=("n",))
def _dbp_between_resident_jit(words, heads, bounds, n: int):
    """Resident packed-delta words k x (W,) -> (k, n) range verdict,
    decode fused in: the same _dbp_decode_jit the shipped path uses
    (bit-identical limbs) followed by the two-limb u64 compare. heads
    (k, 3) uint32: each page's own [first_hi, first_lo, width]; bounds
    (4,) uint32 = [lo_hi, lo_lo, hi_hi, hi_lo]. The pages run one after
    another (lax.map): the decode's scan vmapped over 8 or 16 pages
    takes the TPU's compiler 10 and 35 s, mapped 1-3 s whatever k."""
    from tempo_tpu.ops.pallas_kernels import _dbp_decode_jit

    def page(args):
        w, head = args
        h, l = _dbp_decode_jit(w, head[0], head[1], head[2].astype(jnp.int32), n)
        ge = (h > bounds[0]) | ((h == bounds[0]) & (l >= bounds[1]))
        le = (h < bounds[2]) | ((h == bounds[2]) & (l <= bounds[3]))
        return ge & le

    return jax.lax.map(page, (jnp.stack(words), heads))


def pad_pow2(a: np.ndarray, fill) -> np.ndarray:
    """1-D `a` extended with `fill` to the next power of two (an empty
    array to one element): the shape rule of code sets and of resident
    payloads, so that a jitted scan compiles once a bucket and not once
    a length."""
    k = 1 << max(0, a.size - 1).bit_length()
    if k == a.size:
        return a
    return np.concatenate([a, np.full(k - a.size, fill, a.dtype)])


def pad_codes_u32(codes: np.ndarray) -> np.ndarray:
    """Pow2-pad a code set by REPEATING its first code (bounds the jit
    cache without changing membership — unlike a sentinel pad, which
    would alter verdicts for columns that contain the sentinel). Public:
    the compiled query tier pads its per-unit code sets with the same
    rule, so its membership verdicts inherit this path's exactness
    argument verbatim."""
    codes = np.asarray(codes).astype(np.uint32, copy=False).reshape(-1)
    if codes.size == 0:
        codes = np.array([NO_MATCH_CODE], np.uint32)
    return pad_pow2(codes, codes[0])


_pad_codes_u32 = pad_codes_u32  # compat alias for older call sites


# pages answered by one resident dispatch at most: bounds the (k, n)
# intermediates of the decode, and the programs of a bucket to five sizes
_RESIDENT_GROUP = 16


def _resident_scan(out: list, entries, codec: str, names, dispatch) -> None:
    """Answer every `codec` page of `entries` into `out`, one dispatch a
    group: pages grouped by what a resident program is compiled on (row
    count and the shapes of the arrays `names`), at most _RESIDENT_GROUP
    a group, each group extended to a power of two by repeating its last
    page, so that a bucket compiles once a size class and not once a
    count. dispatch(members, *stacks) -> (k, n) device verdict, one
    tuple of k arrays for each of `names`."""
    groups: dict = {}
    for pos, res in enumerate(entries):
        if res.codec == codec and int(res.meta["n"]) > 0:
            key = (int(res.meta["n"]),
                   tuple(res.arrays[nm].shape for nm in names))
            groups.setdefault(key, []).append(pos)
    for positions in groups.values():
        for at in range(0, len(positions), _RESIDENT_GROUP):
            chunk = positions[at:at + _RESIDENT_GROUP]
            members = [entries[pos] for pos in chunk]
            members += members[-1:] * ((1 << (len(chunk) - 1).bit_length())
                                       - len(chunk))
            mask = np.asarray(dispatch(
                members, *(tuple(m.arrays[nm] for m in members) for nm in names)))
            for row, pos in enumerate(chunk):
                out[pos] = mask[row]


def resident_in_set_masks(entries, codes: np.ndarray,
                          invert: bool = False) -> list:
    """Row masks for `column in codes`, one for each resident entry
    (colcache._Resident duck type: .codec/.arrays/.meta), None where the
    resident form cannot answer (dbp). One dispatch a shape bucket under
    the timing seam: the resident arrays count as `resident`, never h2d
    — only the code set ships."""
    from tempo_tpu.util.devicetiming import timed_dispatch

    codes = _pad_codes_u32(codes)
    out: list = [np.zeros(0, bool) if res.codec in ("rle", "dct")
                 and int(res.meta["n"]) == 0 else None for res in entries]
    _resident_scan(
        out, entries, "rle", ("values", "lengths"),
        lambda ms, values, lengths: timed_dispatch(
            "resident_rle_scan", _rle_in_set_resident_jit, values, lengths,
            codes, int(ms[0].meta["n"]), bool(invert)))
    _resident_scan(
        out, entries, "dct", ("values", "idx"),
        lambda ms, dvals, idx: timed_dispatch(
            "resident_dct_scan", _dct_in_set_resident_jit, dvals, idx,
            codes, bool(invert)))
    return out


def resident_range_masks(entries, lo, hi) -> list:
    """Row masks for lo <= column <= hi, one for each resident entry;
    dbp pages answer by fusing the device delta-decode into the compare."""
    from tempo_tpu.util.devicetiming import timed_dispatch

    out: list = [np.zeros(0, bool) if int(res.meta["n"]) == 0 else None
                 for res in entries]
    if any(res.codec != "dbp" for res in entries):
        lo32, hi32 = np.uint32(lo), np.uint32(hi)
        _resident_scan(
            out, entries, "rle", ("values", "lengths"),
            lambda ms, values, lengths: timed_dispatch(
                "resident_rle_scan", _rle_between_resident_jit, values,
                lengths, lo32, hi32, int(ms[0].meta["n"])))
        _resident_scan(
            out, entries, "dct", ("values", "idx"),
            lambda ms, dvals, idx: timed_dispatch(
                "resident_dct_scan", _dct_between_resident_jit, dvals, idx,
                lo32, hi32))
    lo64, hi64 = int(lo), int(hi)
    bounds = np.array(
        [lo64 >> 32, lo64 & 0xFFFFFFFF, hi64 >> 32, hi64 & 0xFFFFFFFF],
        np.uint32)

    def dbp(ms, words):
        heads = np.array(
            [[int(m.meta["first"]) >> 32, int(m.meta["first"]) & 0xFFFFFFFF,
              int(m.meta["width"])] for m in ms], np.uint32)
        return timed_dispatch(
            "resident_dbp_scan", _dbp_between_resident_jit, words, heads,
            bounds, int(ms[0].meta["n"]))

    _resident_scan(out, entries, "dbp", ("words",), dbp)
    return out


def resident_in_set_mask(res, codes: np.ndarray,
                         invert: bool = False) -> np.ndarray | None:
    """resident_in_set_masks of one page."""
    return resident_in_set_masks([res], codes, invert=invert)[0]


def resident_range_mask(res, lo, hi) -> np.ndarray | None:
    """resident_range_masks of one page."""
    return resident_range_masks([res], lo, hi)[0]


# ---------------------------------------------------------------------------
# host helpers: dictionary-side string predicate resolution
# ---------------------------------------------------------------------------


def dict_codes_matching(entries: list, predicate) -> np.ndarray:
    """Apply a python string predicate to dictionary entries -> uint32 codes.

    Regex/substring/prefix never run on device — only over the (small)
    dictionary, exactly like the reference prunes pages by dictionary
    before scanning (pkg/parquetquery/predicates.go:446).
    Returns [NO_MATCH_CODE] when nothing matches so in_set stays static.
    """
    codes = [i for i, e in enumerate(entries) if predicate(e)]
    if not codes:
        return np.array([NO_MATCH_CODE], dtype=np.uint32)
    return np.asarray(codes, dtype=np.uint32)
