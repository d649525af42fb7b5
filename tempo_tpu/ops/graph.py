"""Trace-graph kernels: parent rank-join, self-time, and the
pointer-doubling critical-path accumulation (host + device arms).

The structural TraceQL path already rank-joins parents and closes
ancestry by pointer doubling (traceql/vector.py:853-892); these kernels
lift that machinery into the cross-block trace-graph engine
(tempo_tpu/graph): service-dependency aggregation joins child->parent
spans with the same rank-compress + searchsorted join, and the critical
path accumulates root->span self-time sums with the same log-round
doubling — a gather-per-round kernel, which is why it has a device arm.

Device arithmetic is TWO-LIMB uint32 (the dbp_decode_device idiom,
ops/pallas_kernels.py): durations are uint64 nanoseconds and jax runs
without x64, so the device adds (lo + carry into hi) mirror host uint64
addition exactly — host and device accumulations are bit-identical, the
same contract the metrics bincount paths keep.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from tempo_tpu.util import backend


# ---------------------------------------------------------------------------
# parent rank-join
# ---------------------------------------------------------------------------


def parent_row_join(seg: np.ndarray, span_id: np.ndarray,
                    parent_id: np.ndarray) -> np.ndarray:
    """Row index of each span's parent within its trace segment, -1 when
    the parent id resolves to no span. One rank-compress + searchsorted
    join over the whole batch (the traceql/vector parent_rows idiom);
    duplicate span ids within a trace resolve to the LAST row, matching
    the object engine's dict insert order."""
    n = len(seg)
    if n == 0:
        return np.empty(0, np.int64)
    sidp = (span_id[:, 0].astype(np.uint64) << np.uint64(32)) | span_id[:, 1]
    parp = (parent_id[:, 0].astype(np.uint64) << np.uint64(32)) | parent_id[:, 1]
    uniq = np.unique(np.concatenate([sidp, parp]))
    k = np.int64(len(uniq) + 1)
    skey = seg.astype(np.int64) * k + np.searchsorted(uniq, sidp)
    qkey = seg.astype(np.int64) * k + np.searchsorted(uniq, parp)
    order = np.argsort(skey, kind="stable")
    sk = skey[order]
    p = np.searchsorted(sk, qkey, side="right") - 1
    safe = np.maximum(p, 0)
    ok = (p >= 0) & (sk[safe] == qkey)
    # a self-parenting span (malformed data) would never terminate the
    # path walk; treat it as a root
    out = np.where(ok, order[safe], -1)
    return np.where(out == np.arange(n), -1, out)


# ---------------------------------------------------------------------------
# self time
# ---------------------------------------------------------------------------


def self_times_ns(parent: np.ndarray, duration: np.ndarray) -> np.ndarray:
    """Per-span self time: duration minus the summed durations of direct
    children, clamped at zero (overlapping/async children can exceed the
    parent). uint64 nanoseconds in, uint64 out."""
    n = len(parent)
    dur = duration.astype(np.uint64)
    child_sum = np.zeros(n, np.uint64)
    has = parent >= 0
    np.add.at(child_sum, parent[has], dur[has])
    return np.where(child_sum >= dur, np.uint64(0), dur - child_sum)


# ---------------------------------------------------------------------------
# pointer-doubling root-path accumulation
# ---------------------------------------------------------------------------


def _n_rounds(n: int) -> int:
    """log2(n)+1 doubling rounds cover any simple path; the fixed cap
    also terminates on pathological parent-id cycles (vector.py's >>
    closure argument — extra rounds are no-ops once pointers hit -1)."""
    return max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)


def root_path_sums_host(parent: np.ndarray, self_ns: np.ndarray) -> np.ndarray:
    """acc[i] = self time summed over i and every ancestor of i (uint64
    ns). Invariant after k rounds: acc covers distance 0..2^k-1, p[i] is
    the ancestor at distance 2^k (or -1)."""
    acc = self_ns.astype(np.uint64).copy()
    p = parent.astype(np.int64).copy()
    for _ in range(_n_rounds(len(parent))):
        if not (p >= 0).any():
            break
        safe = np.maximum(p, 0)
        acc = acc + np.where(p >= 0, acc[safe], np.uint64(0))
        p = np.where(p >= 0, p[safe], -1)
    return acc


@partial(jax.jit, static_argnames=("rounds",))
def _root_sums_limbs(parent, hi, lo, rounds: int):
    def body(_, state):
        a_hi, a_lo, p = state
        safe = jnp.maximum(p, 0)
        live = p >= 0
        g_hi = jnp.where(live, a_hi[safe], jnp.uint32(0))
        g_lo = jnp.where(live, a_lo[safe], jnp.uint32(0))
        new_lo = a_lo + g_lo
        carry = (new_lo < a_lo).astype(jnp.uint32)  # uint32 wrap = borrowed bit
        new_hi = a_hi + g_hi + carry
        new_p = jnp.where(live, p[safe], -1)
        return new_hi, new_lo, new_p
    hi, lo, _ = jax.lax.fori_loop(0, rounds, body, (hi, lo, parent))
    return hi, lo


def root_path_sums_device(parent: np.ndarray, self_ns: np.ndarray,
                          bucket_for=None) -> np.ndarray:
    """Device arm of root_path_sums_host: two-limb uint32 adds with
    explicit carry reproduce host uint64 addition bit-exactly. Pads to a
    static bucket shape (XLA recompiles per shape otherwise); padded
    lanes are roots with zero self time, so they contribute nothing."""
    from tempo_tpu.util.devicetiming import timed_dispatch

    n = len(parent)
    if n == 0:
        return np.empty(0, np.uint64)
    pad = bucket_for(n) if bucket_for is not None else n
    s = np.zeros(pad, np.uint64)
    s[:n] = self_ns.astype(np.uint64)
    p = np.full(pad, -1, np.int32)
    p[:n] = parent.astype(np.int32)
    hi = (s >> np.uint64(32)).astype(np.uint32)
    lo = (s & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    # raw host arrays: the timed_dispatch seam ships them itself, so
    # this kernel's h2d bytes + transfer time land in the device
    # data-movement plane
    out_hi, out_lo = timed_dispatch(
        "graph_critical_path", _root_sums_limbs,
        p, hi, lo,
        rounds=_n_rounds(n),
    )
    out = (np.asarray(out_hi).astype(np.uint64) << np.uint64(32)) | np.asarray(out_lo)
    return out[:n]


def device_enabled() -> bool:
    """Whether the graph critical-path kernel runs on device by default
    (same policy knob shape as make_accumulator's TEMPO_TPU_METRICS_DEVICE)."""
    forced = os.environ.get("TEMPO_TPU_GRAPH_DEVICE", "")
    if forced in ("0", "1"):
        return forced == "1"
    return backend.on_accelerator()


# ---------------------------------------------------------------------------
# critical path
# ---------------------------------------------------------------------------


def critical_path(parent: np.ndarray, duration: np.ndarray, seg: np.ndarray,
                  firsts: np.ndarray, device: bool | None = None,
                  bucket_for=None):
    """Per-trace longest self-time path.

    Returns (self_ns, on_path, path_ns):
      self_ns  (N,) uint64 — per-span self time
      on_path  (N,) bool   — span lies on its trace's winning path
      path_ns  (T,) uint64 — each trace's critical-path total

    The winning path is the root-to-span chain maximizing summed self
    time; ties break to the LOWEST row index (deterministic for any
    fixed block row order, which is what shard-count invariance needs —
    blocks are evaluated whole, so grouping blocks into jobs differently
    can never change any per-block path)."""
    n = len(parent)
    n_traces = len(firsts)
    self_ns = self_times_ns(parent, duration)
    if n == 0:
        return self_ns, np.zeros(0, bool), np.empty(0, np.uint64)
    if device is None:
        device = device_enabled()
    if device:
        acc = root_path_sums_device(parent, self_ns, bucket_for=bucket_for)
    else:
        acc = root_path_sums_host(parent, self_ns)
    # segmented argmax: first row reaching the segment max
    mx = np.maximum.reduceat(acc, firsts)
    best = np.flatnonzero(acc == mx[seg])
    leaf = best[np.searchsorted(seg[best], np.arange(n_traces))]
    # mark the winning chain by walking parents (vectorized over traces;
    # iterations = max depth). visited guard terminates parent cycles.
    on_path = np.zeros(n, bool)
    cur = leaf.copy()
    while len(cur):
        fresh = ~on_path[cur]
        cur = cur[fresh]
        if not len(cur):
            break
        on_path[cur] = True
        nxt = parent[cur]
        cur = nxt[nxt >= 0]
    return self_ns, on_path, mx
