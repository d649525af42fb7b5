"""Block writer: trace-sorted span batches -> a complete vtpu1 block.

Reference analog: tempodb/encoding/vparquet/create.go (streamingBlock:
append rows, flush row groups by size, bloom from IDs, meta last).
Device kernels do the data-plane math: bloom build (ops.bloom), HLL
distinct estimate (ops.sketch), min/max ID (ops.merge).

Write order matters for crash safety: data pages are appended first,
then bloom/index/dict, then meta.json LAST — a block without meta is
invisible and gets garbage-collected, like the reference's write path
(tempodb/tempodb.go WriteBlock).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from tempo_tpu.backend.base import (
    BlockMeta,
    ColumnIndexName,
    DataName,
    DictionaryName,
    TypedBackend,
    bloom_name,
)
from functools import lru_cache

from tempo_tpu.encoding.common import BlockConfig
from tempo_tpu.encoding.vtpu import format as fmt
from tempo_tpu.model.columnar import SpanBatch
from tempo_tpu.ops import bloom, sketch


def _pad_ids(ids: np.ndarray, pad: int) -> tuple[np.ndarray, np.ndarray]:
    """Zero-pad trace-ID limbs to a shape bucket + validity mask (static
    shapes keep XLA compiles bounded, SURVEY.md 7.4)."""
    ids_p = np.zeros((pad, ids.shape[1]), ids.dtype)
    ids_p[: len(ids)] = ids
    valid = np.zeros(pad, bool)
    valid[: len(ids)] = True
    return ids_p, valid


def _unpack_sketch(packed: np.ndarray, plan: "bloom.BloomPlan") -> tuple[np.ndarray, int]:
    """Split the one-fetch packed u32 array back into bloom shard words
    + the bitcast HLL distinct estimate."""
    words = packed[:-1].reshape(plan.n_shards, -1)
    est = int(float(packed[-1:].view(np.float32)[0]))
    return words, est


@lru_cache(maxsize=64)
def _accum_step(plan: "bloom.BloomPlan", hp: "sketch.HLLPlan"):
    """Incremental sketch update with donated device-resident
    accumulators (bloom OR and HLL max are associative, so per-batch
    partials compose exactly)."""
    import jax

    def block_sketch_accumulate(words, regs, ids, valid):
        words = words | bloom.build(ids, plan, valid=valid)
        regs = sketch.hll_update(regs, ids, hp, valid=valid)
        return words, regs

    return jax.jit(block_sketch_accumulate, donate_argnums=(0, 1))


@lru_cache(maxsize=64)
def _accum_finish(hp: "sketch.HLLPlan"):
    import jax

    @jax.jit
    def block_sketch_finish(words, regs):
        est = sketch.hll_estimate(regs, hp)
        est_bits = jax.lax.bitcast_convert_type(est.astype(jnp.float32), jnp.uint32)
        return jnp.concatenate([words.reshape(-1), est_bits[None]])

    return block_sketch_finish


class DeviceSketchAccumulator:
    """Single-device analog of the sharded compactor's sketch plane
    (compactor._ShardedTileMerger): bloom words + HLL registers live ON
    DEVICE across merged batches. Buffered IDs ship asynchronously every
    _FLUSH_IDS traces, overlapping the host's column encode, so for
    production-sized jobs the block writer's final fetch pays one small
    D2H instead of shipping all IDs and building everything in a
    blocking end-of-job dispatch (its cost on the current machine is
    not measured, PERF.md). Jobs under _FLUSH_IDS traces take a
    single dispatch at finish() — same cost as the unbuffered path, and
    far below the padding such small inputs would otherwise waste.

    The bloom plan is sized from the bucketed SUM of input object counts
    — an upper bound on output traces, since compaction only dedupes —
    exactly like the sharded path: the plan is a static jit arg, and
    overshoot only lowers the FP rate below budget (the reference also
    sizes its sharded bloom from an object-count estimate,
    tempodb/encoding/common/bloom.go:20-90).
    """

    def __init__(self, cfg: BlockConfig, est_traces: int):
        self.plan = bloom.plan(
            cfg.bucket_for(max(1, est_traces)), cfg.bloom_fp, cfg.bloom_shard_size_bytes
        )
        self.hp = sketch.HLLPlan(cfg.hll_precision)
        self._bucket = cfg.bucket_for
        self._words = jnp.zeros((self.plan.n_shards, self.plan.words_per_shard), jnp.uint32)
        self._regs = sketch.hll_init(self.hp)
        self._step = _accum_step(self.plan, self.hp)
        self._pending: list[np.ndarray] = []
        self._n_pending = 0

    # ids buffered host-side until one dispatch is worth its padding +
    # launch (merged batches carry ~1k traces each; dispatching every
    # batch wastes bucket padding and queue occupancy)
    _FLUSH_IDS = 8192

    def update(self, batch: SpanBatch) -> None:
        if batch.num_spans == 0:
            return
        firsts, _ = batch.trace_boundaries()
        self.update_ids(batch.cols["trace_id"][firsts])

    def update_ids(self, ids: np.ndarray) -> None:
        """Feed unique trace-ID limbs directly — the zero-decode
        relocation path has the decoded ID column but never builds a
        SpanBatch (bloom OR / HLL max are idempotent, so IDs repeated
        across updates cannot skew the sketches)."""
        if len(ids) == 0:
            return
        self._pending.append(ids)
        self._n_pending += len(ids)
        if self._n_pending >= self._FLUSH_IDS:
            self._flush()

    def _flush(self) -> None:
        if not self._pending:
            return
        from tempo_tpu.util.devicetiming import count_transfer

        ids = self._pending[0] if len(self._pending) == 1 else np.concatenate(self._pending)
        self._pending, self._n_pending = [], 0
        ids_p, valid = _pad_ids(ids, self._bucket(len(ids)))
        # async dispatch: no sync here — the donated accumulators stay on
        # device and the host goes straight back to encoding columns.
        # Movement is accounted WITHOUT the blocking timed_dispatch seam
        # (a per-flush block_until_ready would serialize exactly the
        # overlap this accumulator exists for).
        count_transfer("sketch_accumulate",
                       h2d=ids_p.nbytes + valid.nbytes)
        self._words, self._regs = self._step(
            self._words, self._regs, jnp.asarray(ids_p), jnp.asarray(valid)
        )

    def finish(self) -> dict:
        from tempo_tpu.util.devicetiming import count_transfer

        self._flush()
        packed = np.asarray(_accum_finish(self.hp)(self._words, self._regs))
        # the one D2H sync of the whole accumulation
        count_transfer("sketch_finish", d2h=packed.nbytes)
        words, est = _unpack_sketch(packed, self.plan)
        return {"bloom_plan": self.plan, "bloom_words": words, "est_distinct": est}


@lru_cache(maxsize=64)
def _sketch_step(plan: "bloom.BloomPlan", hp: "sketch.HLLPlan"):
    """One fused device call building bloom words + HLL registers + the
    distinct estimate — a single dispatch per block write, fetched with
    a single D2H sync (one sync per block write by design; what a sync
    costs on the current machine is not measured)."""
    import jax

    @jax.jit
    def block_sketch_build(ids, valid):
        words = bloom.build(ids, plan, valid=valid)
        regs = sketch.hll_update(sketch.hll_init(hp), ids, hp, valid=valid)
        est = sketch.hll_estimate(regs, hp)
        # pack everything into ONE flat u32 array: device_get fetches
        # each output array separately, and the block writer is meant to
        # sync exactly once
        est_bits = jax.lax.bitcast_convert_type(est.astype(jnp.float32), jnp.uint32)
        return jnp.concatenate([words.reshape(-1), est_bits[None]])

    return block_sketch_build


class BlockWriter:
    """Incremental block writer: append encoded row groups (from
    SpanBatches) AND relocated row groups (raw compressed pages moved
    verbatim from an input block), then finish() writes bloom/index/
    dict/meta in the crash-safe order.

    This is write_block() split open so the compactor's zero-decode fast
    path can interleave the two append kinds in global trace-ID order;
    write_block() below remains the one-shot wrapper every other caller
    uses. Counters (pages_copied_verbatim / pages_reencoded and their
    byte twins) make the copy-vs-encode split observable in compaction
    metrics.
    """

    def __init__(self, tenant: str, backend: TypedBackend, cfg: BlockConfig,
                 block_id: str | None = None, compaction_level: int = 0,
                 dictionary=None, collect_ids: bool = False):
        from tempo_tpu.util.xla_cache import ensure_persistent_cache

        ensure_persistent_cache()  # sketch kernels are jitted per plan
        self.backend = backend
        self.cfg = cfg
        self.meta = BlockMeta(tenant_id=tenant, version=cfg.version,
                              compaction_level=compaction_level)
        if block_id:
            self.meta.block_id = block_id
        self.index = fmt.BlockIndex()
        self.offset = 0
        self.dictionary = dictionary
        self.collect_ids = collect_ids
        self._unique_ids: list[np.ndarray] = []
        self._n_traces = 0
        self._n_spans = 0
        self._start_s: int | None = None
        self._end_s = 0
        self._min_id: str | None = None
        self._max_id: str | None = None
        # copy-vs-encode accounting
        self.pages_copied_verbatim = 0
        self.pages_reencoded = 0
        self.bytes_copied_verbatim = 0
        self.bytes_reencoded = 0
        self.row_groups_relocated = 0
        # step-partial downsampling tier (standing/rules.py): rules this
        # writer materializes per row group; () disables
        from tempo_tpu.standing import rules as sp_rules

        self.step_rules = sp_rules.block_rules(cfg)

    # ------------------------------------------------------------------
    def _add_rg(self, rg: fmt.RowGroupMeta) -> None:
        self.index.row_groups.append(rg)
        self._n_spans += rg.n_spans
        self._start_s = rg.start_s if self._start_s is None else min(self._start_s, rg.start_s)
        self._end_s = max(self._end_s, rg.end_s)
        self._min_id = rg.min_id if self._min_id is None else min(self._min_id, rg.min_id)
        self._max_id = rg.max_id if self._max_id is None else max(self._max_id, rg.max_id)

    def append_batch(self, batch: SpanBatch) -> None:
        """Encode a trace-sorted SpanBatch as one or more row groups."""
        if batch.num_spans == 0:
            return
        if self.dictionary is None:
            self.dictionary = batch.dictionary
        elif batch.dictionary is not self.dictionary:
            raise ValueError("all batches of one block must share a dictionary")
        firsts, _ = batch.trace_boundaries()
        self._n_traces += len(firsts)
        if self.collect_ids:
            self._unique_ids.append(batch.cols["trace_id"][firsts])
        partials = self._batch_partials(batch)
        for lo, hi in fmt.row_group_slices(batch, self.cfg.row_group_spans):
            payload, rg = fmt.serialize_row_group(batch, lo, hi, self.offset, self.cfg.codec)
            self.backend.append_named(self.meta, DataName, payload)
            self.offset += len(payload)
            self.pages_reencoded += len(rg.pages)
            self.bytes_reencoded += len(payload)
            self._write_partials(rg, partials, lo, hi)
            self._add_rg(rg)

    def _batch_partials(self, batch) -> list:
        """Per-row (series, abs-bin, bucket) decomposition of the batch
        under every configured downsampling rule — computed once per
        batch, sliced per row group. A rule that can't describe this
        batch exactly (series over ceiling, wild timestamps) yields no
        partial: readers fall back to the span path, never a wrong one."""
        out = []
        for rule in self.step_rules:
            try:
                from tempo_tpu.standing import rules as sp_rules

                bp = sp_rules.batch_partial(batch, self.dictionary, rule)
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "step-partial rule %s skipped for this batch", rule.name)
                bp = None
            if bp is not None:
                out.append(bp)
        return out

    def _write_partials(self, rg: fmt.RowGroupMeta, partials: list,
                        lo: int, hi: int) -> None:
        """Append this row group's step-partial tables as ordinary pages
        right after its column pages (contiguous, so relocation's single
        ranged read and the coalesced span reads both cover them)."""
        from tempo_tpu.encoding.vtpu import codec as codec_mod
        from tempo_tpu.standing import rules as sp_rules

        for bp in partials:
            table = bp.rg_table(lo, hi)
            if table is None:
                continue
            keys, arr = table
            page, crc = codec_mod.encode(arr, codec_mod.resolve_codec(self.cfg.codec))
            name = sp_rules.page_name(bp.rule.name)
            rg.pages[name] = fmt.PageMeta(
                offset=self.offset, length=len(page), dtype=arr.dtype.str,
                shape=tuple(arr.shape), codec=codec_mod.resolve_codec(self.cfg.codec),
                crc=crc,
            )
            rg.partials[bp.rule.name] = sp_rules.partial_meta(bp.rule, keys)
            self.backend.append_named(self.meta, DataName, page)
            self.offset += len(page)
            self.pages_reencoded += 1
            self.bytes_reencoded += len(page)
            sp_rules.partial_pages_written_total.inc()

    def append_relocated(self, rg: fmt.RowGroupMeta, raw_pages: dict,
                         reencode: dict, min_id: str, max_id: str,
                         n_traces: int, decoded: dict | None = None) -> None:
        """Relocate one input row group: copy its compressed pages
        verbatim — per-page crc/dtype/shape/codec preserved, nothing
        recomputed but the page-index offsets — re-encoding only the
        columns in `reencode` (dictionary-coded columns under a
        non-identity remap: the lazy column gather).

        raw_pages: column -> compressed page bytes from the source block
        (fmt.read_row_group_pages). min_id/max_id/n_traces come from the
        decoded trace-ID column the relocation guard already paid for,
        so stale input index metadata cannot propagate.

        Zone maps: remapped columns recompute stats from the remapped
        arrays (input code sets are in the OLD dictionary's code space —
        copying them would make pruning unsound); verbatim columns copy
        the input stats when present, else decode from the page bytes
        already in hand (legacy stats-less inputs gain zone maps on
        their first compaction; no extra backend read either way).

        Lightweight-encoding upgrade, same economics as the zone-map
        back-fill: columns whose arrays are ALREADY decoded — remapped
        columns, stats back-fills, and `decoded` (arrays the caller paid
        for anyway, e.g. the relocation guard's trace-ID column) — are
        re-encoded when the write-time chooser picks a lightweight codec
        their current page lacks. Pages that are not in hand decoded
        stay verbatim: the zero-decode fast path never decodes a page
        just to change its codec.
        """
        from tempo_tpu.encoding.vtpu import codec as codec_mod

        reencode = dict(reencode)
        stat_arrays: dict = {}
        copied_stats: dict = {}
        upgradable: dict = dict(decoded or {})
        for name in fmt.STATS_NUMERIC + fmt.STATS_CODES:
            if name not in rg.pages:
                continue
            arr = reencode.get(name)
            if arr is not None:
                stat_arrays[name] = arr
            elif name in rg.stats:
                copied_stats[name] = rg.stats[name]
            else:
                stat_arrays[name] = fmt.decode_page(raw_pages[name], rg.pages[name])
                upgradable[name] = stat_arrays[name]
        if rg.stats.get("root_first"):
            # sound to copy: relocation preserves row order and neither
            # the trace grouping nor the (non-dictionary) parent ids
            # change under a remap
            copied_stats["root_first"] = True
        elif not rg.stats:
            # fully-legacy input (no stats at all): back-fill root_first
            # from the pages in hand, like every other stat — the ID
            # column is usually already decoded (the relocation guard),
            # only the parent page pays a one-time decode here
            tid = upgradable.get("trace_id")
            if tid is None and "trace_id" in rg.pages:
                tid = fmt.decode_page(raw_pages["trace_id"], rg.pages["trace_id"])
            if tid is not None and "parent_span_id" in rg.pages:
                stat_arrays["trace_id"] = tid
                stat_arrays["parent_span_id"] = fmt.decode_page(
                    raw_pages["parent_span_id"], rg.pages["parent_span_id"])
        stats = {**fmt.compute_stats(stat_arrays), **copied_stats}

        chosen_codecs: dict[str, str] = {}
        for name, arr in upgradable.items():
            if name in reencode or name not in rg.pages:
                continue
            if rg.pages[name].codec in codec_mod.LIGHTWEIGHT_CODECS:
                continue  # already on the lightweight tier: copy verbatim
            chosen = codec_mod.choose_codec(name, arr, self.cfg.codec)
            if chosen in codec_mod.LIGHTWEIGHT_CODECS:
                reencode[name] = arr
                chosen_codecs[name] = chosen  # don't re-run the probe below

        payload = bytearray()
        pages: dict[str, fmt.PageMeta] = {}
        for name, pm in rg.pages.items():
            arr = reencode.get(name)
            if arr is not None:
                chosen = chosen_codecs.get(name) or codec_mod.choose_codec(
                    name, arr, self.cfg.codec)
                page, crc = codec_mod.encode(arr, chosen)
                pages[name] = fmt.PageMeta(
                    offset=self.offset + len(payload), length=len(page),
                    dtype=arr.dtype.str, shape=tuple(arr.shape),
                    codec=chosen, crc=crc,
                )
                self.pages_reencoded += 1
                self.bytes_reencoded += len(page)
            else:
                page = raw_pages[name]
                pages[name] = fmt.PageMeta(
                    offset=self.offset + len(payload), length=pm.length,
                    dtype=pm.dtype, shape=pm.shape, codec=pm.codec, crc=pm.crc,
                )
                self.pages_copied_verbatim += 1
                self.bytes_copied_verbatim += len(page)
            payload.extend(page)
        self.backend.append_named(self.meta, DataName, bytes(payload))
        self.offset += len(payload)
        self._n_traces += n_traces
        self.row_groups_relocated += 1
        self._add_rg(fmt.RowGroupMeta(
            n_spans=rg.n_spans, n_attrs=rg.n_attrs, min_id=min_id,
            max_id=max_id, start_s=rg.start_s, end_s=rg.end_s,
            n_traces=n_traces, pages=pages, stats=stats,
            # step partials relocate with their rows: series keys are
            # strings (dictionary-independent), the count page moved
            # verbatim above, and relocation never drops/dedupes spans —
            # so the copied tables still describe exactly these rows
            partials=dict(rg.partials),
        ))

    # ------------------------------------------------------------------
    def finish(self, sketches=None) -> BlockMeta | None:
        """Write bloom/index/dictionary/meta (meta LAST: a block without
        meta is invisible and gets garbage-collected). sketches:
        zero-arg callable yielding device-accumulated block sketches;
        without it the writer builds them from the trace IDs collected
        by append_batch (requires collect_ids=True)."""
        if self._n_traces == 0:
            return None
        meta, cfg, backend = self.meta, self.cfg, self.backend
        if sketches is not None:
            # index + dictionary writes first: when the device is still
            # draining async sketch updates (large jobs), every host-side
            # byte written here is overlap for free
            backend.write_named(meta, ColumnIndexName, self.index.to_bytes())
            backend.write_named(meta, DictionaryName, fmt.serialize_dictionary(self.dictionary))
            sk = sketches()
            plan = sk["bloom_plan"]
            words = np.asarray(sk["bloom_words"])
            est = int(sk["est_distinct"])
        else:
            ids = np.concatenate(self._unique_ids)
            # pad IDs to a shape bucket AND size the bloom plan from the
            # bucket: both the input shape and the plan are static to XLA,
            # so bucketing both means the kernels compile once per bucket
            # instead of once per distinct trace count (SURVEY.md 7.4
            # static shapes; a fresh XLA compile per block would dwarf the
            # kernel itself). The slightly larger plan only lowers the FP
            # rate below budget.
            pad = cfg.bucket_for(len(ids))
            plan = bloom.plan(pad, cfg.bloom_fp, cfg.bloom_shard_size_bytes)
            ids_p, valid = _pad_ids(ids, pad)
            hp = sketch.HLLPlan(cfg.hll_precision)
            # the dispatch is async: the device builds sketches while the
            # host writes index + dictionary; then ONE fetch of the packed
            # array pays a single round trip (bytes accounted to the
            # transfer plane without a blocking sync)
            from tempo_tpu.util.devicetiming import count_transfer

            out = _sketch_step(plan, hp)(jnp.asarray(ids_p), jnp.asarray(valid))
            count_transfer("block_sketch", h2d=ids_p.nbytes + valid.nbytes)
            backend.write_named(meta, ColumnIndexName, self.index.to_bytes())
            backend.write_named(meta, DictionaryName, fmt.serialize_dictionary(self.dictionary))
            packed = np.asarray(out)
            count_transfer("block_sketch", d2h=packed.nbytes)
            words, est = _unpack_sketch(packed, plan)
        for s in range(plan.n_shards):
            backend.write_named(meta, bloom_name(s), bloom.shard_to_bytes(words[s]))

        meta.start_time = int(self._start_s or 0)
        meta.end_time = int(self._end_s)
        meta.total_objects = int(self._n_traces)
        meta.total_spans = int(self._n_spans)
        meta.size_bytes = self.offset
        meta.min_id = self._min_id
        meta.max_id = self._max_id
        meta.total_records = len(self.index.row_groups)
        meta.bloom_shards = plan.n_shards
        meta.bloom_bits_per_shard = plan.bits_per_shard
        meta.bloom_k = plan.k
        meta.hll_precision = cfg.hll_precision
        meta.est_distinct_traces = est
        backend.write_block_meta(meta)  # last: makes the block visible
        return meta


def write_block(
    batches,
    tenant: str,
    backend: TypedBackend,
    cfg: BlockConfig,
    block_id: str | None = None,
    compaction_level: int = 0,
    sketches=None,
) -> BlockMeta | None:
    """Write one block from an iterable of trace-sorted SpanBatches in
    nondecreasing trace order (a single batch is the common case; the
    compactor streams several). Returns None for empty input.

    sketches: optional zero-arg callable yielding block-level sketches
    already computed on device (the sharded compactor's psum/pmax-merged
    bloom/HLL accumulated per tile) — called after all batches are
    consumed. When given, trace IDs are only counted, never retained, so
    peak memory stays bounded by one batch.
    """
    w = BlockWriter(tenant, backend, cfg, block_id=block_id,
                    compaction_level=compaction_level,
                    collect_ids=(sketches is None))
    for batch in batches:
        w.append_batch(batch)
    return w.finish(sketches=sketches)
