"""The ingester groups a push by trace once and holds row ranges of the
grouped batch (modules/ingester.py). These tests hold it to a plain
reference kept here — the split it replaced: per trace one
`flatnonzero` + `select`, every live trace a list of batches of its own —
for the live map, the byte and span accounting, the pool, every reader of
a live trace and the batch a cut appends to the WAL; then the limits, then
what a CPU run can count (selects a push, dictionary remaps a cut, and
that a cut push's batch is let go)."""

import gc
import weakref

import numpy as np
import pytest

from tempo_tpu.db import DBConfig, TempoDB
from tempo_tpu.model import synth
from tempo_tpu.model import trace as tr
from tempo_tpu.model.columnar import VT_STR, Dictionary, SpanBatch
from tempo_tpu.modules.ingester import (
    Ingester,
    IngesterConfig,
    MaxLiveTraces,
    TraceTooLarge,
)
from tempo_tpu.modules.overrides import Limits, Overrides
from tempo_tpu.util import resource

TENANT = "acme"
IDLE_S = 10.0


class Reference:
    """The split as it was: `np.unique(axis=0)`, then per trace
    `flatnonzero` + `select` + `nbytes`, a live trace a list of batches."""

    def __init__(self, lim):
        self.lim = lim
        self.live: dict[bytes, dict] = {}
        self.pool = 0
        self.traces_created = 0
        self.spans_dropped_too_large = 0

    def push(self, batch, now):
        lim = self.lim
        uniq, inverse = np.unique(batch.cols["trace_id"], axis=0, return_inverse=True)
        inverse = inverse.reshape(-1)
        errors = []
        for u in range(len(uniq)):
            rows = np.flatnonzero(inverse == u)
            key = uniq[u].astype(">u4").tobytes()
            lt = self.live.get(key)
            if lt is None:
                if lim.max_traces_per_user and len(self.live) >= lim.max_traces_per_user:
                    errors.append(MaxLiveTraces(
                        f"tenant {TENANT}: max live traces ({lim.max_traces_per_user})"))
                    continue
                lt = self.live[key] = {"segments": [], "spans": 0, "bytes": 0, "touch": 0.0}
                self.traces_created += 1
            sub = batch.select(rows)
            if lim.max_spans_per_trace and lt["spans"] + sub.num_spans > lim.max_spans_per_trace:
                self.spans_dropped_too_large += sub.num_spans
                errors.append(TraceTooLarge(
                    f"trace {key.hex()} exceeds {lim.max_spans_per_trace} spans"))
                continue
            if lim.max_bytes_per_trace and lt["bytes"] + sub.nbytes() > lim.max_bytes_per_trace:
                self.spans_dropped_too_large += sub.num_spans
                errors.append(TraceTooLarge(f"trace {key.hex()} exceeds byte limit"))
                continue
            lt["segments"].append(sub)
            lt["spans"] += sub.num_spans
            lt["bytes"] += sub.nbytes()
            lt["touch"] = now
            self.pool += sub.nbytes()
        if errors:
            raise errors[0]

    def cut(self, now, immediate):
        cut = [k for k, lt in self.live.items()
               if immediate or now - lt["touch"] > IDLE_S]
        if not cut:
            return None
        segments = [seg for k in cut for seg in self.live[k]["segments"]]
        for k in cut:
            self.pool -= self.live.pop(k)["bytes"]
        return SpanBatch.concat(segments).sorted_by_trace()

    def find(self, key):
        return tr.combine_traces(
            tr.batch_to_traces(SpanBatch.concat(self.live[key]["segments"])))

    def live_only(self):
        return [seg for lt in self.live.values() for seg in lt["segments"]]


def span_rows(batch) -> list:
    """Every span of a batch with its strings spelled out and its
    attributes beside it, in the batch's row order: what two batches with
    different dictionaries can be compared by."""
    c, a, d = batch.cols, batch.attrs, batch.dictionary
    attrs_of: dict[int, list] = {}
    for i in range(batch.num_attrs):
        value = (d[int(a["attr_str"][i])] if a["attr_vtype"][i] == VT_STR
                 else float(a["attr_num"][i]))
        attrs_of.setdefault(int(a["attr_span"][i]), []).append(
            (int(a["attr_scope"][i]), d[int(a["attr_key"][i])], int(a["attr_vtype"][i]), value))
    return [
        (c["trace_id"][r].tobytes(), c["span_id"][r].tobytes(), c["parent_span_id"][r].tobytes(),
         int(c["start_unix_nano"][r]), int(c["duration_nano"][r]), int(c["kind"][r]),
         int(c["status_code"][r]), d[int(c["name"][r])], d[int(c["service"][r])],
         int(c["http_status"][r]), d[int(c["http_method"][r])], d[int(c["http_url"][r])],
         sorted(attrs_of.get(r, []), key=repr))
        for r in range(batch.num_spans)
    ]


def trace_spans(trace) -> list:
    return sorted((s.span_id, s.name, s.start_unix_nano, s.duration_nano,
                   sorted(s.attributes.items()), sorted(res.items()))
                  for res, spans in trace.batches for s in spans)


def make_instance(tmp_path, lim):
    db = TempoDB(DBConfig(backend="mock", wal_path=str(tmp_path / "wal")))
    gov = resource.ResourceGovernor()
    ing = Ingester(db, Overrides(lim), IngesterConfig(max_trace_idle_s=IDLE_S), governor=gov)
    return ing.instance(TENANT), gov


def shuffled(batch, seed):
    return batch.select(np.random.default_rng(seed).permutation(batch.num_spans))


def one_trace_in_three_pushes():
    whole = synth.make_trace(7, n_spans=12)
    spans = [(res, s) for res, group in whole.batches for s in group]
    pushes = []
    for part in range(3):
        piece = tr.Trace(trace_id=whole.trace_id)
        for res, s in spans[part::3]:
            piece.batches.append((res, [s]))
        # company that differs a push, so the three dictionaries do too
        others = synth.make_traces(2, seed=70 + part, spans_per_trace=3)
        pushes.append(tr.traces_to_batch([piece] + others))
    return pushes


def partial_cut_steps():
    """Traces 0-3 go idle; 4-7 are touched again by the second push and
    stay live with 8-11: the first push loses half its rows (and is
    repacked), the rest is cut at the end."""
    traces = synth.make_traces(12, seed=91, spans_per_trace=5)
    again = [synth.make_trace(500 + i, n_spans=2, trace_id=t.trace_id)
             for i, t in enumerate(traces[4:8])]
    return [("push", tr.traces_to_batch(traces[:8]), 100.0),
            ("push", tr.traces_to_batch(again + traces[8:]), 105.0),
            ("cut", 111.0, False),
            ("push", tr.traces_to_batch(traces[8:10]), 112.0),
            ("cut", 116.0, False),
            ("cut", 200.0, True)]


def steps_of(case):
    if case == "64x16":
        return [("push", shuffled(synth.make_batch(64, 16, seed=1), 2), 100.0),
                ("push", shuffled(synth.make_batch(64, 16, seed=3), 4), 101.0),
                ("cut", 200.0, False)]
    if case == "interleaved":
        return [("push", shuffled(synth.make_batch(5, 7, seed=5), 6), 100.0),
                ("cut", 200.0, True)]
    if case == "three_pushes_three_dictionaries":
        return [("push", b, 100.0 + i) for i, b in enumerate(one_trace_in_three_pushes())] + [
            ("cut", 200.0, False)]
    if case == "no_attributes":
        return [("push", shuffled(synth.make_graph_batch(8, 6, seed=8), 9), 100.0),
                ("cut", 200.0, False)]
    if case == "partial_cut":
        return partial_cut_steps()
    if case == "one_span":
        return [("push", synth.make_batch(1, 1, seed=10), 100.0), ("cut", 200.0, False)]
    raise AssertionError(case)


def assert_same_live_state(inst, gov, ref):
    assert list(inst.live) == list(ref.live)
    for key, lt in inst.live.items():
        assert (lt.span_count, lt.byte_count, lt.last_touch) == (
            ref.live[key]["spans"], ref.live[key]["bytes"], ref.live[key]["touch"])
        if lt.segments:
            assert trace_spans(inst.find_trace_by_id(key)) == trace_spans(ref.find(key))
    assert gov.pool("live_traces").used == ref.pool
    assert (inst.traces_created, inst.spans_dropped_too_large) == (
        ref.traces_created, ref.spans_dropped_too_large)
    got = span_rows(SpanBatch.concat(inst.live_only_batches()).sorted_by_trace())
    assert got == span_rows(SpanBatch.concat(ref.live_only()).sorted_by_trace())
    # what pins what: a push held for live traces never holds more than
    # twice the rows they reference
    held = {id(p): p for lt in inst.live.values() for p, _, _ in lt.segments}
    for p in held.values():
        assert p.batch.num_spans < 2 * p.live_rows
        assert p.live_rows == sum(hi - lo for lt in inst.live.values()
                                  for q, lo, hi in lt.segments if q is p)


@pytest.mark.parametrize("case", [
    "64x16", "interleaved", "three_pushes_three_dictionaries", "no_attributes",
    "partial_cut", "one_span"])
def test_grouped_push_equals_the_per_trace_split(case, tmp_path):
    lim = Limits()
    inst, gov = make_instance(tmp_path, lim)
    ref = Reference(lim)
    for step in steps_of(case):
        if step[0] == "push":
            inst.push_batch(step[1], now=step[2])
            ref.push(step[1], step[2])
        else:
            before = inst.head.num_segments()
            n = inst.cut_complete_traces(now=step[1], immediate=step[2])
            want = ref.cut(step[1], step[2])
            if want is None:
                assert n == 0 and inst.head.num_segments() == before
            else:
                got = list(inst.head.iter_batches())[-1]
                assert inst.head.num_segments() == before + 1
                # the same spans in the same (trace_id, span_id) order
                # with the same strings, and so the same traces
                assert span_rows(got) == span_rows(want)
                assert tr.batch_to_traces(got) == tr.batch_to_traces(want)
                assert gov.pool("wal_head").used == inst.head._gov_bytes
        assert_same_live_state(inst, gov, ref)
    assert not inst.live and gov.pool("live_traces").used == 0


def limit_pushes(limit):
    sizes = [3, 5, 7, 9, 4, 6]
    traces = [synth.make_trace(300 + i, n_spans=n) for i, n in enumerate(sizes)]
    first = tr.traces_to_batch(traces)
    more = [synth.make_trace(400 + i, n_spans=3, trace_id=t.trace_id)
            for i, t in enumerate(traces[:3])] + synth.make_traces(2, seed=33, spans_per_trace=2)
    second = tr.traces_to_batch(more)
    if limit == "max_traces_per_user":
        return Limits(max_traces_per_user=4), [first, second]
    if limit == "max_spans_per_trace":
        return Limits(max_spans_per_trace=6), [first, second]
    # a byte limit between the smallest and the largest trace of the push
    probe = Reference(Limits())
    probe.push(first, 1.0)
    sizes = sorted(lt["bytes"] for lt in probe.live.values())
    return Limits(max_bytes_per_trace=(sizes[2] + sizes[3]) // 2), [first, second]


@pytest.mark.parametrize("limit", [
    "max_traces_per_user", "max_spans_per_trace", "max_bytes_per_trace"])
def test_limits_refuse_the_same_traces(limit, tmp_path):
    lim, pushes = limit_pushes(limit)
    inst, gov = make_instance(tmp_path, lim)
    ref = Reference(lim)
    refused = 0
    for i, batch in enumerate(pushes):
        with pytest.raises((MaxLiveTraces, TraceTooLarge)) as want:
            ref.push(batch, 100.0 + i)
        with pytest.raises((MaxLiveTraces, TraceTooLarge)) as got:
            inst.push_batch(batch, now=100.0 + i)
        # the same first error, and the rest of the push ingested once
        assert (type(got.value), str(got.value)) == (type(want.value), str(want.value))
        assert_same_live_state(inst, gov, ref)
        refused += 1
    assert refused == len(pushes)
    if limit != "max_traces_per_user":
        assert inst.spans_dropped_too_large > 0
    inst.cut_complete_traces(immediate=True)
    want = ref.cut(0.0, True)
    assert span_rows(list(inst.head.iter_batches())[-1]) == span_rows(want)
    assert gov.pool("live_traces").used == 0


def _counted(monkeypatch, cls, name):
    calls = []
    real = getattr(cls, name)

    def counting(self, *a, **kw):
        calls.append(self)
        return real(self, *a, **kw)

    monkeypatch.setattr(cls, name, counting)
    return calls


def test_one_select_a_push_and_one_remap_a_push_cut(tmp_path, monkeypatch):
    inst, _ = make_instance(tmp_path, Limits())
    pushes = [shuffled(synth.make_batch(64, 16, seed=20 + i), 3) for i in range(5)]
    selects = _counted(monkeypatch, SpanBatch, "select")
    nbytes = _counted(monkeypatch, SpanBatch, "nbytes")
    inst.push_batch(pushes[0], now=100.0)
    assert len(inst.live) == 64
    assert len(selects) <= 2 and len(nbytes) <= 2  # not one a trace
    for i in range(1, 5):
        inst.push_batch(pushes[i], now=100.0 + i)
    remaps = _counted(monkeypatch, Dictionary, "remap_onto")
    del selects[:]
    assert inst.cut_complete_traces(immediate=True) == 5 * 64
    assert len(remaps) == 5  # one dictionary a push, not one a trace
    assert len(selects) == 1  # whole pushes are passed as they are: only the cut's sort


def test_a_cut_that_takes_every_trace_of_a_push_lets_its_batch_go(tmp_path):
    inst, _ = make_instance(tmp_path, Limits())
    inst.push_batch(synth.make_batch(8, 4, seed=30), now=100.0)
    inst.push_batch(synth.make_batch(8, 4, seed=31), now=150.0)
    first = weakref.ref(next(iter(inst.live.values())).segments[0][0].batch)
    assert first() is not None
    assert inst.cut_complete_traces(now=140.0) == 8  # the first push, whole
    gc.collect()
    assert first() is None and len(inst.live) == 8
