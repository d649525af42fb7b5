"""`shard4-tenant` (benchmark/configs) at a small size, on the (1,4) mesh
of the CPU backend's virtual devices: one tenant, four blocks written
through the ingester's flush path, each re-sending a quarter of the one
before; the five operations of the `mesh` traffic mix asked through
Querier.search_block_batch / query_range_blocks with the mesh and with
one device, by two threads at once, and both compared with a plain numpy
reference over the generated columns; and the tempo_tpu_mesh_* families,
which have to grow by what the shapes give.
"""

import threading

import numpy as np
import pytest

from tempo_tpu.app import DEFAULT_TENANT, App, AppConfig
from tempo_tpu.db import DBConfig, TempoDB
from tempo_tpu.encoding.common import BlockConfig, SearchRequest
from tempo_tpu.metrics_engine import compile_metrics_plan
from tempo_tpu.metrics_engine.evaluate import finalize_matrix, merge_wire, new_wire
from tempo_tpu.model import synth
from tempo_tpu.model.columnar import SpanBatch
from tempo_tpu.modules.querier import Querier
from tempo_tpu.parallel import accounting
from tempo_tpu.util import devicetiming

BLOCKS, TRACES, SPANS, RESENT = 4, 64, 16, 16  # 25 % of a block re-sent by the next
ROW_GROUP = 128  # 8 row groups a block, 32 in all: 8 stacked dispatches a full scan
DEVICES = 4
BASE_S = 1_700_000_000
STEP_S = 60
RANGE = dict(start_s=BASE_S - STEP_S, end_s=BASE_S + 2 * STEP_S, step_s=STEP_S)
SERVICE = "checkout"
QUANTILES = (0.5, 0.99)
SEL = f'{{ resource.service.name = "{SERVICE}" && duration > 250ms }}'
DURATION_NS = 250 * 10**6
MIN_DURATION_NS = 700 * 10**6
QUERIES = {
    "rate_by_name": f"{SEL} | rate() by (name)",
    "rate_total": f"{SEL} | rate()",
    "rate_by_service": f"{SEL} | rate() by (resource.service.name)",
    "quantiles": f"{SEL} | quantile_over_time(duration, {', '.join(map(str, QUANTILES))})",
}


def make_blocks() -> list:
    """What is pushed, block by block: block r re-sends the first traces
    of block r-1's fresh part and fills up with fresh ones."""
    blocks, prev = [], None
    for r in range(BLOCKS):
        fresh = synth.make_batch(TRACES - (RESENT if r else 0), SPANS, seed=100 + r,
                                 base_time_ns=BASE_S * 10**9)
        blocks.append(SpanBatch.concat([prev.select(np.arange(RESENT * SPANS)), fresh])
                      if r else fresh)
        prev = fresh
    return blocks


class Reference:
    """Plain numpy over the pushed columns. Counts add across blocks (a
    re-sent span is counted in each block that holds it); a trace is a
    hit once, whatever the number of blocks that hold it."""

    def __init__(self, blocks: list):
        d = blocks[0].dictionary
        code = {d[int(c)]: int(c) for c in np.unique(blocks[0].cols["service"])}
        self.names = {int(c): d[int(c)] for b in blocks for c in np.unique(b.cols["name"])}
        self.service = np.concatenate([b.cols["service"] for b in blocks]) == code[SERVICE]
        self.name = np.concatenate([b.cols["name"] for b in blocks])
        self.dur = np.concatenate([b.cols["duration_nano"] for b in blocks]).astype(np.int64)
        self.trace = np.concatenate([b.cols["trace_id"] for b in blocks])

    def search_tags(self) -> frozenset:
        rows = self.trace[self.service & (self.dur >= MIN_DURATION_NS)]
        return frozenset(r.astype(">u4").tobytes().hex() for r in rows)

    def counts(self, op: str) -> dict:
        m = self.service & (self.dur > DURATION_NS)
        if op == "rate_by_name":
            return {nm: int((m & (self.name == c)).sum()) for c, nm in self.names.items()
                    if (m & (self.name == c)).any()}
        return {SERVICE if op == "rate_by_service" else "": int(m.sum())}

    def quantiles(self) -> dict:
        d = self.dur[self.service & (self.dur > DURATION_NS)]
        return {q: float(np.quantile(d, q, method="lower")) / 1e9 for q in QUANTILES}

    def units(self, at_least_ns: int) -> int:
        """Row groups the zone maps cannot prune: a block is sorted by
        trace id and cut every ROW_GROUP spans; a group is scanned if it
        holds a span of the service and one at least that long."""
        n = 0
        for lo in range(0, len(self.dur), TRACES * SPANS):  # one block
            tid = self.trace[lo:lo + TRACES * SPANS:SPANS]
            order = np.lexsort(tid.T[::-1])
            for g in range(0, TRACES, ROW_GROUP // SPANS):
                rows = (lo + order[g:g + ROW_GROUP // SPANS, None] * SPANS
                        + np.arange(SPANS)).ravel()
                n += bool(self.service[rows].any() and self.dur[rows].max() >= at_least_ns)
        return n


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """(mesh querier, one-device querier, block ids, reference, row groups a block)."""
    root = tmp_path_factory.mktemp("shard4")
    block = BlockConfig(row_group_spans=ROW_GROUP, min_device_bucket=ROW_GROUP)

    def db_config(shards: int) -> DBConfig:
        return DBConfig(backend="local", backend_path=str(root / "blocks"),
                        wal_path=str(root / f"wal{shards}"), block=block,
                        compaction_device_shards=shards)

    app = App(AppConfig(db=db_config(DEVICES), generator_enabled=False))
    try:
        blocks = make_blocks()
        for b in blocks:  # the ingester's flush path: push, cut, complete, flush
            app.push_spans(b)
            app.sweep_all(immediate=True)
        app.db.poll_now()
        metas = list(app.db.blocklist.metas(DEFAULT_TENANT))
        assert len(metas) == BLOCKS and sum(m.total_objects for m in metas) == BLOCKS * TRACES
        mesh = app.db.compaction_mesh()
        assert mesh is not None and dict(mesh.shape) == {"window": 1, "range": DEVICES}
        one = TempoDB(db_config(1))
        one.poll_now()
        assert one.compaction_mesh() is None and one.mesh_searcher() is None
        groups = [len(app.db.encoding_for(m.version).open_block(
            m, app.db.backend, app.db.cfg.block).index().row_groups) for m in metas]
        try:
            yield (app.querier, Querier(one), [m.block_id for m in metas], Reference(blocks),
                   groups)
        finally:
            one.shutdown()
    finally:
        app.shutdown()


def ask(querier, ids: list, op: str):
    """One operation of the mix, answered as the API would spell it."""
    if op == "search_tags":
        resp = querier.search_block_batch(DEFAULT_TENANT, ids, SearchRequest(
            tags={"service.name": SERVICE}, min_duration_ns=MIN_DURATION_NS, limit=0))
        hits = [t.trace_id_hex for t in resp.traces]
        assert len(hits) == len(set(hits)), "a trace twice in one answer"
        return frozenset(hits)
    wire = querier.query_range_blocks(DEFAULT_TENANT, ids, QUERIES[op], **RANGE)
    plan = compile_metrics_plan(QUERIES[op], **RANGE)
    merged = new_wire()
    merge_wire(merged, wire, plan)
    result = finalize_matrix(plan, merged)["result"]
    if op == "quantiles":
        out = {}
        for s in result:
            out.setdefault(float(s["metric"]["p"]), []).extend(
                float(v[1]) for v in s["values"] if float(v[1]) > 0)
        return out
    label = {"rate_by_name": "name", "rate_by_service": "resource.service.name"}.get(op)
    counts = {}
    for s in result:  # rate x step, summed over the steps: the spans of the series
        total = round(sum(float(v[1]) for v in s["values"]) * STEP_S)
        if total:
            counts[s["metric"].get(label, "") if label else ""] = total
    return counts


def check(op: str, got, ref: Reference) -> None:
    if op == "search_tags":
        assert got == ref.search_tags() and len(got) > 8
    elif op == "quantiles":
        for q, true in ref.quantiles().items():
            assert len(got[q]) == 1, "one step holds every span"
            assert abs(got[q][0] - true) / true <= 0.125  # the sketch's documented error
    else:
        assert got == ref.counts(op) and sum(got.values()) > 100


def grown(before: dict) -> dict:
    """What the mesh families grew by since `before` (see snapshot)."""
    return {k: v - before.get(k, 0) for k, v in snapshot().items() if v != before.get(k, 0)}


def snapshot() -> dict:
    out = {}
    for fam in (accounting.units_total, accounting.slots_total, accounting.rows_total,
                accounting.collective_bytes_total, accounting.shard_rows_total,
                accounting.seconds_total,
                devicetiming.dispatch_total):
        for labels, v in fam.series():
            if labels.get("kernel", "mesh_").startswith("mesh_"):
                out[(fam.name, *sorted(labels.items()))] = v
    return out


def expected_growth(op: str, ref: Reference) -> dict:
    """The counts the shapes give for ONE mesh answer of `op`."""
    if op == "rate_total":  # the compiled tier's fused program: not the mesh's
        return {}
    if op == "search_tags":
        kernel, units = "mesh_rle_scan", ref.units(MIN_DURATION_NS)
        dispatches = -(-units // DEVICES)
        collective = 4 * dispatches  # one int32 hit count a window
        rows = {"valid": units * ROW_GROUP, "padded": dispatches * DEVICES * ROW_GROUP}
    else:
        kernel, units = "mesh_bincount", ref.units(DURATION_NS + 1)
        dispatches = -(-units // DEVICES)
        plan = compile_metrics_plan(QUERIES[op], **RANGE)
        collective = 4 * plan.n_slots * dispatches  # one (n_slots,) int32 vector a window
        rows = {}  # run-compressed streams: their lengths follow the data
    assert dispatches >= 3  # one scan takes >= 3 stacked dispatches
    want = {
        ("tempo_tpu_device_dispatches_total", ("kernel", kernel)): dispatches,
        ("tempo_tpu_mesh_units_total", ("kernel", kernel)): units,
        ("tempo_tpu_mesh_slots_total", ("kernel", kernel)): dispatches * DEVICES,
        ("tempo_tpu_mesh_collective_bytes_total", ("kernel", kernel)): collective,
    }
    for kind, n in rows.items():
        want[("tempo_tpu_mesh_rows_total", ("kernel", kernel), ("kind", kind))] = n
    return want


@pytest.mark.parametrize("op", ["search_tags", "rate_by_name", "rate_total", "rate_by_service",
                                "quantiles"])
def test_the_mesh_answers_as_one_device_and_as_the_reference(deployment, op):
    mesh_q, one_q, ids, ref, groups = deployment
    assert groups == [TRACES * SPANS // ROW_GROUP] * BLOCKS

    before = snapshot()
    got = ask(mesh_q, ids, op)
    after = snapshot()
    grew = grown(before)
    check(op, got, ref)
    alone = ask(one_q, ids, op)
    check(op, alone, ref)
    assert got == alone
    assert snapshot() == after, "the one-device querier moved a mesh family"

    # the families grew by the numbers the shapes give
    want = expected_growth(op, ref)
    for key, n in want.items():
        assert grew.get(key, 0) == n, (key, grew)
    rows = {k: v for k, v in grew.items() if k[0] == "tempo_tpu_mesh_rows_total"}
    shard = {k: v for k, v in grew.items() if k[0] == "tempo_tpu_mesh_shard_rows_total"}
    if not want:
        assert not rows and not shard
    else:
        phases = {k[2][1] for k in grew if k[0] == "tempo_tpu_mesh_seconds_total"}
        assert phases == {"plan", "stack", "wait", "collect"}
        valid = sum(v for k, v in rows.items() if ("kind", "valid") in k)
        padded = sum(v for k, v in rows.items() if ("kind", "padded") in k)
        assert 0 < valid <= padded and sum(shard.values()) == valid
        assert len(shard) == DEVICES  # every shard carried rows

    # two threads asking at once: the dispatch lock serializes the mesh
    # programs, and both answers are the reference's
    answers, errors = [], []

    def worker():
        try:
            answers.append(ask(mesh_q, ids, op))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and len(answers) == 2
    for a in answers:
        check(op, a, ref)
    again = grown(before)
    for key, n in want.items():
        assert again.get(key, 0) == 3 * n, (key, again)
