"""One window that holds writers and readers, on the fixture cell of
mixed_cell.py, which no `workloads` entry names: whole runs rehearsed on
the CPU backend with the fixture as its files state it (the compactor's
30 s cycle, traces cut after 1 s idle, writers paced at 2 pushes/s), the
timed path broken underneath, and the comparison of a store that may have
been compacted, without a server."""

import argparse
import copy
import functools
import json
import re
import time

import numpy as np
import pytest

import check
import corpus
import mixed_cell
import reference
import run
import traffic as tr
from test_runs import REAL, acknowledge_and_drop, one_span_more, servers_running

BASE_S = 1_700_000_040
DRY = argparse.Namespace(cpu_dry_run=True, dry_traces=256, seed=1, seconds=5.0, trace=0,
                         keep_dir=False)  # what run.Run reads of the command line


def run_mixed(monkeypatch, capsys, trace=0, exchange=None) -> tuple:
    """(result line, stdout) of one rehearsal of the fixture cell in this process."""
    bench, cell, config, traffic = mixed_cell.load_cell("allinone-compacting")
    monkeypatch.setattr(run, "load_cell", lambda workload: (bench, cell, config, traffic))
    if exchange:
        monkeypatch.setattr(tr.Client, "exchange", exchange)
    assert run.main(["--workload", cell["name"], "--seed", "3000000021", "--seconds", "5",
                     "--trace", str(trace), "--cpu-dry-run"]) == 0
    out = capsys.readouterr().out
    assert not servers_running()
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_the_fixture_cell_reports_both_families_and_is_correct(monkeypatch, capsys, trace):
    doc, out = run_mixed(monkeypatch, capsys, trace)
    compared = doc["compared"]
    assert doc["correct"], compared
    for number in ("readback_wrong", "count_wrong", "search_wrong", "traceql_wrong",
                   "find_wrong", "span_count_gap", "unanswered"):
        assert compared[number] == {"value": 0, "limit": 0}, number
    assert "find_acked n=" in out and "push n=" in out
    assert "4 writer paced at 2/s + 4 reader closed-loop" in out
    compactor = next(ln for ln in out.splitlines() if "compactor in the window" in ln)
    assert "since the server's start" in compactor
    assert "compaction jobs, ended at (s from the window's start): " in out  # a job or none
    arrivals = doc["paced"]["writer"]
    assert arrivals["offered_per_s"] == 2
    assert arrivals["sent"] + arrivals["dropped"] + arrivals["missed"] >= 10
    if trace == 0:
        assert set(doc["metrics"]) == {"ingest_spans_per_s", "queries_per_s", "query_p95_ms",
                                       "setup_s"}
        assert all(m["value"] > 0 for m in doc["metrics"].values())
        # the rates are over the same window, each over its own records
        n = {op: int(k) for op, k in re.findall(r"(\w+) n=(\d+)", out)}
        assert doc["metrics"]["ingest_spans_per_s"]["value"] <= n["push"] * 1024 / 5
        assert doc["metrics"]["ingest_spans_per_s"]["value"] <= 2 * 1024  # delivered <= offered
        assert doc["metrics"]["queries_per_s"]["value"] <= (sum(n.values()) - n["push"]) / 5
    else:  # a reader per query and a reader per push each found its own count
        assert doc["metrics"]["host_stage_ms.read"]["value"] > 0
        assert doc["metrics"]["decode_ms_per_push.write"]["value"] > 0


def combine_at_query_time(self, req, headers):
    """A `rate()` counts the copies of a re-sent span once, as a merge's output
    would, though no job has run: the combined state's answer, in a store that
    has only the un-compacted one."""
    status, body = REAL(self, req, headers)
    if req.op == "rate_total" and status == 200:
        combined = dry_store_combined(self.seed).rate_total(*req.args).get("", 0)
        doc = json.loads(body)
        for series in doc["data"]["result"]:
            series["values"] = [[t, str(combined / tr.STEP_S if float(v) else 0.0)]
                                for t, v in series["values"]]
        body = json.dumps(doc).encode()
    return status, body


@functools.lru_cache(maxsize=1)
def dry_store_combined(seed):
    """The fixture's store at a rehearsal's size, its two blocks merged."""
    config = mixed_cell.load_cell("allinone-compacting")[2]
    data = run.store_data(config, ["single-tenant"], DRY.dry_traces)
    return reference.Reference(corpus.make_store(seed, data, BASE_S)["single-tenant"],
                               True).states[-1]


def leak_the_pushes(self, req, headers):
    """A search loses its time range on the way: what was pushed seconds ago leaks in."""
    if req.op in ("search_tags", "traceql_filter"):
        req.path = re.sub(r"&(start|end)=\d+", "", req.path)
    return REAL(self, req, headers)


@pytest.mark.parametrize("fault,number,inside", [
    (acknowledge_and_drop, "readback_wrong", "not as expected: find_acked -> 404"),
    (one_span_more, "count_wrong", None),
    (leak_the_pushes, "search_wrong", None),
    (combine_at_query_time, "count_wrong", "compactor in the window (tempodb_compaction_*): {}"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, fault, number, inside):
    doc, out = run_mixed(monkeypatch, capsys, exchange=fault)
    assert doc["correct"] is False
    assert doc["compared"][number]["value"] > doc["compared"][number]["limit"]
    if inside:  # seen by a find of the window, before the readback after it
        assert inside in out


def slow_pushes(self, req, headers):
    """A server that takes 2.5 s a push: longer than 4 writers / 2 arrivals a second."""
    if req.op == "push":
        time.sleep(2.5)
    return REAL(self, req, headers)


def test_a_server_slower_than_the_rate_drops_arrivals(monkeypatch, capsys):
    doc, out = run_mixed(monkeypatch, capsys, exchange=slow_pushes)
    arrivals = doc["paced"]["writer"]
    assert arrivals["dropped"] > 0 and arrivals["p50_ms"] >= 2500
    assert doc["metrics"]["ingest_spans_per_s"]["value"] < arrivals["offered_per_s"] * 1024
    # a dropped arrival is no request: it counts under `paced` alone; what was sent is right
    assert doc["failed"] == 0 and doc["correct"], doc["compared"]
    assert f"dropped={arrivals['dropped']}" in out


# -- the comparison, without a server ---------------------------------------------


def two_blocks(seed=3):
    data = {"tenants": ["single-tenant"], "blocks_per_tenant": 2, "traces_per_block": 256,
            "spans_per_trace": 16, "resend_fraction": 0.25}
    return corpus.make_store(seed, data, BASE_S)["single-tenant"]


def answered(op, args, answer, status=200):
    req = tr.Request(op, "single-tenant", args, "GET", "/")
    return tr.Record(req, 0, 0.0, 0.0, status, answer)


def numbers(records, blocks, compaction_in_run):
    return check.compare(records, {"single-tenant": reference.Reference(blocks, compaction_in_run)})


@pytest.mark.parametrize("n,count", [(2, 2), (3, 5), (4, 15)])
def test_the_partitions_of_n_blocks(n, count):
    parts = reference.partitions(n)
    assert len(parts) == count == len({json.dumps(sorted(map(sorted, p))) for p in parts})
    assert all(sorted(b for g in p for b in g) == list(range(n)) for p in parts)


def test_five_blocks_a_tenant_are_refused_before_a_server_starts():
    with pytest.raises(ValueError, match="at most 4 blocks"):
        reference.partitions(5)
    bench, cell, config, traffic = mixed_cell.load_cell("allinone-compacting")
    config["blocks_per_tenant"] = 5
    with pytest.raises(ValueError, match="compaction_in_run with 5 blocks"):
        run.Run(DRY, bench, cell, config, traffic)
    config["compaction_in_run"] = False
    run.Run(DRY, bench, cell, config, traffic).close()  # without the key any number goes


def test_either_state_of_a_two_block_store_is_right_with_the_key_only():
    blocks = two_blocks()
    ref = reference.Reference(blocks, True)
    every_copy, combined = ref.states
    args = ("cart", 0)
    assert every_copy.rate_total(*args) == reference.Reference(blocks).rate_total(*args)
    resent = 64 * 16  # a quarter of the second block's traces are the first's again
    assert int(every_copy._keep.sum()) - int(combined._keep.sum()) == resent
    for op in ("rate_total", "rate_by_name", "rate_by_service"):
        a, b = getattr(every_copy, op)(*args), getattr(combined, op)(*args)
        assert a != b
        for answer, without_key in ((a, 0), (b, 1)):
            rec = [answered(op, args, {k: float(v) for k, v in answer.items()})]
            assert numbers(rec, blocks, True)["count_wrong"] == 0
            assert numbers(rec, blocks, False)["count_wrong"] == without_key
        # one off from every state's value; and a by() whose series straddle two states
        off = dict(b)
        off[next(iter(off))] += 1
        assert numbers([answered(op, args, off)], blocks, True)["count_wrong"] == 1
    a, b = every_copy.rate_by_name(*args), combined.rate_by_name(*args)
    first = next(k for k in a if a[k] != b[k])
    straddling = {**a, first: b[first]}
    assert numbers([answered("rate_by_name", args, straddling)], blocks, True)["count_wrong"] == 1


def test_a_store_no_job_has_touched_has_one_state_and_a_merge_is_never_undone():
    name = "tempodb_compaction_blocks_compacted_total"
    assert not run.merged({}, "a") and not run.merged({name + '{tenant="a"}': 0.0}, "a")
    scrape = {name + '{tenant="a"}': 2.0, 'tempodb_compaction_runs_total{tenant="b"}': 1.0}
    assert run.merged(scrape, "a") and not run.merged(scrape, "b")
    blocks = two_blocks()
    every_copy, combined = reference.Reference(blocks, True).states
    assert (every_copy.merges, combined.merges) == (0, 1)
    args = ("cart", 0)
    a, b = ({k: float(v) for k, v in s.rate_by_name(*args).items()} for s in (every_copy, combined))

    def at(t0, t1, answer):
        rec = answered("rate_by_name", args, answer)
        rec.t0, rec.t1 = t0, t1
        return rec

    # forward, and two in flight at once either way round: right
    assert numbers([at(0, 1, a), at(2, 3, b), at(4, 5, b)], blocks, True)["count_wrong"] == 0
    assert numbers([at(0, 3, b), at(1, 2, a), at(2.5, 4, a)], blocks, True)["count_wrong"] == 0
    # un-compacted again after a combined answer has ended: each such answer is wrong
    assert numbers([at(0, 1, a), at(2, 3, b), at(4, 5, a), at(6, 7, a)], blocks, True)[
        "count_wrong"] == 2
    # an answer that both states explain holds nothing to an order
    both = {k: float(v) for k, v in every_copy.rate_by_name("cart", 10**12).items()}
    assert both == combined.rate_by_name("cart", 10**12)
    recs = [at(0, 1, b), answered("rate_by_name", ("cart", 10**12), both)]
    recs[1].t0, recs[1].t1 = 2, 3
    assert numbers(recs, blocks, True)["count_wrong"] == 0


def test_quantiles_are_held_to_the_nearer_state():
    blocks = two_blocks()
    ref = reference.Reference(blocks, True)
    args = ("cart", 0)
    for state in ref.states:
        exact = {q: [lo] for q, (lo, _) in state.quantiles(*args, tr.QUANTILES).items()}
        assert numbers([answered("quantiles", args, exact)], blocks, True)["quantile_rel_err"] == 0
    far = {q: [v[0] * 1.3] for q, v in exact.items()}
    assert numbers([answered("quantiles", args, far)], blocks, True)["quantile_rel_err"] > 0.125


def test_sets_do_not_depend_on_the_state_and_a_range_holds_what_overlaps_it():
    blocks = two_blocks()
    ref, plain = reference.Reference(blocks, True), reference.Reference(blocks)
    store = (BASE_S - 60, BASE_S + 120)
    assert ref.search_tags("cart", 990_000_000) == plain.search_tags("cart", 990_000_000, store)
    assert ref.traceql_filter(500, 980_000_000, store) == plain.traceql_filter(500, 980_000_000)
    assert plain.search_tags("cart", 0, (BASE_S + 600, BASE_S + 660)) == frozenset()
    later = corpus.make_block(8, 16, [9], (BASE_S + 600) * 10**9)  # as pushes of the run are
    both = reference.Reference(blocks + [later])
    assert both.search_tags("cart", 0, store) == plain.search_tags("cart", 0)
    assert len(both.search_tags("cart", 0)) >= len(plain.search_tags("cart", 0))
    assert both.traceql_filter(200, 0, store) == plain.traceql_filter(200, 0)


def test_an_acknowledged_trace_is_held_to_its_pushs_own_spans():
    spans = {b"12345678", b"abcdefgh"}
    records = [answered("find_acked", ("ab" * 16, spans), set(spans)),
               answered("find_acked", ("cd" * 16, spans), {b"12345678"}),
               answered("find_acked", ("ef" * 16, spans), None, status=404)]
    out = check.compare(records, {})  # no store at all: a write cell's finds
    assert out["readback_wrong"] == 2 and out["unanswered"] == 1
    correct, table = check.verdict(out)
    assert not correct and table["readback_wrong"] == {"value": 2, "limit": 0}


def test_half_combined_is_not_correct_and_bf16_counts_still_is_not(capsys):
    import control

    assert control.main(["--workload", "allinone.read", "--seed", "3", "--requests", "400",
                         "--dry-traces", "2048", "--compaction-in-run"]) == 0
    out = capsys.readouterr().out
    assert "correct=False" in out and "correct=True" not in out
    for number in ("Bf16Counts count_wrong", "HalfCombined count_wrong",
                   "CoarseQuantiles quantile_rel_err"):
        line = next(ln for ln in out.splitlines() if f" {number}: " in ln)
        value, limit = line.split(": ")[1].split(" (limit ")
        assert float(value) > float(limit.rstrip(")")), line


# -- roles ------------------------------------------------------------------------


def test_clients_are_numbered_through_the_roles_and_dealt_their_own_deck():
    _, _, config, traffic = mixed_cell.load_cell()
    assert [r["name"] for r in tr.roles_of(traffic)] == ["writer", "reader"]
    assert tr.ops_of(traffic) == {"push", "find", "search_tags", "traceql_filter", "rate_by_name",
                                  "rate_total", "rate_by_service", "quantiles"}
    r = run.Run(DRY, {}, {"name": "x"}, config, traffic)
    try:
        assert [role["name"] for role in r.client_roles] == ["writer"] * 4 + ["reader"] * 4
    finally:
        r.close()
    blocks = two_blocks()
    traffic["pool_bodies"] = 2
    pool = tr.make_pool(traffic, 1, 16, BASE_S + 600)
    src = tr.Source(traffic, ["single-tenant"], False, BASE_S,
                    {"single-tenant": np.array(corpus.trace_hex(blocks[0]), dtype=object)}, pool)
    roles = tr.roles_of(traffic)
    writer = tr.Client(0, 1, src, 0, [], role=roles[0])
    reader = tr.Client(4, 1, src, 0, [], role=roles[1])
    assert {writer.next_request().op for _ in range(8)} == {"push"}
    dealt = [reader.next_request() for _ in range(200)]
    ops = [r.op for r in dealt]
    # nothing acknowledged yet: a find of an acknowledged trace falls back on a stored one
    assert ops.count("find") == 80 and ops.count("search_tags") == 30 and "push" not in ops
    assert all("start=" in r.path and "end=" in r.path for r in dealt
               if r.op in ("search_tags", "traceql_filter"))
    assert not any("start=" in r.path for r in dealt if r.op == "find")
    push = writer.next_request()
    src.acknowledged(push)
    dealt = [reader.next_request() for _ in range(200)]
    acked = [r for r in dealt if r.op == "find_acked"]
    assert len(acked) == 40 and [r.op for r in dealt].count("find") == 40
    body, ids = push.args
    assert all(r.args[1] in body.span_sets and bytes.fromhex(r.args[0]) in
               {i.tobytes() for i in ids} for r in acked)


def test_a_file_without_roles_is_one_role_of_its_top_level_keys():
    traffic = {"clients": 3, "deck_size": 10, "deck": [{"op": "find", "weight": 1}]}
    assert tr.roles_of(traffic) == [{"name": "clients", "clients": 3, "deck_size": 10,
                                     "deck": traffic["deck"]}]
    assert tr.range_of(BASE_S) == {"start": BASE_S - 60, "end": BASE_S + 120, "step": 60}
    assert tr.range_of(BASE_S + 59)["start"] == BASE_S - 60  # whole steps around the spans


def test_a_role_that_got_no_answer_fails_the_run():
    _, cell, config, traffic = mixed_cell.load_cell()
    r = run.Run(DRY, {"end_to_end": []}, cell, config, copy.deepcopy(traffic))
    try:
        push = tr.Request("push", "single-tenant", (), "POST", "/v1/traces", spans=1024)
        w = {"records": [tr.Record(push, 0, 0.1, 0.2, 200)], "t0": 0.0, "seconds": 5.0,
             "scrapes": ({}, {}), "cache_files": (set(), set())}
        with pytest.raises(run.BenchFailure, match="role 'reader' got no answer"):
            r.metrics(w, 1.0, None)
    finally:
        r.close()
