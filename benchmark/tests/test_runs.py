"""Whole runs, rehearsed on the CPU backend at 256 traces: every cell with
and without the traced run; the result line's keys; no process left; and
the timed path broken underneath, which has to come out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

import traffic as tr

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)
CELLS = [w["name"] for w in B["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# the one ERROR the CPU rehearsal may log: the service-graph store's race under
# concurrent pushes (PERF.md, Open questions). The harness allows none; a test does
KNOWN_RACE = "generator push failed (non-fatal)"


def correct_but_for_the_known_race(doc: dict, stdout: str) -> bool:
    if doc["correct"]:
        return True
    over = [k for k, e in doc["compared"].items() if e["value"] > e["limit"]]
    logged = [ln for ln in stdout.splitlines() if ln.startswith("[bench]   log: ")]
    return over == ["log_errors"] and logged and all(KNOWN_RACE in ln for ln in logged)


def servers_running() -> list:
    """Pids whose argv is a `python -m tempo_tpu -target=all ...`."""
    pids = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
        except OSError:
            continue
        if b"tempo_tpu" in argv and b"-target=all" in argv:
            pids.append(pid)
    return pids


def run_cell(cell: str, trace: int, seed: int = 11, seconds: float = 4.0):
    before = set(servers_running())
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--cpu-dry-run"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert set(servers_running()) <= before, "a server outlived its run"
    return out


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_dry_run_passes_and_prints_the_contracts_line(cell, trace):
    out = run_cell(cell, trace)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(doc) - {"breakdown", "compared"} == RESULT_KEYS
    assert list(doc)[-1] == "compared" and correct_but_for_the_known_race(doc, out.stdout)
    assert "log_errors" in doc["compared"] and doc["compared"]["log_errors"]["limit"] == 0
    assert doc["failed"] == 0 and doc["attempted"] > 0
    assert doc["device"]["platform"] == "cpu"  # a rehearsal says so
    wanted = B["per_layer"] if trace else B["end_to_end"]
    mine = {m["name"]: m for m in wanted if cell in m.get("workloads", [cell])}
    assert set(doc["metrics"]) <= set(mine)
    for name, m in doc["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == mine[name]["unit"]
    if trace == 0:
        assert set(doc["metrics"]) == set(mine)
        assert all(m["value"] > 0 for m in doc["metrics"].values())
    else:  # on the CPU backend nothing reads a device metric
        assert not [n for n in doc["metrics"] if mine[n]["source"] == "device_trace"]
    for line in out.stderr.strip().splitlines()[-len(doc["compared"]) - 1:-1]:
        assert line.startswith("[bench] compared ") and "(limit " in line


def test_without_a_tpu_and_without_the_opt_in_there_is_no_result():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and "refusing to measure" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert not servers_running()


# -- the timed path broken underneath -------------------------------------------


def _run_in_process(monkeypatch, capsys, cell, exchange):
    import run

    monkeypatch.setattr(tr.Client, "exchange", exchange)
    assert run.main(["--workload", cell, "--seed", "12", "--seconds", "3", "--trace", "0",
                     "--cpu-dry-run"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


REAL = tr.Client.exchange


def drop_a_hit(self, req, headers):
    """A search answers with one trace fewer than it found."""
    status, body = REAL(self, req, headers)
    if req.op in ("search_tags", "traceql_filter") and status == 200:
        doc = json.loads(body)
        doc["traces"] = doc["traces"][1:]
        body = json.dumps(doc).encode()
    return status, body


def one_span_more(self, req, headers):
    """A query_range series counts one span it does not hold."""
    status, body = REAL(self, req, headers)
    if req.op.startswith("rate_") and status == 200:
        doc = json.loads(body)
        for s in doc["data"]["result"][:1]:
            s["values"] = [[t, str(float(v) + 1 / tr.STEP_S)] for t, v in s["values"]]
        body = json.dumps(doc).encode()
    return status, body


def acknowledge_and_drop(self, req, headers):
    """A push is answered 200 and the store's state stays unchanged."""
    if req.op == "push" and self.n == 0:
        return 200, b""
    return REAL(self, req, headers)


def push_half(self, req, headers):
    """Half of each batch is left out: the push carries every other trace."""
    if req.op == "push":
        import corpus

        req.body = corpus.encode_push(corpus.make_block(
            req.args[0].n_traces // 2, 16, [1, 2, 3], self.src.range["start"] * 10**9))
    return REAL(self, req, headers)


@pytest.mark.parametrize("cell,fault,number", [
    ("allinone.read", drop_a_hit, "search_wrong"),
    ("allinone.read", one_span_more, "count_wrong"),
    ("multitenant.search", drop_a_hit, "traceql_wrong"),
    ("allinone.write", acknowledge_and_drop, "span_count_gap"),
    ("allinone.write", push_half, "readback_wrong"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, cell, fault, number):
    result = _run_in_process(monkeypatch, capsys, cell, fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > result["compared"][number]["limit"]
    assert not servers_running()


def test_an_error_in_the_servers_log_is_not_correct(monkeypatch, capsys):
    """A failure the server absorbed and logged at ERROR, answers unharmed."""
    import server

    real_shutdown = server.Child.shutdown

    def shutdown(self, *a, **kw):
        with open(self.log_path, "a") as f:
            f.write("2026-01-01 00:00:00,000 ERROR tempo_tpu.test: absorbed and logged\n")
        return real_shutdown(self, *a, **kw)

    monkeypatch.setattr(server.Child, "shutdown", shutdown)
    result = _run_in_process(monkeypatch, capsys, "multitenant.search", REAL)
    assert result["correct"] is False
    over = {k for k, e in result["compared"].items() if e["value"] > e["limit"]}
    assert over == {"log_errors"} and result["compared"]["log_errors"]["value"] == 1


@pytest.mark.parametrize("cell", ["allinone.read", "multitenant.search", "allinone.write"])
def test_the_control_is_not_correct(cell, capsys):
    """The reference with one guarantee broken (benchmark/control.py), at a
    size a test can hold: the comparison has to fail every control of the cell."""
    import control

    assert control.main(["--workload", cell, "--seed", "3", "--requests", "400",
                         "--dry-traces", "2048" if cell != "allinone.write" else "256"]) == 0
    out = capsys.readouterr().out
    assert "correct=False" in out and "correct=True" not in out
    wanted = {"allinone.read": ("Bf16Counts count_wrong", "CoarseQuantiles quantile_rel_err"),
              "multitenant.search": ("Bf16Counts count_wrong", "LeakyTenants search_wrong"),
              "allinone.write": ("lost pushes span_count_gap",)}[cell]
    for number in wanted:  # each moved the number it is there to move, past its limit
        line = next(ln for ln in out.splitlines() if f" {number}: " in ln)
        value, limit = line.split(": ")[1].split(" (limit ")
        assert float(value) > float(limit.rstrip(")")), line
    assert not servers_running()
