"""`allinone.read-repeat`, rehearsed on the CPU backend at 256 traces: the
run passes traced and untraced with both tiers doing something, and a
result cache that files two queries under one key (a fingerprint blind to
the duration literal, planted in the server) comes out as not correct:
every answer of the window is compared, so every hit is.
"""

import json
import subprocess
import textwrap

import pytest

from test_runs import RESULT_KEYS, correct_but_for_the_known_race, run_cell, servers_running

CELL = "allinone.read-repeat"

# runs in the server's process before `python -m tempo_tpu` does: the result
# cache's fingerprint keeps the query's shape, its string literals (the service)
# and the tags, and drops every number: durations, and the window, which is fixed
BLIND = textwrap.dedent("""
    import os, runpy, sys
    sys.path.insert(0, os.getcwd())
    from tempo_tpu import resultcache
    real = resultcache.fingerprint
    def blind(shape, literals, *rest):
        tags = [p for p in rest if isinstance(p, list)]
        return real(shape, [s for s in literals if s[:1] in '"`'], tags)
    resultcache.fingerprint = blind
    sys.argv[0] = "tempo_tpu"
    runpy.run_module("tempo_tpu", run_name="__main__", alter_sys=True)
""")


@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_passes_and_both_tiers_served(trace):
    out = run_cell(CELL, trace, seed=3000000019)  # beyond 32 signed bits, as the driver's are
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(doc) - {"breakdown", "compared"} == RESULT_KEYS
    assert correct_but_for_the_known_race(doc, out.stdout)
    assert doc["failed"] == 0 and doc["attempted"] > 0
    if not trace:
        assert set(doc["metrics"]) == {"queries_per_s", "query_p95_ms", "setup_s"}
        return
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    assert m["result_cache_hit_share.repeat"] > 50
    assert m["result_cache_bytes_saved_per_query.repeat"] > 0
    assert m["resident_dispatches_per_query.repeat"] > 0
    assert m["device_tier_hit_share.repeat"] > 0
    assert m["transfer_avoided_bytes_per_query.repeat"] > 0 and m["h2d_d2h_bytes_per_query.repeat"] > 0
    assert m["device_tier_admissions_in_window.repeat"] == 0
    assert m["compiles_in_window.read"] == 0 and m["jit_compiles_in_window.read"] == 0
    assert "resident_scan_hbm_share.repeat" not in m  # no device plane on the CPU backend


def test_a_wrong_hit_is_not_correct(monkeypatch, capsys, tmp_path):
    import run
    import server

    wrapper = tmp_path / "blind_server.py"
    wrapper.write_text(BLIND)
    started = []
    real_popen = subprocess.Popen

    def popen(argv, **kw):
        if list(argv[1:3]) == ["-m", "tempo_tpu"]:
            argv = [argv[0], str(wrapper), *argv[3:]]
        proc = real_popen(argv, **kw)
        started.append(proc)
        return proc

    monkeypatch.setattr(server.subprocess, "Popen", popen)
    assert run.main(["--workload", CELL, "--seed", "12", "--seconds", "4", "--trace", "0",
                     "--cpu-dry-run"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["search_wrong"]["value"] + compared["count_wrong"]["value"] > 0
    assert compared["unanswered"]["value"] == 0 and compared["find_wrong"]["value"] == 0
    assert started and all(p.poll() is not None for p in started) and not servers_running()
