"""bench.py artifact contract: the driver parses the LAST stdout line as
JSON no matter how the run dies (round-4 lesson: a fast backend-init
UNAVAILABLE escaped both the watchdog and the JSON error path and the
round shipped `parsed: null`).

Covers: the no-fallback refusal (no TPU and no explicit
JAX_PLATFORMS=cpu -> nonzero exit naming the platform found, never a
per-chip value), the failure artifact on a mid-run crash, and partial
per-arm times surviving into the artifact.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def _last_json(out: str) -> dict:
    return json.loads([l for l in out.splitlines() if l.strip()][-1])


def test_refuses_a_non_tpu_platform_without_the_opt_in(monkeypatch, capsys):
    """This process resolved to the CPU; with JAX_PLATFORMS unset that is
    a missing chip, not a request: the run dies with the failure
    artifact — platform named, value null — and no rep ever runs."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("TEMPO_TPU_FAULTS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "build_inputs", lambda *a, **k: pytest.fail("a rep ran"))
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 1
    art = _last_json(capsys.readouterr().out)
    assert art["value"] is None and art["vs_baseline"] is None
    assert "NoAccelerator" in art["error"] and "'cpu', not 'tpu'" in art["error"]


@pytest.mark.parametrize("rep", ["compiled", "ingest"])
def test_standalone_reps_refuse_too(monkeypatch, capsys, rep):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py", rep])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 2
    cap = capsys.readouterr()
    assert "'cpu', not 'tpu'" in cap.err and not cap.out.strip()


def test_explicit_cpu_run_never_uses_the_per_chip_name():
    assert bench._headline_metric("cpu") == (
        "blocks_compacted_per_sec_cpu", "blocks/s (cpu)")
    assert bench._headline_metric("tpu") == (
        "blocks_compacted_per_sec_per_chip", "blocks/s/chip")


def test_bench_suite_refuses_without_the_opt_in(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_suite", os.path.join(REPO, "tools", "bench_suite.py"))
    suite = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(suite)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench_suite.py", "ingest"])
    assert suite.main() == 1
    cap = capsys.readouterr()
    assert "'cpu', not 'tpu'" in cap.err and not cap.out.strip()


def test_self_tracing_guard_refuses(monkeypatch):
    """Perf reps must never include dogfood traffic: an installed
    self-tracing exporter makes bench refuse up front (same contract as
    the TEMPO_TPU_FAULTS guard)."""
    from tempo_tpu.util import tracing

    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("TEMPO_TPU_FAULTS", raising=False)
    tracing.install_exporter(lambda traces: None)
    try:
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 2
    finally:
        tracing.TRACER.exporter = None


def test_midrun_crash_emits_artifact(monkeypatch, capsys):
    """Any exception after the watchdog starts must still produce one
    parseable JSON line with value:null + error, and exit nonzero."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(
        bench, "build_inputs",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("simulated UNAVAILABLE")))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 1
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    art = json.loads(lines[-1])
    assert art["value"] is None
    assert art["vs_baseline"] is None
    assert "simulated UNAVAILABLE" in art["error"]
    # the crash came after the platform resolved: a CPU run's artifact
    # carries its device tags and never the per-chip name
    assert art["metric"] == "blocks_compacted_per_sec_cpu"
    assert (art["platform"], art["device_kind"]) == ("cpu", "cpu")
    assert art["device_count"] >= 1


def test_partial_times_reach_artifact(monkeypatch, capsys):
    """A crash mid-way keeps whatever rep times already completed in the
    failure artifact (the judge can still see the CPU arms)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    def run_then_die(dog, partial):
        partial["platform"] = "cpu"
        partial["cpu_single_times_s"] = [1.25, 1.31]
        raise RuntimeError("died after 2 reps")

    monkeypatch.setattr(bench, "_run", run_then_die)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit):
        bench.main()
    art = json.loads([l for l in capsys.readouterr().out.splitlines() if l.strip()][-1])
    assert art["cpu_single_times_s"] == [1.25, 1.31]
    assert art["platform"] == "cpu"
    assert art["value"] is None
