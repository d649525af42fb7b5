"""chip_smoke.py contract, at a tiny size on the CPU: the dry run passes
and says "cpu"; without the explicit opt-in a non-TPU backend is
refused with no result line; a failing phase makes the exit nonzero;
the smoke parent never imports jax."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout=300):
    return subprocess.run([sys.executable, SMOKE, *args], capture_output=True,
                          text=True, timeout=timeout, cwd=REPO)


def _result_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_tiny_cpu_dry_run_passes_and_says_cpu():
    """The whole sequence — push, two flushes, compactor loop, every
    query against the numpy reference before and after — in one child
    server. The script itself asserts its parent stayed off jax."""
    out = _run("--cpu-dry-run", "--traces", "128", "--cycle-s", "1")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    # the result line holds exactly these keys: the driver refuses more
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    summary = [ln for ln in lines if ln.startswith("[smoke] summary: ")]
    doc = json.loads(summary[-1].split("summary: ", 1)[1])
    assert doc["cpu_dry_run"] is True and doc["spans_pushed"] == 128 * 16
    assert "SIZE CUT: 128 traces" in out.stdout
    # a CPU run never borrows a device metric's name
    assert "per_chip" not in out.stdout and "/chip" not in out.stdout


def test_non_tpu_backend_is_refused_without_the_opt_in():
    """conftest pins JAX_PLATFORMS=cpu in the environment, like the
    sandbox does: that is not an opt-in. Nonzero exit, the platform
    named, and no result line."""
    out = _run("--traces", "64")
    assert out.returncode != 0
    assert "'cpu', not 'tpu'" in out.stderr
    assert not _result_lines(out.stdout)


def test_failing_phase_gives_nonzero_exit_and_no_result(monkeypatch, capsys):
    def boom(cpu_dry_run):
        raise chip_smoke.SmokeFailure("stubbed phase failure")

    monkeypatch.setattr(chip_smoke, "certify_scan_kernels", boom)
    assert chip_smoke.main(["--cpu-dry-run", "--traces", "64"]) == 1
    cap = capsys.readouterr()
    assert "stubbed phase failure" in cap.err
    assert not _result_lines(cap.out)


def test_parent_imports_stay_off_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; sys.exit('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    import shutil

    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert not _result_lines(out.stdout)
