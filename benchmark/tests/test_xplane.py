"""The trace reducer: its arithmetic on a small recorded trace, and a
clear failure where a trace holds no device plane."""

import json
import os

import pytest

from conftest import HERE

import xplane


def test_union_counts_overlap_once():
    # [0,10) + [5,20) + [30,35) nested [31,32): 20 + 5 ns
    assert xplane.union_seconds([(0, 10), (5, 15), (30, 5), (31, 1)]) == pytest.approx(25e-9)
    assert xplane.union_seconds([]) == 0.0


def test_reduce_synthetic_planes():
    planes = [
        ("/host:CPU", []),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_a", 0.0, 100.0), ("jit_b", 400.0, 100.0)]),
            ("XLA Ops", [("fusion.1", 0.0, 60.0), ("copy.2", 50.0, 50.0), ("fusion.1", 400.0, 100.0)]),
            ("Steps", [("0", 0.0, 500.0)]),
        ]),
    ]
    out = xplane.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(200e-9)  # [0,100) and [400,500)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(160e-9)]
    assert out["idle_gaps"] == [["jit_a -> jit_b", pytest.approx(300e-9)]]
    with pytest.raises(xplane.NoDevicePlane, match="XLA Ops"):
        xplane.reduce_planes([("/device:TPU:0", [("Steps", [("0", 0.0, 5.0)])])])


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_traced_window_is_read_from_the_trace():
    host = _Obj(name="/host:CPU", stats=[], lines=[])
    env = _Obj(name="Task Environment", lines=[], stats=[
        ("profile_start_time", 1_000_000_000), ("profile_stop_time", 6_250_000_000)])
    assert xplane.traced_window(_Obj(planes=[host, env])) == pytest.approx(5.25)
    with pytest.raises(xplane.NoDevicePlane, match="started and stopped"):
        xplane.traced_window(_Obj(planes=[host]))


def test_no_device_plane_fails_and_says_what_was_there():
    with pytest.raises(xplane.NoDevicePlane, match="/host:CPU"):
        xplane.reduce_planes([("/host:CPU", []), ("Task Environment", [])])


def test_recorded_chip_trace_gives_known_numbers():
    """Device planes of a 5 s capture of allinone.read on one v5e (PR 28,
    chip call 1), cut to the first 300 events a line."""
    with open(os.path.join(HERE, "recorded_planes.json")) as f:
        planes = json.load(f)
    with open(os.path.join(HERE, "recorded_planes.expect.json")) as f:
        want = json.load(f)
    out = xplane.reduce_planes(planes)
    assert [d["plane"] for d in out["devices"]] == want["planes"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    assert 0 < out["busy_s"] < want["span_s"]


def test_cli_on_a_cpu_trace_reports_the_missing_device_plane(tmp_path):
    """A real .xplane.pb, written by the CPU backend's profiler: read by
    the same code, and refused for having no device plane."""
    import subprocess
    import sys

    script = (
        "import jax, jax.numpy as jnp\n"
        "opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0\n"
        f"jax.profiler.start_trace({str(tmp_path)!r}, profiler_options=opts)\n"
        "jnp.arange(8).sum().block_until_ready()\n"
        "jax.profiler.stop_trace()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    subprocess.run([sys.executable, "-c", script], check=True, env=env, timeout=120)
    out = subprocess.run([sys.executable, os.path.join(os.path.dirname(HERE), "xplane.py"),
                          str(tmp_path)], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 3
    doc = json.loads(out.stdout)
    assert "no device plane" in doc["error"]
    # the trace's own start and stop times, not the seconds that were asked for
    assert 0 < doc["window_s"] < 60
