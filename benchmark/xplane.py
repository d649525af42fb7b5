"""The reduction from a profiler trace to device numbers.

    JAX_PLATFORMS=cpu python benchmark/xplane.py <dir-or-.xplane.pb> [<planes.json>]

prints one JSON object: the seconds the trace covers (its own start and
stop times), the device planes found, the seconds in which an operation
ran on each (the union of the intervals on its operation line), their
mean, the ten operations with the most total time and the ten longest
gaps. run.py calls it in a short-lived child, so the process
that drives the server never imports JAX and this one — pinned to the
CPU backend — can never touch the chip.

`reduce_planes` is the arithmetic and takes plain tuples, so a test can
pin it on a small recorded trace.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"  # one event per executed operation
MODULE_LINE = "XLA Modules"  # one event per executed program


class NoDevicePlane(Exception):
    pass


def union_seconds(intervals: list) -> float:
    """Seconds covered by the union of (start_ns, duration_ns) intervals."""
    busy, end = 0.0, -1.0
    for a, d in sorted(intervals):
        b = a + d
        if a > end:
            busy += d
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy / 1e9


def reduce_planes(planes: list, top: int = 10) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns), ...]), ...]), ...] as the trace holds them."""
    devices = [(n, lines) for n, lines in planes if n.startswith(DEVICE_PREFIX)]
    if not devices:
        raise NoDevicePlane("the trace holds no device plane; planes: "
                            + ", ".join(n for n, _ in planes))
    per_device, op_seconds, gaps = [], {}, []
    for name, lines in devices:
        by_name = dict(lines)
        if OP_LINE not in by_name:
            raise NoDevicePlane(f"plane {name} has no {OP_LINE!r} line; lines: "
                                + ", ".join(by_name))
        events = by_name[OP_LINE]
        per_device.append({"plane": name, "lines": {ln: len(ev) for ln, ev in lines},
                           "busy_s": union_seconds([(a, d) for _, a, d in events])})
        for ev_name, _, d in events:
            op_seconds[ev_name] = op_seconds.get(ev_name, 0.0) + d / 1e9
        # a gap is named by the program that ran before it and the one after
        mods = sorted(by_name.get(MODULE_LINE) or events, key=lambda e: e[1])
        for (n0, a0, d0), (n1, a1, _) in zip(mods, mods[1:]):
            if a1 > a0 + d0:
                gaps.append((f"{n0} -> {n1}", (a1 - a0 - d0) / 1e9))
    ranked = sorted(op_seconds.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "devices": per_device,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "device_ops": [[k, v] for k, v in ranked[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(gaps, key=lambda kv: kv[1], reverse=True)[:top]],
    }


def traced_window(profile) -> float:
    """Seconds the trace covers: the profiler stamps its start and stop on
    the `Task Environment` plane."""
    for plane in profile.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats and "profile_stop_time" in stats:
            return (stats["profile_stop_time"] - stats["profile_start_time"]) / 1e9
    raise NoDevicePlane("the trace does not say when it started and stopped; planes: "
                        + ", ".join(p.name for p in profile.planes))


def load(path: str) -> tuple:
    """(the trace's planes as tuples, the seconds it covers); host planes are
    listed without their events (the Python tracer fills them with millions)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    profile = ProfileData.from_file(path)
    planes = []
    for plane in profile.planes:
        lines = []
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [(ln.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                                for e in ln.events]) for ln in plane.lines]
        planes.append((plane.name, lines))
    return planes, traced_window(profile)


def main(argv: list) -> int:
    window = {}
    try:
        planes, seconds = load(argv[1])
        window = {"window_s": seconds}
        if len(argv) > 2:  # keep the device planes as read, for a look by hand or a test
            with open(argv[2], "w") as f:
                json.dump([p for p in planes if p[1]], f)
        print(json.dumps({**window, **reduce_planes(planes)}))
    except (NoDevicePlane, FileNotFoundError) as e:
        print(json.dumps({**window, "error": str(e)}))
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
