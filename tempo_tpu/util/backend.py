"""The one place that decides "is there an accelerator", and says so.

Every device arm that defaults from the hardware — device page encode
(ops/encode), the Pallas bincount behind query_range and standing folds
(ops/pallas_kernels, metrics_engine/evaluate, standing/engine), the
graph critical-path kernel (ops/graph), compiled-vs-interpreted Pallas,
and the persistent compile cache (util/xla_cache) — asks this module,
so a process that resolved to the CPU turns every arm off together and
`describe()` reports exactly what the gates saw. App.__init__ logs it
and /status/device serves it under `backend`.

A run that measures has no CPU fallback: `check_measurable` refuses any
platform but "tpu" unless the caller asked for a CPU dry run by name
(`--cpu-dry-run`), because a time taken on the CPU backend says nothing
about the device and must never be read as one. An environment that
merely pins JAX_PLATFORMS=cpu is not that opt-in.
"""

from __future__ import annotations

import functools


class NoAccelerator(RuntimeError):
    """A measurement entry point resolved to something other than a TPU
    and the caller did not opt in to the CPU."""


@functools.cache
def platform() -> str:
    """JAX's resolved default backend ("tpu", "cpu", "gpu"). Initializes
    the backend on first call; it cannot change afterwards."""
    import jax

    return jax.default_backend()


def on_accelerator() -> bool:
    """True when the device arms default on: this process holds a TPU."""
    return platform() == "tpu"


def check_measurable(found: str, cpu_ok: bool) -> None:
    """Raise NoAccelerator unless `found` (a resolved platform name) is
    "tpu", or is "cpu" and the entry point's caller opted in to a CPU
    dry run (`cpu_ok`)."""
    if found == "tpu" or (found == "cpu" and cpu_ok):
        return
    raise NoAccelerator(
        f"JAX resolved platform {found!r}, not 'tpu': refusing to measure "
        "(there is no CPU fallback; a CPU dry run must be asked for "
        "explicitly, is labelled cpu and carries no device metric)"
    )


def describe() -> dict:
    """What this process resolved: the boot log line and the `backend`
    section of /status/device. Per-device memory is reported where the
    backend has it (`memory_stats()` is None on the CPU backend)."""
    import jax

    from tempo_tpu import native
    from tempo_tpu.encoding.vtpu import codec

    devices = []
    for d in jax.devices():
        doc = {"id": d.id, "device_kind": d.device_kind}
        stats = d.memory_stats()
        if stats:
            doc["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
            doc["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
        devices.append(doc)
    return {
        "platform": platform(),
        "device_kind": devices[0]["device_kind"],
        "device_count": len(devices),
        "pallas": "compiled" if on_accelerator() else "interpret",
        "native_codec": native.lib() is not None,
        "default_codec": codec.best_codec(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir or "",
        "devices": devices,
    }
