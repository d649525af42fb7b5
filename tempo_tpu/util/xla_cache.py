"""Persistent XLA compilation cache for the engine's jitted kernels.

The block writer / compactor jits are keyed on static plans (bloom
geometry, HLL precision, shape buckets), and a compaction sweep walks
through several plans as levels deepen — each a fresh XLA compile.
JAX's persistent cache amortizes those compiles across jobs AND
processes, which is exactly the reference's steady-state: a long-lived
compactor daemon never re-pays codegen.

Placement comes from outside: when JAX_COMPILATION_CACHE_DIR is set JAX
itself reads it and no directory is set in code; otherwise the cache
lives at `<checkout>/.jax_cache` — a fixed path derived from the
package's location (the path is part of the cache key, so a directory
that moves never hits). Armed whenever the resolved backend is not the
CPU (util/backend.platform()): CPU kernel compiles are cheap, and
XLA:CPU AOT artifacts embed host machine features — reloading them
warns (and can SIGILL) if the feature probe shifts.
Opt-out with TEMPO_TPU_XLA_CACHE=0.

Arming also starts the count of every jit compile of the process
(jax.monitoring): tempo_tpu_jit_compiles_total{source}, backend compiles
and loads from the persistent cache apart. Either means a program was not
ready when asked; only a backend compile stalls its caller for seconds.
"""

from __future__ import annotations

import logging
import os
import threading

from tempo_tpu.util import metrics, profiling

log = logging.getLogger(__name__)

_done = False

jit_compiles_total = metrics.counter(
    "tempo_tpu_jit_compiles_total",
    "Programs jit had to make ready, by source: backend (XLA compiled it) "
    "or cache (loaded from the persistent compilation cache)",
)
for _source in ("backend", "cache"):  # both series from the first scrape on
    jit_compiles_total.inc(0, source=_source)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compiling = threading.local()  # .hit: a cache load ran; .open: compile/<fun> annotations


def _on_compile_start(event: str, _start: float, fun_name: str = "", **_kw) -> None:
    """A compile begins (jax records its start as a scalar): while a device
    profiler capture runs, open `compile/<fun>` on this thread."""
    if event == _COMPILE_EVENT and profiling.capturing:
        ann = profiling.annotation(f"compile/{fun_name}")
        ann.__enter__()
        _compiling.open = getattr(_compiling, "open", []) + [ann]


def _on_duration(event: str, _seconds: float, **_kw) -> None:
    """jax reports the load from the persistent cache inside the compile
    event of the same program, on the same thread."""
    if event == _CACHE_HIT_EVENT:
        _compiling.hit = True
    elif event == _COMPILE_EVENT:
        source = "cache" if getattr(_compiling, "hit", False) else "backend"
        _compiling.hit = False
        jit_compiles_total.inc(source=source)
        if getattr(_compiling, "open", None):
            _compiling.open.pop().__exit__(None, None, None)


def default_cache_dir() -> str:
    """`<checkout>/.jax_cache`: beside the tempo_tpu package."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def ensure_persistent_cache() -> None:
    global _done
    if _done:
        return
    _done = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_scalar_listener(_on_compile_start)
    if os.environ.get("TEMPO_TPU_XLA_CACHE", "1").strip().lower() in ("0", "false", "no"):
        return
    import jax

    from tempo_tpu.util import backend

    if backend.platform() == "cpu":
        return
    try:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            path = default_cache_dir()
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        # a chip call starts with no compiled code and every plan costs
        # a compile: cache everything, however small or quick
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    except OSError as e:  # unwritable checkout
        log.warning(
            "persistent XLA cache disabled (%s); every new kernel plan will "
            "re-pay its compile — set JAX_COMPILATION_CACHE_DIR to a writable "
            "path or TEMPO_TPU_XLA_CACHE=0 to silence",
            e,
        )
