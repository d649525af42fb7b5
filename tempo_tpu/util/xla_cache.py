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
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger(__name__)

_done = False


def default_cache_dir() -> str:
    """`<checkout>/.jax_cache`: beside the tempo_tpu package."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def ensure_persistent_cache() -> None:
    global _done
    if _done:
        return
    _done = True
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
