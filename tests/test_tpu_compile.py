"""The resident scan programs compile for a v5e at the benchmark's own
widths: the TPU's compiler is installed here and compiles for a chip that
is described, not attached. Nothing runs, so this says nothing of results
or times; it refuses what the chip's compiler would refuse. All such
compiles live in THIS file: the worker that is handed it loads the TPU's
library, and no other may (the topology is described inside a fixture,
never at import)."""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from tempo_tpu.ops import scan

N = 1 << 15  # a full row group


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Such a compile is written to the persistent cache and cannot be
    read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()


@pytest.mark.parametrize("k", [1, 8, 16])
@pytest.mark.parametrize("codec", ["rle", "dct", "dbp"])
def test_resident_scan_programs_compile_for_v5e(one_chip, no_compile_cache, codec, k):
    """k pages of one shape bucket in one program, (k, N) bool out."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pages(width, dtype):
        return tuple(s((width,), dtype) for _ in range(k))

    u32, codes = s((), jnp.uint32), s((4,), jnp.uint32)
    if codec == "rle":
        runs = (pages(4096, jnp.uint32), pages(4096, jnp.int32))
        lowered = [scan._rle_in_set_resident_jit.lower(*runs, codes, n=N, invert=False),
                   scan._rle_between_resident_jit.lower(*runs, u32, u32, n=N)]
    elif codec == "dct":
        page = (pages(64, jnp.uint32), pages(N, jnp.int32))
        lowered = [scan._dct_in_set_resident_jit.lower(*page, codes, invert=True),
                   scan._dct_between_resident_jit.lower(*page, u32, u32)]
    else:
        lowered = [scan._dbp_between_resident_jit.lower(
            pages(N, jnp.uint32), s((k, 3), jnp.uint32), s((4,), jnp.uint32), n=N)]
    for low in lowered:
        out, = jax.tree_util.tree_leaves(low.compile().out_info)
        assert out.shape == (k, N) and out.dtype == jnp.bool_
