"""A mesh dispatch ships its operands into the mesh's sharding.

The `shard_map` programs of `parallel/` declare a `NamedSharding` for
every operand (`parallel.mesh.mesh_program`), and `timed_dispatch` puts
a dispatch's host leaves straight into them in one `device_put`: each
device receives its own slice, and no call reshards an operand that
landed on the default device. Every mesh read path runs here inside
`jax.transfer_guard_device_to_device("disallow")`, which raises on an
implicit device-to-device copy, and answers as the one-device path
does; `tempo_tpu_device_ship_leaves_total{placement}` counts each host
leaf where it went.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from tempo_tpu.backend import LocalBackend, MockBackend, TypedBackend
from tempo_tpu.db import DBConfig, TempoDB
from tempo_tpu.encoding import from_version
from tempo_tpu.encoding.common import BlockConfig, SearchRequest
from tempo_tpu.encoding.vtpu import colcache
from tempo_tpu.metrics_engine import HostAccumulator, compile_metrics_plan
from tempo_tpu.metrics_engine.evaluate import evaluate_block
from tempo_tpu.model import synth
from tempo_tpu.parallel import search as mesh_search
from tempo_tpu.parallel import metrics as mesh_metrics
from tempo_tpu.parallel.mesh import RANGE_AXIS, WINDOW_AXIS, compaction_mesh
from tempo_tpu.parallel.metrics import MeshMetricsEvaluator
from tempo_tpu.parallel.search import MeshSearcher, make_sharded_rle_scan
from tempo_tpu.util import devicetiming

BASE_S = 1_700_000_000
MESH_KERNELS = ("mesh_rle_scan", "mesh_scan", "batched_rle_scan", "mesh_bincount")


@pytest.fixture(scope="module")
def mesh():
    return compaction_mesh(4)  # S's (window=1, range=4)


@pytest.fixture(scope="module")
def store():
    """Six one-row-group blocks whose service pages are rle: two stacked
    dispatches a scan on the (1, 4) mesh."""
    db = TempoDB(DBConfig(backend="mock"), raw_backend=MockBackend())
    services = set()
    for i in range(6):
        batch = synth.make_batch(48, 16, seed=4600 + i).sorted_by_trace()
        db.write_batch("t", batch)
        services |= {batch.dictionary[int(c)] for c in np.unique(batch.cols["service"])}
    return db, sorted(services)


def _blocks(db):
    enc = from_version("vtpu1")
    return [enc.open_block(m, db.backend, db.cfg.block) for m in db.blocklist.metas("t")]


def _one_device(db, req):
    """The same search answered block by block on one device."""
    hits = set()
    for blk in _blocks(db):
        hits |= {h.trace_id_hex for h in blk.search(req).traces}
    return hits


def _ids(resp):
    return {t.trace_id_hex for t in resp.traces}


def _ships():
    return {(labels["kernel"], labels["placement"]): v
            for labels, v in devicetiming.ship_leaves_total.series()}


def _grew(before):
    return {k: v - before.get(k, 0.0) for k, v in _ships().items()
            if v != before.get(k, 0.0)}


@pytest.fixture
def host_leaves(monkeypatch):
    """Host leaves handed to the mesh dispatches, by kernel: what the
    sharded ship count has to grow by."""
    seen: dict = {}
    real = devicetiming.timed_dispatch

    def spy(kernel, fn, *args, **kwargs):
        n = sum(isinstance(a, np.ndarray) for a in jax.tree_util.tree_leaves((args, kwargs)))
        seen[kernel] = seen.get(kernel, 0) + n
        return real(kernel, fn, *args, **kwargs)

    monkeypatch.setattr(mesh_search, "timed_dispatch", spy)
    monkeypatch.setattr(mesh_metrics, "timed_dispatch", spy)
    return seen


def _sharded_only(grew, seen):
    """Every host leaf of the mesh dispatches went into the mesh's
    sharding, and none to the default device."""
    assert seen and all(n > 0 for n in seen.values())
    assert {k: v for k, v in grew.items() if k[0] in MESH_KERNELS} == {
        (kernel, "sharded"): n for kernel, n in seen.items()}


@pytest.mark.parametrize("path,kernel", [("rle", "mesh_rle_scan"), ("expanded", "mesh_scan")])
def test_search_blocks_ships_into_the_mesh(mesh, store, host_leaves, monkeypatch, path, kernel):
    db, services = store
    if path == "expanded":  # predicate pages expand: the row path, mesh_scan
        monkeypatch.setenv("TEMPO_TPU_RUNSPACE", "0")
    searcher = MeshSearcher(mesh, db.cfg.block.bucket_for)
    for svc in services[:3]:
        req = SearchRequest(tags={"service.name": svc}, limit=0)
        before = _ships()
        with jax.transfer_guard_device_to_device("disallow"):
            got = searcher.search_blocks(_blocks(db), req)
        _sharded_only(_grew(before), host_leaves)
        assert set(host_leaves) == {kernel}
        assert _ids(got) == _one_device(db, req) and _ids(got)
        host_leaves.clear()


def test_search_blocks_multi_ships_into_the_mesh(mesh, store, host_leaves):
    db, services = store
    reqs = [SearchRequest(tags={"service.name": s}, limit=0) for s in services[:3]]
    reqs.append(SearchRequest(tags={"service.name": services[0]}, min_duration_ns=1, limit=0))
    searcher = MeshSearcher(mesh, db.cfg.block.bucket_for)
    before = _ships()
    with jax.transfer_guard_device_to_device("disallow"):
        multi = searcher.search_blocks_multi(_blocks(db), reqs)
    _sharded_only(_grew(before), host_leaves)
    assert set(host_leaves) == {"batched_rle_scan"}
    for req, got in zip(reqs, multi):
        assert _ids(got) == _one_device(db, req)
    assert any(_ids(r) for r in multi)


@pytest.mark.parametrize("q", ["{} | rate() by (resource.service.name)",
                               "{ duration > 1ms } | quantile_over_time(duration, 0.5, 0.9)"])
def test_query_range_ships_into_the_mesh(mesh, host_leaves, tmp_path, q):
    backend = TypedBackend(LocalBackend(str(tmp_path)))
    enc = from_version("vtpu1")
    cfg = BlockConfig(row_group_spans=1024)
    metas = [enc.create_block([synth.make_batch(300, 8, seed=4610 + i)], "t", backend, cfg)
             for i in range(3)]
    plan = compile_metrics_plan(q, BASE_S, BASE_S + 60, 10)
    want = HostAccumulator(plan)
    for m in metas:
        evaluate_block(plan, enc.open_block(m, backend, cfg), want)
    got = HostAccumulator(plan)
    before = _ships()
    with jax.transfer_guard_device_to_device("disallow"):
        MeshMetricsEvaluator(mesh, cfg.bucket_for).evaluate_blocks(
            (enc.open_block(m, backend, cfg) for m in metas), plan, got)
    _sharded_only(_grew(before), host_leaves)
    assert set(host_leaves) == {"mesh_bincount"}
    assert (got.counts == want.counts).all() and got.counts.sum() > 0
    assert got.series.slots == want.series.slots


@pytest.fixture
def device_tier():
    """A private DeviceTier, admission forced open, installed as the
    process tier for one test."""
    tier = colcache.DeviceTier(32 << 20, refresh_s=3600.0)
    tier.should_admit = lambda page_keys: True
    old = colcache._shared_device
    colcache._shared_device = tier
    try:
        yield tier
    finally:
        colcache._shared_device = old


def test_resident_mesh_stack_ships_and_reshards_nothing(mesh, store, device_tier):
    db, services = store
    req = SearchRequest(tags={"service.name": services[0]}, limit=0)
    searcher = MeshSearcher(mesh, db.cfg.block.bucket_for)
    cold = searcher.search_blocks(_blocks(db), req)  # admits the stacks
    stacks = [res for key, res in device_tier._lru.items() if key[0] == "mesh_stack"]
    assert stacks, "the mesh stacks were not admitted"
    want = NamedSharding(mesh, P(WINDOW_AXIS, RANGE_AXIS))
    for res in stacks:
        for a in res.arrays.values():
            assert a.sharding.is_equivalent_to(want, a.ndim)

    hits0 = device_tier.hits
    h2d0 = devicetiming.transfer_bytes_total.total(direction="h2d", kernel="mesh_rle_scan")
    before = _ships()
    # even an explicit copy between devices raises here: the resident
    # payload is where the program wants it
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        hot = searcher.search_blocks(_blocks(db), req)
    grew = _grew(before)
    assert device_tier.hits > hits0
    dispatches = searcher.last_stats["dispatches"]
    assert grew == {("mesh_rle_scan", "sharded"): 2 * dispatches}  # codes and valid only
    h2d = devicetiming.transfer_bytes_total.total(direction="h2d", kernel="mesh_rle_scan") - h2d0
    assert h2d == searcher.last_stats["h2d_bytes"]  # the payload stayed on the devices
    assert _ids(hot) == _ids(cold) == _one_device(db, req)


def test_a_misplaced_device_operand_is_placed_at_the_seam(mesh):
    """A device operand outside the program's sharding is moved
    explicitly by the dispatch, never resharded inside the call; it
    ships no host leaf."""
    w, r = mesh.devices.shape
    scan = make_sharded_rle_scan(mesh, 1, 4, 64)
    values = np.full((w, r, 1, 8), 0xFFFFFFFF, np.uint32)
    values[..., 0] = 7
    lengths = np.zeros((w, r, 1, 8), np.int32)
    lengths[..., 0] = 64
    codes = np.full((w, r, 1, 4), 0xFFFFFFFF, np.uint32)
    codes[..., 0] = 7
    valid = np.ones((w, r, 64), bool)
    on_chip0 = jax.device_put(values, jax.devices()[0])
    before = _ships()
    with jax.transfer_guard_device_to_device("disallow"):
        masks, hits = devicetiming.timed_dispatch(
            "mesh_rle_scan", scan, on_chip0, lengths, codes, valid)
    assert _grew(before) == {("mesh_rle_scan", "sharded"): 3}
    assert np.asarray(masks).all() and int(np.asarray(hits).sum()) == w * r * 64
    with pytest.raises(ValueError):  # the program itself never takes it as it is
        scan(on_chip0, lengths, codes, valid)


def test_a_one_device_kernel_ships_to_the_default_device():
    prog = jax.jit(lambda a, b: a * 2 + b)
    a, b = np.arange(16, dtype=np.int32), np.ones(16, np.int32)
    before = _ships()
    out = devicetiming.timed_dispatch("one_device_probe", prog, a, b)
    assert _grew(before) == {("one_device_probe", "default"): 2}
    np.testing.assert_array_equal(np.asarray(out), a * 2 + b)
    assert out.sharding.is_equivalent_to(
        jax.sharding.SingleDeviceSharding(jax.devices()[0]), out.ndim)


def test_both_placements_exist_at_zero_for_every_mesh_kernel():
    series = _ships()
    for kernel in MESH_KERNELS:
        for placement in ("sharded", "default"):
            assert (kernel, placement) in series
