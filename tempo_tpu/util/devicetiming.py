"""Device dispatch timing + transfer accounting: the per-kernel half of
the waterfall, and the data-movement half of the device plane.

The ROADMAP's recurring finding is the per-op device round trip tax —
but until now nothing MEASURED it per dispatch in production, and the
timing alone conflated transfer with compute. Every host-level
pallas/mesh dispatch site wraps its call in
`timed_dispatch(label, fn, ...)`, which now does three things:

1. SHIPS host-resident (numpy) argument leaves to the device itself,
   timed separately, so the waterfall's `transfer` stage is real
   measurement, not an estimate. Leaves that are already device arrays
   ship nothing and are counted as `resident`. A mesh program
   (`parallel.mesh.mesh_program`) declares its operands' shardings:
   its host leaves go in one `device_put` straight into them, each
   device receiving its own slice, and a device leaf placed elsewhere
   is moved there explicitly at the same seam; every other program's
   leaves go to the default device.
2. Times the remaining dispatch wall clock INCLUDING
   `jax.block_until_ready` (an async dispatch that hasn't materialized
   hasn't been paid for yet) as the `kernel` stage. transfer + kernel
   partition the dispatch wall exactly, so stage sums still bound
   request wall clock.
3. SIZES the movement from the arg/result pytrees and publishes it:

  tempo_tpu_device_dispatch_seconds{kernel="..."}            histogram
      (whole dispatch: transfer + kernel execution +
       compile-cache lookup + block_until_ready; the split rides
       the waterfall's transfer/kernel stages)
  tempo_tpu_device_dispatches_total{kernel="..."}            counter
  tempo_tpu_device_transfer_bytes_total{direction,kernel}    counter
      direction: h2d (host arrays shipped), d2h (result bytes
      fetched home), resident (args already on device — counted,
      never re-shipped)
  tempo_tpu_device_ship_leaves_total{kernel,placement}      counter
      one count a host leaf shipped: placement sharded (into a mesh
      program's declared sharding) or default (the default device)

and, when a query's StageTimings accumulator is active, folds times into
its `transfer`/`kernel` stages + dispatch count, and charges the active
cost vector (`device_seconds`, `device_dispatches`, `transfer_bytes`) —
so a slow p99 can be blamed on device time OR data movement from the
dashboard, a single response's waterfall, or a tenant's bill.

Async accumulator sites (the compaction sketch planes) must NOT block
per step; they account bytes without the timing seam via
`count_transfer(kernel, h2d=..., d2h=...)` at the same statements that
update their local stats — the exactness contract (per-tenant
`transfer_bytes` sums bit-exactly to the untagged counter) holds
because the counter inc and the usage charge share one statement.

Only call timed_dispatch at HOST level (outside jit): inside a traced
program there is no wall clock to read.
"""

from __future__ import annotations

import time

import numpy as np

from tempo_tpu.util import metrics, profiling, stagetimings, usage

dispatch_hist = metrics.histogram(
    "tempo_tpu_device_dispatch_seconds",
    "Wall-clock seconds per host-level device dispatch, by kernel label "
    "(transfer + kernel execution + compile-cache lookup + "
    "block_until_ready; the transfer/kernel split rides the query "
    "waterfall stages)",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5, 5.0, 10.0),
)
dispatch_total = metrics.counter(
    "tempo_tpu_device_dispatches_total",
    "Host-level device dispatches, by kernel label",
)
transfer_bytes_total = metrics.counter(
    "tempo_tpu_device_transfer_bytes_total",
    "Bytes crossing (h2d|d2h) or parked at (resident) the host<->device "
    "boundary per host-level dispatch, by direction and kernel label",
)
ship_leaves_total = metrics.counter(
    "tempo_tpu_device_ship_leaves_total",
    "Host array leaves shipped by a host-level dispatch, by kernel label "
    "and placement: sharded (straight into a mesh program's declared "
    "sharding) or default (the default device)",
)
transfer_avoided_bytes_total = metrics.counter(
    "tempo_tpu_device_transfer_bytes_avoided_total",
    "H2D bytes NOT moved because the scan was served from the "
    "device-resident hot tier (what the host path would have shipped), "
    "by kernel label — the hot tier's measured win",
)

# jax import hoisted out of the dispatch hot path: resolved once, kept
# lazy so processes that never dispatch (a pure distributor) don't pay
# the jax import at module load
_jax = None


def _get_jax():
    global _jax
    if _jax is None:
        import jax

        _jax = jax
    return _jax


def count_transfer(kernel: str, h2d: int = 0, d2h: int = 0,
                   resident: int = 0) -> None:
    """Account data movement for one dispatch. THE exactness seam: the
    untagged direction counters and the active cost vector's
    `transfer_bytes` move here, together — attribution splits the
    measurement, it never re-measures. Resident bytes are device-side
    reuse, not movement, and are never charged as transfer."""
    if h2d:
        transfer_bytes_total.inc(h2d, direction="h2d", kernel=kernel)
    if d2h:
        transfer_bytes_total.inc(d2h, direction="d2h", kernel=kernel)
    if resident:
        transfer_bytes_total.inc(resident, direction="resident", kernel=kernel)
    moved = h2d + d2h
    if moved:
        usage.charge("transfer_bytes", moved)


def declare_ship_series(*kernels: str) -> None:
    """Publish both placements of each kernel's ship count at 0, so a
    window in which the kernel ran reads 0 rather than nothing."""
    for kernel in kernels:
        for placement in ("sharded", "default"):
            ship_leaves_total.inc(0, kernel=kernel, placement=placement)


def count_avoided(kernel: str, nbytes: int) -> None:
    """One resident-tier serve elided `nbytes` of h2d. Avoided bytes are
    the counterfactual (what the host path WOULD have shipped) — kept in
    their own counter, never mixed into the movement totals, so the
    exactness contract on transfer_bytes stays bit-true."""
    if nbytes:
        transfer_avoided_bytes_total.inc(nbytes, kernel=kernel)


def avoided_total() -> float:
    """Lifetime h2d bytes the hot tier elided."""
    return transfer_avoided_bytes_total.total()


def moved_total() -> float:
    """Untagged bytes actually moved (h2d + d2h; resident excluded) —
    what the per-tenant `transfer_bytes` vectors must sum to."""
    return (transfer_bytes_total.total(direction="h2d")
            + transfer_bytes_total.total(direction="d2h"))


def transfer_report() -> dict:
    """Per-kernel movement rollup for /status/device."""
    by_kernel: dict = {}
    totals = {"h2d": 0, "d2h": 0, "resident": 0}
    for labels, v in transfer_bytes_total.series():
        d = labels.get("direction", "")
        k = labels.get("kernel", "")
        if d not in totals:
            continue
        by_kernel.setdefault(k, {"h2d": 0, "d2h": 0, "resident": 0})[d] = int(v)
        totals[d] += int(v)
    return {
        "byKernel": by_kernel,
        "totals": {**totals, "moved": totals["h2d"] + totals["d2h"],
                   "avoided": int(avoided_total())},
        "avoidedByKernel": {
            labels.get("kernel", ""): int(v)
            for labels, v in transfer_avoided_bytes_total.series()
        },
        "dispatchesByKernel": {
            labels.get("kernel", ""): int(v)
            for labels, v in dispatch_total.series()
        },
    }


def _nbytes_of(leaf) -> int:
    n = getattr(leaf, "nbytes", None)
    return int(n) if isinstance(n, int) else 0


def timed_dispatch(kernel: str, fn, *args, ship: bool = True, **kwargs):
    """Run one host-level device dispatch under the timing + transfer
    plane.

    ship=True (default): numpy ndarray leaves of args/kwargs are put on
    device HERE (timed as the `transfer` stage, sized as h2d) and fn
    receives device arrays — callers pass host arrays and drop their own
    jnp.asarray conversions. Device-array leaves are counted `resident`.
    Where fn declares `mesh_in_shardings` (one a positional argument),
    every host leaf goes in ONE device_put into its sharding, and a
    device leaf whose sharding differs is moved there in the same put,
    so the program never reshards an operand inside the call.
    ship=False: for host-side wrapper fns that need numpy inputs (they
    convert internally); movement is sized from the pytrees but the
    transfer clock stays at zero, so all time lands in `kernel` exactly
    as before the split.

    While a profiler capture runs the same two intervals are written
    into its trace: `dispatch/<kernel>` around the whole call and a child
    `transfer/<kernel>` around the ship; the remainder is the kernel wait.

    Returns fn's result after block_until_ready. Timing failures never
    mask the dispatch's own result or error."""
    ann = None
    if profiling.capturing:
        ann = profiling.annotation(f"dispatch/{kernel}")
        ann.__enter__()
    t0 = time.perf_counter()
    transfer_s = 0.0
    h2d = d2h = resident = 0
    try:
        jax = _get_jax()
        if args or kwargs:
            shipped: list = []
            n_host = 0  # host leaves shipped
            shardings = getattr(fn, "mesh_in_shardings", None) if ship else None

            def put(leaf):
                nonlocal h2d, resident, n_host
                if isinstance(leaf, np.ndarray):
                    h2d += leaf.nbytes
                    if not ship:
                        return leaf
                    import jax.numpy as jnp

                    n_host += 1
                    dev = jnp.asarray(leaf)
                    shipped.append(dev)
                    return dev
                if isinstance(leaf, jax.Array):
                    resident += _nbytes_of(leaf)
                return leaf

            def place() -> tuple:
                # ONE device_put a dispatch: the host leaves, and any device
                # leaf outside its declared sharding, each into its sharding
                nonlocal h2d, resident, n_host
                moves = []
                for i, (leaf, sharding) in enumerate(zip(args, shardings, strict=True)):
                    if isinstance(leaf, np.ndarray):
                        h2d += leaf.nbytes
                        n_host += 1
                    elif isinstance(leaf, jax.Array):
                        resident += _nbytes_of(leaf)
                        if leaf.sharding.is_equivalent_to(sharding, leaf.ndim):
                            continue
                    else:
                        continue
                    moves.append(i)
                placed = list(args)
                if moves:
                    devs = jax.device_put([args[i] for i in moves],
                                          [shardings[i] for i in moves])
                    for i, dev in zip(moves, devs):
                        placed[i] = dev
                    shipped.extend(devs)
                return tuple(placed)

            t_ship = time.perf_counter()
            with profiling.annotation(f"transfer/{kernel}") if ann else profiling.NULL_CONTEXT:
                if shardings is None:
                    args, kwargs = jax.tree_util.tree_map(put, (args, kwargs))
                else:
                    args = place()
                if shipped:
                    # the ship isn't paid for until it materializes; closing
                    # the clock here keeps transfer EXCLUSIVE of kernel
                    jax.block_until_ready(shipped)
                    transfer_s = time.perf_counter() - t_ship
            if n_host:
                ship_leaves_total.inc(
                    n_host, kernel=kernel,
                    placement="default" if shardings is None else "sharded")
        out = fn(*args, **kwargs)
        # never raises for plain numpy/scalar/pytree results, so any
        # exception here is a REAL device failure (faulted kernel, OOM)
        # and must propagate with this dispatch's attribution — the
        # finally still records the attempt's wall clock
        jax.block_until_ready(out)
        for leaf in jax.tree_util.tree_leaves(out):
            d2h += _nbytes_of(leaf)
        return out
    finally:
        dt = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        dispatch_hist.observe(dt, kernel=kernel)
        dispatch_total.inc(kernel=kernel)
        # transfer + kernel PARTITION the dispatch wall: stage sums keep
        # bounding request wall clock after the split
        stagetimings.add("transfer", transfer_s)
        stagetimings.add("kernel", max(0.0, dt - transfer_s))
        stagetimings.count_dispatch()
        # cost plane: device time and data movement are charged to
        # whoever this dispatch serves (the worker's job vector, or
        # compaction's)
        usage.charge("device_seconds", dt)
        usage.charge("device_dispatches")
        count_transfer(kernel, h2d=h2d, d2h=d2h, resident=resident)
        if ann is not None:
            profiling.dispatch_bytes_total.inc(h2d + d2h + resident, kernel=kernel)
