"""What a mesh job costs, on /metrics: the counts of every stacked
(W, R) dispatch and the host seconds of the mesh path around it.

MeshSearcher and MeshMetricsEvaluator call `count_dispatch` at the
statement that launches a dispatch and run each job inside `job()`,
which hands them its `PhaseClock`.
The dispatch itself (transfer + kernel wall) stays
devicetiming.timed_dispatch's; the clock covers the rest of the job:

  plan     open blocks, resolve predicates, prune row groups, evaluate
           units to slot ids (metrics): everything between dispatches
  stack    pad and stack the pending units into the (W, R, ...) arrays
  wait     for the process-wide dispatch lock
  collect  sharded results back to the host and into the answer

While a profiler capture runs the same edges are the spans
`mesh/search_blocks` / `mesh/evaluate_blocks` (self time = plan) with
children `mesh/stack`, `mesh/wait` and `mesh/collect`, so the idle
partition names the mesh path's host work (a `*/wait` is blamed only
when no other thread works).
"""

from __future__ import annotations

import contextlib
import time

from tempo_tpu.util import metrics, tracing

units_total = metrics.counter(
    "tempo_tpu_mesh_units_total",
    "Row-group units stacked on the mesh, by kernel",
)
slots_total = metrics.counter(
    "tempo_tpu_mesh_slots_total",
    "Mesh slots dispatched (W*R a dispatch, filled or not), by kernel",
)
rows_total = metrics.counter(
    "tempo_tpu_mesh_rows_total",
    "Rows shipped to the mesh, by kernel: kind=valid carried data, "
    "kind=padded is slots x the dispatch's bucket (valid included)",
)
collective_bytes_total = metrics.counter(
    "tempo_tpu_mesh_collective_bytes_total",
    "Bytes each dispatch's psum over the range axis reduces, from the "
    "shapes, by kernel",
)
shard_rows_total = metrics.counter(
    "tempo_tpu_mesh_shard_rows_total",
    "Valid rows stacked on each mesh slot (shard = index in the "
    "flattened (W, R) mesh)",
)
seconds_total = metrics.counter(
    "tempo_tpu_mesh_seconds_total",
    "Host seconds of the mesh path outside its dispatches, by kernel and "
    "phase: plan, stack, wait (for the dispatch lock), collect",
)


def count_dispatch(kernel: str, units: int, shard_rows, pad: int,
                   collective_bytes: int) -> None:
    """One stacked dispatch: `units` of the len(shard_rows) slots carry
    a row group, slot s holds shard_rows[s] valid rows of `pad`."""
    units_total.inc(units, kernel=kernel)
    slots_total.inc(len(shard_rows), kernel=kernel)
    rows_total.inc(int(sum(shard_rows)), kernel=kernel, kind="valid")
    rows_total.inc(len(shard_rows) * pad, kernel=kernel, kind="padded")
    collective_bytes_total.inc(collective_bytes, kernel=kernel)
    for s, n in enumerate(shard_rows):
        if n:
            shard_rows_total.inc(int(n), shard=str(s))


@contextlib.contextmanager
def job(name: str):
    """One mesh job: the span `mesh/<name>` around it and the clock of
    its host phases; what is left when it ends (verdicts, sort, merge)
    is the tail of `plan`."""
    clock = PhaseClock()
    with tracing.span(f"mesh/{name}"):
        try:
            yield clock
        finally:
            clock.lap("plan")


class PhaseClock:
    """perf_counter at the phase edges of one mesh job: `lap` gives the
    time since the last edge to a phase, `skip` moves the edge without
    counting (a dispatch: timed_dispatch's)."""

    def __init__(self):
        self.t = time.perf_counter()
        self.kernel = "none"  # the last dispatch's: where a job's tail goes

    def lap(self, phase: str, kernel: str | None = None) -> None:
        now = time.perf_counter()
        if kernel is not None:
            self.kernel = kernel
        seconds_total.inc(now - self.t, kernel=self.kernel, phase=phase)
        self.t = now

    def skip(self) -> None:
        self.t = time.perf_counter()

    @contextlib.contextmanager
    def phase(self, phase: str):
        """A span `mesh/<phase>` whose time goes to `phase`."""
        with tracing.span(f"mesh/{phase}"):
            try:
                yield
            finally:
                self.lap(phase)

    @contextlib.contextmanager
    def dispatching(self, lock):
        """Hold `lock` around one dispatch: the wait for it is the
        `wait` phase, the time under it the dispatch's own."""
        with self.phase("wait"):
            lock.acquire()
        try:
            yield
        finally:
            lock.release()
            self.skip()
