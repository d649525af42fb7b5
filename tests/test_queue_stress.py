"""RequestQueue fairness/starvation/churn stress.

Satellite of the PR-8 overload control plane: one heavy tenant flooding
the queue, trickle tenants submitting occasionally, and churn tenants
appearing/draining continuously. Asserts the three properties the
round-robin + pruning design promises:

- no starvation: every trickle job is served despite the flood,
- bounded wait: a trickle job never waits more than ~one rotation of
  the active tenant set behind the heavy tenant's backlog,
- bounded state: after the churn, `_queues`/`_rr` hold only tenants
  with queued jobs (the pre-PR-8 implementation grew them forever and
  scanned every dead tenant on each dequeue).
"""

from __future__ import annotations

import threading
import time

from tempo_tpu.modules.queue import RequestQueue, TooManyRequests


class TestQueueFairnessStress:
    def test_heavy_tenant_cannot_starve_trickle_tenants(self):
        q = RequestQueue(max_per_tenant=10_000)
        n_heavy = 2_000
        trickle_tenants = [f"trickle-{i}" for i in range(5)]
        n_active = len(trickle_tenants) + 1
        # the queue's own serve order. `step` makes "enqueue + stamp" and
        # "dequeue + record" one step each, so a job's wait is counted in
        # serves and never read off a clock: a saturated host stretches
        # every sleep below and changes none of the counts (ROADMAP C11)
        order: list[tuple] = []
        step = threading.Lock()
        stop = threading.Event()

        for i in range(n_heavy):
            q.enqueue("heavy", ("heavy", i))

        def consumer():
            while not stop.is_set():
                with step:
                    item = q.dequeue(timeout=0)
                    if item is not None:
                        order.append(item[1])
                # simulate work (or an idle poll) so producers interleave
                time.sleep(0.0002 if item is not None else 0.001)

        def trickle_producer(tenant: str):
            for i in range(20):
                with step:
                    ahead = q.lengths().get(tenant, 0)  # its own jobs still queued
                    q.enqueue(tenant, (tenant, i, len(order), ahead))
                time.sleep(0.002)

        consumers = [threading.Thread(target=consumer, daemon=True) for _ in range(3)]
        producers = [
            threading.Thread(target=trickle_producer, args=(t,), daemon=True)
            for t in trickle_tenants
        ]
        for t in consumers:
            t.start()
        for t in producers:
            t.start()
        for t in producers:
            t.join()

        want = 20 * len(trickle_tenants)
        deadline = time.monotonic() + 120  # a guard against a hang, not a bound
        while time.monotonic() < deadline:
            with step:
                if sum(1 for job in order if job[0] != "heavy") == want or not q.depth():
                    break
            time.sleep(0.01)
        stop.set()
        for t in consumers:
            t.join(timeout=5)

        served: dict[str, int] = {}
        for job in order:
            served[job[0]] = served.get(job[0], 0) + 1
        for t in trickle_tenants:
            assert served.get(t, 0) == 20, f"{t} starved: {served.get(t, 0)}/20 served"
        # the flood never drained: every trickle job below was served while
        # the heavy tenant still had a backlog (a FIFO queue would have
        # served all 2000 first)
        assert served["heavy"] < n_heavy
        # bounded wait, in serves: a job joins behind the round-robin
        # cursor, so the other active tenants are served at most once each
        # before it, once more for every job of its own tenant ahead of it
        for pos, job in enumerate(order):
            if job[0] == "heavy":
                continue
            tenant, i, enqueued_at, ahead = job
            waited = pos - enqueued_at
            assert waited < (ahead + 1) * n_active, (
                f"{tenant} job {i} waited {waited} serves with {ahead} of its own ahead")
        # no reverse starvation: with a backlog the heavy tenant has its
        # turn in every rotation, so the trickles are never served more
        # than once each between two of its jobs
        run = worst = 0
        for job in order:
            run = 0 if job[0] == "heavy" else run + 1
            worst = max(worst, run)
        assert worst <= len(trickle_tenants), f"{worst} trickle serves in a row"

    def test_tenant_churn_does_not_grow_state(self):
        """10k one-shot tenants through a live consumer: the tenant maps
        must end empty, not remember every ID ever seen."""
        q = RequestQueue(max_per_tenant=10)
        drained = []
        stop = threading.Event()

        def consumer():
            while not stop.is_set():
                item = q.dequeue(timeout=0.05)
                if item is not None:
                    drained.append(item[0])

        threads = [threading.Thread(target=consumer, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        for i in range(10_000):
            q.enqueue(f"churn-{i}", i)
        deadline = time.monotonic() + 20
        while len(drained) < 10_000 and time.monotonic() < deadline:
            time.sleep(0.02)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert len(drained) == 10_000
        assert q.tenant_count() == 0
        assert q._rr == [] and q._queues == {}

    def test_concurrent_churn_with_backpressure(self):
        """Producers racing consumers under tiny per-tenant caps: no job
        is lost or duplicated, rejections are the only losses, and the
        state maps end empty."""
        q = RequestQueue(max_per_tenant=4)
        accepted: list = []
        acc_lock = threading.Lock()
        drained: list = []
        drain_lock = threading.Lock()
        stop = threading.Event()

        def producer(pid: int):
            for i in range(500):
                key = (pid, i)
                try:
                    q.enqueue(f"tenant-{pid}-{i % 7}", key)
                except TooManyRequests:
                    continue
                with acc_lock:
                    accepted.append(key)

        def consumer():
            while not stop.is_set():
                item = q.dequeue(timeout=0.05)
                if item is not None:
                    with drain_lock:
                        drained.append(item[1])

        consumers = [threading.Thread(target=consumer, daemon=True) for _ in range(3)]
        producers = [threading.Thread(target=producer, args=(p,), daemon=True)
                     for p in range(4)]
        for t in consumers + producers:
            t.start()
        for t in producers:
            t.join(timeout=15)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with acc_lock, drain_lock:
                if len(drained) >= len(accepted):
                    break
            time.sleep(0.02)
        stop.set()
        for t in consumers:
            t.join(timeout=5)
        assert sorted(drained) == sorted(accepted), "accepted == drained exactly once"
        assert q.tenant_count() == 0 and q._rr == []

    def test_round_robin_order_preserved_across_prune(self):
        """Single-threaded determinism: removing a drained tenant must
        not skip or double-serve the survivors."""
        q = RequestQueue()
        for t in ("a", "b", "c"):
            for i in range(2 if t == "b" else 3):
                q.enqueue(t, f"{t}{i}")
        got = []
        while True:
            item = q.dequeue(timeout=0.01)
            if item is None:
                break
            got.append(item[1])
        # rotation a,b,c repeats; b drains after round 2 and the a/c
        # rotation continues seamlessly
        assert got == ["a0", "b0", "c0", "a1", "b1", "c1", "a2", "c2"]
        assert q._rr == []
