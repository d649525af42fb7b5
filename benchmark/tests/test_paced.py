"""A role that arrives at a stated rate (`arrival_rate_per_s`, k6's
`constant-arrival-rate`): the schedule, the drop rule and the requests
sent, on a fake clock and a fake exchange, so nothing here reads the wall
clock; and a file without the key, which is left closed-loop."""

import hashlib
import threading
import time

import numpy as np
import pytest

import corpus
import mixed_cell
import traffic as tr

BASE_S = 1_700_000_040
SEED = 3000000029


def source(traffic: dict) -> tr.Source:
    traffic["pool_bodies"] = 4
    pool = tr.make_pool(traffic, SEED, 16, BASE_S + 600)
    block = corpus.make_block(64, 16, [SEED], BASE_S * 10**9)
    return tr.Source(traffic, ["single-tenant"], False, BASE_S,
                     {"single-tenant": np.array(corpus.trace_hex(block), dtype=object)}, pool)


def digest(req: tr.Request) -> tuple:
    body = hashlib.sha256(req.body).hexdigest() if req.body is not None else "-"
    return req.method, req.path, body, req.expect


class FakeTime:
    """The pacer's clock and sleep, and an exchange that takes `service_s` of
    that clock. `sleep` lets every client handed an arrival begin its send,
    moves the clock on, then lets every send due to end by then end and hand
    its client back, so that which client is free at an arrival is decided
    by the fake clock alone."""

    def __init__(self, service_s: float):
        self.now, self.service_s = 1000.0, service_s
        self.cv = threading.Condition()
        self.ends: dict = {}  # client number -> when its send ends
        self.sent: list = []  # (client number, when, request digest)
        self.pacer = None

    def clock(self) -> float:
        return self.now

    def _settle(self, ok) -> None:
        deadline = time.monotonic() + 20
        while True:
            with self.pacer._lock:
                idle = {c.n for c in self.pacer._idle}
            with self.cv:
                if all(c.n in idle or ok(self.ends.get(c.n)) for c in self.pacer.clients):
                    return
            assert time.monotonic() < deadline, "the paced clients did not settle"
            time.sleep(0.0005)

    def sleep(self, seconds: float) -> None:
        self._settle(lambda end: end is not None)
        with self.cv:
            self.now += seconds
            self.cv.notify_all()
        self._settle(lambda end: end is not None and end > self.now)

    def finish(self) -> None:
        """After the last arrival: every send in flight ends."""
        with self.cv:
            self.now = float("inf")
            self.cv.notify_all()

    def exchange(self, client, req, headers):
        with self.cv:
            end = self.now + self.service_s
            self.ends[client.n] = end
            self.sent.append((client.n, self.now, digest(req)))
            assert self.cv.wait_for(lambda: self.now >= end, timeout=20)
            del self.ends[client.n]
        return 200, b""


def run_paced(monkeypatch, expected, rate=2.0, clients=4, seconds=40.0, service_s=0.1,
              hold=None, set_hold_at=None, wake_late=None):
    """One paced writer role over `seconds` of the fake clock, run until it has
    had `expected` arrivals; (pacer, fake, the clients' logs). `wake_late`:
    (t, s), the pacer's first sleep that ends at or after `t` on the fake
    clock ends `s` later."""
    fake = FakeTime(service_s)
    monkeypatch.setattr(tr.Client, "exchange", lambda self, req, headers:
                        fake.exchange(self, req, headers))
    _, _, _, traffic = mixed_cell.load_cell()
    role = dict(tr.roles_of(traffic)[0], clients=clients, arrival_rate_per_s=rate)
    logs = [[] for _ in range(clients)]
    [pacer] = tr.role_threads([role], 0, SEED, source(traffic), 0, logs, hold)
    pacer.clock = fake.clock
    pacer.sleep = fake.sleep
    if set_hold_at is not None:
        def sleep(s):  # the capture comes back at `set_hold_at` on the fake clock
            fake.sleep(s)
            if fake.now >= set_hold_at:
                hold.set()
        pacer.sleep = sleep
    if wake_late is not None:
        late = list(wake_late)
        def sleep(s):  # held once, as by the interpreter or a capture
            if late and fake.now + s >= late[0]:
                s += late.pop(1)
                late.clear()
            fake.sleep(s)
        pacer.sleep = sleep
    fake.pacer = pacer
    pacer.stop_at = fake.now + seconds
    pacer.start()
    deadline = time.monotonic() + 60
    while pacer.arrived < expected:
        assert time.monotonic() < deadline and pacer.is_alive(), pacer.arrived
        time.sleep(0.001)
    fake._settle(lambda end: end is not None)  # the last arrival's send has begun
    fake.finish()
    pacer.join(timeout=20)
    assert not pacer.is_alive()
    return pacer, fake, logs


def test_arrivals_fall_at_k_over_rate_and_each_finds_a_free_client(monkeypatch):
    pacer, fake, logs = run_paced(monkeypatch, 80)
    assert pacer.arrived == 80 and pacer.dropped == 0  # ceil(2/s x 40 s)
    assert [t - 1000.0 for _, t, _ in fake.sent] == [k / 2 for k in range(80)]
    # the client free longest takes the arrival: round the pool in order
    assert [n for n, _, _ in fake.sent] == [k % 4 for k in range(80)]
    assert sum(len(log) for log in logs) == 80
    assert all(r.ok and r.req.op == "push" for log in logs for r in log)


def test_an_arrival_that_finds_every_client_busy_is_dropped(monkeypatch):
    # 4 clients, 2 arrivals a second, 3 s a send: each 3 s of arrivals (6) finds 4 free
    pacer, fake, logs = run_paced(monkeypatch, 80, service_s=3.0)
    assert pacer.arrived == 80
    sent = [t - 1000.0 for _, t, _ in fake.sent]
    assert sent == [k / 2 for k in range(80) if k % 6 < 4]
    assert pacer.dropped == 26 and len(sent) == 54 == sum(len(log) for log in logs)
    # a dropped arrival is never sent later: no two sends leave at one time
    assert len(set(sent)) == len(sent)


def test_a_pacer_that_wakes_late_skips_what_is_overdue_and_sends_no_burst(monkeypatch):
    # the sleep to the arrival due at 5.0 s ends at 6.3 s: the arrivals due at 5.0
    # and 5.5 s have their successors due by then, the one due at 6.0 s has not
    pacer, fake, logs = run_paced(monkeypatch, 80, wake_late=(1005.0, 1.3))
    assert pacer.arrived == 80 and pacer.missed == 2 and pacer.dropped == 0
    sent = [round(t - 1000.0, 6) for _, t, _ in fake.sent]
    assert sent == [k / 2 for k in range(10)] + [6.3] + [k / 2 for k in range(13, 80)]
    assert sum(len(log) for log in logs) == 78
    # no send leaves later than the next arrival's due time, so none two at once
    assert all(b - a > 0.1 for a, b in zip(sent, sent[1:]))


def test_a_traced_run_keeps_arriving_until_the_capture_is_back(monkeypatch):
    hold = threading.Event()
    pacer, fake, _ = run_paced(monkeypatch, 101, hold=hold, set_hold_at=1050.0)
    assert pacer.arrived == 101 and pacer.dropped == 0  # due 0 .. 50 s: the hold is set at 50


@pytest.mark.parametrize("rate", [0.5, 2.0, 3.0])
def test_a_window_offers_the_ceiling_of_rate_times_seconds(monkeypatch, rate):
    expected = int(np.ceil(rate * 10.5))  # 6, 21, 32
    pacer, _, _ = run_paced(monkeypatch, expected, rate=rate, seconds=10.5)
    assert pacer.arrived == expected


def test_a_paced_client_sends_what_a_closed_loop_client_of_its_number_sends(monkeypatch):
    pacer, fake, _ = run_paced(monkeypatch, 40, seconds=20.0)
    _, _, _, traffic = mixed_cell.load_cell()
    writer, reader = tr.roles_of(traffic)
    closed = {n: tr.Client(n, SEED, source(traffic), 0, [], role=writer) for n in range(4)}
    by_client: dict = {}
    for n, _, d in fake.sent:
        by_client.setdefault(n, []).append(d)
    assert sorted(by_client) == [0, 1, 2, 3]
    for n, sent in by_client.items():
        assert sent == [digest(closed[n].next_request()) for _ in sent], n
    # and a reader's deck, drawn without a send (its finds of acknowledged pushes
    # depend on what was acknowledged, so none has been)
    src = source(traffic)
    paced = tr.PacedClient(5, SEED, src, 0, [], role=reader)
    plain = tr.Client(5, SEED, src, 0, [], role=reader)
    assert [digest(paced.next_request()) for _ in range(300)] == [
        digest(plain.next_request()) for _ in range(300)]


def test_a_role_without_a_rate_stays_closed_loop():
    _, _, _, traffic = mixed_cell.load_cell()
    roles = tr.roles_of(traffic)
    src = source(traffic)
    threads = tr.role_threads(roles, 90, SEED, src, 0, [[] for _ in range(8)])
    pacer, *readers = threads
    assert isinstance(pacer, tr.Pacer) and [c.n for c in pacer.clients] == [90, 91, 92, 93]
    assert pacer.rate == 2.0 and all(type(c) is tr.PacedClient for c in pacer.clients)
    assert [type(c) for c in readers] == [tr.Client] * 4
    assert [c.n for c in readers] == [94, 95, 96, 97] and all(c.role is roles[1] for c in readers)
