"""The load generator: one schedule per seed, the same work on every
seed, and time to result counted from the due time."""

from collections import Counter
from types import SimpleNamespace

import pytest

from bench import harness, loadgen, readings

MIXES = ["text8-poisson", "code-saturated"]


def mix(name):
    return harness._json(harness.BENCH / "traffic" / f"{name}.json")


def shape(reqs):
    return [(r.seq_len, r.num_samples, r.seed, r.due_s) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule_other_seed_other_order(name):
    m = mix(name)
    big = 2 ** 31 + 12345678901
    a, b = loadgen.schedule(m, big, 10.0), loadgen.schedule(m, big, 10.0)
    c = loadgen.schedule(m, big + 1, 10.0)
    assert shape(a) == shape(b)
    assert shape(a) != shape(c)
    # the same multiset of work in another order (closed loop: per block)
    k = m.get("block", len(a))
    for i in range(0, min(len(a), 4 * k), k):
        work = lambda rs: Counter((r.seq_len, r.num_samples)
                                  for r in rs[i:i + k])
        assert work(a) == work(c)
    assert all(m["length"]["min"] <= r.seq_len <= m["length"]["max"]
               for r in a)
    assert all(0 <= r.seed < 2 ** 31 for r in a)


def test_open_loop_rate_and_window():
    m = mix("text8-poisson")
    for seed in (1, 2, 3):
        reqs = loadgen.schedule(m, seed, 20.0)
        assert len(reqs) == round(m["rate_rps"] * 20.0)
        due = [r.due_s for r in reqs]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 20.0
        assert sorted(loadgen._gaps(m["rate_rps"], 8)) == \
            sorted(loadgen._gaps(m["rate_rps"], 8))


class FakeClock:
    """Time moves only when the sender sleeps, or when a stall is
    injected into a send."""

    def __init__(self):
        self.now = 100.0

    def time(self):
        return self.now

    def sleep(self, dt):
        self.now += dt


def test_stall_counts_against_every_request_due_during_it():
    m = dict(mix("text8-poisson"), rate_rps=10.0)
    reqs = loadgen.schedule(m, 7, 4.0)
    clock = FakeClock()
    origin = clock.now
    stall_at, stall = 1.0, 1.5
    sent = {}

    def send(r, due):
        if "stalled" not in sent and clock.now >= origin + stall_at:
            sent["stalled"] = clock.now
            clock.now += stall          # the sender (or server) is stuck
        sent[r.index] = (due, clock.now)

    loadgen.open_loop(reqs, origin, send, clock)
    # the server answers the moment a request reaches it
    run = SimpleNamespace(requests={
        i: {"due": due, "sent": at, "done": at, "status": "completed"}
        for i, (due, at) in ((k, v) for k, v in sent.items()
                             if k != "stalled")})
    start = sent["stalled"]
    hit = [i for i, r in run.requests.items()
           if start <= r["due"] < start + stall]
    assert len(hit) >= 5
    for i in hit:
        r = run.requests[i]
        # charged from its due time: the stall's remainder is in it
        assert r["done"] - r["due"] == pytest.approx(start + stall - r["due"])
    # timing from the send (admission) would hide the stall entirely
    assert all(r["done"] - r["sent"] == 0 for r in run.requests.values())
    assert readings.ttr_ms(run, 100) == pytest.approx(stall * 1e3)
