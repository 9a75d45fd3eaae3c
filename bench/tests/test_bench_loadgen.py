"""The open-loop schedule is fixed by the seed, and the generator's lag is measured."""
import time

import numpy as np
import pytest

from bench import loadgen, spec

TENANTS = [loadgen.TenantInfo(f"t{i}", "dense" if i % 2 else "rff", 4,
                              (0.1, 1.0)) for i in range(6)]


@pytest.mark.parametrize("mix", ["stream", "burst"])
def test_schedule_is_deterministic_per_seed(mix):
    traffic = spec.traffic(mix)
    a = loadgen.schedule(traffic, TENANTS, 20.0, 2**31 + 7)
    b = loadgen.schedule(traffic, TENANTS, 20.0, 2**31 + 7)
    c = loadgen.schedule(traffic, TENANTS, 20.0, 2**31 + 8)
    assert a == b
    assert a != c
    # Every seed sends the same amount of work of each kind.
    for kind in ("solve", "delta"):
        assert (sum(q.kind == kind for q in a)
                == sum(q.kind == kind for q in c))
    assert len(a) == round(loadgen.rate(traffic) * 20.0)
    dues = [q.due for q in a]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] < 20.0


def test_deltas_go_to_dense_tenants_and_are_numbered():
    traffic = spec.traffic("stream")
    reqs = loadgen.schedule(traffic, TENANTS, 30.0, 3)
    deltas = [q for q in reqs if q.kind == "delta"]
    assert [q.delta for q in deltas] == list(range(len(deltas)))
    assert all(q.tenant in {"t1", "t3", "t5"} for q in deltas)
    assert all(q.group == (q.tenant, "site", q.site) for q in deltas)
    assert all(q.sigma in (0.1, 1.0) for q in reqs if q.kind == "solve")


def test_gamma_arrivals_are_bursty_and_span_the_window():
    rng = np.random.default_rng(0)
    t = loadgen.arrival_times({"process": "gamma", "cv": 2.0}, 20000, 100.0,
                              rng)
    gaps = np.diff(t)
    assert 1.7 < gaps.std() / gaps.mean() < 2.3
    assert 0.0 < t[0] and t[-1] < 100.0


def test_zipf_popularity_reshuffles_its_ranks():
    traffic = spec.traffic("burst")
    reqs = loadgen.schedule(traffic, TENANTS, 40.0, 5,
                            rate_per_s=500.0)
    top = []
    for epoch in range(4):
        names = [q.tenant for q in reqs if int(q.due // 10) == epoch]
        counts = {n: names.count(n) for n in set(names)}
        top.append(max(counts, key=counts.get))
        assert max(counts.values()) > 2 * min(counts.values())
    assert len(set(top)) > 1


def test_open_loop_times_from_due_and_measures_lag():
    """One session, three requests due at once: the queue wait is lag."""
    def send(client, q):
        time.sleep(0.05)
        return q.idx

    groups = {("t", "solve"): 1}
    loop = loadgen.OpenLoop(lambda tenant: _Client(), groups, send)
    loop.start()
    reqs = [loadgen.Request(i, 0.0, "solve", "t", 1.0, -1, -1,
                            ("t", "solve")) for i in range(3)]
    t0 = time.perf_counter()
    loop.drive(reqs, t0)
    assert loop.wait_idle(10.0)
    loop.close()
    lags = sorted(loop.outcomes[i].sent - t0 for i in range(3))
    assert lags[0] < 0.04 and lags[2] >= 0.09
    assert all(loop.outcomes[i].ok and loop.outcomes[i].result == i
               for i in range(3))


def test_open_loop_records_a_failed_request_and_reconnects():
    def send(client, q):
        if q.idx == 0:
            raise ConnectionError("peer closed")
        return client

    clients = []

    def connect(tenant):
        clients.append(_Client())
        return clients[-1]

    loop = loadgen.OpenLoop(connect, {("t", "solve"): 1}, send)
    loop.start()
    reqs = [loadgen.Request(i, 0.01 * i, "solve", "t", 1.0, -1, -1,
                            ("t", "solve")) for i in range(2)]
    loop.drive(reqs, time.perf_counter())
    assert loop.wait_idle(10.0)
    loop.close()
    assert not loop.outcomes[0].ok and "peer closed" in loop.outcomes[0].error
    assert loop.outcomes[1].ok and loop.outcomes[1].result is clients[1]
    assert clients[0].closed


class _Client:
    closed = False

    def close(self):
        self.closed = True
