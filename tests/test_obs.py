"""The layer counters: N frames advance each count by N, and every timed
wait lies between 0 and the wall time of the frames that caused it."""
import gc
import threading
import time

import numpy as np
import pytest

from repro.core.sufficient_stats import compute_stats
from repro.fed import transport
from repro import obs
from repro.server import EnginePool, SolveBatcher

N = 4
D = 8


def _rows(rng, n):
    return (rng.normal(size=(n, D)).astype(np.float32),
            rng.normal(size=n).astype(np.float32))


@pytest.mark.parametrize("batched", [True, False])
def test_counters_advance_by_the_frames_and_stay_within_their_time(
        tmp_path, batched):
    rng = np.random.default_rng(1)
    t_start = time.perf_counter()
    with EnginePool(journal_dir=str(tmp_path / "j")) as pool, \
            SolveBatcher(pool) as batcher:
        disp = transport.WireDispatcher(
            pool, solve_batcher=batcher if batched else None)
        client = transport.FrameClient(transport.LoopbackChannel(disp))
        client.hello("t")
        client.upload_stats(compute_stats(*_rows(rng, 32)), "c0")
        client.solve(0.1)                 # compiles and caches the factor
        before = (disp.summary(), batcher.summary(), pool.summary(),
                  pool.get("t").summary())
        wall = 0.0
        for _ in range(N):
            A, b = _rows(rng, 4)
            t0 = time.perf_counter()
            client.solve(0.1)
            client.stream_rows(A, b, "c0")
            wall += time.perf_counter() - t0
        after = (disp.summary(), batcher.summary(), pool.summary(),
                 pool.get("t").summary())
    lifetime = time.perf_counter() - t_start
    (d0, b0, p0, e0), (d1, b1, p1, e1) = before, after

    def delta(s0, s1, key):
        return s1[key] - s0[key]

    assert delta(d0, d1, "frames_handled") == 2 * N
    assert delta(d0, d1, "solve_frames") == N
    assert delta(d0, d1, "upload_frames") == N
    assert delta(b0, b1, "requests") == (N if batched else 0)
    assert delta(p0, p1, "lock_waits") == N
    assert delta(p0["journal"], p1["journal"], "appends") == N
    timed = [(d0, d1, "decode_s"), (d0, d1, "encode_s"),
             (d0, d1, "fetch_s"), (b0, b1, "queue_wait_s"),
             (b0, b1, "sweep_s"), (p0, p1, "lock_wait_s"),
             (p0["journal"], p1["journal"], "append_s"),
             (p0["journal"], p1["journal"], "fsync_s"),
             (e0, e1, "ingest_host_s")]
    for s0, s1, key in timed:
        assert 0.0 <= delta(s0, s1, key) <= wall, key
    for s, key in [(d1, "fetch_s"), (d1, "decode_s"), (d1, "encode_s"),
                   (e1, "ingest_host_s")]:
        assert s[key] > 0.0, key
    assert (p1["journal"]["fsync_s"] - p0["journal"]["fsync_s"]
            <= p1["journal"]["append_s"] - p0["journal"]["append_s"])
    for s, key in [(d1, "fetch_max_s"), (b1, "queue_wait_max_s"),
                   (p1, "lock_wait_max_s")]:
        assert 0.0 <= s[key] <= lifetime, key
    if batched:
        assert b1["queue_wait_s"] > 0.0 and b1["sweep_s"] > 0.0
    # Collector pauses are the process's: the dispatcher watches and
    # reports them, the pool does not.
    assert obs._gc_watch in gc.callbacks and "gc" not in p1
    assert set(d1["gc"]) == {"pauses", "pause_s", "pause_max_s"}
    assert d1["gc"]["pauses"] >= d0["gc"]["pauses"]


def test_gc_pauses_are_counted_once_watched():
    obs.watch_gc()
    obs.watch_gc()                        # idempotent: one hook
    assert gc.callbacks.count(obs._gc_watch) == 1
    s0 = obs.gc_summary()
    t0 = time.perf_counter()
    gc.collect()
    wall = time.perf_counter() - t0
    s1 = obs.gc_summary()
    assert s1["pauses"] - s0["pauses"] >= 1
    assert 0.0 < s1["pause_s"] - s0["pause_s"] <= wall
    assert s1["pause_max_s"] >= ((s1["pause_s"] - s0["pause_s"])
                                 / (s1["pauses"] - s0["pauses"]))


def test_request_ids_are_per_thread_and_per_frame():
    obs.set_request(7)
    assert obs.request() == 7
    seen = []
    t = threading.Thread(target=lambda: seen.append(obs.request()))
    t.start()
    t.join(timeout=5)
    assert not t.is_alive() and seen == [0]
    with EnginePool() as pool:
        disp = transport.WireDispatcher(pool)
        client = transport.FrameClient(transport.LoopbackChannel(disp))
        client.hello("t")
        first = obs.request()
        client.hello("t")
        assert obs.request() == first + 1
