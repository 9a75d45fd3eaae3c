"""The program's spans, read back from a trace recorded on the CPU, and the
gap rule that names idle time after the innermost program span."""
import gc
import pathlib

import jax
import numpy as np
import pytest

from bench import spans, trace
from repro.core.sufficient_stats import compute_stats
from repro.fed import transport
from repro.server import EnginePool

SPAN_NAMES = {
    "wire.decode", "wire.encode", "batcher.wait",
    "session.fetch", "batcher.collect", "batcher.sweep", "pool.snapshot",
    "batch.dispatch", "pool.admit", "pool.lock_wait", "journal.append",
    "journal.fsync", "engine.ingest", "engine.touch_factors", "host.gc"}


def _ev(name, s, e, line, **args):
    return spans.Event(name, s, e, line, args)


def test_gaps_go_to_the_innermost_program_span_and_its_runtime_call():
    busy = [(10, 20), (60, 70)]
    events = [
        # Session thread (line 1): an upload whose admission holds a lock
        # wait and then the ingest, which holds a runtime call.
        _ev("pool.admit", 0, 100, 1),
        _ev("pool.lock_wait", 2, 40, 1),
        _ev("engine.ingest", 40, 58, 1),
        _ev("PjitFunction(add)", 41, 57, 1),
        # Batcher thread (line 2): a sweep overlapping the first gap less.
        _ev("batcher.sweep", 21, 25, 2),
        # Client thread: a benchmark span, never a candidate.
        _ev("bench.solve", 0, 100, 3),
    ]
    gaps = spans.attribute_gaps(busy, events, 0, 100)
    # 0-10: pool.admit encloses pool.lock_wait, so the wait names it.
    # 20-60: pool.lock_wait (20 ns) beats engine.ingest (18 ns) and the
    # sweep (4 ns); the runtime call nested in the ingest does not count.
    # 70-100: only pool.admit is open.
    assert gaps == pytest.approx({"pool.lock_wait": 50e-9,
                                  "pool.admit": 30e-9})


def test_fetch_with_its_runtime_call_and_gaps_without_program_spans():
    busy = [(0, 10), (30, 40)]
    events = [
        _ev("session.fetch", 10, 30, 1),
        _ev("np.asarray(jax.Array)", 12, 28, 1),
        _ev("ExecuteHelper", 40, 60, 2),
        _ev("bench.solve", 60, 100, 3),
    ]
    gaps = spans.attribute_gaps(busy, events, 0, 100)
    # 40-100 has no program span: bench/trace.py's rule names it (the
    # runtime call, which a benchmark span only names where it is alone).
    assert gaps == pytest.approx({
        "session.fetch/np.asarray(jax.Array)": 20e-9,
        **trace._attribute_gaps([(0, 40)], [(40, 60, "ExecuteHelper"),
                                            (60, 100, "bench.solve")],
                                0, 100)})
    assert sum(gaps.values()) == pytest.approx(80e-9)


def test_a_trace_without_program_spans_reads_as_bench_trace_reads_it():
    fixture = (pathlib.Path(__file__).parent / "fixtures"
               / "v5e_gemm_chol.xplane.pb")
    read = spans.read(fixture)
    assert read.table == {}
    assert read.gaps == pytest.approx(trace.reduce(fixture).gaps)


def test_self_time_leaves_out_child_program_spans_only():
    events = [
        _ev("pool.admit", 0, 100, 1),
        _ev("journal.append", 10, 40, 1),
        _ev("journal.fsync", 20, 35, 1),
        _ev("PjitFunction(x)", 50, 60, 1),
        _ev("engine.ingest", 60, 90, 1),
        _ev("pool.admit", 0, 10, 2),
    ]
    table = spans.span_table(events)
    assert table["pool.admit"] == (2, pytest.approx(110e-9),
                                   pytest.approx(50e-9))
    assert table["journal.append"] == (1, pytest.approx(30e-9),
                                       pytest.approx(15e-9))
    assert "PjitFunction(x)" not in table


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """SOLVE and DELTA frames through a journaled pool behind a TCP
    ``FrameServer`` with a batcher, under the profiler."""
    tmp = tmp_path_factory.mktemp("spans")
    rng = np.random.default_rng(0)
    d = 8
    with EnginePool(journal_dir=str(tmp / "journal")) as pool, \
            transport.FrameServer(pool, solve_window_s=0.002) as srv:
        client = transport.FrameClient(
            transport.TCPChannel("127.0.0.1", srv.port))
        client.hello("t")
        A, b = rng.normal(size=(32, d)), rng.normal(size=32)
        client.upload_stats(compute_stats(A.astype(np.float32),
                                          b.astype(np.float32)), "c0")
        client.solve(0.1)           # caches a factor, so deltas update it
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp / "trace"),
                                 profiler_options=options)
        try:
            client.solve(0.1)
            client.stream_rows(rng.normal(size=(4, d)).astype(np.float32),
                               rng.normal(size=4).astype(np.float32), "c0")
            client.solve(0.1)
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        client.close()
    return spans.read(trace.find_xplane(tmp / "trace"))


def test_every_program_span_is_in_the_trace(traced):
    assert SPAN_NAMES <= set(traced.table)
    for name, (count, total, own) in traced.table.items():
        assert count >= 1 and 0.0 <= own <= total + 1e-9, name


def test_a_solves_spans_share_its_request_id_across_threads(traced):
    ev = traced.events
    wait = next(e for e in ev if e.name == "batcher.wait")
    req = wait.args["req"]
    mine = {e.name: e for e in ev if e.program and e.args.get("req") == req}
    assert {"wire.decode", "wire.encode", "batcher.wait", "session.fetch",
            "pool.snapshot"} <= set(mine)
    session = {e.line for n, e in mine.items() if n != "pool.snapshot"}
    assert session == {wait.line}
    snap = mine["pool.snapshot"]
    sweep = next(e for e in ev if e.name == "batcher.sweep"
                 and e.args["sweep"] == wait.args["sweep"])
    assert snap.line == sweep.line != wait.line
    assert sweep.start <= snap.start and snap.end <= sweep.end
    order = [mine[n] for n in ("wire.decode", "batcher.wait",
                               "session.fetch", "wire.encode")]
    assert all(a.end <= b.start for a, b in zip(order, order[1:]))


def test_fsync_nests_in_its_append_and_the_delta_carries_its_id(traced):
    ev = traced.events
    fsync = next(e for e in ev if e.name == "journal.fsync")
    append = next(e for e in ev if e.name == "journal.append"
                  and e.line == fsync.line and e.start <= fsync.start
                  and fsync.end <= e.end)
    frame = next(e for e in ev if e.name == "pool.admit"
                 and e.args["kind"] == "DeltaRowsFrame")
    assert append.args["req"] == fsync.args["req"] == frame.args["req"]
    for name in ("wire.decode", "pool.lock_wait", "engine.ingest",
                 "engine.touch_factors", "wire.encode"):
        assert any(e.name == name and e.args["req"] == frame.args["req"]
                   for e in ev), name
