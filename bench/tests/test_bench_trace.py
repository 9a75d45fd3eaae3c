"""The trace reduction, on a small trace recorded on a TPU v5e and by hand.

The fixture (fixtures/v5e_gemm_chol.xplane.pb) holds three rounds of one
Pallas ``gemm_nt`` tile and one jitted Cholesky at 256, each round inside
a ``bench.solve`` host span, recorded with the python tracer off.
"""
import pathlib
from types import SimpleNamespace

import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_gemm_chol.xplane.pb"


def test_merge_unions_overlapping_intervals():
    assert trace._merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace._merge([]) == []


def test_gaps_go_to_the_host_span_that_covers_them_most():
    busy = [(10, 20), (40, 50)]
    host = [(0, 100, "bench.solve"), (22, 38, "PjitFunction(step)"),
            (55, 60, "ExecuteHelper")]
    gaps = trace._attribute_gaps(busy, host, 0, 100)
    # 0-10 and 20-40 and 50-100: a session span alone names the first gap.
    assert gaps["bench.solve in flight"] == pytest.approx(10e-9)
    assert gaps["PjitFunction(step)"] == pytest.approx(20e-9)
    assert gaps["ExecuteHelper"] == pytest.approx(50e-9)
    assert sum(gaps.values()) == pytest.approx(80e-9)


def test_reduce_recorded_v5e_trace():
    r = trace.reduce(FIXTURE)
    assert r.devices == 1
    assert 0.0 < r.busy_s < r.window_s
    seconds, calls = r.op_time("gemm_nt")
    assert calls == 3 and seconds > 0.0
    assert any("cholesky" in name or "lambda" in name for name in r.modules)
    assert sum(r.module_counts.values()) >= 6
    assert sum(r.gaps.values()) == pytest.approx(r.window_s - r.busy_s,
                                                 rel=1e-6)
    bd = r.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_module_planes_of_a_one_chip_trace():
    r = trace.reduce(FIXTURE)
    assert r.module_planes and set(r.module_planes) == set(r.modules)
    assert all(n == 1 for n in r.module_planes.values())
    name = next(iter(r.modules))
    assert r.module_plane_count(name) == 1
    assert r.module_plane_count("no such program") == 0


#: Three sharded solves (d=256) on a 2x2 mesh of TPU v5e chips, recorded
#: under ``bench.solve`` host spans with the python tracer off, then cut to
#: what the reduction reads: the four device planes' ``XLA Modules`` and
#: ``XLA Ops`` lines, operation names before their `` = ``, and the host's
#: ``bench.*`` and ``PjitFunction`` events.
FIXTURE_2X2 = FIXTURE.parent / "v5e_2x2_sharded_solve.xplane.pb"


def test_reduce_recorded_2x2_sharded_solve():
    from types import SimpleNamespace

    from bench import roofline, spec
    from bench.run import RunData

    r = trace.reduce(FIXTURE_2X2)
    assert r.devices == 4
    assert 0.0 < r.busy_s < r.window_s
    assert r.module_plane_count("_local_tri_solve") == 4
    assert r.module_count("_local_tri_solve") == 12
    reqs = [SimpleNamespace(kind="solve", idx=i, tenant="silo")
            for i in range(3)]
    outcomes = {i: SimpleNamespace(ok=True, done=1.0) for i in range(3)}
    run = RunData(reqs, outcomes, 0.0, ({}, {}), r, (0.0, 2.0),
                  {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                  {"silo": 256}, 1)
    per_chip_ms = spec.metric_reader("linalg.sharded_solve_device_ms").read(
        run)
    assert per_chip_ms == pytest.approx(
        1e3 * r.module_time("_local_tri_solve") / 4 / 3)
    assert 0.0 < per_chip_ms < 1e3 * r.busy_s
    share = spec.metric_reader("sharded_tri_solve_roofline").read(run)
    _, nbytes = roofline.sharded_tri_solve(256, 4)
    assert share == pytest.approx(100 * 3 * nbytes / 819e9
                                  / r.module_time("_local_tri_solve"))
    assert 0.0 < share <= 100.0


@pytest.mark.parametrize("phase", [0.0, 0.3, 0.7, 0.99])
def test_trace_window_holds_one_paced_upload(phase):
    from bench.run import TRACE_LEAD_S, TRACE_MAX_S, trace_start

    # silo_d4096.stream: 12 paced uploads in 51 s, 4.25 s apart, reads
    # between them; the 3 s trace holds an upload at any phase.
    seconds, gap = 51.0, 51.0 / 12
    reqs = [SimpleNamespace(kind="solve", due=0.1 * i) for i in range(510)]
    reqs += [SimpleNamespace(kind="delta", due=(i + phase) * gap)
             for i in range(12)]
    start = trace_start(reqs, seconds, TRACE_MAX_S)
    held = [q.due for q in reqs if q.kind == "delta"
            and start <= q.due <= start + TRACE_MAX_S]
    assert held and held[0] - start == pytest.approx(TRACE_LEAD_S)
    assert 0.3 * seconds <= start < 0.3 * seconds + gap
    assert start + TRACE_MAX_S <= seconds


def test_trace_window_without_uploads_starts_at_its_share():
    from bench.run import TRACE_START_SHARE, trace_start

    reqs = [SimpleNamespace(kind="solve", due=0.01 * i) for i in range(900)]
    assert trace_start(reqs, 51.0, 3.0) == TRACE_START_SHARE * 51.0
    # An upload too late for the trace to end inside the window is passed.
    late = reqs + [SimpleNamespace(kind="delta", due=49.0)]
    assert trace_start(late, 51.0, 3.0) == TRACE_START_SHARE * 51.0
