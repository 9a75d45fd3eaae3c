"""The trace reduction, on a small trace recorded on a TPU v5e and by hand.

The fixture (fixtures/v5e_gemm_chol.xplane.pb) holds three rounds of one
Pallas ``gemm_nt`` tile and one jitted Cholesky at 256, each round inside
a ``bench.solve`` host span, recorded with the python tracer off.
"""
import pathlib

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
