"""Operation and byte counts against values worked out by hand."""
import pytest

from bench import roofline

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_cho_solve_lane_counts():
    flops, nbytes = roofline.cho_solve_lane(2048)
    assert flops == 2 * 2048 * 2048
    # two passes over the 2048*2049/2 lower triangle, plus h, y and w
    assert nbytes == 4 * (2048 * 2049 + 3 * 2048)


def test_gemm_nt_update_counts_small():
    # d=96, panel 32, rank 8: panels end at 32, 64 (96 is the last panel,
    # with no trailing rows). k = 40 for both GEMMs.
    flops, nbytes, calls = roofline.gemm_nt_update(96, 8)
    assert calls == 2
    assert flops == 2 * 64 * 40 * 40 + 2 * 32 * 40 * 40
    assert nbytes == 4 * ((2 * 64 * 40 + 1600) + (2 * 32 * 40 + 1600))


def test_gemm_nt_update_calls_at_d4096():
    _, _, calls = roofline.gemm_nt_update(4096, 8)
    assert calls == 127


def test_share_is_bound_by_the_slower_side():
    # 819 MB at 819 GB/s is 1 ms: in 2 ms that is 50% of the roofline.
    assert roofline.share(1.0, 819e6, 2e-3, PEAK) == pytest.approx(50.0)
    # 197 GFLOP at 197 TFLOP/s is 1 ms as well.
    assert roofline.share(197e9, 1.0, 4e-3, PEAK) == pytest.approx(25.0)
