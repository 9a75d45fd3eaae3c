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


def test_sharded_tri_solve_counts():
    flops, nbytes = roofline.sharded_tri_solve(16384, 4)
    assert flops == 2 * 16384 * 16384
    # two passes over the lower triangle, wherever its tiles live, plus
    # h, y and w on each of the 4 chips
    assert nbytes == 4 * (16384 * 16385 + 3 * 16384 * 4)


def test_sharded_tri_solve_share_stays_under_100():
    d, chips = 16384, 4
    flops, nbytes = roofline.sharded_tri_solve(d, chips)
    # Fastest correct program: each chip streams only its part of the
    # triangle and the vectors at peak bandwidth.
    fastest = nbytes / PEAK["hbm_bytes_per_s"]
    assert roofline.share(flops, nbytes, fastest, PEAK) == pytest.approx(100)
    # The 2x2 block layout's way: every chip reads its whole (d/2)^2 tile
    # once a pass; summed over the chips that is at least the triangle.
    tiles = chips * 2 * 4 * (d // 2) ** 2 / PEAK["hbm_bytes_per_s"]
    assert roofline.share(flops, nbytes, tiles, PEAK) <= 100.0
