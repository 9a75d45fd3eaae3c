"""Operations and bytes each measured program needs, from its shapes.

These are what the algorithm requires, not what a kernel happens to move
(no padding, no scratch), so a roofline share computed from them is a
lower bound on the chip's efficiency and can never pass 100% for a
correctly timed program. Every matmul on the measured paths runs at
HIGHEST (six bf16 passes on the MXU); the compute bound is still taken
against the published bf16 peak, so a compute-bound share reads low.
"""
from __future__ import annotations

F32 = 4

#: Panel width of the dense backend's blocked rank-r factor update.
UPDATE_PANEL = 32


def share(flops: float, nbytes: float, seconds: float, peak: dict) -> float:
    """Least time the chip could take over the time taken, in percent."""
    ideal = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * ideal / seconds


def gemm_nt_update(d: int, r: int, panel: int = UPDATE_PANEL
                   ) -> tuple[float, float, int]:
    """The trailing-panel GEMMs of one blocked rank-r update of a d x d factor.

    Per panel [c0, c1): Z (d - c1, panel + r) times the panel's
    (panel + r)^2 transform; Z and the transform are read, Z' written.
    Returns (flops, bytes, calls) summed over the update's panels.
    """
    flops = nbytes = 0.0
    calls = 0
    for c0 in range(0, d, panel):
        c1 = min(c0 + panel, d)
        if c1 >= d:
            break
        m, k = d - c1, (c1 - c0) + r
        flops += 2.0 * m * k * k
        nbytes += F32 * (2.0 * m * k + k * k)
        calls += 1
    return flops, nbytes, calls


def cho_solve_lane(d: int) -> tuple[float, float]:
    """One lane of a Cholesky solve: forward and back substitution with a
    d x d lower factor, each pass reading its triangle once."""
    return 2.0 * d * d, F32 * (d * (d + 1) + 3.0 * d)


def sharded_tri_solve(d: int, chips: int) -> tuple[float, float]:
    """One solve with a d x d lower factor block-sharded over ``chips``,
    summed over the chips.

    The forward and the back pass each read every entry of the lower
    triangle once, on whichever chip holds it: the chips' tiles split the
    triangle, so their reads add up to it. Tiles, or parts of tiles, above
    the diagonal hold zeros that the algorithm need not read. Every chip
    reads the replicated h and writes the replicated y and w.
    """
    return 2.0 * d * d, F32 * (d * (d + 1) + 3.0 * d * chips)
