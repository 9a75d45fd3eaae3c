"""The solve programs' share of their roofline, %.

Two triangular passes over each answered lane's factor (bench/roofline.py)
against the device time of the stacked and lone solve programs in the
traced window. Pad lanes and the stacking copy are work the roofline does
not need, so they lower the share."""
from bench import roofline


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.module_time("_stacked_solve", "_factor_solve")
    if t <= 0:
        return None
    flops = nbytes = 0.0
    for d in run.solve_dims_in_trace():
        f, b = roofline.cho_solve_lane(d)
        flops, nbytes = flops + f, nbytes + b
    return roofline.share(flops, nbytes, t, run.peak) if flops else None
