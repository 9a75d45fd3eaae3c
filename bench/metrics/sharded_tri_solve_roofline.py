"""The sharded solve program's share of its roofline, %.

The bytes two block-triangular passes need for each execution of
``_local_tri_solve`` in the traced window (one a SOLVE), summed over the
chips that ran it (bench/roofline.py), against its device seconds summed
over the same planes: both sides on the basis of all the chips together.
Reads nothing where the cell's tenants differ in width (the trace does not
say which tenant an execution served)."""
from bench import roofline

PROGRAM = "_local_tri_solve"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.module_time(PROGRAM)
    planes = run.trace.module_plane_count(PROGRAM)
    dims = set(run.dims.values())
    if t <= 0 or not planes or len(dims) != 1:
        return None
    solves = run.trace.module_count(PROGRAM) / planes
    flops, nbytes = roofline.sharded_tri_solve(dims.pop(), planes)
    return roofline.share(solves * flops, solves * nbytes, t, run.peak)
