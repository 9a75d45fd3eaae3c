"""The Pallas ``gemm_nt`` tile inside the blocked factor update, % of roofline.

Its device operations in the trace against the operations and bytes the
update's trailing-panel GEMMs need (bench/roofline.py), for the streamed
tenants' width and the delta batch's power-of-two rank bucket."""
from bench import roofline


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds, calls = run.trace.op_time("gemm_nt")
    if not calls or seconds <= 0:
        return None
    d, r = run.update_shape()
    flops, nbytes, per_update = roofline.gemm_nt_update(d, r)
    updates = calls / per_update
    return roofline.share(flops * updates, nbytes * updates, seconds,
                          run.peak)
