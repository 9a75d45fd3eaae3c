"""Device time of the sharded solve program per SOLVE, ms, per chip.

Every SOLVE of a sharded tenant runs the block-triangular solve
(``_local_tri_solve``, one program across the mesh) once on each chip, so
its executions in the traced window count the SOLVEs the trace holds, on
each plane: the program's device seconds over its executions, summed over
the planes alike, are its time per SOLVE on one chip. (Replies counted by
their host clock would also count those answered while the trace was being
written out.)"""

PROGRAM = "_local_tri_solve"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_time(PROGRAM)
    n = run.trace.module_count(PROGRAM)
    return 1e3 * t / n if n and t else None
