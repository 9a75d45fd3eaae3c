"""Device time of the solve programs per SOLVE, ms.

The stacked sweep (``_stacked_solve``) and the lone solve
(``_factor_solve``) in the traced window, over the SOLVE replies inside
it."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_time("_stacked_solve", "_factor_solve")
    n = run.completed_in_trace("solve")
    return 1e3 * t / n if n and t else None
