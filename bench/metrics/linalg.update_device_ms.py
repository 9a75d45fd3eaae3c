"""Device time of the factor-update programs per upload frame, ms.

The mean device time of one update program (``chol_update*`` in the
traced window) times the factor updates one upload frame caused over the
whole window (the engines' incremental updates over the upload frames
ACKed). Reads nothing where the trace holds no update."""


def read(run):
    if run.trace is None:
        return None
    n = run.trace.module_count("chol_update")
    if not n:
        return None
    e0, e1 = run.counters[0]["engines"], run.counters[1]["engines"]
    updates = sum(e1[k]["incremental_updates"] - e0[k]["incremental_updates"]
                  for k in e1)
    uploads = sum(1 for q in run.reqs if q.kind == "delta"
                  and q.idx in run.outcomes and run.outcomes[q.idx].ok)
    if not uploads:
        return None
    return 1e3 * run.trace.module_time("chol_update") / n * updates / uploads
