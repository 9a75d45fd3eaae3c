"""Host time of the engines' ingest per ACKed upload frame, ms.

The engines' ``ingest_host_s`` over the window (statistics, fusion and the
dispatch of the factor updates, not their device time) over the upload
frames ACKed in it. Reads nothing from a program without the counter."""


def read(run):
    e0, e1 = run.counters[0]["engines"], run.counters[1]["engines"]
    if any("ingest_host_s" not in e for e in e1.values()):
        return None
    seconds = sum(e1[n]["ingest_host_s"] - e0[n]["ingest_host_s"]
                  for n in e1)
    uploads = sum(1 for q in run.reqs if q.kind == "delta"
                  and q.idx in run.outcomes and run.outcomes[q.idx].ok)
    return 1e3 * seconds / uploads if uploads else None
