"""Factor mutations per admitted upload frame over the window.

(incremental updates + cold factorizations) of every engine, over the
upload frames ACKed in the window. With one rank-r update per cached
sigma factor per frame it reads the number of cached factors; coalescing
on the wire would bring it under that."""


def read(run):
    e0, e1 = run.counters[0]["engines"], run.counters[1]["engines"]
    work = sum(e1[n]["incremental_updates"] - e0[n]["incremental_updates"]
               + e1[n]["cold_factorizations"] - e0[n]["cold_factorizations"]
               for n in e1)
    uploads = sum(1 for q in run.reqs if q.kind == "delta"
                  and q.idx in run.outcomes and run.outcomes[q.idx].ok)
    return work / uploads if uploads else None
