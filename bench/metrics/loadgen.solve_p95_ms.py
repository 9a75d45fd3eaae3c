"""SOLVE latency, due -> WEIGHTS reply, 95th percentile, ms: the read tail.

Where reads share the chip with factor updates, about one read in twenty
lands in an update and waits it out, so the 95th percentile sits on the
seam between the reads that wait for nothing and those that wait for an
update, and swings from run to run. It is read here, per layer, beside the
cell's end-to-end median. Reads nothing where no SOLVE was answered."""
import numpy as np


def read(run):
    lat = [run.outcomes[q.idx].done - (run.t0 + q.due) for q in run.reqs
           if q.kind == "solve" and q.idx in run.outcomes]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
