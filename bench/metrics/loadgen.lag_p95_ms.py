"""How late the open loop handed requests out: send - due, 95th percentile, ms.

The wait for an idle session of the request's group counts. A high value
means the generator or the sessions were starved, not that the server was
fast."""
import numpy as np


def read(run):
    lags = [run.outcomes[q.idx].sent - (run.t0 + q.due) for q in run.reqs
            if q.idx in run.outcomes]
    return 1e3 * float(np.percentile(lags, 95)) if lags else None
