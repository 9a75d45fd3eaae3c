"""SOLVE requests per stacked sweep of the SolveBatcher over the window."""


def read(run):
    b0, b1 = run.counters[0]["batcher"], run.counters[1]["batcher"]
    sweeps = b1["sweeps"] - b0["sweeps"]
    return (b1["requests"] - b0["requests"]) / sweeps if sweeps else None
