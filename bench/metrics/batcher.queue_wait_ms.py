"""Mean wait of a SOLVE in the SolveBatcher's queue over the window, ms.

From ``submit`` to the start of the sweep that answers it
(``queue_wait_s`` over ``requests``): the window hold and the sweeps ahead
of it, not the sweep itself. Reads nothing from a program without the
counter."""


def read(run):
    b0, b1 = run.counters[0]["batcher"], run.counters[1]["batcher"]
    if "queue_wait_s" not in b1:
        return None
    n = b1["requests"] - b0["requests"]
    return 1e3 * (b1["queue_wait_s"] - b0["queue_wait_s"]) / n if n else None
