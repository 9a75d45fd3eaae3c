#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate served without a growing backlog.

    python bench/sweep.py --workload <cell> --rates 2,4,8 --seconds 10

Runs the cell once per rate in this one process (the rate replaces the
mix's ``knee_per_s x share_of_knee``), each on its own seed, and prints per
rate the offered and completed request rates, the latency quantiles, the
generator's lag and the drain: how long after the window closed the last
due reply came. Under capacity the drain stays near one request's latency;
past it the queue grows through the window and the drain grows with it.
The knee found is written into the mix's ``knee_per_s`` by hand.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1000)
    args = ap.parse_args(argv)
    from bench import run

    run.enable_cache()
    for i, r in enumerate(float(x) for x in args.rates.split(",")):
        result, data = run.run_cell(
            args.workload, args.seed + i, args.seconds, trace=False,
            overrides={"traffic": {"knee_per_s": r, "share_of_knee": 1.0}})
        done = [o.done for o in data.outcomes.values() if o.ok]
        close = data.t0 + args.seconds
        lags = [o.sent - (data.t0 + q.due) for q in data.reqs
                if (o := data.outcomes.get(q.idx)) is not None]
        row = {"rate_per_s": r, "correct": result["correct"],
               "attempted": result["attempted"], "failed": result["failed"],
               "completed_in_window_per_s":
                   sum(d <= close for d in done) / args.seconds,
               "drain_s": max(done) - close if done else None,
               "lag_p95_ms": 1e3 * float(np.percentile(lags, 95)),
               **{k: v["value"] for k, v in result["metrics"].items()}}
        print("SWEEP " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]
    sys.exit(main())
