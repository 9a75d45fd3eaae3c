#!/usr/bin/env python3
"""One benchmark run, split by layer: the program's layer counters over the
window, and for a traced run the program's spans.

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--small]

runs the cell as ``bench/run.py`` does, and prints before its result line

  * ``LAYERS {...}``: the per-frame or per-request means of the counters
    that ``repro.obs`` and the servers' ``summary()`` dicts keep
    (admission lock wait, journal append and its fsync share, the engines'
    ingest host time per ACKed delta, wire codec, batcher queue wait and
    sweep, the weights' fetch, collector pauses), their longest single
    waits, and every counter's change over the window;
  * ``SPANS {...}`` (``--trace 1``): the span table and the idle gaps
    named after the innermost program span (``bench/spans.py``).

A program without a counter reads ``None`` for it. ``--small`` runs the
cell at the size of the CPU tests (``bench/tests/small_cells.py``) on any
backend.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_root = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_root), str(_root / "src")]

from bench import run, spec, trace  # noqa: E402


def _counters(dep) -> dict:
    """``run._counters`` and the dispatcher's and pool's own summaries."""
    out = _run_counters(dep)
    d = dep.server.dispatcher.summary()
    d.pop("solve_batcher", None)
    p = dep.pool.summary()
    p.pop("per_tenant", None)
    out["dispatcher"], out["pool"] = d, p
    return out


_run_counters = run._counters


def _delta(a: dict, b: dict, path: str = "") -> dict:
    """Numeric changes from ``a`` to ``b``, nested keys joined by dots."""
    out = {}
    for k, v in b.items():
        if isinstance(v, dict) and isinstance(a.get(k), dict):
            out.update(_delta(a[k], v, f"{path}{k}."))
        elif (isinstance(v, (int, float)) and not isinstance(v, bool)
              and isinstance(a.get(k), (int, float))):
            out[path + k] = v - a[k]
    return out


def _ratio(x, y):
    return None if x is None or not y else x / y


def _ms(delta: dict, key: str):
    return None if key not in delta else 1e3 * delta[key]


def layers(data: "run.RunData") -> dict:
    """The layer split of one run's window (see the module docstring)."""
    c0, c1 = data.counters
    d = _delta(c0["dispatcher"], c1["dispatcher"])
    p = _delta(c0["pool"], c1["pool"])
    b = _delta(c0["batcher"], c1["batcher"])
    e: dict[str, float] = {}
    for name in c1["engines"]:
        for k, v in _delta(c0["engines"][name],
                           c1["engines"][name]).items():
            e[k] = e.get(k, 0) + v
    acked = sum(1 for q in data.reqs if q.kind == "delta"
                and q.idx in data.outcomes and data.outcomes[q.idx].ok)
    codec = (None if "decode_s" not in d
             else 1e3 * (d["decode_s"] + d["encode_s"]))
    return {
        "acked_deltas": acked,
        "lock_wait_ms": _ratio(_ms(p, "lock_wait_s"), p.get("lock_waits")),
        "journal_append_ms": _ratio(_ms(p, "journal.append_s"),
                                    p.get("journal.appends")),
        "journal_fsync_share": _ratio(p.get("journal.fsync_s"),
                                      p.get("journal.append_s")),
        "ingest_host_ms": _ratio(_ms(e, "ingest_host_s"), acked),
        "codec_ms": _ratio(codec, d.get("frames_handled")),
        "queue_wait_ms": _ratio(_ms(b, "queue_wait_s"), b.get("requests")),
        "sweep_ms": _ratio(_ms(b, "sweep_s"), b.get("sweeps")),
        "fetch_ms": _ratio(_ms(d, "fetch_s"), d.get("solve_frames")),
        "gc_pause_ms": _ms(d, "gc.pause_s"),
        "gc_pauses": d.get("gc.pauses"),
        "max_s": {"fetch": c1["dispatcher"].get("fetch_max_s"),
                  "queue_wait": c1["batcher"].get("queue_wait_max_s"),
                  "lock_wait": c1["pool"].get("lock_wait_max_s"),
                  "gc_pause": c1["dispatcher"].get("gc", {}).get(
                      "pause_max_s")},
        "window_deltas": {"dispatcher": d, "pool": p, "batcher": b,
                          "engines": e},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)
    kw = {}
    if args.small:
        from bench.tests.small_cells import CELLS, FOUR_CHIP_CELLS

        kw = {"require_device": False,
              "overrides": {**CELLS, **FOUR_CHIP_CELLS}[args.workload]}
    else:
        run.enable_cache()
    run._counters = _counters
    result, data = run.run_cell(args.workload, args.seed, args.seconds,
                                trace=bool(args.trace), t_start=T_PROCESS,
                                **kw)
    print("LAYERS " + json.dumps(layers(data)), flush=True)
    if args.trace:
        from bench import spans

        sp = spans.read(trace.find_xplane(
            spec.OUT / args.workload / f"trace_seed{args.seed}"))
        print("SPANS " + json.dumps({
            "table": sorted(([k, *v] for k, v in sp.table.items()),
                            key=lambda r: -r[2]),
            "gaps": sorted(([k, v] for k, v in sp.gaps.items()),
                           key=lambda r: -r[1])[:12]}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
