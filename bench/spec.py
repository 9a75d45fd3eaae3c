"""Where the benchmark finds its parts: every one by the name BENCHMARK.json gives.

    BENCHMARK.json                   cells, configurations, metrics
    bench/configs/<config>.json      a deployment: tenants, sizes, server, limits
    bench/traffic/<mix>.json         generator parameters of a traffic mix
    bench/metrics/<metric>.py        one reader per per-layer metric
    bench/peaks.json                 device peaks keyed by device_kind

A later cell, mix, configuration or metric is a new file and a new entry;
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: Run outputs (raw traces, journals of the control script); git-ignored.
OUT = ROOT / ".bench_out"


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(name: str, bm: dict | None = None) -> dict:
    bm = benchmark() if bm is None else bm
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config(name: str, bm: dict | None = None) -> dict:
    bm = benchmark() if bm is None else bm
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((ROOT / c["file"]).read_text())
    raise SystemExit(f"bench: no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def peaks() -> dict:
    return json.loads((BENCH / "peaks.json").read_text())


def metrics_for(workload_name: str, kind: str, bm: dict | None = None
                ) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    bm = benchmark() if bm is None else bm
    return [m for m in bm[kind]
            if "workloads" not in m or workload_name in m["workloads"]]


def metric_reader(name: str) -> ModuleType:
    """``bench/metrics/<name>.py``; its ``read(run)`` returns a number or None."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
