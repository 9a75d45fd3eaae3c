#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, because a chip belongs to one
process: a ``FrameServer`` in front of an ``EnginePool`` is brought up
from the cell's configuration (bench/configs/), with data made on the
device from the seed; set-up warms the cell's programs; then an open loop
(bench/loadgen.py) drives the cell's traffic mix (bench/traffic/) over TCP
loopback for ``--seconds``. Once every reply is in, the window's answers are
compared with the float64 reference (bench/check.py).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
few seconds of the window with the profiler, reduces the trace in-process
and reports the per-layer metrics (bench/metrics/) with a breakdown; the
raw trace stays under ``.bench_out/``. The last stdout line is one JSON
object; the last stderr lines are each compared number beside its limit.
A host without the chips the cell asks for exits nonzero with no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

if __package__ in (None, ""):
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402

#: The traced part of a ``--trace 1`` window: its start and length as
#: shares of the window, capped (traces are large and slow the host). In a
#: cell with uploads the trace starts ``TRACE_LEAD_S`` before the first
#: upload due from that start on, so that it holds the factor updates of
#: one upload whatever the seed's phase of the paced schedule.
TRACE_START_SHARE = 0.3
TRACE_SHARE = 0.2
TRACE_MAX_S = 3.0
TRACE_LEAD_S = 0.25
#: How long after the window closes a due reply may still come.
REPLY_GRACE_S = 60.0


def require_chips(chips: int, peaks: dict) -> dict:
    """The device as JAX reports it; exits unless it is a known TPU."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"bench: no TPU; JAX found {d0.platform!r} devices")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    if d0.device_kind not in peaks or d0.device_kind == "source":
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{d0.device_kind!r} in bench/peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


class Compiles:
    """Programs compiled or loaded from the persistent cache, counted
    while the context is open."""

    def __init__(self):
        self.compiled = 0
        self.loaded = 0

    def __enter__(self) -> "Compiles":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _counters(dep) -> dict:
    return {"batcher": dep.server.dispatcher.summary()["solve_batcher"],
            "engines": {t.name: dep.pool.get(t.name).summary()
                        for t in dep.tenants}}


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader sees of one run."""

    reqs: list
    outcomes: dict
    t0: float
    counters: tuple[dict, dict]
    trace: object | None
    trace_span: tuple[float, float] | None
    peak: dict | None
    dims: dict[str, int]
    delta_rows: int

    def completed_in_trace(self, kind: str) -> int:
        if self.trace_span is None:
            return 0
        s, e = self.trace_span
        return sum(1 for q in self.reqs if q.kind == kind
                   and q.idx in self.outcomes and self.outcomes[q.idx].ok
                   and s <= self.outcomes[q.idx].done <= e)

    def solve_dims_in_trace(self) -> list[int]:
        s, e = self.trace_span
        return [self.dims[q.tenant] for q in self.reqs if q.kind == "solve"
                and q.idx in self.outcomes and self.outcomes[q.idx].ok
                and s <= self.outcomes[q.idx].done <= e]

    def update_shape(self) -> tuple[int, int]:
        from repro.kernels.ops import pow2_bucket

        d = max(self.dims[q.tenant] for q in self.reqs if q.kind == "delta")
        return d, pow2_bucket(self.delta_rows)


def _pct(xs, q) -> float | None:
    return float(np.percentile(xs, q)) if len(xs) else None


def trace_start(reqs, seconds: float, length: float) -> float:
    """Where in the window the trace starts, s: ``TRACE_START_SHARE`` of
    it, or ``TRACE_LEAD_S`` before the first upload due from there whose
    trace still ends inside the window."""
    start = TRACE_START_SHARE * seconds
    dues = [q.due for q in reqs if q.kind == "delta"
            and start + TRACE_LEAD_S <= q.due <= seconds - length]
    return min(dues) - TRACE_LEAD_S if dues else start


def end_to_end(reqs, outcomes, t0) -> dict:
    lat = {"solve": [], "delta": []}
    for q in reqs:
        out = outcomes.get(q.idx)
        if out is not None:
            lat[q.kind].append(1e3 * (out.done - (t0 + q.due)))
    return {"solve_p50_ms": _pct(lat["solve"], 50),
            "solve_p95_ms": _pct(lat["solve"], 95),
            "upload_p50_ms": _pct(lat["delta"], 50)}


def run_cell(workload: str, seed: int, seconds: float, *, trace: bool,
             control: bool = False, require_device: bool = True,
             overrides: dict | None = None,
             t_start: float | None = None) -> tuple[dict, "RunData"]:
    """One run of a cell: the result line's object, and what it measured."""
    t_start = time.perf_counter() if t_start is None else t_start
    bm = spec.benchmark()
    wl = spec.workload(workload, bm)
    overrides = overrides or {}
    cfg = _merge(spec.config(wl["config"], bm), overrides.get("config", {}))
    traffic = _merge(spec.traffic(wl["traffic"]), overrides.get("traffic", {}))
    peaks = spec.peaks()
    import jax

    if require_device:
        device = require_chips(int(wl["chips"]), peaks)
        peak = peaks[device["kind"]]
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
        peak = None
    from bench import check, deploy, loadgen

    tenants = deploy.tenant_infos(cfg)
    reqs = loadgen.schedule(traffic, tenants, seconds, seed)
    delta_rows = max([int(c["rows"]) for c in traffic["classes"]
                      if c["kind"] == "delta"], default=1)
    marks = [("start", t_start), ("jax", time.perf_counter())]
    compiles = Compiles().__enter__()
    chips = int(wl["chips"])
    dep = deploy.build(cfg, seed, [q for q in reqs if q.kind == "delta"],
                       delta_rows, chips)
    marks.append(("data", time.perf_counter()))
    peaks = [("data", _peak_bytes(chips))]
    warm_deltas = len(dep.delta_frames) - sum(q.kind == "delta" for q in reqs)

    def send(client, q):
        if q.kind == "solve":
            return np.asarray(client.solve(q.sigma))
        ack = client.upload_raw(dep.delta_frames[warm_deltas + q.delta])
        if ack.duplicate:
            raise RuntimeError("a fresh delta was answered as a duplicate")
        return None

    groups = loadgen.session_groups(traffic, tenants)
    solve_sessions = sum(n for g, n in groups.items() if g[1] == "solve")
    try:
        deploy.admit(dep)
        marks.append(("admit", time.perf_counter()))
        peaks.append(("admit", _peak_bytes(chips)))
        deploy.warm(dep, solve_sessions)
        marks.append(("warm", time.perf_counter()))
        peaks.append(("warm", _peak_bytes(chips)))
        loop = loadgen.OpenLoop(dep.connect, groups, send)
        loop.start()
        gc.collect()
        counters0 = _counters(dep)
        marks.append(("sessions", time.perf_counter()))
        setup_s = marks[-1][1] - t_start
        print("setup: " + ", ".join(
            f"{name} {t - marks[i][1]:.3f} s"
            for i, (name, t) in enumerate(marks[1:])) + f"; compiled "
            f"{compiles.compiled}, loaded {compiles.loaded}; placements "
            f"{dep.placements}; fullest chip's peak bytes by then: "
            + ", ".join(f"{name} {b}" for name, b in peaks), flush=True)
        compiles_before = (compiles.compiled, compiles.loaded)
        t0 = time.perf_counter() + 0.05
        driver = threading.Thread(target=loop.drive, args=(reqs, t0),
                                  name="bench-driver", daemon=True)
        driver.start()
        trace_dir = trace_span = None
        if trace:
            trace_dir = spec.OUT / workload / f"trace_seed{seed}"
            shutil.rmtree(trace_dir, ignore_errors=True)
            length = min(TRACE_MAX_S, TRACE_SHARE * seconds)
            _sleep_until(t0 + trace_start(reqs, seconds, length))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
            s = time.perf_counter()
            _sleep_until(s + length)
            jax.profiler.stop_trace()
            trace_span = (s, time.perf_counter())
        driver.join()
        _sleep_until(t0 + seconds)
        compiles_window = (compiles.compiled - compiles_before[0],
                           compiles.loaded - compiles_before[1])
        loop.wait_idle(REPLY_GRACE_S)
        counters1 = _counters(dep)
        device["memory_peak_bytes"] = _peak_bytes(chips)
        print(f"peak bytes in use per chip: {_chip_peaks(chips)}",
              flush=True)
        loop.close()
        outcomes = dict(loop.outcomes)
        dep.stop()
        gc.collect()
        print(f"compiles in window: {sum(compiles_window)} "
              f"({compiles_window[0]} compiled, {compiles_window[1]} loaded "
              f"from the persistent cache)", flush=True)
        reduced = None
        if trace:
            from bench import trace as trace_lib

            t_reduce = time.perf_counter()
            xplane = trace_lib.find_xplane(trace_dir)
            reduced = trace_lib.reduce(xplane)
            print(f"trace: {xplane.stat().st_size} bytes at {xplane}, "
                  f"reduced in {time.perf_counter() - t_reduce:.1f} s",
                  flush=True)
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
        t_check = time.perf_counter()
        checks = check.compare(dep, reqs, outcomes, seed, control=control)
        print(f"compared with the reference in "
              f"{time.perf_counter() - t_check:.1f} s", flush=True)
    finally:
        compiles.__exit__()
        dep.cleanup()
    failed = sum(1 for q in reqs
                 if q.idx not in outcomes or not outcomes[q.idx].ok)
    e2e = dict(end_to_end(reqs, outcomes, t0), setup_s=setup_s)
    print(f"attempted {len(reqs)}, failed {failed}; "
          + ", ".join(f"{k} {v}" for k, v in e2e.items()), flush=True)
    result = {"correct": check.passed(checks), "attempted": len(reqs),
              "failed": failed}
    run = RunData(reqs, outcomes, t0, (counters0, counters1),
                  reduced, trace_span, peak,
                  {t.name: g["dim"] for g in cfg["tenants"]
                   for t in deploy.tenant_infos({"tenants": [g]})},
                  delta_rows)
    if trace:
        metrics = {}
        for m in spec.metrics_for(workload, "per_layer", bm):
            value = spec.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics_for(workload, "end_to_end", bm)
                   if e2e.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result, run


def _chip_peaks(chips: int) -> list:
    """Each of the cell's chips' peak bytes in use (None where the backend
    keeps no count)."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:chips]]


def _peak_bytes(chips: int) -> int | None:
    """The peak bytes in use of the cell's fullest chip."""
    return max((b for b in _chip_peaks(chips) if b is not None),
               default=None)


def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        time.sleep(delay)


def enable_cache() -> None:
    """The persistent compilation cache at its fixed checkout path (or where
    JAX_COMPILATION_CACHE_DIR says), keeping every program however fast it
    compiled, so that only a cell's first run in a checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    print(f"compilation cache: {enable_compilation_cache()}", flush=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_cache()
    result, _ = run_cell(args.workload, args.seed, args.seconds,
                         trace=bool(args.trace), t_start=T_PROCESS)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
