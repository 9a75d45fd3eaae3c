"""The profiler's ``.xplane.pb`` reduced to device busy time and program times.

Device planes are ``/device:<platform>:<n>``. On each, the ``XLA Ops`` line
holds every operation's execution and the ``XLA Modules`` line every
program's (jitted function's) execution. Reduced here:

  * ``busy_s``: the union of the operation intervals, averaged over the
    device planes that ran anything;
  * ``window_s``: the span of all events the trace holds, host and device;
  * ``modules`` / ``ops``: device seconds per program / per operation (a
    program's trailing ``(<id>)`` dropped; an operation named by its HLO
    instruction, ``%gemm_nt_pallas.3``, not the whole instruction text),
    summed over the device planes, and how many planes ran each program;
  * ``gaps``: device idle time inside the window, attributed to the host
    span that overlapped each gap most (spans of the benchmark's own
    sessions, ``bench.*``, only where nothing else ran).
"""
from __future__ import annotations

import dataclasses
import heapq
import pathlib
import re

_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    modules: dict[str, float]
    module_counts: dict[str, int]
    ops: dict[str, float]
    op_counts: dict[str, int]
    gaps: dict[str, float]
    devices: int
    module_planes: dict[str, int] = dataclasses.field(default_factory=dict)

    def module_time(self, *needles: str) -> float:
        return sum(t for name, t in self.modules.items()
                   if any(n in name for n in needles))

    def module_plane_count(self, *needles: str) -> int:
        """Device planes that ran a matching program (the most of any)."""
        return max((c for name, c in self.module_planes.items()
                    if any(n in name for n in needles)), default=0)

    def module_count(self, *needles: str) -> int:
        return sum(c for name, c in self.module_counts.items()
                   if any(n in name for n in needles))

    def op_time(self, *needles: str) -> tuple[float, int]:
        names = [n for n in self.ops if any(s in n for s in needles)]
        return (sum(self.ops[n] for n in names),
                sum(self.op_counts[n] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": best(self.modules), "idle_gaps": best(self.gaps)}


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce(path: str | pathlib.Path) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    t_min, t_max = None, None
    busy_total, devices = 0.0, 0
    modules: dict[str, float] = {}
    module_counts: dict[str, int] = {}
    ops: dict[str, float] = {}
    op_counts: dict[str, int] = {}
    busy_all: list[tuple[int, int]] = []
    host: list[tuple[int, int, str]] = []
    module_planes: dict[str, int] = {}
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        intervals: list[tuple[int, int]] = []
        ran: set[str] = set()
        for line in plane.lines:
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                t_min = s if t_min is None else min(t_min, s)
                t_max = s + d if t_max is None else max(t_max, s + d)
                if not is_device:
                    if plane.name.startswith("/host:") and d > 0:
                        host.append((s, s + d, ev.name))
                    continue
                if line.name == "XLA Modules":
                    name = _ID.sub("", ev.name)
                    modules[name] = modules.get(name, 0.0) + d * 1e-9
                    module_counts[name] = module_counts.get(name, 0) + 1
                    ran.add(name)
                elif line.name == "XLA Ops":
                    name = ev.name.split(" = ", 1)[0]
                    ops[name] = ops.get(name, 0.0) + d * 1e-9
                    op_counts[name] = op_counts.get(name, 0) + 1
                    intervals.append((s, s + d))
        for name in ran:
            module_planes[name] = module_planes.get(name, 0) + 1
        if is_device and intervals:
            merged = _merge(intervals)
            busy_total += sum(e - s for s, e in merged) * 1e-9
            busy_all += merged
            devices += 1
    window = 0.0 if t_min is None else (t_max - t_min) * 1e-9
    return Reduced(busy_s=busy_total / max(devices, 1), window_s=window,
                   modules=modules, module_counts=module_counts, ops=ops,
                   op_counts=op_counts,
                   gaps=_attribute_gaps(_merge(busy_all), host, t_min, t_max),
                   devices=devices, module_planes=module_planes)


def _attribute_gaps(busy, host, t_min, t_max) -> dict[str, float]:
    if t_min is None:
        return {}
    gaps, cursor = [], t_min
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t_max > cursor:
        gaps.append((cursor, t_max))
    host = sorted(host)
    active: list[tuple[int, int, str]] = []   # heap by end time
    nxt = 0
    out: dict[str, float] = {}
    for gs, ge in gaps:
        while nxt < len(host) and host[nxt][0] < ge:
            hs, he, name = host[nxt]
            heapq.heappush(active, (he, hs, name))
            nxt += 1
        while active and active[0][0] <= gs:
            heapq.heappop(active)
        best, best_bench = ("idle, no host span", 0), ("", 0)
        for he, hs, name in active:
            ov = min(he, ge) - max(hs, gs)
            if ov <= 0:
                continue
            if name.startswith("bench."):
                if ov > best_bench[1]:
                    best_bench = (name + " in flight", ov)
            elif ov > best[1]:
                best = (name, ov)
        name = best[0] if best[1] or not best_bench[1] else best_bench[0]
        out[name] = out.get(name, 0.0) + (ge - gs) * 1e-9
    return out
