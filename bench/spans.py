#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and the idle gaps they explain.

    python3 bench/spans.py <trace directory or .xplane.pb>

prints a table of the program's spans (count, total and self seconds) and
the device's idle gaps named after the program's innermost span, for a
trace that ``bench/run.py --trace 1`` left under
``.bench_out/<cell>/trace_seed<n>`` or any other ``jax.profiler`` trace.

The program's spans (``repro.obs``) are host events whose names
start with a layer prefix (``wire.``, ``batcher.``, ``journal.``, ...); each
host thread is one line of the host plane, so a span's parent is the
innermost program span that encloses it on its line. Self time is a span's
duration less that of its child program spans.

Idle gaps (``attribute_gaps``): the device's idle intervals, as
``bench/trace.py`` finds them. Where program spans overlap a gap, only the
innermost ones (those enclosing no other program span that overlaps the
gap on their line) are candidates, and the one overlapping the gap most
names it: ``<span>``, or ``<span>/<runtime call>`` where a profiler-emitted
host event nested inside that span overlaps the gap (the one overlapping
most). A gap no program span overlaps keeps ``bench/trace.py``'s name.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import pathlib
import sys

if __package__ in (None, ""):
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

from bench import trace  # noqa: E402
from repro.obs import SPAN_PREFIXES  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Event:
    """One host event: a program span or a profiler-emitted call."""

    name: str
    start: int          # ns
    end: int            # ns
    line: int           # the host thread's line, numbered across planes
    args: dict

    @property
    def program(self) -> bool:
        return self.name.startswith(SPAN_PREFIXES)


@dataclasses.dataclass
class Spans:
    events: list[Event]                       # every host event, by start
    table: dict[str, tuple[int, float, float]]  # name -> count, total, self
    gaps: dict[str, float]                    # idle gap name -> seconds


def read(path: str | pathlib.Path) -> Spans:
    """Host events, the span table and the named idle gaps of one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    t_min = t_max = None
    events: list[Event] = []
    busy: list[tuple[int, int]] = []
    line_no = 0
    for plane in pd.planes:
        is_host = plane.name.startswith("/host:")
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            line_no += 1
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                t_min = s if t_min is None else min(t_min, s)
                t_max = s + d if t_max is None else max(t_max, s + d)
                if is_host and d > 0:
                    name = ev.name
                    args = (dict(ev.stats) if name.startswith(SPAN_PREFIXES)
                            else {})
                    events.append(Event(name, s, s + d, line_no, args))
                elif is_device and line.name == "XLA Ops":
                    busy.append((s, s + d))
    events.sort(key=lambda e: (e.start, -e.end))
    return Spans(events, span_table(events),
                 attribute_gaps(trace._merge(busy), events, t_min, t_max))


def span_table(events: list[Event]) -> dict[str, tuple[int, float, float]]:
    """Per program span name: count, total seconds, self seconds."""
    count: dict[str, int] = {}
    total: dict[str, float] = {}
    child: dict[int, int] = {}
    stacks: dict[int, list[tuple[int, Event]]] = {}
    prog = sorted((e for e in events if e.program),
                  key=lambda e: (e.start, -e.end))
    for i, e in enumerate(prog):
        stack = stacks.setdefault(e.line, [])
        while stack and stack[-1][1].end <= e.start:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            child[parent] = child.get(parent, 0) + (e.end - e.start)
        stack.append((i, e))
        count[e.name] = count.get(e.name, 0) + 1
        total[e.name] = total.get(e.name, 0.0) + (e.end - e.start) * 1e-9
    self_s = dict.fromkeys(count, 0.0)
    for i, e in enumerate(prog):
        self_s[e.name] += (e.end - e.start - child.get(i, 0)) * 1e-9
    return {n: (count[n], total[n], self_s[n]) for n in count}


def _overlap(e: Event, gs: int, ge: int) -> int:
    return min(e.end, ge) - max(e.start, gs)


def _innermost(cands: list[Event]) -> list[Event]:
    """Of the program spans overlapping one gap, those that enclose no
    other of them on their line (spans on one line nest properly)."""
    out = []
    by_line: dict[int, list[Event]] = {}
    for e in cands:
        by_line.setdefault(e.line, []).append(e)
    for line in by_line.values():
        line.sort(key=lambda e: (e.start, -e.end))
        for a, b in zip(line, line[1:] + [None]):
            if b is None or b.start >= a.end:
                out.append(a)
    return out


def attribute_gaps(busy: list[tuple[int, int]], events: list[Event],
                   t_min: int | None, t_max: int | None) -> dict[str, float]:
    """Device idle seconds by the name of what the host was doing (above)."""
    if t_min is None:
        return {}
    gaps, cursor = [], t_min
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t_max > cursor:
        gaps.append((cursor, t_max))
    events = sorted(events, key=lambda e: (e.start, -e.end))
    prog = [e for e in events if e.program]
    runtime: dict[int, list[Event]] = {}
    for e in events:
        if not e.program and not e.name.startswith("bench."):
            runtime.setdefault(e.line, []).append(e)
    starts = {line: [e.start for e in evs] for line, evs in runtime.items()}
    out: dict[str, float] = {}
    unexplained: list[tuple[int, int]] = []
    active: list[tuple[int, int, Event]] = []   # heap by end time
    nxt = 0
    for gs, ge in gaps:
        while nxt < len(prog) and prog[nxt].start < ge:
            heapq.heappush(active, (prog[nxt].end, nxt, prog[nxt]))
            nxt += 1
        while active and active[0][0] <= gs:
            heapq.heappop(active)
        cands = [e for _, _, e in active if _overlap(e, gs, ge) > 0]
        if not cands:
            unexplained.append((gs, ge))
            continue
        best = max(_innermost(cands), key=lambda e: _overlap(e, gs, ge))
        name, call, call_ov = best.name, None, 0
        evs = runtime.get(best.line, [])
        i = bisect.bisect_left(starts.get(best.line, []), best.start)
        while i < len(evs) and evs[i].start < best.end:
            ov = _overlap(evs[i], gs, ge)
            if evs[i].end <= best.end and ov > call_ov:
                call, call_ov = evs[i].name, ov
            i += 1
        if call is not None:
            name = name + "/" + call
        out[name] = out.get(name, 0.0) + (ge - gs) * 1e-9
    # The rest by bench/trace.py's own rule: hand it a busy set whose only
    # gaps are the unexplained ones.
    between, cursor = [], t_min
    for gs, ge in unexplained:
        between.append((cursor, gs))
        cursor = ge
    between.append((cursor, t_max))
    host = [(e.start, e.end, e.name) for e in events if not e.program]
    for name, secs in trace._attribute_gaps(between, host, t_min,
                                            t_max).items():
        out[name] = out.get(name, 0.0) + secs
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit(__doc__.split("\n\n")[1])
    path = pathlib.Path(argv[0])
    spans = read(trace.find_xplane(path) if path.is_dir() else path)
    print(f"{'span':<24} {'count':>7} {'total_s':>10} {'self_s':>10}")
    for name, (n, tot, own) in sorted(spans.table.items(),
                                      key=lambda kv: -kv[1][1]):
        print(f"{name:<24} {n:>7} {tot:>10.4f} {own:>10.4f}")
    print("\nidle gaps, seconds:")
    for name, secs in sorted(spans.gaps.items(), key=lambda kv: -kv[1])[:10]:
        print(f"  {secs:10.4f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
