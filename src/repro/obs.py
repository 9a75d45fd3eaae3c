"""Spans and counters at the federation server's layer boundaries.

Kept apart from ``repro.server`` and ``repro.fed`` so that both may use it.
Spans are ``jax.profiler.TraceAnnotation`` (TraceMe) events. They cost
about a microsecond each and record nothing until a profiler session is
open (``jax.profiler.start_trace``); inside one they land on the
profiler's host plane, one line per thread, on the clock of the device's
own events. Span names are constant strings whose prefix names the layer
(:data:`SPAN_PREFIXES`); identifiers ride along as keyword arguments.

Every wire frame gets a request id (``WireDispatcher``). The session
thread that handles the frame records it here (:func:`set_request`), so a
span deeper down the same thread (admission, journal, engine) tags itself
with :func:`request` without the id being passed through every call.

Counters stay with the object whose work they time (the ``summary()``
dicts of ``WireDispatcher``, ``SolveBatcher``, ``EnginePool`` and
``FusionEngine``), with one exception: garbage-collector pauses belong to
the whole process, so :func:`watch_gc` installs one process-wide
``gc.callbacks`` hook and :func:`gc_summary` reads it; the server's
``WireDispatcher`` installs it and reports it.
"""
from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation as span

#: Name prefixes of the program's spans, one per layer.
SPAN_PREFIXES = ("wire.", "session.", "batcher.", "batch.", "pool.",
                 "journal.", "engine.", "host.")


class _Local(threading.local):
    req = 0


_local = _Local()


def set_request(req: int) -> None:
    """Make ``req`` the id of the request this thread is handling."""
    _local.req = req


def request() -> int:
    """The id of the request this thread is handling (0 outside one)."""
    return _local.req


class _GCWatch:
    """Collector pauses, timed by a ``gc.callbacks`` start/stop pair.

    A collection runs on whichever thread triggered it, start and stop on
    that thread, and never two at once, so one pending start suffices.
    """

    def __init__(self):
        self.pauses = 0
        self.pause_s = 0.0
        self.pause_max_s = 0.0
        self._t0 = 0.0
        self._span = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = span("host.gc", gen=info["generation"])
            self._span.__enter__()
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        self.pauses += 1
        self.pause_s += dt
        self.pause_max_s = max(self.pause_max_s, dt)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


_gc_watch = _GCWatch()


def watch_gc() -> None:
    """Time every collector pause from now on (idempotent)."""
    if _gc_watch not in gc.callbacks:
        gc.callbacks.append(_gc_watch)


def gc_summary() -> dict:
    """Collector pauses since :func:`watch_gc`: count, total and longest."""
    return {"pauses": _gc_watch.pauses, "pause_s": _gc_watch.pause_s,
            "pause_max_s": _gc_watch.pause_max_s}
