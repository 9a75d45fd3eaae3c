"""Cross-tenant batched Phase-3 solves: stacked sweeps + the micro-batcher.

The paper makes the server's query path embarrassingly batchable: every
tenant's Phase-3 solve is ``cho_solve(L_t, h_t)`` off an already-cached
factor, and T tenants sharing a dimension differ only in data. Today the
pool runs those T solves sequentially — T jit dispatches, T host round
trips — even when the requests arrived together. This module collapses them:

  * :func:`solve_stacked` — stack T snapshotted ``(L, h)`` pairs into one
    ``[T, d, d]`` / ``[T, d]`` batch and run ONE jitted sweep. The sweep is
    a ``lax.scan`` of the SAME ``cho_solve`` the lone-solve path jits (jax's
    batched triangular solve lowers poorly on CPU; a scan of per-item solves
    inside one program does not), so each lane's weights are bit-identical
    to that tenant's lone ``solve`` at the same state — pinned by tests, and
    the batch extent is padded to a power of two with identity factors /
    zero moments (exact lanes, sliced away) so varying T reuses a bounded
    set of compiled programs.
  * :class:`SolveBatcher` — the micro-batching window in front of
    ``EnginePool.solve_many``. Requests landing within ``window_s`` of each
    other coalesce into one stacked sweep; a lone request on an idle server
    dispatches immediately (the window only opens when traffic is actually
    arriving back-to-back, so idle-regime latency is never taxed).

Entries the backends decline to snapshot (``solve_operands`` -> None, e.g.
sharded block factors) never reach here — ``EnginePool.solve_many`` solves
them under their tenant lock and only stacks the dense rest.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.ops import pow2_bucket
from repro.obs import span


@jax.jit
def _stacked_solve(Ls: tuple[jax.Array, ...], hs: tuple[jax.Array, ...]
                   ) -> tuple[jax.Array, ...]:
    """One sweep of cho_solves over T factor/moment pairs, ONE dispatch.

    Takes (and returns) *tuples* of per-tenant arrays rather than
    pre-stacked batches: the stack, the solve scan, and the per-lane
    unstacking all live inside one compiled program, so a sweep costs one
    dispatch regardless of T — on a CPU host the op-by-op stack/slice
    overhead would otherwise dwarf the actual triangular solves. Retraces
    once per batch extent, which the caller bounds via pow2 bucketing.

    A scan, not a vmap: each step runs the identical (d, d) triangular-solve
    program the lone-solve path runs, which is what makes the batched lanes
    bit-identical to sequential per-tenant solves — and what dodges jax's
    slow batched triangular solve on CPU (same trade ``backends.
    _multi_sigma_factor_solve`` already makes for the multi-sigma sweep).
    """
    def step(_, Lh):
        L, h = Lh
        return None, jax.scipy.linalg.cho_solve((L, True), h)

    _, ws = jax.lax.scan(step, None, (jnp.stack(Ls), jnp.stack(hs)))
    return tuple(ws[i] for i in range(len(Ls)))


# Pad lanes per (d, dtype), built once: ``jnp.eye`` is itself several op-by-op
# dispatches (iota/eq/convert) and each compiles on first use — inside a hot
# sweep that is a ~100ms stall and a per-sweep tax afterwards.
_PAD_LANES: dict[tuple[int, str], tuple[jax.Array, jax.Array]] = {}


def _pad_lane(d: int, dtype) -> tuple[jax.Array, jax.Array]:
    key = (int(d), str(jnp.dtype(dtype)))
    lane = _PAD_LANES.get(key)
    if lane is None:
        lane = (jnp.eye(d, dtype=dtype), jnp.zeros((d,), dtype))
        _PAD_LANES[key] = lane
    return lane


def solve_stacked(entries: Sequence[tuple[jax.Array, jax.Array]]
                  ) -> list[jax.Array]:
    """Solve every snapshotted ``(L, h)`` pair in ONE stacked jit dispatch.

    All entries must share (d, dtype) — the caller buckets. The batch extent
    is padded to the next power of two with identity factors and zero
    moments: ``cho_solve(I, 0) = 0`` exactly, each scan lane is independent,
    and the pad lanes are sliced away, so bucketing costs no accuracy while
    bounding compiled programs at log2(max batch).
    """
    T = len(entries)
    if T == 0:
        return []
    d = entries[0][0].shape[0]
    dtype = entries[0][0].dtype
    Ls = [L for L, _ in entries]
    hs = [h for _, h in entries]
    pad = pow2_bucket(T) - T
    if pad:
        eye, zero = _pad_lane(d, dtype)
        Ls.extend([eye] * pad)
        hs.extend([zero] * pad)
    with span("batch.dispatch", lanes=T):
        ws = _stacked_solve(tuple(Ls), tuple(hs))
    return list(ws[:T])


@dataclasses.dataclass
class _Pending:
    tenant: str
    sigma: float
    future: Future
    req: int                      # the wire request id, for spans
    submitted: float              # time.perf_counter() at submit
    sweep: int = 0                # the sweep that answered it


_STOP = object()


class SolveBatcher:
    """Micro-batching window in front of ``EnginePool.solve_many``.

    Group-commit scheduling with an *adaptive* window: the batcher tracks
    when its last sweep finished, and a request is only held back (for up to
    ``window_s``, collecting companions) when it arrived within ``window_s``
    of that — i.e. when traffic is streaming and a peer request is actually
    likely. A request hitting an idle batcher dispatches immediately (after
    draining whatever is already queued), so the lone-request latency floor
    is one solve, not one solve plus a window. Requests queued while a sweep
    is in flight coalesce for free.

    ``submit`` returns a ``concurrent.futures.Future``; ``solve`` blocks on
    it. Failures of the stacked path fall back to per-request lone solves so
    one bad tenant name cannot fail a whole batch.
    """

    def __init__(self, pool, *, window_s: float = 0.002,
                 max_batch: int = 256, lifted: bool = True):
        self.pool = pool
        self.window_s = window_s
        self.max_batch = max_batch
        self.lifted = lifted
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: threading.Thread | None = None
        self._last_sweep_end = -float("inf")
        # Observability (surfaced via summary()).
        self.sweeps = 0
        self.requests = 0
        self.lone_dispatches = 0
        self.max_batch_seen = 0
        self.fallbacks = 0
        # submit -> start of the request's sweep, summed and longest; host
        # seconds in pool.solve_many. Written by the batcher thread only.
        self.queue_wait_s = 0.0
        self.queue_wait_max_s = 0.0
        self.sweep_s = 0.0

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "SolveBatcher":
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name=f"SolveBatcher-{id(self):x}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        if self._thread is None:
            return
        self._q.put(_STOP)
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():   # pragma: no cover - join timed out
            raise RuntimeError("SolveBatcher thread failed to stop")
        self._thread = None

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def __enter__(self) -> "SolveBatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- submission ----------------------------------------------------------

    def submit(self, tenant: str, sigma: float) -> Future:
        """Enqueue one solve; the Future resolves to the (lifted) weights."""
        return self._submit(tenant, sigma, 0).future

    def _submit(self, tenant: str, sigma: float, req: int) -> _Pending:
        if not self.alive:
            raise RuntimeError("SolveBatcher is not running; call start()")
        p = _Pending(tenant, float(sigma), Future(), req,
                     time.perf_counter())
        self._q.put(p)
        return p

    def solve(self, tenant: str, sigma: float, *, req: int = 0) -> jax.Array:
        """Submit and wait; ``req`` labels the spans of this request."""
        with span("batcher.wait", req=req) as s:
            p = self._submit(tenant, sigma, req)
            try:
                return p.future.result()
            finally:
                s.set_metadata(sweep=p.sweep)

    def summary(self) -> dict:
        return {
            "window_s": self.window_s,
            "sweeps": self.sweeps,
            "requests": self.requests,
            "lone_dispatches": self.lone_dispatches,
            "max_batch_seen": self.max_batch_seen,
            "fallbacks": self.fallbacks,
            "queue_wait_s": self.queue_wait_s,
            "queue_wait_max_s": self.queue_wait_max_s,
            "sweep_s": self.sweep_s,
        }

    # -- scheduler loop ------------------------------------------------------

    def _collect(self, first: _Pending) -> tuple[list[_Pending], bool]:
        """Gather the batch for one sweep; returns (batch, saw stop)."""
        batch = [first]
        arrived = time.monotonic()
        if arrived - self._last_sweep_end <= self.window_s:
            # Load regime: traffic is back-to-back, so holding the window
            # open actually collects companions.
            deadline = arrived + self.window_s
            while len(batch) < self.max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    return batch, True
                batch.append(nxt)
        else:
            # Idle regime: dispatch now; only sweep up what already queued
            # while we were blocked (e.g. during the previous sweep).
            while len(batch) < self.max_batch:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is _STOP:
                    return batch, True
                batch.append(nxt)
        return batch, False

    def _run(self) -> None:
        while True:
            first = self._q.get()
            if first is _STOP:
                return
            with span("batcher.collect"):
                batch, stopping = self._collect(first)
            self._dispatch(batch)
            self._last_sweep_end = time.monotonic()
            if stopping:
                return

    def _dispatch(self, batch: list[_Pending]) -> None:
        start = time.perf_counter()
        self.sweeps += 1
        self.requests += len(batch)
        self.max_batch_seen = max(self.max_batch_seen, len(batch))
        if len(batch) == 1:
            self.lone_dispatches += 1
        for p in batch:
            p.sweep = self.sweeps
            self.queue_wait_s += start - p.submitted
            self.queue_wait_max_s = max(self.queue_wait_max_s,
                                        start - p.submitted)
        try:
            with span("batcher.sweep", sweep=self.sweeps, lanes=len(batch)):
                try:
                    ws = self.pool.solve_many(
                        [(p.tenant, p.sigma) for p in batch],
                        lifted=self.lifted, req_ids=[p.req for p in batch])
                finally:
                    self.sweep_s += time.perf_counter() - start
            for p, w in zip(batch, ws):
                p.future.set_result(w)
        except Exception:
            # Isolate the failure: re-run each request alone so one bad
            # tenant/sigma only fails its own future.
            self.fallbacks += 1
            for p in batch:
                try:
                    w = (self.pool.solve_lifted(p.tenant, p.sigma)
                         if self.lifted else self.pool.solve(p.tenant, p.sigma))
                    p.future.set_result(w)
                except Exception as e:
                    p.future.set_exception(e)
