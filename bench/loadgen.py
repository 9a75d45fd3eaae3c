"""Open-loop traffic over the real wire: the schedule and the sessions.

The schedule is a pure function of the traffic file, the tenants and the
seed. Its size is fixed by the mix (rate x seconds requests, split over the
request classes by their shares), so every seed sends the same amount of
work, in another order and at other instants:

  * ``poisson``: arrival instants uniform over the window (a Poisson
    process conditioned on its count);
  * ``gamma``: a renewal process with Gamma gaps of the given coefficient
    of variation (bursts for cv > 1), scaled to span the window;
  * ``paced``: evenly spaced, at a phase drawn from the seed (sites that
    upload on a schedule).

Tenants are drawn by the mix's popularity law (uniform, or Zipf(s) over a
rank order that is reshuffled every ``reshuffle_s``); a solve's sigma is
uniform over its tenant's cached grid; a delta comes from a uniformly
drawn site of its tenant.

Sessions are threads, each holding one connected, negotiated
``FrameClient``. A due request is handed to its session group (a tenant's
analysts, or one site's own session); it waits there until a session of
the group is idle, and that wait counts: latency runs from when the
request was due, and ``sent - due`` is the generator's lag.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int
    due: float          # seconds after the window opens
    kind: str           # "solve" or "delta"
    tenant: str
    sigma: float        # solves
    site: int           # deltas: the site's client index
    delta: int          # deltas: index into the delta batches
    group: tuple        # session group


@dataclasses.dataclass(frozen=True)
class TenantInfo:
    name: str
    kind: str
    clients: int
    sigmas: tuple[float, ...]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


def arrival_times(arrivals: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    process = arrivals["process"]
    if process == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    if process == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0, n + 1)
        return np.cumsum(gaps)[:n] / gaps.sum() * seconds
    if process == "paced":
        return (np.arange(n) + rng.uniform()) * (seconds / max(n, 1))
    raise SystemExit(f"bench: unknown arrival process {process!r}")


def _popularity(law: dict, names: list[str], dues: np.ndarray,
                seconds: float, rng: np.random.Generator) -> list[str]:
    if law["law"] == "uniform":
        return [names[i] for i in rng.integers(0, len(names), len(dues))]
    if law["law"] == "zipf":
        p = 1.0 / np.arange(1, len(names) + 1) ** float(law["s"])
        p /= p.sum()
        epochs = int(np.ceil(seconds / law["reshuffle_s"])) + 1
        orders = [rng.permutation(len(names)) for _ in range(epochs)]
        ranks = rng.choice(len(names), size=len(dues), p=p)
        epoch = (dues // law["reshuffle_s"]).astype(int)
        return [names[orders[e][r]] for e, r in zip(epoch, ranks)]
    raise SystemExit(f"bench: unknown popularity law {law['law']!r}")


def rate(traffic: dict) -> float:
    return float(traffic["knee_per_s"]) * float(traffic["share_of_knee"])


def schedule(traffic: dict, tenants: list[TenantInfo], seconds: float,
             seed: int, *, rate_per_s: float | None = None) -> list[Request]:
    """Every request due in a window of ``seconds``, in due order.

    Each request class arrives by the mix's process, or by its own
    ``arrivals`` where it names one.
    """
    rng = rng_for(seed, 1)
    r = rate(traffic) if rate_per_s is None else rate_per_s
    n = int(round(r * seconds))
    classes = traffic["classes"]
    counts = [int(round(c["share"] * n)) for c in classes]
    counts[-1] = n - sum(counts[:-1])
    by_name = {t.name: t for t in tenants}
    out: list[Request] = []
    for cls, count in zip(classes, counts):
        dues = arrival_times(cls.get("arrivals", traffic["arrivals"]), count,
                             seconds, rng)
        eligible = [t.name for t in tenants
                    if t.kind in cls.get("tenant_kinds", [t.kind])]
        names = _popularity(traffic["popularity"], eligible, dues, seconds,
                            rng)
        for due, name in zip(dues, names):
            t = by_name[name]
            if cls["kind"] == "solve":
                sigma = t.sigmas[int(rng.integers(0, len(t.sigmas)))]
                out.append(Request(-1, float(due), "solve", name,
                                   float(sigma), -1, -1, (name, "solve")))
            elif cls["kind"] == "delta":
                site = int(rng.integers(0, t.clients))
                out.append(Request(-1, float(due), "delta", name, 0.0, site,
                                   -1, (name, "site", site)))
            else:
                raise SystemExit(f"bench: unknown request kind {cls['kind']!r}")
    out.sort(key=lambda q: q.due)
    numbered, n_delta = [], 0
    for i, q in enumerate(out):
        q = dataclasses.replace(q, idx=i)
        if q.kind == "delta":
            q = dataclasses.replace(q, delta=n_delta)
            n_delta += 1
        numbered.append(q)
    return numbered


def session_groups(traffic: dict, tenants: list[TenantInfo]) -> dict:
    """Session group -> number of sessions, for every group a mix can use."""
    groups: dict[tuple, int] = {}
    for cls in traffic["classes"]:
        for t in tenants:
            if t.kind not in cls.get("tenant_kinds", [t.kind]):
                continue
            if cls["kind"] == "solve":
                groups[(t.name, "solve")] = int(cls["sessions_per_tenant"])
            else:
                for k in range(t.clients):
                    groups[(t.name, "site", k)] = 1
    return groups


@dataclasses.dataclass
class Outcome:
    sent: float = float("nan")
    done: float = float("nan")
    ok: bool = False
    error: str = ""
    result: object = None


_STOP = object()


class OpenLoop:
    """The sessions of one run and the dispatcher that feeds them."""

    def __init__(self, connect: Callable[[str], object],
                 groups: dict[tuple, int],
                 send: Callable[[object, Request], object]):
        self._connect = connect
        self._send = send
        self._queues = {g: queue.SimpleQueue() for g in groups}
        self._clients = {g: [connect(g[0]) for _ in range(n)]
                         for g, n in groups.items()}
        self._threads: list[threading.Thread] = []
        self.outcomes: dict[int, Outcome] = {}
        self._lock = threading.Lock()
        self._pending = 0
        self._idle = threading.Condition(self._lock)

    def _session(self, group: tuple, slot: int) -> None:
        q = self._queues[group]
        while True:
            req = q.get()
            if req is _STOP:
                return
            out = Outcome(sent=time.perf_counter())
            client = self._clients[group][slot]
            try:
                with jax.profiler.TraceAnnotation(f"bench.{req.kind}"):
                    out.result = self._send(client, req)
                out.ok = True
            except Exception as e:  # noqa: BLE001 - every failure is recorded
                out.error = f"{type(e).__name__}: {e}"
                try:
                    client.close()
                except OSError:
                    pass
                self._clients[group][slot] = self._connect(group[0])
            out.done = time.perf_counter()
            with self._lock:
                self.outcomes[req.idx] = out
                self._pending -= 1
                if self._pending == 0:
                    self._idle.notify_all()

    def start(self) -> None:
        for g, clients in self._clients.items():
            for slot in range(len(clients)):
                th = threading.Thread(target=self._session, args=(g, slot),
                                      name=f"bench-session-{g}-{slot}",
                                      daemon=True)
                th.start()
                self._threads.append(th)

    def submit(self, req: Request) -> None:
        with self._lock:
            self._pending += 1
        self._queues[req.group].put(req)

    def drive(self, reqs: list[Request], t0: float) -> None:
        """Hand each request to its group when it falls due (open loop)."""
        for req in reqs:
            delay = t0 + req.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self.submit(req)

    def wait_idle(self, timeout_s: float) -> bool:
        with self._lock:
            return self._idle.wait_for(lambda: self._pending == 0,
                                       timeout=timeout_s)

    def close(self) -> None:
        """Stop the sessions; requests still queued are dropped (never
        answered), and a session stuck in a request is left to its socket
        timeout."""
        for g, clients in self._clients.items():
            q = self._queues[g]
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            for _ in clients:
                q.put(_STOP)
        for th in self._threads:
            th.join(timeout=5.0)
        for clients in self._clients.values():
            for c in clients:
                try:
                    c.close()
                except OSError:
                    pass
