"""FusionEngine — the stateful one-shot fusion server (policy layer).

The paper's server is, in full, the pair ``(G, h)`` plus algebra on it. This
module makes that literal: one object owns the fused :class:`SuffStats`,
retains per-client contributions, and exposes every server-side capability
of the paper as a method:

==================  =======================================================
method              paper surface
==================  =======================================================
``ingest``          Phase 2 aggregation (Thm 1) / streaming updates (§VI-C)
``ingest_rows``     §VI-C with row-level deltas (incremental factor update)
``ingest_async``    queued §VI-C deltas, coalesced into one rank-r mutation
``flush``           apply the async queue as ONE fused delta (Thm 1 batching)
``ingest_distributed``  Phases 1+2 on-mesh: psum of shard-local stats
``drop/restore``    client dropout and rejoin (Thm 8) — exact on the subset
``solve``           Phase 3 ridge solve (Thm 3), factor cached per sigma
``solve_batch``     one batched multi-sigma solve (batched Phase 3)
``loco_weights``    all K leave-one-client-out models, all sigmas (Prop 5)
``loco_cv``         Prop 5 sigma selection as ONE vectorized solve
``predict``         serving hot path: x -> x @ w_sigma off the cached factor
``inference``       stderr / CI / PI off the cached factor (server.inference)
==================  =======================================================

The engine itself is *backend-agnostic*: all representation-dependent linear
algebra — where the fused ``(G, h)`` lives, what a "factor" is, how a solve
runs — is delegated to a :class:`~repro.server.backends.LinalgBackend`
(dense single-device by default; ``server.distributed.ShardedBackend`` keeps
``G`` block-sharded across a mesh end to end). What stays here is policy:

  * the per-client ledger behind ``drop``/``restore`` and LOCO;
  * the async ingest coalescer (:class:`CoalescerPolicy`): queued deltas
    are folded into the server state as one fused delta per flush, so a
    stream of rank-1 §VI-C updates costs one rank-r factor mutation per
    flush instead of one per delta — every read drains the queue first, so
    solves are always exact on everything ingested;
  * per-sigma factor caching with staleness-bounded incremental updates —
    PSD low-rank mutations up/down-date every cached factor in O(r d^2)
    (when the backend supports it) instead of refactorizing at O(d^3/3);
    once a factor has absorbed more than ``max_update_rank`` update vectors
    it is evicted and lazily refactorized on next use;
  * the chol-vs-spectral ``solve_batch`` method choice, falling back to the
    Cholesky sweep when the backend has no spectral path.

The pure-function reference implementations live in ``core.fusion`` and stay
authoritative for correctness; tests pin the engine against them.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Hashable, Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.core.sufficient_stats import (MATMUL_PRECISION, SuffStats,
                                         compute_stats, fuse_stats)
from repro import obs
from repro.server.backends import DenseBackend, LinalgBackend
from repro.server.cholesky import psd_update_vectors
from repro.obs import span


@dataclasses.dataclass
class _CachedFactor:
    factor: Any       # backend-opaque factor of G + sigma I
    stale_rank: int   # update vectors absorbed since the last full factorization


@dataclasses.dataclass(frozen=True)
class CoalescerPolicy:
    """When the async ingest queue folds itself into the factors.

    ``ingest_async``/``ingest_rows_async`` only queue; a flush applies the
    whole queue as ONE fused delta — one backend ``fuse`` and one rank-r
    factor mutation instead of one per delta. Auto-flush triggers when the
    queued update rank reaches ``max_rank`` (keep it <= the engine's
    ``max_update_rank`` so a flush stays on the incremental path) or when
    the oldest queued delta is older than ``max_staleness_s`` — checked on
    every queue/read operation. The engine itself has no background thread
    (the serving loop drives its clock); ``server.pool.EnginePool`` adds one
    that enforces ``max_staleness_s`` even when no reads arrive.
    """

    max_rank: int = 64
    max_staleness_s: float = math.inf


@dataclasses.dataclass
class _PendingDelta:
    stats: SuffStats
    client_id: Hashable | None
    update_vectors: jax.Array | None
    rank_bound: int           # conservative rank if vectors are unknown
    queued_at: float


@jax.jit
def _loco_solve(G, h, Gk, hk, sigmas):
    """w_{-k}(sigma) for every client k and sigma: (K, S, d)."""
    Gm = G[None] - Gk                      # (K, d, d)
    hm = h[None] - hk                      # (K, d)
    eye = jnp.eye(G.shape[0], dtype=G.dtype)

    def per_sigma(sigma):
        def per_client(gm, hmk):
            L = jnp.linalg.cholesky(gm + sigma * eye)
            return jax.scipy.linalg.cho_solve((L, True), hmk)

        return jax.vmap(per_client)(Gm, hm)

    return jnp.transpose(jax.vmap(per_sigma)(sigmas), (1, 0, 2))


class FusionEngine:
    """Stateful fusion server over one model's sufficient statistics."""

    def __init__(self, dim: int, *, dtype=None,
                 backend: LinalgBackend | None = None,
                 max_update_rank: int | None = None,
                 coalesce: CoalescerPolicy | None = None):
        if backend is None:
            backend = DenseBackend(dim, dtype=dtype if dtype is not None
                                   else jnp.float32)
        elif dtype is not None and jnp.dtype(dtype) != jnp.dtype(backend.dtype):
            # A silent downcast here would make precision differ between
            # backends for the same call; construct the backend with the
            # dtype you want instead.
            raise ValueError(f"requested dtype {jnp.dtype(dtype)} != backend "
                             f"dtype {jnp.dtype(backend.dtype)}")
        self.backend: LinalgBackend = backend
        if self.backend.dim != dim:
            raise ValueError(
                f"backend dim {self.backend.dim} != engine dim {dim}")
        self._clients: dict[Hashable, SuffStats] = {}
        # dropped id -> (stats, update vectors computed at drop time, reused
        # verbatim on restore so drop->restore round-trips the factors)
        self._dropped: dict[Hashable, tuple[SuffStats, jax.Array | None]] = {}
        self._factors: dict[float, _CachedFactor] = {}
        self.max_update_rank = (max(1, dim // 4) if max_update_rank is None
                                else max_update_rank)
        self.dtype = self.backend.dtype
        self.coalesce = (CoalescerPolicy(max_rank=self.max_update_rank)
                         if coalesce is None else coalesce)
        self._pending: list[_PendingDelta] = []
        # Observability counters (surfaced by benchmarks and serve_fusion).
        self.stats_version = 0
        self.cold_factorizations = 0
        self.incremental_updates = 0
        self.flushes = 0
        self.coalesced_deltas = 0
        # Host seconds in ingest/ingest_rows: statistics, fusion and the
        # dispatch of the factor updates (not their device time).
        self.ingest_host_s = 0.0

    # -- construction -------------------------------------------------------

    @classmethod
    def from_clients(cls, stats: Mapping[Hashable, SuffStats] | Sequence[SuffStats],
                     **kwargs) -> "FusionEngine":
        """Engine over per-client stats; retains each for drop/restore/LOCO.

        ``backend="auto"`` (with optional ``mesh=`` and ``threshold=``)
        picks dense vs sharded from the measured crossover table — see
        :mod:`repro.server.select`.
        """
        items = (stats.items() if isinstance(stats, Mapping)
                 else enumerate(stats))
        items = list(items)
        if not items:
            raise ValueError("need at least one client's statistics")
        d = items[0][1].dim
        kwargs.setdefault("dtype", items[0][1].gram.dtype)
        if kwargs.get("backend") == "auto":
            from repro.server.select import auto_backend

            kwargs["backend"] = auto_backend(
                d, kwargs.pop("mesh", None),
                threshold=kwargs.pop("threshold", None),
                dtype=kwargs["dtype"])
        backend = kwargs.get("backend")
        if backend is not None and int(backend.count) != 0:
            # Reusing a populated backend would silently fuse ON TOP of its
            # existing (G, h), double-counting statistics.
            raise ValueError(
                "backend already holds fused statistics "
                f"(count={int(backend.count)}); build the engine with "
                "from_stats, or pass a fresh backend")
        eng = cls(d, **kwargs)
        for cid, s in items:
            eng.ingest(s, client_id=cid)
        return eng

    @classmethod
    def from_stats(cls, stats: SuffStats, **kwargs) -> "FusionEngine":
        """Engine over pre-fused statistics (no per-client retention)."""
        kwargs.setdefault("dtype", stats.gram.dtype)
        eng = cls(stats.dim, **kwargs)
        eng.backend.set_stats(stats)
        eng.stats_version += 1
        return eng

    # -- inspection ---------------------------------------------------------

    @property
    def stats(self) -> SuffStats:
        """Dense view of the fused statistics (gathers on a sharded backend)."""
        self.flush()
        return self.backend.stats()

    @property
    def dim(self) -> int:
        return self.backend.dim

    @property
    def client_ids(self) -> tuple[Hashable, ...]:
        return tuple(self._clients)

    @property
    def dropped_ids(self) -> tuple[Hashable, ...]:
        return tuple(self._dropped)

    @property
    def count(self) -> int:
        """Effective sample size currently fused (Thm 8 reporting)."""
        self.flush()
        return int(self.backend.count)

    def summary(self) -> dict:
        return {
            "dim": self.dim,
            "backend": self.backend.name,
            "clients": len(self._clients),
            "dropped": len(self._dropped),
            # backend count read directly: summary is pure observability and
            # must not drain the coalescer queue the way ``self.count`` does.
            "rows": int(self.backend.count),
            "cached_sigmas": sorted(self._factors),
            "spectral_cached": self.backend.spectral_ready,
            "stats_version": self.stats_version,
            "cold_factorizations": self.cold_factorizations,
            "incremental_updates": self.incremental_updates,
            # by path, for backends that count them (DenseBackend)
            "update_paths": dict(getattr(self.backend, "update_paths", {})),
            "flushes": self.flushes,
            "coalesced_deltas": self.coalesced_deltas,
            "pending_deltas": self.pending_deltas,
            "ingest_host_s": self.ingest_host_s,
        }

    # -- mutation (Thm 1 / Thm 8 / §VI-C) -----------------------------------

    def ingest(self, stats: SuffStats, client_id: Hashable | None = None, *,
               update_vectors: jax.Array | None = None) -> None:
        """Fold a statistics delta into the server state (Thm 1 additivity).

        ``client_id`` retains the contribution for later ``drop``/``restore``
        and LOCO CV; repeated ingests under one id accumulate (a client
        uploading in installments, §VI-C). ``update_vectors`` (r, d) with
        ``U^T U = stats.gram`` lets cached factors be up-dated incrementally;
        without them the PSD square root is derived (or, when the delta is
        clearly high-rank, the cache is simply invalidated).
        """
        with span("engine.ingest", req=obs.request()):
            t0 = time.perf_counter()
            self._ingest(stats, client_id, update_vectors)
            self.ingest_host_s += time.perf_counter() - t0

    def _ingest(self, stats: SuffStats, client_id: Hashable | None,
                update_vectors: jax.Array | None) -> None:
        if stats.dim != self.dim:
            raise ValueError(f"stats dim {stats.dim} != engine dim {self.dim}")
        self.flush()
        self.backend.fuse(stats, 1.0)
        if client_id is not None:
            prev = self._clients.get(client_id)
            self._clients[client_id] = stats if prev is None else prev + stats
        self._touch_factors(stats, update_vectors, sign=1.0)

    def ingest_rows(self, A: jax.Array, b: jax.Array,
                    client_id: Hashable | None = None) -> SuffStats:
        """§VI-C streaming: fold raw rows in; the rows ARE the update vectors."""
        with span("engine.ingest", req=obs.request()):
            t0 = time.perf_counter()
            s = compute_stats(A, b)
            self._ingest(s, client_id, A.astype(self.dtype))
            self.ingest_host_s += time.perf_counter() - t0
        return s

    # -- async ingest (coalescing queue) -------------------------------------

    @property
    def pending_deltas(self) -> int:
        return len(self._pending)

    @property
    def oldest_pending_age_s(self) -> float:
        """Age of the oldest queued delta (0 when the queue is empty).

        Pure observability — unlike ``count``/``stats`` it never drains the
        queue, so a background flusher can poll it to decide *whether* to
        flush without perturbing the thing it is measuring.
        """
        if not self._pending:
            return 0.0
        return time.monotonic() - self._pending[0].queued_at

    @property
    def pending_rank(self) -> int:
        """Conservative update rank the queue would apply when flushed."""
        return sum(p.rank_bound for p in self._pending)

    def ingest_async(self, stats: SuffStats,
                     client_id: Hashable | None = None, *,
                     update_vectors: jax.Array | None = None) -> None:
        """Queue a statistics delta; visible only after the next flush.

        Many queued deltas are folded into the server state as ONE fused
        delta (Thm 1 makes the batching exact), so a stream of small §VI-C
        updates costs one rank-r factor mutation per flush instead of one
        per delta. Flushing happens on :meth:`flush`, on any read of the
        fused state (``solve``/``predict``/``stats``/...), before any
        synchronous mutation, or automatically per :class:`CoalescerPolicy`.
        """
        if stats.dim != self.dim:
            raise ValueError(f"stats dim {stats.dim} != engine dim {self.dim}")
        bound = (int(update_vectors.shape[0]) if update_vectors is not None
                 else min(int(stats.count), self.dim))
        self._pending.append(_PendingDelta(stats, client_id, update_vectors,
                                           bound, time.monotonic()))
        self._autoflush()

    def ingest_rows_async(self, A: jax.Array, b: jax.Array,
                          client_id: Hashable | None = None) -> SuffStats:
        """§VI-C streaming through the coalescer: queue rows, flush later."""
        s = compute_stats(A, b)
        self.ingest_async(s, client_id=client_id,
                          update_vectors=A.astype(self.dtype))
        return s

    def flush(self) -> int:
        """Apply the whole queue as one fused delta; returns #deltas folded.

        One backend ``fuse`` and ONE ``_touch_factors`` mutation: queued
        update vectors are stacked into a single (sum r_i, d) block so every
        cached factor absorbs the batch in one blocked rank-r update (when
        any queued delta lacks explicit vectors the combined delta falls
        back to the usual derive-or-evict path — still a single mutation).
        """
        if not self._pending:
            return 0
        with span("engine.flush", req=obs.request()):
            return self._flush()

    def _flush(self) -> int:
        pending, self._pending = self._pending, []
        combined = fuse_stats([p.stats for p in pending])
        vectors = None
        if all(p.update_vectors is not None for p in pending):
            vectors = jnp.concatenate([p.update_vectors for p in pending])
        self.backend.fuse(combined, 1.0)
        for p in pending:
            if p.client_id is not None:
                prev = self._clients.get(p.client_id)
                self._clients[p.client_id] = (p.stats if prev is None
                                              else prev + p.stats)
        self._touch_factors(combined, vectors, sign=1.0)
        self.flushes += 1
        self.coalesced_deltas += len(pending)
        return len(pending)

    def _autoflush(self) -> None:
        if not self._pending:
            return
        over_rank = self.pending_rank >= self.coalesce.max_rank
        stale = (time.monotonic() - self._pending[0].queued_at
                 >= self.coalesce.max_staleness_s)
        if over_rank or stale:
            self.flush()

    def ingest_distributed(self, A: jax.Array, b: jax.Array, **kwargs) -> None:
        """Phases 1+2 on-mesh: each shard's stats are psum'd straight into the
        backend-held (sharded) state — the fused Gram never lands replicated.

        Requires a backend with a ``fuse_distributed`` method (ShardedBackend).
        Mesh shards are not ledger clients: dropout on this path is the
        ``participation`` mask (Thm 8), not ``drop``/``restore``.
        """
        self.flush()
        fuse = getattr(self.backend, "fuse_distributed", None)
        if fuse is None:
            raise NotImplementedError(
                f"backend {self.backend.name!r} has no on-mesh fusion path")
        fuse(A, b, **kwargs)
        # Unknown-rank delta folded behind the engine's back: drop all caches.
        self._factors.clear()
        self.stats_version += 1

    def drop(self, client_id: Hashable) -> None:
        """Thm 8: remove a client; state becomes exact on the remaining subset."""
        self.flush()   # the client's queued deltas must be in the ledger first
        s = self._clients.pop(client_id)  # KeyError for unknown/already-dropped
        vectors = self._touch_factors(s, None, sign=-1.0)
        self.backend.fuse(s, -1.0)
        self._dropped[client_id] = (s, vectors)

    def restore(self, client_id: Hashable) -> None:
        """Thm 8 rejoin: add a dropped client back, exactly."""
        self.flush()
        s, vectors = self._dropped.pop(client_id)
        self.backend.fuse(s, 1.0)
        # Accumulate, never overwrite: deltas ingested under this id between
        # drop and restore (e.g. queued async rows the flush above just
        # registered) are already in the backend state — clobbering the
        # ledger entry would orphan them for any later drop.
        prev = self._clients.get(client_id)
        self._clients[client_id] = s if prev is None else prev + s
        self._touch_factors(s, vectors, sign=1.0)

    def export_ledger(self) -> tuple[dict[Hashable, SuffStats],
                                     dict[Hashable, SuffStats]]:
        """Snapshot of the retained ledger: ``(clients, dropped)`` stats.

        Drains the coalescer queue first, so the export is consistent with
        ``stats`` read at the same point. Dropped clients export their
        statistics only — the drop-time update vectors are a factor-cache
        optimization, and a restored process starts with cold factors anyway.
        """
        self.flush()
        return (dict(self._clients),
                {cid: s for cid, (s, _) in self._dropped.items()})

    def import_ledger(self, clients: Mapping[Hashable, SuffStats],
                      dropped: Mapping[Hashable, SuffStats]) -> None:
        """Install a retained ledger (crash-recovery restore path).

        The fused backend state is NOT touched: the caller restored it via
        ``from_stats`` and this re-attaches the per-client decomposition the
        snapshot captured alongside it. Only valid on an engine whose ledger
        is still empty — anything else would double-count contributions.
        """
        if self._clients or self._dropped or self._pending:
            raise ValueError("import_ledger requires an empty ledger "
                             f"({len(self._clients)} clients, "
                             f"{len(self._dropped)} dropped, "
                             f"{len(self._pending)} pending)")
        for cid, s in list(clients.items()) + list(dropped.items()):
            if s.dim != self.dim:
                raise ValueError(f"client {cid!r} stats dim {s.dim} != "
                                 f"engine dim {self.dim}")
        self._clients = dict(clients)
        self._dropped = {cid: (s, None) for cid, s in dropped.items()}

    def apply(self, fn: Callable[[SuffStats], SuffStats]) -> None:
        """Post-process fused stats (e.g. privacy.psd_repair); drops caches.

        Per-client retained stats are left untouched, so LOCO/dropout algebra
        after an ``apply`` mixes repaired and raw statistics — acceptable for
        PSD repair (a projection), but the caller owns that judgement.
        """
        self.flush()
        self.backend.set_stats(fn(self.backend.stats()))
        self._factors.clear()
        self.stats_version += 1

    def _touch_factors(self, delta: SuffStats, update_vectors, sign: float):
        """Up/down-date every cached factor by a PSD delta, or evict it."""
        with span("engine.touch_factors", req=obs.request(),
                  factors=len(self._factors)):
            return self._touch(delta, update_vectors, sign)

    def _touch(self, delta: SuffStats, update_vectors, sign: float):
        self.stats_version += 1
        if not self._factors:
            return update_vectors
        if not self.backend.supports_update:
            # Backend has no incremental path (e.g. sharded block factors):
            # evict everything; next solve per sigma refactorizes on-mesh.
            self._factors.clear()
            return update_vectors
        if update_vectors is None:
            # rank(G_k) <= min(rows, d); skip the eigh when it cannot pay
            # off: no cached factor has that much staleness budget left.
            bound = min(int(delta.count), self.dim)
            room = self.max_update_rank - min(
                f.stale_rank for f in self._factors.values())
            if bound <= room:
                update_vectors = psd_update_vectors(delta.gram)
        rank = None if update_vectors is None else int(update_vectors.shape[0])
        fresh: dict[float, _CachedFactor] = {}
        for sigma, f in self._factors.items():
            if rank is not None and f.stale_rank + rank <= self.max_update_rank:
                updated = self.backend.update(f.factor, update_vectors, sign)
                if updated is not None:
                    fresh[sigma] = _CachedFactor(updated, f.stale_rank + rank)
                    self.incremental_updates += 1
                # None: the backend declined THIS factor (e.g. a sharded CG
                # marker holds no L) — evict it like any other stale factor.
            # else: evict; next solve at this sigma refactorizes from scratch.
        self._factors = fresh
        return update_vectors

    def release_factors(self) -> int:
        """Drop every cached factor (and the backend's spectral cache).

        The fused ``(G, h)`` and the client ledger are untouched — the next
        solve at any sigma simply refactorizes cold. This is the eviction
        hook a multi-tenant pool uses to reclaim a cold tenant's O(S d^2)
        factor memory without evicting the tenant itself.
        """
        n = len(self._factors) + (1 if self.backend.spectral_ready else 0)
        self._factors.clear()
        release = getattr(self.backend, "release", None)
        if release is not None:
            release()
        return n

    @property
    def cached_factor_count(self) -> int:
        """Cached per-sigma factors currently held (LRU accounting)."""
        return len(self._factors)

    @property
    def retained_clients(self) -> int:
        """Ledger entries held for drop/restore/LOCO (active + dropped)."""
        return len(self._clients) + len(self._dropped)

    @staticmethod
    def _factor_bytes(factor: Any) -> int:
        if hasattr(factor, "nbytes"):           # dense: the L array itself
            return int(factor.nbytes)
        L = getattr(factor, "L", None)          # sharded: opaque wrapper
        return int(L.nbytes) if L is not None else 0

    @property
    def resident_bytes(self) -> int:
        """Device/host bytes this tenant pins right now.

        Three tiers, from irreducible to evictable: the backend-held fused
        statistics (``state_bytes`` — what admission control budgets
        against), the per-client ledger retained for Thm-8 drop/restore and
        LOCO, and the per-sigma factor cache (reclaimable via
        :meth:`release_factors`, so a pool's LRU eviction shrinks this
        number without touching correctness).
        """
        n = int(getattr(self.backend, "state_bytes", 0))
        for s in self._clients.values():
            n += s.gram.nbytes + s.moment.nbytes
        for s, vectors in self._dropped.values():
            n += s.gram.nbytes + s.moment.nbytes
            if vectors is not None:
                n += vectors.nbytes
        for f in self._factors.values():
            n += self._factor_bytes(f.factor)
        return n

    # -- solving (Thm 3 / Prop 5) -------------------------------------------

    def factor(self, sigma: float):
        """Cached (or freshly computed) factor of G + sigma I (backend-opaque)."""
        self.flush()
        key = float(sigma)
        f = self._factors.get(key)
        if f is None:
            f = _CachedFactor(self.backend.factor(key), 0)
            self._factors[key] = f
            self.cold_factorizations += 1
        return f.factor

    def solve(self, sigma: float) -> jax.Array:
        """Phase 3 (Thm 3): w = (G + sigma I)^{-1} h off the cached factor."""
        return self.backend.solve(self.factor(sigma))

    def solve_batch(self, sigmas: Sequence[float], *,
                    method: str = "auto") -> jax.Array:
        """All sigmas in one batched solve; returns (S, d) weights.

        ``method="chol"``: one batched Cholesky sweep; also warms the per-
        sigma factor cache (subsequent ``solve``/``predict`` at these sigmas
        are O(d^2)).

        ``method="spectral"``: one eigendecomposition of G — cached until
        the stats next change — after which ANY sigma grid costs only
        matmuls (Corollary-1 spectral-shift structure). The right choice for
        many-sigma / many-tenant serving; does not warm the Cholesky cache.
        Backends without a spectral path (sharded) fall back to ``chol``.

        ``"auto"`` picks spectral when its eigh is already cached or the
        grid is large enough (>= 16) to amortize it.
        """
        self.flush()
        keys = [float(s) for s in sigmas]
        if method == "auto":
            method = ("spectral" if self.backend.spectral_ready
                      or len(keys) >= 16 else "chol")
        if method == "spectral":
            was_ready = self.backend.spectral_ready
            ws = self.backend.spectral(keys)
            if ws is not None:
                if not was_ready:
                    self.cold_factorizations += 1
                return ws
            method = "chol"  # backend declined; fall through to the sweep
        if method != "chol":
            raise ValueError(f"unknown method {method!r}")
        factors, ws = self.backend.solve_batch(keys)
        if factors is not None:
            for k, fac in zip(keys, factors):
                # Overwrite: the fresh factor supersedes any stale
                # incrementally updated one (free accuracy/staleness reset).
                self._factors[k] = _CachedFactor(fac, 0)
        return ws

    def loco_weights(self, sigmas: Sequence[float]
                     ) -> tuple[list[Hashable], jax.Array]:
        """Prop 5 server step for ALL (k, sigma): one call, (K, S, d).

        Runs on the dense view of the fused stats: the per-client statistics
        it subtracts are retained densely regardless of backend, so LOCO is
        only meaningful at dimensions where K dense Grams fit anyway.
        """
        self.flush()
        if not self._clients:
            raise ValueError("no retained per-client statistics")
        ids = list(self._clients)
        fused = self.backend.stats()
        Gk = jnp.stack([self._clients[i].gram for i in ids])
        hk = jnp.stack([self._clients[i].moment for i in ids])
        W = _loco_solve(fused.gram, fused.moment, Gk, hk,
                        jnp.asarray([float(s) for s in sigmas],
                                    fused.gram.dtype))
        return ids, W

    def loco_cv(self, client_data: Mapping[Hashable, tuple[jax.Array, jax.Array]]
                | Sequence[tuple[jax.Array, jax.Array]],
                sigmas: Sequence[float]):
        """Prop 5 end-to-end: vectorized solves + per-client loss evaluation.

        ``client_data`` maps client id -> (A_k, b_k) (a sequence is treated
        as ids 0..K-1), emulating step 3 where each held-out client scores
        w_{-k}(sigma) locally and returns |Sigma| scalars.

        Returns ``(best_sigma, losses)`` like ``core.fusion.loco_cv``.
        """
        if not isinstance(client_data, Mapping):
            client_data = dict(enumerate(client_data))
        ids, W = self.loco_weights(sigmas)          # (K, S, d)
        losses = jnp.zeros((len(sigmas),), self.dtype)
        for k, cid in enumerate(ids):
            A_k, b_k = client_data[cid]
            resid = (jnp.matmul(A_k, W[k].T, precision=MATMUL_PRECISION)
                     - b_k[:, None])                  # (n_k, S)
            losses = losses + jnp.mean(resid**2, axis=0)
        best = int(jnp.argmin(losses))
        return sigmas[best], losses

    # -- serving ------------------------------------------------------------

    def predict(self, A: jax.Array, sigma: float) -> jax.Array:
        """Hot path: ridge predictions for query rows at one sigma."""
        return jnp.matmul(A, self.solve(sigma), precision=MATMUL_PRECISION)

    def inference(self, sigma: float, *, level: float = 0.95,
                  queries: jax.Array | None = None) -> dict | None:
        """Standard errors / intervals for the solve at ``sigma``.

        Computed off the SAME cached factor ``solve`` uses — a warm call
        performs no new factorization (``cold_factorizations`` untouched),
        only triangular solves (server.inference). Returns None when the
        fused statistics carry no residual second moment (legacy or
        DP-degraded uploads), when the backend declines to expose dense
        solve operands (sharded), or when the residual degrees of freedom
        are non-positive — point serving is never affected.
        """
        from repro.server.inference import inference_report

        self.flush()
        s = self.backend.stats()
        if s.yty is None:
            return None
        factor = self.factor(sigma)
        ops = self.backend.solve_operands(factor)
        if ops is None:
            return None
        L, _ = ops
        w = self.backend.solve(factor)
        return inference_report(L, s, w, sigma, level=level, queries=queries)

    def predict_batch(self, A: jax.Array, sigmas: Sequence[float]) -> jax.Array:
        """(S, n) predictions — n query rows against S regularizations."""
        return jnp.matmul(self.solve_batch(sigmas), A.T,
                          precision=MATMUL_PRECISION)
