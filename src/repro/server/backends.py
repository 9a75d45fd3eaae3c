"""Pluggable linear-algebra backends for the fusion server.

``FusionEngine`` (server.engine) is the *policy* layer — client ledger,
staleness-bounded factor reuse, sigma cache, LOCO — and delegates every
representation-dependent operation on the fused ``(G, h)`` to a
``LinalgBackend``:

  * ``DenseBackend`` (here): one replicated ``(d, d)`` Gram on one device,
    cached-Cholesky / eigh algebra. The right choice while ``G`` fits a
    single chip's HBM.
  * ``ShardedBackend`` (server.distributed): ``G`` lives 2-D block-sharded
    across a mesh and is fused, factored, and solved without ever being
    gathered to one device.

The protocol is intentionally small: ``fuse`` (fold a stats delta into the
backend-held state), ``factor``/``solve``/``solve_batch`` (Phase 3),
``update`` (incremental factor maintenance under PSD deltas — a backend may
decline by returning ``None``, in which case the engine evicts and lazily
refactorizes), ``spectral`` (the Corollary-1 eigh serving path, likewise
optional), and ``solve_operands`` (an immutable ``(L, h)`` snapshot for a
lock-free / cross-tenant-stacked solve — a backend whose solve is not a pure
function of two replicated arrays declines with ``None`` and keeps solving
under the tenant lock). Everything the engine caches is opaque to it: a
"factor" is whatever object the backend's ``factor`` returned.
"""
from __future__ import annotations

from typing import Any, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.sufficient_stats import (MATMUL_PRECISION, SuffStats,
                                         zeros_like_stats)
from repro.kernels.ops import pow2_bucket
from repro.server.cholesky import chol_update, chol_update_blocked


@runtime_checkable
class LinalgBackend(Protocol):
    """What the engine needs from a linear-algebra backend.

    ``stats()`` returns a *dense* view of the fused statistics and is a
    debug/interop surface (reference checks, LOCO over retained dense client
    stats) — never part of the solve path; distributed backends may gather
    to implement it.
    """

    name: str
    supports_update: bool

    @property
    def dim(self) -> int: ...

    @property
    def dtype(self) -> Any: ...

    @property
    def count(self) -> jax.Array: ...

    @property
    def spectral_ready(self) -> bool: ...

    def fuse(self, delta: SuffStats, sign: float = 1.0) -> None: ...

    def stats(self) -> SuffStats: ...

    def set_stats(self, stats: SuffStats) -> None: ...

    def factor(self, sigma: float) -> Any: ...

    def solve(self, factor: Any) -> jax.Array: ...

    def solve_batch(self, sigmas: Sequence[float]
                    ) -> tuple[list[Any] | None, jax.Array]: ...

    def update(self, factor: Any, update_vectors: jax.Array,
               sign: float) -> Any | None: ...

    def spectral(self, sigmas: Sequence[float]) -> jax.Array | None: ...

    def solve_operands(self, factor: Any
                       ) -> tuple[jax.Array, jax.Array] | None: ...


# -- dense kernels (jitted once per shape) ----------------------------------

@jax.jit
def _cold_factor(G, sigma):
    d = G.shape[0]
    return jnp.linalg.cholesky(G + sigma * jnp.eye(d, dtype=G.dtype))


@jax.jit
def _factor_solve(L, h):
    return jax.scipy.linalg.cho_solve((L, True), h)


def solve_snapshot(L: jax.Array, h: jax.Array) -> jax.Array:
    """Solve off a snapshotted ``(L, h)`` pair — outside any tenant lock.

    This is the SAME jitted program ``DenseBackend.solve`` runs, so a solve
    over operands snapshotted under a lock is bit-identical to the locked
    solve at the same state; jax arrays are immutable, so the snapshot is a
    reference grab, not a copy.
    """
    return _factor_solve(L, h)


@jax.jit
def _multi_sigma_factor_solve(G, h, sigmas):
    """Batched Phase 3: factors and solutions for every sigma in one call.

    One batched Cholesky over the stacked (S, d, d) shifted Grams, then a
    scan of cho_solves (jax's *batched* triangular solve is slow on CPU;
    a scan of rank-1-batch solves inside the same jit is not).
    """
    eye = jnp.eye(G.shape[0], dtype=G.dtype)
    Ls = jnp.linalg.cholesky(G[None] + sigmas[:, None, None] * eye[None])

    def step(_, L):
        return None, jax.scipy.linalg.cho_solve((L, True), h)

    _, ws = jax.lax.scan(step, None, Ls)
    return Ls, ws


@jax.jit
def _eigh_gram(G):
    return jnp.linalg.eigh(G)


@jax.jit
def _spectral_solve(lam, Q, h, sigmas):
    """w(sigma) for all sigmas from G's eigendecomposition.

    Corollary-1 structure: G + sigma I shares G's eigenbasis, so after ONE
    eigh every sigma costs only matmuls — O(d^2) per sigma, no factorization.
    """
    qh = jnp.matmul(Q.T, h, precision=MATMUL_PRECISION)
    return jnp.matmul(qh[None] / (lam[None] + sigmas[:, None]), Q.T,
                      precision=MATMUL_PRECISION)


class DenseBackend:
    """Single-device dense backend: the extracted FusionEngine linalg.

    The factor object is the lower-triangular Cholesky factor itself; PSD
    low-rank deltas are absorbed into cached factors via the blocked
    rank-r up/downdate (server.cholesky.chol_update_blocked; the scalar
    LINPACK recurrence below ``blocked_update_min_rank``), and the spectral
    path caches one eigh of G until the stats next change.
    """

    name = "dense"
    supports_update = True

    #: below this rank the scan-of-rank-1 reference wins (panel-transform
    #: overhead is O(bd^2 r) regardless of how small r is); above it the
    #: blocked path turns the O(r d^2) into trailing GEMMs.
    blocked_update_min_rank = 8

    def __init__(self, dim: int, *, dtype=jnp.float32,
                 update_block_size: int = 32, use_pallas: bool | None = None):
        self._stats = zeros_like_stats(dim, dtype)
        self._eigh: tuple[jax.Array, jax.Array] | None = None
        self.update_block_size = update_block_size
        #: ``update`` calls by path: the blocked update (one Householder
        #: reflector a panel column), the blocked downdate (hyperbolic
        #: Givens) and the scan below ``blocked_update_min_rank``.
        self.update_paths = {"householder": 0, "givens": 0, "scan": 0}
        self.use_pallas = (jax.default_backend() == "tpu"
                           if use_pallas is None else use_pallas)

    @property
    def dim(self) -> int:
        return self._stats.dim

    @property
    def dtype(self):
        return self._stats.gram.dtype

    @property
    def count(self) -> jax.Array:
        return self._stats.count

    @property
    def spectral_ready(self) -> bool:
        return self._eigh is not None

    def fuse(self, delta: SuffStats, sign: float = 1.0) -> None:
        self._stats = (self._stats + delta) if sign > 0 else (self._stats - delta)
        self._eigh = None

    def stats(self) -> SuffStats:
        return self._stats

    def set_stats(self, stats: SuffStats) -> None:
        if stats.dim != self.dim:
            raise ValueError(f"stats dim {stats.dim} != backend dim {self.dim}")
        self._stats = stats
        self._eigh = None

    def release(self) -> None:
        """Drop derived caches (the spectral eigh); (G, h) stay intact."""
        self._eigh = None

    def factor(self, sigma: float) -> jax.Array:
        return _cold_factor(self._stats.gram,
                            jnp.asarray(sigma, self._stats.gram.dtype))

    def solve(self, factor: jax.Array) -> jax.Array:
        return _factor_solve(factor, self._stats.moment)

    def solve_batch(self, sigmas: Sequence[float]
                    ) -> tuple[list[jax.Array], jax.Array]:
        keys = list(sigmas)
        # Bucket the grid length to a power of two (same idiom as the
        # update-rank bucketing below): tenants bring variable-length sigma
        # grids, and an S-specialized program per distinct length would
        # retrace without bound. The pad sigma repeats the last entry — a
        # valid shift whose factor/solution are computed and sliced away;
        # batched Cholesky factors each slice independently, so the kept
        # entries are bit-identical to the unpadded sweep.
        padded = keys + [keys[-1]] * (pow2_bucket(len(keys)) - len(keys))
        Ls, ws = _multi_sigma_factor_solve(
            self._stats.gram, self._stats.moment,
            jnp.asarray(padded, self.dtype))
        return [Ls[i] for i in range(len(keys))], ws[:len(keys)]

    def update(self, factor: jax.Array, update_vectors: jax.Array,
               sign: float) -> jax.Array:
        r = update_vectors.shape[0]
        if r >= self.blocked_update_min_rank:
            # Rank-bucket to the next power of two so variable coalescer
            # flush ranks reuse a bounded set of compiled programs; zero
            # rows are exact identities in the up/downdate recurrence.
            bucket = pow2_bucket(r)
            if bucket != r:
                update_vectors = jnp.pad(update_vectors,
                                         ((0, bucket - r), (0, 0)))
            self.update_paths["householder" if sign > 0 else "givens"] += 1
            return chol_update_blocked(
                factor, update_vectors, sign=sign,
                block_size=min(self.update_block_size, self.dim),
                use_pallas=self.use_pallas)
        self.update_paths["scan"] += 1
        return chol_update(factor, update_vectors, sign=sign)

    def spectral(self, sigmas: Sequence[float]) -> jax.Array:
        if self._eigh is None:
            self._eigh = _eigh_gram(self._stats.gram)
        lam, Q = self._eigh
        return _spectral_solve(lam, Q, self._stats.moment,
                               jnp.asarray(list(sigmas), self.dtype))

    def solve_operands(self, factor: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
        """The (L, h) pair :func:`solve_snapshot` solves — both immutable, so
        the caller can release its lock (or stack many tenants' pairs into
        one cross-tenant sweep) and still get bit-identical weights."""
        return factor, self._stats.moment

    @property
    def state_bytes(self) -> int:
        """Resident bytes of the fused statistics (the irreducible tenant
        footprint — factor caches are accounted separately and evictable)."""
        n = self._stats.gram.nbytes + self._stats.moment.nbytes
        if self._eigh is not None:
            n += self._eigh[0].nbytes + self._eigh[1].nbytes
        return n
