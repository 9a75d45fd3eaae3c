"""ShardedBackend — fused (G, h) kept block-sharded on a mesh, end to end.

The dense backend caps ``d`` at what one chip's HBM holds: ``G`` is d x d and
every factor/solve is single-device. This backend removes that ceiling by
never materializing the fused Gram on one device:

  * **storage** — ``G`` is a 2-D block-sharded array whose layout comes from
    the logical-axis rules in ``launch.sharding`` (``FUSION_RULES``: rows
    over the client/data axes, columns over the model axis). ``d`` is padded
    up to the block/mesh lcm; the pad block of ``G + sigma I`` is ``sigma I``
    and the pad of ``h`` is zero, so padded solves are *exact* on the first
    ``d`` coordinates — ``d`` need not divide the tiling.
  * **fusion** — ``fuse_distributed`` runs the paper's Phases 1+2 as the
    existing on-mesh psum (core.sufficient_stats.distributed_stats), but the
    reduction is a reduce-scatter straight into the block layout: each shard
    computes its local client stats and only ever receives its own block of
    the fused Gram. Dense deltas (``fuse``) are padded and added under a jit
    whose output sharding pins the block layout.
  * **solve** — a shard_map right-looking block-Cholesky. Per block column:
    the panel (one d x bs column strip) is assembled with a psum + all-gather
    — the only communication, never full ``G`` — the bs x bs panel factor is
    computed redundantly on every device, and the TRSM + SYRK trailing
    update run on local tiles, optionally through the Pallas GEMM tile in
    ``kernels.gram`` (``use_pallas``; the TRSM is re-expressed as a GEMM
    against the inverted diagonal tile so both inner ops ride the same MXU
    kernel — the bs^3 panel factor itself stays on the XLA path). Triangular
    solves run block-sequentially with one bs-float psum per step.
  * **CG fallback** — for meshes whose tiling fits ``d`` badly (padding
    would more than double it), ``method="auto"`` switches to matrix-free
    Jacobi-preconditioned conjugate gradients: per iteration one G-block
    matvec, a psum over the column axes and an all-gather over the row axes
    — ``G`` stays sharded there too.

The engine treats factors as opaque: a :class:`ShardedFactor` wraps either
the block-sharded lower factor (reused across solves at the same sigma) or a
CG marker (re-solved per call). ``block_chol`` factors support *incremental*
rank-r mutation (``update``): the same blocked up/downdate the dense backend
runs (server.cholesky.panel_transform) executed over the existing block
layout — per block column, the bs x bs diagonal tile is psum-replicated,
every device computes the panel transform T redundantly (O((bs+r)^2 bs r)
scalar work, tiny), and the trailing application ``[L21 | X2^T] @ T`` is a
LOCAL GEMM on each shard's rows of that block column (Pallas ``gemm_nt``
tile under ``use_pallas``), with one (dp, r) all-gather re-replicating the
transformed update vectors. Mutations therefore cost O(dp (bs + r) r) comm
and O(dp^2 (bs+r)^2 / (bs * shards)) local flops instead of the O(d^3/3)
on-mesh refactorization they used to trigger. CG factors decline (return
``None``): they hold no L to update, and the engine evicts as before.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sufficient_stats import (MATMUL_PRECISION, SuffStats,
                                         compute_stats)
from repro.kernels import ops as kernel_ops
from repro.launch.sharding import FUSION_RULES, GRAM_AXES, ShardingRules
from repro.server.cholesky import panel_transform


@dataclasses.dataclass
class ShardedFactor:
    """Backend-opaque factor handle: sharded Cholesky factor or CG marker."""

    kind: str                    # "block_chol" | "cg"
    sigma: float
    L: jax.Array | None = None   # (dp, dp) block-sharded lower factor


def _flat_index(axes: tuple[str, ...]) -> jax.Array:
    """Row-major flat position of this shard along ``axes`` (0 if none)."""
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return idx


def _spec_entry(axes: tuple[str, ...]):
    """PartitionSpec entry for an axis tuple (unwrap singletons, () -> None)."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _psum(x, axes: tuple[str, ...]):
    return jax.lax.psum(x, axes) if axes else x


def _gather(x, axes: tuple[str, ...]):
    return jax.lax.all_gather(x, axes, tiled=True) if axes else x


class ShardedBackend:
    """Mesh-sharded linalg backend for :class:`~repro.server.FusionEngine`."""

    name = "sharded"
    supports_update = True

    def __init__(self, dim: int, mesh: Mesh, *, dtype=jnp.float32,
                 block_size: int | None = None, method: str = "auto",
                 rules: ShardingRules = FUSION_RULES,
                 use_pallas: bool | None = None,
                 cg_iters: int | None = None, cg_tol: float = 1e-6):
        if method not in ("auto", "block_chol", "cg"):
            raise ValueError(f"unknown method {method!r}")
        self.mesh = mesh
        self.method = method
        self._dim = dim
        self._dtype = jnp.dtype(dtype)
        self.use_pallas = (jax.default_backend() == "tpu"
                           if use_pallas is None else use_pallas)
        self.cg_tol = cg_tol

        # Resolve the block layout from the logical-axis rules. Resolving
        # against a shape divisible by every mesh axis product yields the
        # axes the rules would assign; padding then guarantees the real
        # (dp, dp) shape divides them too.
        m_all = math.prod(mesh.shape.values()) or 1
        spec = rules.resolve(GRAM_AXES, (m_all, m_all), mesh)
        self._row_axes = self._norm(spec[0] if len(spec) > 0 else None)
        self._col_axes = self._norm(spec[1] if len(spec) > 1 else None)
        self._nrows = math.prod(mesh.shape[a] for a in self._row_axes) \
            if self._row_axes else 1
        self._ncols = math.prod(mesh.shape[a] for a in self._col_axes) \
            if self._col_axes else 1
        self.spec = P(_spec_entry(self._row_axes), _spec_entry(self._col_axes))

        if block_size is None:
            # nb <= 16 bounds the unrolled factor loop's trace/compile time;
            # bs >= 8 keeps tiles VPU-sublane sized.
            block_size = 8
            while dim / block_size > 16:
                block_size *= 2
        self.block_size = block_size
        lcm_pq = math.lcm(self._nrows, self._ncols)
        unit = block_size * lcm_pq
        self.padded = -(-dim // unit) * unit
        self._nb = self.padded // block_size
        self._rl = self.padded // self._nrows   # local rows per shard
        self._cl = self.padded // self._ncols   # local cols per shard

        self._gram_sharding = NamedSharding(mesh, self.spec)
        self._rep = NamedSharding(mesh, P())
        self._G = jax.device_put(
            jnp.zeros((self.padded, self.padded), self._dtype),
            self._gram_sharding)
        self._h = jax.device_put(jnp.zeros((self.padded,), self._dtype),
                                 self._rep)
        self._count = jnp.zeros((), jnp.int32)
        self._diag = None          # cached diag(G) for the CG preconditioner
        self.cg_iters = cg_iters if cg_iters is not None \
            else min(4 * self.padded, 2000)
        self._jitted: dict[str, object] = {}

    @staticmethod
    def _norm(entry) -> tuple[str, ...]:
        if entry is None:
            return ()
        return (entry,) if isinstance(entry, str) else tuple(entry)

    # -- protocol surface ----------------------------------------------------

    @property
    def dim(self) -> int:
        return self._dim

    @property
    def dtype(self):
        return self._dtype

    @property
    def count(self) -> jax.Array:
        return self._count

    @property
    def spectral_ready(self) -> bool:
        return False

    @property
    def gram(self) -> jax.Array:
        """The live block-sharded (padded) Gram — for sharding assertions."""
        return self._G

    @property
    def fusion_axis_sizes(self) -> dict[str, int]:
        """Mesh axes (and sizes) the fusion reduction crosses — for the
        cross-shard ledger in ``fed.comm.sharded_oneshot_record``. Only the
        row/client axes appear: the reduce-scatter runs over them, while the
        column (model) axis just slices its block locally."""
        return {str(a): int(self.mesh.shape[a]) for a in self._row_axes}

    def fuse(self, delta: SuffStats, sign: float = 1.0) -> None:
        if delta.dim != self._dim:
            raise ValueError(f"stats dim {delta.dim} != backend dim {self._dim}")
        fn = self._jitted.get("fuse")
        if fn is None:
            pad = self.padded - self._dim

            def _fuse(G, h, count, dg, dh, dc, s):
                dg = jnp.pad(dg.astype(G.dtype), ((0, pad), (0, pad)))
                dh = jnp.pad(dh.astype(h.dtype), (0, pad))
                return G + s * dg, h + s * dh, count + dc

            fn = jax.jit(_fuse, out_shardings=(self._gram_sharding,
                                               self._rep, self._rep))
            self._jitted["fuse"] = fn
        s = jnp.asarray(sign, self._dtype)
        dc = jnp.asarray(delta.count, jnp.int32) * (1 if sign > 0 else -1)
        self._G, self._h, self._count = fn(self._G, self._h, self._count,
                                           delta.gram, delta.moment, dc, s)
        self._diag = None

    def stats(self) -> SuffStats:
        """Dense (gathered) view — debug/interop only, never the solve path."""
        d = self._dim
        return SuffStats(jnp.asarray(self._G[:d, :d]),
                         jnp.asarray(self._h[:d]), self._count)

    def set_stats(self, stats: SuffStats) -> None:
        if stats.dim != self._dim:
            raise ValueError(f"stats dim {stats.dim} != backend dim {self._dim}")
        pad = self.padded - self._dim
        self._G = jax.device_put(
            jnp.pad(stats.gram.astype(self._dtype), ((0, pad), (0, pad))),
            self._gram_sharding)
        self._h = jax.device_put(
            jnp.pad(stats.moment.astype(self._dtype), (0, pad)), self._rep)
        self._count = jnp.asarray(stats.count, jnp.int32)
        self._diag = None

    def release(self) -> None:
        """Drop derived caches (the CG diag preconditioner); (G, h) and the
        compiled shard_map programs stay — eviction reclaims factor memory,
        not compilation work."""
        self._diag = None

    def update(self, factor: ShardedFactor, update_vectors: jax.Array,
               sign: float) -> ShardedFactor | None:
        """Blocked rank-r up/downdate of a block-sharded factor, on-mesh.

        Returns a fresh :class:`ShardedFactor` whose L absorbed
        ``sign * U^T U`` without leaving the block layout; ``None`` for CG
        factors (nothing to update — the engine evicts and re-solves).
        """
        r = int(update_vectors.shape[0])
        if factor.kind != "block_chol":
            return None
        if r == 0:
            return factor
        # Bucket the rank to the next power of two: coalescer flush ranks are
        # timing-dependent, and a shard_map retrace per distinct r would grow
        # the jit cache without bound on the hot mutation path. Zero rows are
        # exact identities in both panel recurrences (a zero row is 0 in
        # every reflector vector and gives every rotation a zero sine), so
        # rank padding costs some flops but no accuracy.
        bucket = kernel_ops.pow2_bucket(r)
        key = ("update", bucket, sign > 0)
        fn = self._jitted.get(key)
        if fn is None:
            fn = jax.jit(jax.shard_map(
                partial(self._local_update, sign=1.0 if sign > 0 else -1.0),
                mesh=self.mesh, in_specs=(self.spec, P()),
                out_specs=self.spec, check_vma=False))
            self._jitted[key] = fn
        U = jnp.pad(update_vectors.astype(self._dtype),
                    ((0, bucket - r), (0, self.padded - self._dim)))
        return ShardedFactor("block_chol", factor.sigma, fn(factor.L, U))

    def spectral(self, sigmas):
        return None   # no on-mesh eigh: engine falls back to the Cholesky sweep

    # -- on-mesh fusion (Phases 1+2, reduce-scattered into the block layout) --

    def fuse_distributed(self, A: jax.Array, b: jax.Array, *,
                         participation: jax.Array | None = None,
                         noise_fn=None) -> None:
        """Fold on-mesh rows in: shard-local stats, one reduction, no gather.

        Mirrors ``core.sufficient_stats.distributed_stats`` — each shard
        along the row (client) axes computes its local ``(G_k, h_k)`` and
        the single reduction is the paper's one communication round — except
        the Gram reduction is a psum-scatter into this backend's block
        layout: no device ever holds the fused ``G``, only its own block.
        ``participation``/``noise_fn`` are the Thm 8 / Alg 2 hooks.
        """
        if A.shape[-1] != self._dim:
            raise ValueError(f"A has dim {A.shape[-1]}, backend {self._dim}")
        row_axes, col_axes = self._row_axes, self._col_axes
        n_clients = self._nrows
        rl, cl, dp, d = self._rl, self._cl, self.padded, self._dim

        if participation is None:
            participation = jnp.ones((n_clients,), jnp.float32)

        def local(a_k, b_k, part):
            s = compute_stats(a_k, b_k)
            idx = _flat_index(row_axes)
            if noise_fn is not None:
                g_t, h_t = noise_fn(idx, s.gram, s.moment)
                s = SuffStats(g_t, h_t, s.count)
            s = s.scale(part[idx])
            gp = jnp.pad(s.gram.astype(self._dtype),
                         ((0, dp - d), (0, dp - d)))
            if row_axes:
                rows = jax.lax.psum_scatter(gp, row_axes,
                                            scatter_dimension=0, tiled=True)
            else:
                rows = gp                                   # (rl, dp)
            ci = _flat_index(col_axes)
            blk = jax.lax.dynamic_slice(rows, (0, ci * cl), (rl, cl))
            h_t = _psum(jnp.pad(s.moment.astype(self._dtype), (0, dp - d)),
                        row_axes)
            # s.count was participation-scaled (float) by scale() above.
            c_t = _psum(s.count.astype(jnp.float32), row_axes)
            return blk, h_t, c_t

        fn = self._jitted.get("fuse_dist")
        if fn is None:
            fn = jax.jit(jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(_spec_entry(row_axes)), P(_spec_entry(row_axes)),
                          P()),
                out_specs=(self.spec, P(), P()),
                check_vma=False))
            self._jitted["fuse_dist"] = fn
        dG, dh, dc = fn(A, b, participation)
        add = self._jitted.get("fuse_add")
        if add is None:
            add = jax.jit(
                lambda G, D, h, dh, c, dc: (G + D, h + dh,
                                            c + jnp.round(dc).astype(c.dtype)),
                out_shardings=(self._gram_sharding, self._rep, self._rep))
            self._jitted["fuse_add"] = add
        self._G, self._h, self._count = add(self._G, dG, self._h, dh,
                                            self._count, dc)
        self._diag = None

    # -- factorization + solves ----------------------------------------------

    def _resolve_method(self) -> str:
        if self.method != "auto":
            return self.method
        # Padding past 2x means the mesh tiling fits d badly; matrix-free CG
        # sidesteps the (padded) block factorization entirely.
        return "cg" if self.padded >= 2 * self._dim else "block_chol"

    def factor(self, sigma: float) -> ShardedFactor:
        if not sigma > 0:
            raise ValueError("sharded solves require sigma > 0 "
                             "(the pad block of G + sigma I is sigma I)")
        kind = self._resolve_method()
        if kind == "cg":
            return ShardedFactor("cg", float(sigma))
        fn = self._jitted.get("factor")
        if fn is None:
            fn = jax.jit(jax.shard_map(
                self._local_chol, mesh=self.mesh,
                in_specs=(self.spec, P()), out_specs=self.spec,
                check_vma=False))
            self._jitted["factor"] = fn
        L = fn(self._G, jnp.asarray(sigma, self._dtype))
        return ShardedFactor("block_chol", float(sigma), L)

    def solve(self, factor: ShardedFactor) -> jax.Array:
        if factor.kind == "cg":
            return self._cg_solve(factor.sigma)
        fn = self._jitted.get("solve")
        if fn is None:
            fn = jax.jit(jax.shard_map(
                self._local_tri_solve, mesh=self.mesh,
                in_specs=(self.spec, P()), out_specs=P(),
                check_vma=False))
            self._jitted["solve"] = fn
        return fn(factor.L, self._h)[: self._dim]

    def solve_batch(self, sigmas: Sequence[float]
                    ) -> tuple[list[ShardedFactor], jax.Array]:
        factors = [self.factor(s) for s in sigmas]
        ws = jnp.stack([self.solve(f) for f in factors])
        return factors, ws

    def solve_operands(self, factor: ShardedFactor) -> None:
        """Decline the snapshot path: a sharded solve is a shard_map over the
        block-sharded L (or a CG re-solve against the live G), not a pure
        function of two replicated arrays — the pool keeps solving sharded
        tenants under their lock and excludes them from cross-tenant stacks."""
        return None

    @property
    def state_bytes(self) -> int:
        """Resident bytes of the fused (padded, block-sharded) statistics."""
        return self._G.nbytes + self._h.nbytes

    # -- shard-local kernels ---------------------------------------------------

    def _local_chol(self, Gl, sigma):
        """Right-looking block Cholesky; Gl is this shard's (rl, cl) block."""
        bs, nb, rl, cl, dp = (self.block_size, self._nb, self._rl, self._cl,
                              self.padded)
        row_axes, col_axes = self._row_axes, self._col_axes
        ri = _flat_index(row_axes)
        ci = _flat_index(col_axes)
        ro, co = ri * rl, ci * cl

        rows = ro + jnp.arange(rl)
        cols = co + jnp.arange(cl)
        Gl = Gl + sigma * (rows[:, None] == cols[None, :]).astype(Gl.dtype)
        Ll = jnp.zeros_like(Gl)

        for k in range(nb):
            c0 = k * bs
            qk = c0 // cl                      # owning device column (static)
            lc0 = c0 - qk * cl                 # static local column offset
            # Panel assembly: the d x bs column strip is the ONLY data that
            # ever leaves a shard — full G never does.
            contrib = jnp.where(ci == qk, Gl[:, lc0:lc0 + bs], 0.0)
            my_rows = _psum(contrib, col_axes)            # (rl, bs)
            C = _gather(my_rows, row_axes)                # (dp, bs)

            D = C[c0:c0 + bs]
            Lkk = jnp.linalg.cholesky(D)                  # redundant, bs^3
            below = C[c0 + bs:]
            if below.shape[0]:
                Lpan = self._trsm(Lkk, below)
                Lcol = jnp.concatenate([
                    jnp.zeros((c0, bs), Gl.dtype), Lkk, Lpan])
            else:
                Lcol = jnp.concatenate([jnp.zeros((c0, bs), Gl.dtype), Lkk])

            mine = jax.lax.dynamic_slice(Lcol, (ro, 0), (rl, bs))
            cur = Ll[:, lc0:lc0 + bs]
            Ll = Ll.at[:, lc0:lc0 + bs].set(jnp.where(ci == qk, mine, cur))

            # Trailing update on local tiles only: G_ij -= L_ik L_jk^T.
            # Lcol is zero above row c0, so already-factored columns are
            # untouched implicitly; the freshly factored panel columns of Gl
            # do get clobbered but are never read again.
            lc = jax.lax.dynamic_slice(Lcol, (co, 0), (cl, bs))
            Gl = self._syrk(Gl, mine, lc)
        return Ll

    def _local_update(self, Ll, X, *, sign):
        """Blocked rank-r up/downdate over the block layout.

        Ll is this shard's (rl, cl) block of the factor; X the replicated
        (r, dp) update vectors (zero on pad columns, so pad stays exactly
        sqrt(sigma) I). Per block column: the bs x bs diagonal tile is
        psum-replicated, :func:`~repro.server.cholesky.panel_transform`
        runs redundantly everywhere (panel-local scalar work), and each
        shard applies the trailing transformation to ITS rows of the block
        column in one local GEMM — the only collectives are the bs-wide
        strip psum, the bs^2 tile psum, and the (dp, r) gather that
        re-replicates the transformed update vectors.
        """
        bs, nb, rl, cl = self.block_size, self._nb, self._rl, self._cl
        row_axes, col_axes = self._row_axes, self._col_axes
        r = X.shape[0]
        ri = _flat_index(row_axes)
        ci = _flat_index(col_axes)
        ro = ri * rl
        g = ro + jnp.arange(rl)                    # global row ids of my rows

        for k in range(nb):
            c0 = k * bs
            qk, lc0 = c0 // cl, c0 % cl
            pk, lr0 = c0 // rl, c0 % rl
            # My rows of the block column, replicated across device columns.
            contrib = jnp.where(ci == qk, Ll[:, lc0:lc0 + bs], 0.0)
            strip = _psum(contrib, col_axes)                  # (rl, bs)
            # Diagonal tile, replicated everywhere (one bs^2 psum).
            tile = _psum(jnp.where(ri == pk, strip[lr0:lr0 + bs], 0.0),
                         row_axes)
            Lkk_new, T = panel_transform(tile, X[:, c0:c0 + bs], sign=sign)

            # Trailing application on MY rows only (local GEMM).
            Xloc = jax.lax.dynamic_slice(X, (0, ro), (r, rl)).T   # (rl, r)
            Z = jnp.concatenate([strip, Xloc], axis=1)            # (rl, bs+r)
            if self.use_pallas:
                Zn = kernel_ops.gemm_nt(jnp.zeros_like(Z), Z, T.T, alpha=1.0)
            else:
                Zn = jnp.matmul(Z, T, precision=MATMUL_PRECISION)
            below = (g >= c0 + bs)[:, None]
            new_strip = jnp.where(below, Zn[:, :bs], strip)
            new_strip = new_strip.at[lr0:lr0 + bs].set(
                jnp.where(ri == pk, Lkk_new, new_strip[lr0:lr0 + bs]))
            Ll = Ll.at[:, lc0:lc0 + bs].set(
                jnp.where(ci == qk, new_strip, Ll[:, lc0:lc0 + bs]))

            # Re-replicate the transformed update vectors (consumed rows of
            # X are frozen; only rows below the panel changed).
            Xloc_new = jnp.where(below, Zn[:, bs:], Xloc)
            X = _gather(Xloc_new, row_axes).T
        return Ll

    def _trsm(self, Lkk, below):
        """Panel solve: X with X @ Lkk^T = below."""
        if self.use_pallas:
            # Re-express as a GEMM against the inverted bs x bs tile so the
            # panel rides the same Pallas MXU tile as the trailing update.
            # Lkk's diagonal is >= sqrt(sigma) (Prop 1), so the explicit
            # small-triangular inverse is well conditioned.
            eye = jnp.eye(Lkk.shape[0], dtype=Lkk.dtype)
            Linv = jax.scipy.linalg.solve_triangular(Lkk, eye, lower=True)
            return kernel_ops.gemm_nt(jnp.zeros_like(below), below, Linv,
                                      alpha=1.0)
        return jax.lax.linalg.triangular_solve(
            Lkk, below, left_side=False, lower=True, transpose_a=True)

    def _syrk(self, Gl, a, bmat):
        """Trailing update Gl - a @ bmat^T on this shard's tile."""
        if self.use_pallas:
            return kernel_ops.gemm_nt(Gl, a, bmat, alpha=-1.0)
        return Gl - jnp.matmul(a, bmat.T, precision=MATMUL_PRECISION)

    def _diag_tiles(self, Ll):
        """All nb diagonal bs x bs tiles, replicated (one psum up front)."""
        bs, rl, cl = self.block_size, self._rl, self._cl
        ri = _flat_index(self._row_axes)
        ci = _flat_index(self._col_axes)
        tiles = []
        for k in range(self._nb):
            c0 = k * bs
            pk, qk = c0 // rl, c0 // cl
            tile = Ll[c0 - pk * rl:c0 - pk * rl + bs,
                      c0 - qk * cl:c0 - qk * cl + bs]
            own = jnp.logical_and(ri == pk, ci == qk)
            tiles.append(jnp.where(own, tile, 0.0))
        return _psum(jnp.stack(tiles), self._row_axes + self._col_axes)

    def _local_tri_solve(self, Ll, h):
        """w = (L L^T)^{-1} h by block forward/back substitution.

        Sequential over the nb block rows; each step is one local (bs, cl)
        matvec and one bs-float psum — O(dp^2 / shards) local work total.
        """
        bs, nb, rl, cl = self.block_size, self._nb, self._rl, self._cl
        row_axes, col_axes = self._row_axes, self._col_axes
        all_axes = row_axes + col_axes
        ri = _flat_index(row_axes)
        ci = _flat_index(col_axes)
        ro, co = ri * rl, ci * cl

        diag = self._diag_tiles(Ll)

        # Forward: L y = h. Entries of y past block k are still zero and L is
        # lower triangular, so the unmasked row-block matvec sums exactly
        # sum_{j<k} L[k-block, j] y_j.
        y = jnp.zeros_like(h)
        for k in range(nb):
            c0 = k * bs
            pk = c0 // rl
            lr0 = c0 - pk * rl
            yc = jax.lax.dynamic_slice(y, (co,), (cl,))
            part = jnp.matmul(Ll[lr0:lr0 + bs, :], yc,
                              precision=MATMUL_PRECISION)
            s = _psum(jnp.where(ri == pk, part, 0.0), all_axes)
            yk = jax.scipy.linalg.solve_triangular(
                diag[k], h[c0:c0 + bs] - s, lower=True)
            y = y.at[c0:c0 + bs].set(yk)

        # Backward: L^T w = y, over block rows in reverse; x entries at and
        # before block k are still zero, so the unmasked column-block matvec
        # sums exactly sum_{j>k} L[j, k-block]^T x_j.
        x = jnp.zeros_like(h)
        for k in reversed(range(nb)):
            c0 = k * bs
            qk = c0 // cl
            lc0 = c0 - qk * cl
            xr = jax.lax.dynamic_slice(x, (ro,), (rl,))
            part = jnp.matmul(Ll[:, lc0:lc0 + bs].T, xr,
                              precision=MATMUL_PRECISION)
            s = _psum(jnp.where(ci == qk, part, 0.0), all_axes)
            xk = jax.scipy.linalg.solve_triangular(
                diag[k].T, y[c0:c0 + bs] - s, lower=False)
            x = x.at[c0:c0 + bs].set(xk)
        return x

    # -- CG fallback -----------------------------------------------------------

    def _matvec_fn(self):
        fn = self._jitted.get("matvec")
        if fn is None:
            rl, cl = self._rl, self._cl
            row_axes, col_axes = self._row_axes, self._col_axes

            def local_mv(Gl, x, sigma):
                co = _flat_index(col_axes) * cl
                xc = jax.lax.dynamic_slice(x, (co,), (cl,))
                rows = _psum(Gl @ xc, col_axes)           # (rl,) my rows
                full = _gather(rows, row_axes)            # (dp,)
                return full + sigma * x

            fn = jax.shard_map(local_mv, mesh=self.mesh,
                           in_specs=(self.spec, P(), P()), out_specs=P(),
                           check_vma=False)
            self._jitted["matvec"] = fn
        return fn

    def _gram_diag(self) -> jax.Array:
        if self._diag is None:
            fn = self._jitted.get("diag")
            if fn is None:
                rl, cl = self._rl, self._cl
                row_axes, col_axes = self._row_axes, self._col_axes

                def local_diag(Gl):
                    ro = _flat_index(row_axes) * rl
                    co = _flat_index(col_axes) * cl
                    eq = (ro + jnp.arange(rl))[:, None] == \
                         (co + jnp.arange(cl))[None, :]
                    mine = _psum(jnp.sum(jnp.where(eq, Gl, 0.0), axis=1),
                                 col_axes)
                    return _gather(mine, row_axes)

                fn = jax.jit(jax.shard_map(local_diag, mesh=self.mesh,
                                       in_specs=(self.spec,), out_specs=P(),
                                       check_vma=False))
                self._jitted["diag"] = fn
            self._diag = fn(self._G)
        return self._diag

    def _cg_solve(self, sigma: float) -> jax.Array:
        """Jacobi-preconditioned CG on (G + sigma I) w = h, G kept sharded."""
        matvec = self._matvec_fn()
        diag = self._gram_diag()
        fn = self._jitted.get("cg")
        if fn is None:
            iters, tol = self.cg_iters, self.cg_tol

            @jax.jit
            def cg(G, h, sigma, diag):
                M = diag + sigma                     # Jacobi preconditioner

                def mv(x):
                    return matvec(G, x, sigma)

                r0 = h - mv(jnp.zeros_like(h))
                z0 = r0 / M
                thresh = (tol ** 2) * jnp.vdot(h, h).real + \
                    jnp.finfo(h.dtype).tiny

                def cond(state):
                    _, r, _, _, it = state
                    return jnp.logical_and(it < iters,
                                           jnp.vdot(r, r).real > thresh)

                def body(state):
                    w, r, p, rz, it = state
                    Ap = mv(p)
                    alpha = rz / jnp.vdot(p, Ap).real
                    w = w + alpha * p
                    r = r - alpha * Ap
                    z = r / M
                    rz_new = jnp.vdot(r, z).real
                    p = z + (rz_new / rz) * p
                    return w, r, p, rz_new, it + 1

                state = (jnp.zeros_like(h), r0, z0,
                         jnp.vdot(r0, z0).real, jnp.asarray(0, jnp.int32))
                w, *_ = jax.lax.while_loop(cond, body, state)
                return w

            fn = cg
            self._jitted["cg"] = fn
        w = fn(self._G, self._h, jnp.asarray(sigma, self._dtype), diag)
        return w[: self._dim]
