"""Incremental Cholesky machinery for the fusion server.

The server's regularized Gram ``G + sigma I`` changes only by PSD low-rank
deltas: streaming rows arrive (§VI-C, rank = #rows), a client drops out or
rejoins (Thm 8, rank = rank(G_k)). A cached factor L with L L^T = G + sigma I
can therefore be maintained by rank-r up/downdates at O(r d^2) each instead
of an O(d^3/3) refactorization.

Two implementations of the same algebra:

  * ``chol_rank1`` / ``chol_update`` — the classic LINPACK recurrence, one
    rank-1 sweep per update vector (``lax.scan``). O(r d) sequential steps,
    each touching a full d-column: simple, and the pinned numerical
    reference.
  * ``chol_update_blocked`` — the production mutation path. L is processed
    in (bd x bd) diagonal panels; within a panel the scalar recurrence runs
    against ALL r update vectors at once on panel-local data only, while
    accumulating the (bd+r) x (bd+r) right-transformation T the elementary
    steps would apply to every trailing row. The trailing panel then absorbs
    the whole panel's worth of rotations in ONE GEMM
    ``[L21 | X2^T] @ T^T`` — MXU-shaped, and routed through the Pallas
    ``gemm_nt`` tile on TPU. Same r*d elementary-step chain, but each step
    is O(bd + r) instead of O(d), and the O(r d^2) bulk rides matmuls.

Both orders perform *identical* elementary operations (the (k, j) scalars
depend only on steps (k, j' < j) and (k' < k, j), which both orders share),
so the blocked path is the reference up to float-associativity in the GEMM.

Numerical caveat: downdates lose accuracy as the downdated matrix approaches
singularity. Here the result is always >= sigma I (Prop 1), but the engine
still bounds the *accumulated* update rank per cached factor and falls back
to a fresh factorization past that staleness threshold.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sufficient_stats import MATMUL_PRECISION


@partial(jax.jit, static_argnames=("sign",))
def chol_rank1(L: jax.Array, x: jax.Array, *, sign: float = 1.0) -> jax.Array:
    """Factor of ``L L^T + sign * x x^T`` from the factor L (lower).

    ``sign=+1`` is an update, ``sign=-1`` a downdate; the downdate is valid
    only when the result stays positive definite (guaranteed here by the
    sigma I floor). O(d^2).
    """
    d = L.shape[0]
    idx = jnp.arange(d)

    def body(k, carry):
        L, x = carry
        Lkk = L[k, k]
        xk = x[k]
        r = jnp.sqrt(jnp.maximum(Lkk * Lkk + sign * xk * xk,
                                 jnp.finfo(L.dtype).tiny))
        c = r / Lkk
        s = xk / Lkk
        below = idx > k
        col = L[:, k]
        new_col = jnp.where(below, (col + sign * s * x) / c, col)
        new_col = new_col.at[k].set(r)
        x = jnp.where(below, c * x - s * new_col, x)
        return L.at[:, k].set(new_col), x

    L, _ = jax.lax.fori_loop(0, d, body, (L, x))
    return L


@partial(jax.jit, static_argnames=("sign",))
def chol_update(L: jax.Array, U: jax.Array, *, sign: float = 1.0) -> jax.Array:
    """Factor of ``L L^T + sign * U^T U`` for U of shape (r, d). O(r d^2)."""

    def step(L, u):
        return chol_rank1(L, u, sign=sign), None

    L, _ = jax.lax.scan(step, L, U)
    return L


def panel_transform(L11: jax.Array, X1: jax.Array, *, sign: float = 1.0
                    ) -> tuple[jax.Array, jax.Array]:
    """Factor one diagonal panel against all r update vectors at once.

    Args:
      L11: (bw, bw) lower-triangular diagonal panel of L.
      X1:  (r, bw) the panel's column slice of the update vectors.
      sign: +1 update / -1 downdate.

    Returns ``(L11', T)``: the updated panel factor and the accumulated
    (bw+r, bw+r) right-transformation, such that every trailing row obeys

        [L21 | X2^T] @ T  =  [L21' | X2'^T]

    T is exactly the product of the elementary 2x2 column maps the scalar
    recurrence applies — computing it costs O(bw r (bw + r)) panel-local
    work, after which the trailing update is one GEMM.
    """
    bw = L11.shape[0]
    r = X1.shape[0]
    s = sign
    idx = jnp.arange(bw)
    T = jnp.eye(bw + r, dtype=L11.dtype)

    def col_step(k, carry):
        def vec_step(j, carry2):
            L11, X1, T = carry2
            Lkk = L11[k, k]
            xk = X1[j, k]
            rho = jnp.sqrt(jnp.maximum(Lkk * Lkk + s * xk * xk,
                                       jnp.finfo(L11.dtype).tiny))
            c = rho / Lkk
            st = xk / Lkk
            below = idx > k
            col = L11[:, k]
            xrow = X1[j, :]
            new_col = jnp.where(below, (col + s * st * xrow) / c, col)
            new_col = new_col.at[k].set(rho)
            X1 = X1.at[j, :].set(jnp.where(below, (-st * col + xrow) / c,
                                           xrow))
            L11 = L11.at[:, k].set(new_col)
            tk = T[:, k]
            tj = T[:, bw + j]
            T = T.at[:, k].set((tk + s * st * tj) / c)
            T = T.at[:, bw + j].set((-st * tk + tj) / c)
            return L11, X1, T

        return jax.lax.fori_loop(0, r, vec_step, carry)

    L11, _, T = jax.lax.fori_loop(0, bw, col_step, (L11, X1, T))
    return L11, T


@partial(jax.jit,
         static_argnames=("sign", "block_size", "use_pallas"))
def chol_update_blocked(L: jax.Array, U: jax.Array, *, sign: float = 1.0,
                        block_size: int = 32,
                        use_pallas: bool = False) -> jax.Array:
    """Blocked factor of ``L L^T + sign * U^T U`` for U of shape (r, d).

    The trailing-panel GEMM carries the O(r d^2) bulk; ``use_pallas`` routes
    it through the ``kernels.ops.gemm_nt`` MXU tile (TPU; interpret-mode
    elsewhere). ``chol_update`` is the pinned scan-of-rank-1 reference.
    """
    d = L.shape[0]
    r = U.shape[0]
    if r == 0:
        return L
    X = U.astype(L.dtype)
    for c0 in range(0, d, block_size):
        bw = min(block_size, d - c0)
        L11, T = panel_transform(L[c0:c0 + bw, c0:c0 + bw],
                                 X[:, c0:c0 + bw], sign=sign)
        L = L.at[c0:c0 + bw, c0:c0 + bw].set(L11)
        c1 = c0 + bw
        if c1 < d:
            Z = jnp.concatenate([L[c1:, c0:c1], X[:, c1:].T], axis=1)
            if use_pallas:
                from repro.kernels import ops as kernel_ops

                Zn = kernel_ops.gemm_nt(jnp.zeros_like(Z), Z, T.T, alpha=1.0)
            else:
                Zn = jnp.matmul(Z, T, precision=MATMUL_PRECISION)
            L = L.at[c1:, c0:c1].set(Zn[:, :bw])
            X = X.at[:, c1:].set(Zn[:, bw:].T)
    return L


def psd_update_vectors(G: jax.Array) -> jax.Array:
    """Rows U (r, d) with ``U^T U ~= G`` for PSD G, r = numerical rank.

    One eigendecomposition turns an arbitrary PSD delta (e.g. a departing
    client's Gram, for which the server holds no row-level factor) into
    explicit update vectors. The O(d^3) cost is paid once per delta and
    amortized across every cached per-sigma factor it is applied to.

    The rank cutoff is ``eps * d * lambda_max`` for G's dtype: the
    eigensolver's round-off on a d x d matrix reaches that scale, so any
    fixed relative tolerance is either below the f32 noise floor (noise
    vectors join the downdate) or far above the f64 one.

    Host-side on purpose: r must be concrete so downstream scans have a
    static shape.
    """
    d = G.shape[0]
    evals, evecs = jnp.linalg.eigh(G)
    evals = jax.device_get(evals)
    eps = float(jnp.finfo(G.dtype).eps)
    cutoff = eps * d * max(float(evals[-1]), float(jnp.finfo(G.dtype).tiny))
    r = int((evals > cutoff).sum())
    if r == 0:
        return jnp.zeros((0, d), G.dtype)
    vecs = evecs[:, -r:]
    vals = jnp.clip(jnp.asarray(evals[-r:]), 0.0, None)
    return (vecs * jnp.sqrt(vals)).T
