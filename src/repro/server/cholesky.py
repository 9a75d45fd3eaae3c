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
    in (bd x bd) diagonal panels; within a panel the column recurrence runs
    against ALL r update vectors at once on panel-local data only, while
    accumulating the (bd+r) x (bd+r) orthogonal right-transformation T it
    would apply to every trailing row. The trailing panel then absorbs the
    whole panel in ONE GEMM ``[L21 | X2^T] @ T`` — MXU-shaped, and routed
    through the Pallas ``gemm_nt`` tile on TPU. Each step is O(bd + r)
    panel-local work instead of O(d), and the O(r d^2) bulk rides matmuls.

The blocked path picks its panel recurrence by ``sign``. An update uses one
Householder reflector per panel column: it zeroes all r update entries of
that column at once, so the serial chain is d steps long. A downdate keeps
the hyperbolic Givens chain, one rotation per (column, update vector): r*d
steps, the same elementary operations as the scan in the same order, so
there the blocked path is the reference up to float-associativity in the
GEMM. An update's L matches the scan's up to round-off (the Cholesky factor
with a positive diagonal is unique); the transformed update vectors differ
by an r x r orthogonal factor, which later panels cannot see, since only
``X^T X`` enters them.

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
      L11: (bw, bw) lower-triangular diagonal panel of L, positive diagonal.
      X1:  (r, bw) the panel's column slice of the update vectors.
      sign: +1 update / -1 downdate (static).

    Returns ``(L11', T)``: the updated panel factor (positive diagonal) and
    the accumulated (bw+r, bw+r) right-transformation, such that every row
    of the panel column obeys

        [L21 | X2^T] @ T  =  [L21' | X2'^T]

    and the panel's own rows obey ``[L11 | X1^T] @ T = [L11' | 0]``. For an
    update T is orthogonal: the product of bw Householder reflectors, one
    per column (:func:`_panel_householder`). A downdate's T is the product
    of the hyperbolic 2x2 column maps of the Givens chain
    (:func:`_panel_givens`). Either costs O(bw r (bw + r)) panel-local work,
    after which the trailing update is one GEMM.
    """
    if sign > 0:
        return _panel_householder(L11, X1)
    return _panel_givens(L11, X1, sign=sign)


def _panel_householder(L11: jax.Array, X1: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Update panel: one reflector per column zeroes all r entries of X1[:, k].

    Column k's reflector H = I - tau v v^T acts on the r+1 columns
    {k} + update columns and maps x = [L11[k,k]; X1[:,k]] to ||x|| e_1. Its
    first entry v_1 = x_1 - ||x|| = -||X1[:,k]||^2 / (L11[k,k] + ||x||) is
    free of cancellation since L11[k,k] > 0. A zero X1[:,k] (a pad column
    of the sharded layout) gives tau = 0, an exact identity; a zero row of
    X1 (the rank bucket's pad) is 0 in every v, so its T column stays a unit
    vector. Before step k, T's column k is still e_k, so T is kept as its bw
    finished columns plus the (bw+r, r) block of update columns.
    """
    bw = L11.shape[0]
    r = X1.shape[0]
    idx = jnp.arange(bw)
    eye = jnp.eye(bw + r, dtype=L11.dtype)

    def col_step(k, carry):
        L11, X1, Tpanel, TU = carry
        Lkk = L11[k, k]
        xk = X1[:, k]
        sq = jnp.sum(xk * xk)
        nonzero = sq > 0
        alpha = jnp.where(nonzero, jnp.sqrt(Lkk * Lkk + sq), Lkk)
        v1 = -sq / (Lkk + alpha)
        tau = jnp.where(nonzero, 2 / (v1 * v1 + sq), 0)
        below = idx > k
        col = L11[:, k]
        # Each panel row's entries [L11[i,k], X1[:,i]] dotted with v.
        p = tau * (v1 * col + jnp.sum(xk[:, None] * X1, axis=0))
        new_col = jnp.where(below, col - v1 * p,
                            jnp.where(idx == k, alpha, col))
        X1 = jnp.where(below[None, :], X1 - xk[:, None] * p[None, :], 0)
        # The same reflector on T's columns {k} + update columns.
        ek = (jnp.arange(bw + r) == k).astype(L11.dtype)
        w = v1 * ek + jnp.sum(TU * xk[None, :], axis=1)
        Tpanel = Tpanel.at[:, k].set(ek - (tau * v1) * w)
        TU = TU - tau * w[:, None] * xk[None, :]
        return L11.at[:, k].set(new_col), X1, Tpanel, TU

    L11, _, Tpanel, TU = jax.lax.fori_loop(
        0, bw, col_step, (L11, X1, eye[:, :bw], eye[:, bw:]))
    return L11, jnp.concatenate([Tpanel, TU], axis=1)


def _panel_givens(L11: jax.Array, X1: jax.Array, *, sign: float
                  ) -> tuple[jax.Array, jax.Array]:
    """Downdate panel: the scalar recurrence, one 2x2 map per (k, j).

    T is exactly the product of the elementary column maps the scan applies,
    in the same order: r*bw dependent steps per panel.
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

    One :func:`panel_transform` per ``block_size`` panel (Householder for an
    update, hyperbolic Givens for a downdate), then one trailing-panel GEMM
    that carries the O(r d^2) bulk; ``use_pallas`` routes it through the
    ``kernels.ops.gemm_nt`` MXU tile (TPU; interpret-mode elsewhere).
    ``chol_update`` is the pinned scan-of-rank-1 reference: an update's L
    matches it up to round-off, a downdate's up to GEMM associativity.
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
