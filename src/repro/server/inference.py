"""Federated inference from one-shot second moments (the EconML direction).

The protocol's sufficient statistics (G = AᵀA, h = Aᵀb, n) extend with one
scalar — yty = Σ bᵢ² — to a *complete* statistic for classical ridge
inference: the residual sum of squares telescopes exactly like (G, h),

    RSS = ||b - A w||²  =  yty - 2 hᵀw + wᵀ G w,

so the server can serve standard errors, confidence intervals, and
prediction intervals without ever seeing a row. With the ridge hat matrix
H = A M Aᵀ, M = (G + σI)⁻¹, the effective degrees of freedom are

    dof = tr(G M) = d - σ tr(M),

the (approximately) unbiased noise estimate is σ̂² = RSS / (n - dof), and
the sandwich covariance of ŵ = M h is

    Cov(ŵ) = σ̂² · M G M.

Everything here is computed off the engine's CACHED Cholesky factor L of
(G + σI): M = L⁻ᵀL⁻¹ via one triangular solve against the identity — no new
factorization (the engine's cold-factorization counter is untouched, which
tests assert). ``reference_inference`` builds the centralized closed-form
reference through the SAME jitted programs (``backends._cold_factor`` /
``backends._factor_solve`` and the shared kernel below), so engine-served
intervals are bit-identical to a cold single-machine fit on the pooled data
— the paper's exactness claim extended from point estimates to inference.

Degraded mode: statistics from a moments-less (legacy) source carry
``yty=None`` and any fusion containing one degrades to None (core
``SuffStats``); callers then serve point weights exactly as before and the
inference fields are None. DP tenants degrade by design — an un-noised Σy²
next to privatized (G, h) would leak (core.privacy).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sufficient_stats import MATMUL_PRECISION, SuffStats


@jax.jit
def _inference_kernel(L, G, h, w, yty, n, sigma):
    """All inference scalars/arrays off the cached factor, one jitted program.

    M = (G + σI)⁻¹ comes from one triangular solve of L against I (L is
    already lower-triangular — O(d³/3) flops, no factorization); tr(G M)
    uses the shift identity tr(G M) = d - σ tr(M) so G M is never formed
    for the trace.
    """
    d = G.shape[0]
    eye = jnp.eye(d, dtype=G.dtype)
    Linv = jax.scipy.linalg.solve_triangular(L, eye, lower=True)
    mm = partial(jnp.matmul, precision=MATMUL_PRECISION)
    M = mm(Linv.T, Linv)
    dof = d - sigma * jnp.trace(M)
    rss = yty - 2.0 * mm(h, w) + mm(w, mm(G, w))
    denom = n - dof
    sigma2 = rss / denom
    cov = sigma2 * mm(M, mm(G, M))
    stderr = jnp.sqrt(jnp.clip(jnp.diag(cov), 0.0))
    return rss, dof, denom, sigma2, cov, stderr


@jax.jit
def _pi_kernel(X, w, cov, sigma2):
    """Prediction mean and std at query rows X (solve-space coordinates).

    Var(y* - ŷ*) = σ̂² + xᵀ Cov(ŵ) x: irreducible noise plus estimation
    variance propagated through the query point.
    """
    mean = jnp.matmul(X, w, precision=MATMUL_PRECISION)
    var = sigma2 + jnp.einsum("ni,ni->n",
                              jnp.matmul(X, cov, precision=MATMUL_PRECISION),
                              X, precision=MATMUL_PRECISION)
    return mean, jnp.sqrt(jnp.clip(var, 0.0))


def z_value(level: float) -> float:
    """Two-sided normal critical value for a ``level`` interval."""
    if not 0.0 < level < 1.0:
        raise ValueError(f"level must be in (0, 1), got {level}")
    return float(jax.scipy.special.ndtri((1.0 + level) / 2.0))


def inference_report(
    L: jax.Array,
    stats: SuffStats,
    w: jax.Array,
    sigma: float,
    *,
    level: float = 0.95,
    queries: jax.Array | None = None,
) -> dict | None:
    """Standard errors and intervals for ŵ, off an existing factor.

    Args:
      L: lower-triangular Cholesky factor of (G + sigma I) — the engine's
        cached factor; this function never factorizes.
      stats: the fused statistics. ``yty=None`` (a legacy / DP-degraded
        fusion) returns None — point weights are served, inference is not.
      w: the served solution M h (``backends._factor_solve(L, h)``).
      sigma: the ridge shift L was factored at.
      level: two-sided coverage of the confidence/prediction intervals.
      queries: optional (q, d) rows in SOLVE-space coordinates (featurized
        already for sketch/RFF tenants) for prediction intervals.

    Returns None when inference is undefined: missing moments, or a
    non-positive residual degrees of freedom n - dof (underdetermined fit).
    """
    if stats.yty is None:
        return None
    z = z_value(level)
    G = stats.gram
    n = jnp.asarray(stats.count, G.dtype)
    rss, dof, denom, sigma2, cov, stderr = _inference_kernel(
        L, G, stats.moment, w, jnp.asarray(stats.yty, G.dtype), n,
        jnp.asarray(sigma, G.dtype))
    if not float(denom) > 0.0:
        return None
    ci = jnp.stack([w - z * stderr, w + z * stderr], axis=1)
    report = {
        "level": float(level),
        "n": int(stats.count),
        "dof": float(dof),
        "rss": float(rss),
        "sigma2": float(sigma2),
        "stderr": np.asarray(stderr),
        "ci": np.asarray(ci),
        "pi": None,
    }
    if queries is not None:
        X = jnp.atleast_2d(jnp.asarray(queries, G.dtype))
        if X.shape[-1] != G.shape[0]:
            raise ValueError(f"queries have {X.shape[-1]} features, "
                             f"solve space is {G.shape[0]}-dimensional")
        mean, std = _pi_kernel(X, w, cov, sigma2)
        report["pi"] = np.asarray(
            jnp.stack([mean - z * std, mean + z * std], axis=1))
        report["pi_mean"] = np.asarray(mean)
    return report


def reference_inference(
    stats: SuffStats,
    sigma: float,
    *,
    level: float = 0.95,
    queries: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Cold centralized closed-form reference: (ŵ, report).

    Factors from scratch and solves through the SAME jitted programs the
    dense engine path runs (``backends._cold_factor`` /
    ``backends._factor_solve``), then the same inference kernel — so an
    engine that fused the same statistics serves bit-identical weights,
    standard errors, and intervals. Benchmarks and tests pin that equality.
    """
    from repro.server import backends

    G = stats.gram
    L = backends._cold_factor(G, jnp.asarray(sigma, G.dtype))
    w = backends._factor_solve(L, stats.moment)
    return w, inference_report(L, stats, w, sigma, level=level,
                               queries=queries)
