"""Random-projection protocol for high-dimensional features (paper §IV-F).

For d > ~1000 the d^2 Gram upload dominates; a shared Gaussian sketch
R in R^{d x m}, R_ij ~ N(0, 1/m), lets each client transmit the m x m
statistics of A_k R instead (Prop 2: JL distance preservation with
m = O(eps^-2 log n); Prop 3: ||w~ - w|| <= O(sqrt(d/m)) ||w||).

The server solves in sketch space, getting v in R^m; predictions use x^T R v,
i.e. the effective weight vector is w~ = R v in the original space — that is
what Prop 3's error bound is measured against here and in benchmarks/table_vii.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sufficient_stats import (MATMUL_PRECISION, SuffStats,
                                         compute_stats)


def make_projection(key: jax.Array, d: int, m: int, dtype=jnp.float32) -> jax.Array:
    """Shared sketch matrix R (broadcast once; seed sharing costs O(1))."""
    if not 0 < m <= d:
        raise ValueError(f"need 0 < m <= d, got {m=}, {d=}")
    return jax.random.normal(key, (d, m), dtype) / jnp.sqrt(jnp.asarray(m, dtype))


def project_data(A: jax.Array, R: jax.Array) -> jax.Array:
    """Client-side feature sketch A~ = A R  (n_k x m)."""
    return jnp.matmul(A, R, precision=MATMUL_PRECISION)


def projected_stats(A: jax.Array, b: jax.Array, R: jax.Array) -> SuffStats:
    """Phase 1 in sketch space: G~_k = (A R)^T (A R), h~_k = (A R)^T b."""
    return compute_stats(project_data(A, R), b)


def lift(v: jax.Array, R: jax.Array) -> jax.Array:
    """Map the sketch-space solution back: w~ = R v (for x^T R v predictions)."""
    return jnp.matmul(R, v, precision=MATMUL_PRECISION)


def upload_floats(d: int, m: int | None = None) -> int:
    """Per-client upload size in floats (Thm 4 / Prop 2 accounting).

    Full protocol: d(d+1)/2 (symmetric Gram) + d. Sketched: m(m+1)/2 + m.
    """
    k = d if m is None else m
    return k * (k + 1) // 2 + k


def error_bound(d: int, m: int, w_norm: float, c: float = 1.0) -> float:
    """Prop 3's bound shape: c * sqrt(d/m) * ||w|| (constant not specified by
    the paper; benchmarks fit/validate the sqrt(d/m) *trend*)."""
    return c * (d / m) ** 0.5 * w_norm
