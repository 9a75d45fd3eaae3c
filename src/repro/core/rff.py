"""Random Fourier features — the paper's §IV-F kernel-extension path [10].

phi(x) = sqrt(2/D) cos(W x + c),  W_ij ~ N(0, 1/ell^2), c ~ U[0, 2pi)
approximates the RBF kernel k(x,y) = exp(-||x-y||^2 / (2 ell^2)). One-shot
fusion then runs verbatim on phi(A): communication O(D^2) where D is the
feature count — nonlinear decision functions from pure linear algebra.
This is the random-feature sibling of ``projection.py``'s Gaussian sketch:
both instantiate §IV-F's m ≪ d upload reduction, and the Prop-2/Prop-3
accounting there (``upload_floats``, ``error_bound``) prices this path's
D(D+1)/2 + D wire cost identically with m = D.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.sufficient_stats import (MATMUL_PRECISION, SuffStats,
                                         compute_stats)


@dataclasses.dataclass(frozen=True)
class RFFMap:
    """A shared random-feature map (broadcast by seed, like the JL sketch)."""

    W: jax.Array      # (d, D)
    c: jax.Array      # (D,)

    @property
    def num_features(self) -> int:
        return self.W.shape[1]

    def __call__(self, X: jax.Array) -> jax.Array:
        D = self.num_features
        Z = jnp.matmul(X, self.W, precision=MATMUL_PRECISION)
        return jnp.sqrt(2.0 / D) * jnp.cos(Z + self.c)


def make_rff(key: jax.Array, d: int, num_features: int, lengthscale: float = 1.0,
             dtype=jnp.float32) -> RFFMap:
    kw, kc = jax.random.split(key)
    W = jax.random.normal(kw, (d, num_features), dtype) / lengthscale
    c = jax.random.uniform(kc, (num_features,), dtype, 0.0, 2.0 * jnp.pi)
    return RFFMap(W=W, c=c)


def rff_stats(A: jax.Array, b: jax.Array, feat: RFFMap) -> SuffStats:
    """Client Phase 1 on random features: G_k = phi(A_k)^T phi(A_k), etc."""
    return compute_stats(feat(A), b)


def kernel_gram_exact(X: jax.Array, Y: jax.Array, lengthscale: float = 1.0) -> jax.Array:
    """Exact RBF kernel matrix (test oracle for the RFF approximation)."""
    sq = (
        jnp.sum(X**2, 1)[:, None] + jnp.sum(Y**2, 1)[None, :] - 2.0 * X @ Y.T
    )
    return jnp.exp(-sq / (2.0 * lengthscale**2))
