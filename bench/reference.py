"""The plain float64 reference: ridge statistics and solves in NumPy.

Shares no code with the server. The rows it is given are the benchmark's
own inputs; the random-Fourier-feature arrays are regenerated here from the
map's seed by the published recipe (W ~ N(0, 1/ell^2), c ~ U[0, 2 pi)),
not taken from the program.
"""
from __future__ import annotations

import math

import numpy as np


class Ridge64:
    """Ridge statistics and closed forms in float64 on the host."""

    #: From this width on, ``solve_many`` runs conjugate gradients: below
    #: it a float64 factorization takes under a second.
    CG_MIN_DIM = 8192
    #: Conjugate gradients stop once each sigma's relative residual is
    #: below ``CG_TOL``; an answer whose true residual is not, after at
    #: most ``CG_MAX_ITER`` passes over G, is solved by ``solve`` instead.
    CG_TOL = 1e-13
    CG_MAX_ITER = 500

    def __init__(self, d: int):
        self.G = np.zeros((d, d))
        self.h = np.zeros(d)
        self.yty = 0.0
        self.n = 0

    def add(self, T, y, sign: int = 1) -> None:
        T = np.asarray(T, np.float64)
        y = np.asarray(y, np.float64)
        self.G += sign * (T.T @ T)
        self.h += sign * (T.T @ y)
        self.yty += sign * float(y @ y)
        self.n += sign * len(y)

    def copy(self) -> "Ridge64":
        out = Ridge64(len(self.h))
        out.G, out.h = self.G.copy(), self.h.copy()
        out.yty, out.n = self.yty, self.n
        return out

    def solve(self, sigma: float) -> np.ndarray:
        """(G + sigma I)^{-1} h by a float64 Cholesky factor (G + sigma I
        is symmetric positive definite for sigma > 0)."""
        from scipy.linalg import cho_factor, cho_solve

        A = self.G + sigma * np.eye(len(self.h))
        return cho_solve(cho_factor(A, lower=True, overwrite_a=True,
                                    check_finite=False), self.h,
                         check_finite=False)

    def solve_many(self, sigmas) -> dict[float, np.ndarray]:
        """``solve`` at every sigma, by one float64 factorization each or,
        from ``CG_MIN_DIM`` on, by conjugate gradients over all of them at
        once: one pass over G an iteration instead of d^3/3 operations a
        sigma. A gradient answer stands only when its true residual
        ||(G + sigma I) w - h|| / ||h|| is under 10 x ``CG_TOL``."""
        S = np.array(sorted({float(s) for s in sigmas}))
        h, hn = self.h, float(np.linalg.norm(self.h))
        if len(h) < self.CG_MIN_DIM or hn == 0.0:
            return {s: self.solve(s) for s in S}
        X = np.zeros((len(h), len(S)))
        R = np.repeat(h[:, None], len(S), axis=1)
        P = R.copy()
        rr = np.einsum("ij,ij->j", R, R)
        for _ in range(self.CG_MAX_ITER):
            act = np.sqrt(rr) > self.CG_TOL * hn
            if not act.any():
                break
            Pa = P[:, act]
            AP = self.G @ Pa + S[act] * Pa
            alpha = rr[act] / np.einsum("ij,ij->j", Pa, AP)
            X[:, act] += alpha * Pa
            Ra = R[:, act] - alpha * AP
            rr_new = np.einsum("ij,ij->j", Ra, Ra)
            P[:, act] = Ra + (rr_new / rr[act]) * Pa
            R[:, act], rr[act] = Ra, rr_new
        true = np.linalg.norm(self.G @ X + S * X - h[:, None], axis=0) / hn
        return {s: X[:, c].copy() if true[c] <= 10 * self.CG_TOL
                else self.solve(s) for c, s in enumerate(S)}

    def residual(self, w, sigma: float) -> float:
        """||(G + sigma I) w - h|| / ||h||: how well w solves this state."""
        w = np.asarray(w, np.float64)
        r = self.G @ w + sigma * w - self.h
        return float(np.linalg.norm(r) / np.linalg.norm(self.h))


def rff_arrays(seed: int, d_orig: int, m: int, lengthscale: float
               ) -> tuple[np.ndarray, np.ndarray]:
    """(W, c) of an RFF map from its identity, by the published recipe.

    Drawn in float32 with jax.random exactly as a client holding the seed
    draws them, then widened.
    """
    import jax
    import jax.numpy as jnp

    kw, kc = jax.random.split(jax.random.PRNGKey(seed))
    W = jax.random.normal(kw, (d_orig, m), jnp.float32) / lengthscale
    c = jax.random.uniform(kc, (m,), jnp.float32, 0.0, 2.0 * jnp.pi)
    return (np.asarray(jax.device_get(W), np.float64),
            np.asarray(jax.device_get(c), np.float64))


def rff_features64(X, W, c) -> np.ndarray:
    """sqrt(2/D) cos(X W + c) in float64."""
    W = np.asarray(W, np.float64)
    Z = np.asarray(X, np.float64) @ W + np.asarray(c, np.float64)
    return math.sqrt(2.0 / W.shape[1]) * np.cos(Z)


def rel(x, ref) -> float:
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))
