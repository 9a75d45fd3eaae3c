"""Unified §IV-F feature-map identity — the sketch / RFF tenant contract.

The paper's kernel-extension claim (§IV-F, Props 2–3) covers two feature
maps that both reduce per-client upload from O(d²) to O(m²): the Gaussian
sketch x -> R^T x (projection.py) and random Fourier features
x -> sqrt(2/D) cos(W^T x + c) (rff.py). Serving either requires every
participant to hold the SAME map, so the map needs an *identity* that can
cross the wire: (kind, seed, m, d_orig, lengthscale) regenerates the arrays
deterministically, and :func:`feature_hash` fingerprints the actual bytes so
version skew between two derivations of "the same" map is a typed rejection
at admission, never a silent mis-fuse.

``FeatureMap`` is hashable/frozen — the pool caches materialized arrays per
map, and two tenants declaring identical parameters share one cache entry.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projection, rff
from repro.core.sufficient_stats import MATMUL_PRECISION, SuffStats

KINDS = ("sketch", "rff")


def feature_hash(*arrays) -> int:
    """CRC32 chained over each array's canonical f32 bytes.

    For a single array this equals ``fed.wire.projection_hash`` (pinned by
    test) — the wire layer and the map identity must agree on fingerprints,
    but core cannot import fed, so the tiny codec is duplicated here.
    """
    h = 0
    for a in arrays:
        arr = np.ascontiguousarray(np.asarray(a), dtype="<f4")
        h = zlib.crc32(arr.tobytes(), h)
    return h & 0xFFFFFFFF


@dataclasses.dataclass(frozen=True)
class FeatureMap:
    """Identity of a shared §IV-F feature map.

    kind: "sketch" (Gaussian JL projection, Props 2–3) or "rff" (random
    Fourier features approximating the RBF kernel at ``lengthscale``).
    m is the feature count — the solve-space dimension (sketch m <= d_orig;
    RFF D may exceed d_orig). seed regenerates the arrays; sharing it costs
    O(1) on the wire versus O(dm) for shipping the map itself.
    """

    kind: str
    seed: int
    d_orig: int
    m: int
    lengthscale: float = 1.0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"kind must be one of {KINDS}, got {self.kind!r}")
        if self.kind == "sketch":
            if not 0 < self.m <= self.d_orig:
                raise ValueError(f"sketch needs 0 < m <= d_orig, got "
                                 f"m={self.m}, d_orig={self.d_orig}")
        else:
            if self.m <= 0 or self.d_orig <= 0:
                raise ValueError(f"rff needs m, d_orig > 0, got m={self.m}, "
                                 f"d_orig={self.d_orig}")
        if not (math.isfinite(self.lengthscale) and self.lengthscale > 0):
            raise ValueError(f"lengthscale must be finite and > 0, "
                             f"got {self.lengthscale}")

    # -- materialization -----------------------------------------------------

    def materialize(self) -> tuple[jax.Array, ...]:
        """The map's arrays, derived deterministically from the seed.

        (R,) for sketch, (W, c) for rff. Cached per identity — repeated
        calls (pool admission checks, lifts, predictions) pay zero RNG.
        """
        return _materialize(self)

    @property
    def fhash(self) -> int:
        """Fingerprint of the materialized bytes (cached with them)."""
        return _fhash(self)

    # -- the map itself ------------------------------------------------------

    def __call__(self, X: jax.Array) -> jax.Array:
        """Featurize rows: X (n, d_orig) -> (n, m)."""
        if self.kind == "sketch":
            (R,) = self.materialize()
            return projection.project_data(X, R)
        W, c = self.materialize()
        return rff.RFFMap(W=W, c=c)(X)

    def stats(self, A: jax.Array, b: jax.Array, *,
              use_pallas: bool = False) -> SuffStats:
        """Client Phase 1 in feature space: G = T^T T, h = T^T b, T = phi(A).

        ``use_pallas`` routes through the fused featurize->Gram ingest
        kernel (kernels.ops.sketch_gram / rff_gram) — T never hits HBM;
        the default is the two-pass XLA reference path.
        """
        # yty = Σ b² is featurization-invariant (targets never featurize):
        # feature-space inference uses the same residual second moment.
        yty = jnp.einsum("n,n->", b, b, precision=MATMUL_PRECISION
                         ).astype(jnp.asarray(A).dtype)
        if use_pallas:
            from repro.kernels import ops

            if self.kind == "sketch":
                (R,) = self.materialize()
                G, h = ops.sketch_gram(A, b, R)
            else:
                W, c = self.materialize()
                G, h = ops.rff_gram(A, b, W, c)
            return SuffStats(gram=G, moment=h,
                             count=jnp.asarray(A.shape[0], jnp.int32),
                             yty=yty.astype(G.dtype))
        if self.kind == "sketch":
            (R,) = self.materialize()
            s = projection.projected_stats(A, b, R)
        else:
            W, c = self.materialize()
            s = rff.rff_stats(A, b, rff.RFFMap(W=W, c=c))
        return SuffStats(s.gram, s.moment, s.count,
                         yty=yty.astype(s.gram.dtype))

    # -- serving -------------------------------------------------------------

    def lift(self, v: jax.Array) -> jax.Array:
        """Solve-space solution -> served weights.

        Sketch: w~ = R v in the original d_orig space (predictions are
        x^T R v, Prop 3 measures against this). RFF: identity — weights
        live in feature space and predictions featurize first.
        """
        if self.kind == "sketch":
            (R,) = self.materialize()
            return projection.lift(v, R)
        return v

    def predict(self, X: jax.Array, w: jax.Array) -> jax.Array:
        """Predictions from *served* (lifted) weights on raw rows X."""
        if self.kind == "sketch":
            return jnp.matmul(X, w, precision=MATMUL_PRECISION)
        return jnp.matmul(self(X), w, precision=MATMUL_PRECISION)

    def error_bound(self, w_norm: float, c: float = 1.0) -> float | None:
        """Prop 3's c·sqrt(d/m)·||w|| shape for the sketch; None for RFF
        (its approximation error is O(1/sqrt(D)) in the *kernel*, not a
        weight-space bound of this form)."""
        if self.kind == "sketch":
            return projection.error_bound(self.d_orig, self.m, w_norm, c)
        return None

    def upload_floats(self) -> int:
        """Per-client upload in floats: m(m+1)/2 + m (§IV-F accounting)."""
        return projection.upload_floats(self.d_orig, self.m)


@functools.lru_cache(maxsize=64)
def _materialize(fm: FeatureMap) -> tuple[jax.Array, ...]:
    key = jax.random.PRNGKey(fm.seed)
    if fm.kind == "sketch":
        return (projection.make_projection(key, fm.d_orig, fm.m),)
    feat = rff.make_rff(key, fm.d_orig, fm.m, lengthscale=fm.lengthscale)
    return (feat.W, feat.c)


@functools.lru_cache(maxsize=64)
def _fhash(fm: FeatureMap) -> int:
    return feature_hash(*_materialize(fm))
