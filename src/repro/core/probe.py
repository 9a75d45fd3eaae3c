"""One-shot federated linear probing of backbone features.

This is where the paper's technique integrates with the assigned
architectures (DESIGN.md §4): the nonlinear backbone f_theta is frozen; the
readout head IS a ridge regression on features Phi = f_theta(x) in R^{d_feat},
so Theorems 1/2/5/8 apply verbatim to the head. One all-reduce of
(d_feat^2 + d_feat) floats replaces iterative head training — the paper's
NTK / random-feature scope made concrete.

Works on a device mesh: data is row-sharded over the client axes, features are
computed shard-locally, and ``distributed_stats`` performs the single fusion
round. Multi-target heads (e.g. num_classes regression targets) are supported
by stacking moment vectors.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fusion
from repro.core.sufficient_stats import SuffStats, compute_stats


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    weights: jax.Array          # (d_feat,) or (d_feat, n_targets)
    stats: SuffStats            # fused feature statistics (reusable for LOCO-CV)
    sigma: float


def _feature_stats(feats: jax.Array, targets: jax.Array) -> SuffStats:
    """Stats on features; targets may be (n,) or (n, t) (stacked moments)."""
    acc = jnp.float32
    gram = jnp.einsum("ni,nj->ij", feats, feats, preferred_element_type=acc)
    if targets.ndim == 1:
        moment = feats.T @ targets
    else:
        moment = jnp.einsum("ni,nt->it", feats, targets, preferred_element_type=acc)
    return SuffStats(gram, moment, jnp.asarray(feats.shape[0], jnp.int32))


def solve_head(stats: SuffStats, sigma: float) -> jax.Array:
    """(G + sigma I)^{-1} H for single- or multi-target moments."""
    d = stats.gram.shape[0]
    reg = stats.gram + sigma * jnp.eye(d, dtype=stats.gram.dtype)
    c, low = jax.scipy.linalg.cho_factor(reg)
    return jax.scipy.linalg.cho_solve((c, low), stats.moment)


def one_shot_probe(
    feature_fn: Callable[[jax.Array], jax.Array],
    inputs: jax.Array,
    targets: jax.Array,
    *,
    sigma: float = 1e-2,
    mesh: Mesh | None = None,
    client_axes: tuple[str, ...] = ("data",),
) -> ProbeResult:
    """Fit a ridge readout head on frozen backbone features, one-shot.

    Args:
      feature_fn: frozen backbone, maps (n, ...) inputs -> (n, d_feat)
        features. Any jittable callable (e.g. partial(model.apply, params)
        returning pooled hidden states).
      inputs / targets: global arrays; if ``mesh`` is given they must be (or
        will be) row-sharded over ``client_axes`` and fusion is the single
        psum; otherwise everything runs on one device (K=1 degenerate case —
        still the exact centralized solution, by Thm 2).
    """
    if mesh is None:
        feats = feature_fn(inputs)
        stats = _feature_stats(feats, targets)
        return ProbeResult(solve_head(stats, sigma), stats, sigma)

    row = P(client_axes)

    def local(x_k, y_k):
        feats = feature_fn(x_k)
        s = _feature_stats(feats, y_k)
        return jax.tree.map(lambda v: jax.lax.psum(v, client_axes), s)

    fused = jax.shard_map(local, mesh=mesh, in_specs=(row, row),
                          out_specs=P(), check_vma=False)(inputs, targets)
    return ProbeResult(solve_head(fused, sigma), fused, sigma)


def probe_mse(feature_fn, inputs, targets, result: ProbeResult) -> jax.Array:
    pred = feature_fn(inputs) @ result.weights
    return jnp.mean((pred - targets) ** 2)


def head_as_params(result: ProbeResult) -> dict:
    """Package the fused head so checkpointing/serving treats it as a layer."""
    w = result.weights
    return {"kernel": w if w.ndim == 2 else w[:, None],
            "bias": jnp.zeros((w.shape[1] if w.ndim == 2 else 1,), w.dtype)}
