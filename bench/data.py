"""The benchmark's inputs, made on the device from ``--seed``.

Rows follow the paper's §V-A generator (unit-norm w*, client means
gamma * u_k, per-client diagonal scales in [0.8, 1.2], noise std 0.1), drawn
for a whole tenant group in one jitted call. Streamed delta batches are
drawn from their site's own distribution in the same call. Client Phase 1
(the sufficient statistics each site uploads) runs the system's client
library, vmapped over every client of the group in one call; a group placed
``sharded`` instead makes one client's statistics at a time, when its
upload is sent, so that chip 0 never holds more than one of them.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rff
from repro.core.features import FeatureMap
from repro.core.sufficient_stats import SuffStats, compute_stats


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number, all of its bits used."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("tenants", "clients", "rows", "dim",
                                   "delta_rows", "gamma", "noise_std"))
def _federations(key, delta_tenant, delta_site, *, tenants, clients, rows,
                 dim, delta_rows, gamma, noise_std):
    kw, kmu, kcov, kfeat, knoise, kdf, kdn = jax.random.split(key, 7)
    w = jax.random.normal(kw, (tenants, dim))
    w = w / jnp.linalg.norm(w, axis=-1, keepdims=True)
    u = jax.random.normal(kmu, (tenants, clients, dim))
    mus = gamma * u / jnp.linalg.norm(u, axis=-1, keepdims=True)
    scales = jax.random.uniform(kcov, (tenants, clients, dim),
                                minval=0.8, maxval=1.2)
    A = mus[:, :, None] + jax.random.normal(
        kfeat, (tenants, clients, rows, dim)) * scales[:, :, None]
    b = (jnp.einsum("tknd,td->tkn", A, w, precision="highest")
         + noise_std * jax.random.normal(knoise, (tenants, clients, rows)))
    n_delta = delta_site.shape[0]
    dA = (mus[delta_tenant, delta_site][:, None]
          + jax.random.normal(kdf, (n_delta, delta_rows, dim))
          * scales[delta_tenant, delta_site][:, None])
    db = (jnp.einsum("xnd,xd->xn", dA, w[delta_tenant], precision="highest")
          + noise_std * jax.random.normal(kdn, (n_delta, delta_rows)))
    return A, b, dA, db


@jax.jit
def _dense_stats(A, b):
    return jax.vmap(jax.vmap(compute_stats))(A, b)


_client_stats = jax.jit(compute_stats)


@jax.jit
def _rff_stats(X, y, W, c):
    def one_tenant(X, y, W, c):
        fmap = rff.RFFMap(W=W, c=c)
        return jax.vmap(lambda x, t: rff.rff_stats(x, t, fmap))(X, y)

    return jax.vmap(one_tenant)(X, y, W, c)


@dataclasses.dataclass
class Group:
    """One tenant group of a configuration, with its data."""

    spec: dict
    names: list[str]
    maps: list[FeatureMap | None]
    rows: tuple[np.ndarray, np.ndarray]          # host copies (T, K, n, d_in)
    deltas: tuple[np.ndarray, np.ndarray]        # host copies (N, r, d_in)
    stats: SuffStats | None                      # device, leading (T, K)

    def client_stats(self, t: int, k: int) -> SuffStats:
        """Client k of tenant t: a slice of the group's statistics, or, for
        a sharded group, made now from that client's rows alone."""
        if self.placement == "sharded":
            A, b = self.rows
            return _client_stats(jnp.asarray(A[t, k]), jnp.asarray(b[t, k]))
        s = self.stats
        return SuffStats(s.gram[t, k], s.moment[t, k], s.count[t, k],
                         yty=s.yty[t, k])

    @property
    def kind(self) -> str:
        return self.spec["kind"]

    @property
    def placement(self) -> str:
        return placement_of(self.spec)


def placement_of(group: dict) -> str:
    """Where the group's tenants live: ``dense`` (one chip, the default) or
    ``sharded`` (block-sharded over the cell's chips)."""
    where = group.get("placement", "dense")
    if where not in ("dense", "sharded"):
        raise SystemExit(f"bench: unknown placement {where!r}")
    if where == "sharded" and group["kind"] != "dense":
        raise SystemExit(f"bench: a sharded group takes dense tenants, not "
                         f"{group['kind']!r}")
    return where


def tenant_names(group: dict) -> list[str]:
    return [f"{group['name']}{i:02d}" if group["count"] > 1
            else group["name"] for i in range(group["count"])]


def make_group(seed: int, gi: int, group: dict, delta_tenant, delta_site,
               delta_rows: int) -> Group:
    """Rows, deltas and client statistics of one tenant group."""
    T, K = group["count"], group["clients"]
    d_in = group.get("d_orig", group["dim"])
    where = placement_of(group)
    key = jax.random.fold_in(key_from_seed(seed), gi)
    A, b, dA, db = _federations(
        key, jnp.asarray(delta_tenant, jnp.int32),
        jnp.asarray(delta_site, jnp.int32), tenants=T, clients=K,
        rows=group["rows_per_client"], dim=d_in, delta_rows=delta_rows,
        gamma=float(group["gamma"]), noise_std=float(group["noise_std"]))
    maps: list[FeatureMap | None] = [None] * T
    if group["kind"] == "rff":
        rng = np.random.default_rng([int(seed) % 2**63, gi])
        # sqrt(d_orig)/4 keeps the phases within about +-25 (accurate f32
        # cos) and the feature Gram well conditioned, as chip_smoke.py.
        maps = [FeatureMap("rff", seed=int(s), d_orig=d_in, m=group["dim"],
                           lengthscale=math.sqrt(d_in) / 4)
                for s in rng.integers(0, 2**31 - 1, size=T)]
        W = jnp.stack([fm.materialize()[0] for fm in maps])
        c = jnp.stack([fm.materialize()[1] for fm in maps])
        stats = _rff_stats(A, b, W, c)
    elif where == "sharded":
        stats = None
    elif group["kind"] == "dense":
        stats = _dense_stats(A, b)
    else:
        raise SystemExit(f"bench: unknown tenant kind {group['kind']!r}")
    rows = (np.asarray(jax.device_get(A)), np.asarray(jax.device_get(b)))
    deltas = (np.asarray(jax.device_get(dA)), np.asarray(jax.device_get(db)))
    del A, b, dA, db
    return Group(group, tenant_names(group), maps, rows, deltas, stats)
