"""Sufficient statistics for ridge regression (paper §III-D, Theorem 1).

The ridge solution w_sigma = (A^T A + sigma I)^{-1} A^T b depends on the data
only through

    G = A^T A   (d x d Gram matrix)
    h = A^T b   (d   moment vector)

and both decompose additively over any row partition of (A, b) — Theorem 1.
This module provides:

  * ``compute_stats``       — local (G_k, h_k) on one client's data
  * ``compute_stats_streaming`` — chunked scan over rows (bounded memory)
  * ``fuse_stats``          — Phase-2 server aggregation (a tree-sum)
  * ``distributed_stats``   — the protocol as a shard_map: each mesh shard is a
                              client, Phase 2 is one psum over the client axes.
                              This all-reduce IS the paper's single
                              communication round; its payload (d^2 + d floats)
                              is what Theorem 4 counts.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Precision of every matmul on the statistic, solve and inference paths
#: (the Gram kernels, compute_stats, the factor updates, predictions and the
#: inference algebra). A TPU's default f32 matmul runs one bf16 pass, ~3
#: significant digits: far short of the f32 statistics exact fusion promises.
#: HIGHEST is full f32 on the chip and a no-op on the CPU.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SuffStats:
    """Sufficient statistics of ridge regression (Definition 1).

    Attributes:
      gram:   G = A^T A, shape (d, d), symmetric PSD.
      moment: h = A^T b, shape (d,).
      count:  number of rows n that went into the statistics. Carried so the
              server can report effective sample size under dropout (Thm 8)
              and so streaming updates (§VI-C) stay self-describing.
      yty:    residual second moment Σ b_i² (scalar), or None when unknown.
              With (G, h, n) it closes the inference algebra — RSS =
              yty - 2 h^T w + w^T G w — so the server can serve standard
              errors and intervals without ever seeing rows. ``None`` marks
              statistics from a moments-less (legacy) source; combining a
              None with anything degrades the result to None (the fused
              RSS would be wrong by the unknown client's share), which is
              exactly the backward-compatible behaviour: point estimates
              are untouched, inference fields degrade.
    """

    gram: jax.Array
    moment: jax.Array
    count: jax.Array
    yty: jax.Array | None = None

    @property
    def dim(self) -> int:
        return self.gram.shape[-1]

    @staticmethod
    def _combine_yty(a, b, op):
        # Moments telescope exactly like (G, h) — but only when both sides
        # carry them; a legacy (None) side degrades the combination.
        if a is None or b is None:
            return None
        return op(a, b)

    def __add__(self, other: "SuffStats") -> "SuffStats":
        # Theorem 1: additivity over row partitions.
        return SuffStats(
            gram=self.gram + other.gram,
            moment=self.moment + other.moment,
            count=self.count + other.count,
            yty=self._combine_yty(self.yty, other.yty, lambda a, b: a + b),
        )

    def __sub__(self, other: "SuffStats") -> "SuffStats":
        # Additivity also licenses removal (Thm 8 dropout, Prop 5 LOCO).
        return SuffStats(
            gram=self.gram - other.gram,
            moment=self.moment - other.moment,
            count=self.count - other.count,
            yty=self._combine_yty(self.yty, other.yty, lambda a, b: a - b),
        )

    def scale(self, s) -> "SuffStats":
        """Scale a client's contribution (0/1 masks give Thm 8 dropout)."""
        return SuffStats(self.gram * s, self.moment * s, self.count * s,
                         yty=None if self.yty is None else self.yty * s)

    def without_moments(self) -> "SuffStats":
        """The same statistics with the second moment dropped (yty=None)."""
        return SuffStats(self.gram, self.moment, self.count, yty=None)


def zeros_like_stats(d: int, dtype=jnp.float32) -> SuffStats:
    return SuffStats(
        gram=jnp.zeros((d, d), dtype),
        moment=jnp.zeros((d,), dtype),
        count=jnp.zeros((), jnp.int32),
        yty=jnp.zeros((), dtype),
    )


def compute_stats(A: jax.Array, b: jax.Array, *, use_pallas: bool = False) -> SuffStats:
    """Local Phase-1 computation: G_k = A_k^T A_k, h_k = A_k^T b_k.

    Args:
      A: (n_k, d) feature matrix of one client.
      b: (n_k,) target vector.
      use_pallas: route the fused Gram+moment Pallas kernel (TPU hot path;
        interpret-mode on CPU). The default XLA path is the reference.
    """
    if A.ndim != 2:
        raise ValueError(f"A must be (n, d), got {A.shape}")
    if b.shape != (A.shape[0],):
        raise ValueError(f"b must be ({A.shape[0]},), got {b.shape}")
    if use_pallas:
        from repro.kernels import ops as kernel_ops

        gram, moment = kernel_ops.gram_moment(A, b)
    else:
        acc = jnp.float32 if A.dtype in (jnp.bfloat16, jnp.float16) else A.dtype
        gram = jnp.einsum("ni,nj->ij", A, A, preferred_element_type=acc,
                          precision=MATMUL_PRECISION)
        moment = jnp.einsum("ni,n->i", A, b, preferred_element_type=acc,
                            precision=MATMUL_PRECISION)
    acc = jnp.float32 if b.dtype in (jnp.bfloat16, jnp.float16) else b.dtype
    yty = jnp.einsum("n,n->", b, b, preferred_element_type=acc,
                     precision=MATMUL_PRECISION)
    yty = yty.astype(gram.dtype)
    return SuffStats(gram=gram, moment=moment,
                     count=jnp.asarray(A.shape[0], jnp.int32), yty=yty)


@partial(jax.jit, static_argnames=("chunk",))
def _streaming_main(A: jax.Array, b: jax.Array, chunk: int) -> SuffStats:
    """Full chunks via fori_loop + dynamic_slice: the working set beyond the
    input is one (chunk, d) window and the (d, d) accumulator — A is read in
    place, never reshaped or copied wholesale."""
    n, d = A.shape

    def body(i, carry: SuffStats) -> SuffStats:
        a_c = jax.lax.dynamic_slice(A, (i * chunk, 0), (chunk, d))
        b_c = jax.lax.dynamic_slice(b, (i * chunk,), (chunk,))
        return carry + compute_stats(a_c, b_c)

    init = zeros_like_stats(d, jnp.promote_types(A.dtype, jnp.float32))
    return jax.lax.fori_loop(0, n // chunk, body, init)


def compute_stats_streaming(A: jax.Array, b: jax.Array, *, chunk: int = 1024) -> SuffStats:
    """Streaming Phase-1 over row chunks (bounded working set).

    Mirrors what a memory-constrained edge client does: G accumulates in a
    d x d buffer while rows stream through, one ``dynamic_slice`` window at
    a time. Only the ragged tail chunk is zero-padded — zero rows contribute
    zero to both G and h, so padding is exact — keeping the working set at
    O(chunk * d) instead of materializing a padded copy of the full A.
    """
    n, d = A.shape
    n_main = (n // chunk) * chunk
    out = _streaming_main(A[:n_main], b[:n_main], chunk) if n_main \
        else zeros_like_stats(d, jnp.promote_types(A.dtype, jnp.float32))
    if n_main < n:
        tail = n - n_main
        a_t = jnp.pad(A[n_main:], ((0, chunk - tail), (0, 0)))
        b_t = jnp.pad(b[n_main:], (0, chunk - tail))
        out = out + compute_stats(a_t, b_t)
    # chunk-sized steps over-count padded rows; fix the true count (padded
    # rows contribute exact zeros to G, h, AND yty).
    return SuffStats(out.gram, out.moment, jnp.asarray(n, jnp.int32),
                     yty=out.yty)


def fuse_stats(stats: Sequence[SuffStats], *, chunk: int = 8) -> SuffStats:
    """Phase-2 server aggregation: G = sum_k G_k, h = sum_k h_k (Thm 1).

    A chunked tree reduction: at most ``chunk`` Grams are ever stacked into
    one buffer (a (chunk, d, d) stack-and-sum is one XLA reduce, not a
    chunk-deep dependency chain), and the chunk partials recurse. Peak extra
    allocation is O(chunk * d^2 + K/chunk * d^2) instead of the O(K * d^2)
    a single (K, d, d) stack costs — at K in the hundreds of clients and
    production d, the full stack is the server's largest transient buffer.
    """
    if not stats:
        raise ValueError("need at least one client's statistics")
    if any(s.yty is None for s in stats) and \
            any(s.yty is not None for s in stats):
        # Mixed moments-carrying and legacy stats: degrade the whole fusion
        # to yty=None (matching __add__) so the tree structures are uniform
        # for the stacked reduction below.
        stats = [s if s.yty is None else s.without_moments() for s in stats]
    if len(stats) == 1:
        return stats[0]
    if len(stats) <= chunk:
        return jax.tree.map(lambda *leaves: jnp.stack(leaves).sum(axis=0),
                            *stats)
    partials = [fuse_stats(stats[i:i + chunk], chunk=chunk)
                for i in range(0, len(stats), chunk)]
    return fuse_stats(partials, chunk=chunk)


# ---------------------------------------------------------------------------
# Distributed protocol: clients = mesh shards, Phase 2 = one psum.
# ---------------------------------------------------------------------------

def distributed_stats(
    A: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    client_axes: tuple[str, ...] = ("data",),
    participation: jax.Array | None = None,
    noise_fn=None,
) -> SuffStats:
    """One-Shot protocol Phases 1+2 on a device mesh.

    Each shard along ``client_axes`` plays one client: it computes its local
    (G_k, h_k) and the single ``psum`` is the one-and-only communication round
    (an all-reduce of d^2 + d floats — exactly Theorem 4's upload cost, visible
    as one all-reduce op in the compiled HLO).

    Args:
      A: (n, d) global feature matrix, row-sharded over ``client_axes``.
      b: (n,) targets, sharded to match.
      mesh: the device mesh.
      client_axes: mesh axes along which rows (clients) are sharded. For the
        production mesh this is ("data",) or ("pod", "data").
      participation: optional (K,) 0/1 float vector indexed by client id
        (= flattened position along client_axes) implementing Thm 8 dropout:
        a dropped client's statistics are zeroed before the psum.
      noise_fn: optional callable (client_id, G, h) -> (G~, h~) applied
        *before* aggregation — Algorithm 2's per-client DP noise hook.
    """
    d = A.shape[-1]
    row_spec = P(client_axes)
    n_clients = 1
    for ax in client_axes:
        n_clients *= mesh.shape[ax]

    def local(a_k, b_k, part):
        s = compute_stats(a_k, b_k)
        idx = _flat_client_index(client_axes, mesh)
        if noise_fn is not None:
            # DP noise covers (G, h) only; an un-noised Σy² riding along
            # would leak, so the privatized statistics drop it (yty=None).
            g_t, h_t = noise_fn(idx, s.gram, s.moment)
            s = SuffStats(g_t, h_t, s.count)
        s = s.scale(part[idx])
        return jax.tree.map(partial(jax.lax.psum, axis_name=client_axes), s)

    if participation is None:
        participation = jnp.ones((n_clients,), jnp.float32)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row_spec, row_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(A, b, participation)


def _flat_client_index(client_axes: tuple[str, ...], mesh: Mesh) -> jax.Array:
    """Row-major flat index of this shard along the client axes."""
    idx = jnp.zeros((), jnp.int32)
    for ax in client_axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def streaming_update(old: SuffStats, delta_A: jax.Array, delta_b: jax.Array) -> SuffStats:
    """§VI-C streaming extension: fold newly arrived rows into existing stats."""
    return old + compute_stats(delta_A, delta_b)
