"""Fused streaming Gram + moment Pallas kernels — the paper's Phase-1 hot spot.

``gram_moment_pallas`` computes G = A^T A and h = A^T b in ONE pass over A.
The XLA baseline emits two HLO ops that each read A from HBM; on a TPU the
fused kernel streams each (bn, bd) tile of A into VMEM once per (i, k) pair
and feeds the MXU directly, accumulating both outputs in fp32.

Grid (d/bd, d/bd, n/bn), row-chunks innermost so output tiles are revisited
for accumulation:

  G[i, j] += A[k, i]^T @ A[k, j]         every (i, j, k)
  H[i]    += A[k, i]^T @ B[k]            only when j == 0

``sketch_gram_pallas`` / ``rff_gram_pallas`` extend the same design to the
§IV-F featurize->Gram ingest. Per (output tile (i, j), row-chunk k) the two
feature tiles T_i = phi(A_k @ R[:, i]) and T_j = phi(A_k @ R[:, j]) are
built in VMEM scratch across d-chunks, then folded straight into
G[i, j] += T_i^T T_j and H[i] += T_i^T B[k] — the (n x m) feature matrix
NEVER materializes in HBM, and VMEM holds (bn, bm) feature tiles, never an
(m, m) or (bn, m) block, so it stays bounded as m grows into the thousands:

  T_i  = sum_s A[k, s] @ R[s, i]        accumulated in VMEM scratch
  G[i, j] += T_i^T T_j                  once per row-chunk (s == last)

Every operand is 2-D and (8, 128)-tile aligned: the moment vector b rides in
column 0 of a zero (n, 128) block B, and h comes back as column 0 of the
(d, 128) output H (the TPU compiler rejects 1-D blocks whose XLA and Mosaic
tilings disagree). The RFF phase c rides as an (8, D) block. Every MXU
contraction runs at ``MATMUL_PRECISION`` — full f32, not the chip's default
bf16 passes, which would cost the statistics three decimal digits.

Tiles are MXU-aligned (bd and bm multiples of 128, bn a multiple of 8);
``ops.gram_moment`` / ``ops.sketch_gram`` / ``ops.rff_gram`` pad ragged
shapes with zero rows/cols (exact for the plain Gram and the sketch: zero
rows contribute nothing; the RFF kernel masks padded rows in-kernel because
cos(0 + c) != 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sufficient_stats import MATMUL_PRECISION

LANES = 128     # width of the B / H moment blocks (one lane tile)
SUBLANES = 8    # height of the replicated RFF phase block


def _contract(x, y, dims):
    """MXU contraction with f32 accumulation at full f32 precision."""
    if x.dtype != y.dtype:
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    return jax.lax.dot_general(x, y, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=MATMUL_PRECISION)


def _dot(x, y):
    """x @ y."""
    return _contract(x, y, ((1,), (0,)))


def _dot_tn(x, y):
    """x^T @ y (contract the row axis of both) — the Gram contraction."""
    return _contract(x, y, ((0,), (0,)))


def _moment_block(b: jax.Array, n: int) -> jax.Array:
    """(n,) moment vector -> (n, LANES) block with b in column 0."""
    return jnp.pad(b.reshape(n, 1), ((0, 0), (0, LANES - 1)))


def _gram_kernel(a_i_ref, a_j_ref, b_ref, g_ref, h_ref):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    a_i = a_i_ref[...]
    g_ref[...] += _dot_tn(a_i, a_j_ref[...])

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_h():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(j == 0)
    def _acc_h():
        h_ref[...] += _dot_tn(a_i, b_ref[...])


def _gemm_nt_kernel(alpha, c_ref, a_ref, b_ref, o_ref):
    """O = C + alpha * A @ B^T for one (bm, bn) output tile.

    The inner tile of the sharded block-Cholesky (server.distributed): with
    alpha=-1 it is the SYRK/GEMM trailing update ``G_ij -= L_ik L_jk^T``;
    with alpha=+1 and C=0 it is the TRSM panel solve re-expressed as a GEMM
    against the inverted bs x bs diagonal tile. Same MXU contraction pattern
    as the Gram kernel above (A and B contract over their last axis), so the
    whole factorization's O(d^3) lives on this one tile.
    """
    acc = _contract(a_ref[...], b_ref[...], ((1,), (1,)))
    o_ref[...] = c_ref[...] + alpha * acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "block_m", "block_n", "interpret"))
def gemm_nt_pallas(C: jax.Array, A: jax.Array, B: jax.Array, *,
                   alpha: float = -1.0, block_m: int = 128,
                   block_n: int = 128, interpret: bool = False):
    """C + alpha * A @ B^T. C: (m, n), A: (m, k), B: (n, k); blocks divide.

    k is a panel width (one block column of the factorization), so each
    output tile needs exactly one A tile and one B tile — no accumulation
    grid axis.
    """
    m, n = C.shape
    k = A.shape[1]
    assert A.shape == (m, k) and B.shape == (n, k), (C.shape, A.shape, B.shape)
    assert m % block_m == 0 and n % block_n == 0, (C.shape, block_m, block_n)
    grid = (m // block_m, n // block_n)

    return pl.pallas_call(
        functools.partial(_gemm_nt_kernel, alpha),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), C.dtype),
        interpret=interpret,
    )(C, A, B)


def _feature_tiles(a_ref, ri_ref, rj_ref, g_ref, h_ref, ti_ref, tj_ref):
    """Shared prologue of the featurize->Gram kernels; returns the ids.

    Zeroes the (i, j) output tile at its first visit (and the i moment tile
    at its first visit, which only the j == 0 column owns), re-zeroes the
    two feature-tile scratches at the first d-chunk, then accumulates this
    d-chunk's share of T_i = A_k @ R_i and T_j = A_k @ R_j.
    """
    j = pl.program_id(1)
    k = pl.program_id(2)
    s = pl.program_id(3)
    first = jnp.logical_and(k == 0, s == 0)

    @pl.when(first)
    def _init_g():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(jnp.logical_and(first, j == 0))
    def _init_h():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(s == 0)
    def _zero_t():
        ti_ref[...] = jnp.zeros_like(ti_ref)
        tj_ref[...] = jnp.zeros_like(tj_ref)

    a = a_ref[...]
    ti_ref[...] += _dot(a, ri_ref[...])
    tj_ref[...] += _dot(a, rj_ref[...])
    return j, k, s == pl.num_programs(3) - 1


def _fold(t_i, t_j, j, b_ref, g_ref, h_ref):
    """G[i, j] += T_i^T T_j; H[i] += T_i^T B[k] on the j == 0 column."""
    g_ref[...] += _dot_tn(t_i, t_j)

    @pl.when(j == 0)
    def _acc_h():
        h_ref[...] += _dot_tn(t_i, b_ref[...])


def _sketch_gram_kernel(a_ref, ri_ref, rj_ref, b_ref, g_ref, h_ref,
                        ti_ref, tj_ref):
    """One (i, j, row-chunk k, d-chunk s) step of the fused sketch ingest."""
    j, _, last = _feature_tiles(a_ref, ri_ref, rj_ref, g_ref, h_ref,
                                ti_ref, tj_ref)

    @pl.when(last)
    def _():
        _fold(ti_ref[...], tj_ref[...], j, b_ref, g_ref, h_ref)


def _rff_gram_kernel(scale, n_valid, block_n,
                     x_ref, wi_ref, wj_ref, ci_ref, cj_ref, b_ref,
                     g_ref, h_ref, ti_ref, tj_ref):
    """Fused RFF featurize->Gram: T = sqrt(2/D) cos(X W + c), G += T^T T.

    Same scratch scheme as the sketch kernel, with the nonlinearity applied
    at the fold. Padded rows MUST be masked here (not just zero-padded):
    cos(0 + c) != 0, so a zero row of X still produces a nonzero feature row
    that would corrupt G. n_valid is the true (unpadded) row count.
    """
    j, k, last = _feature_tiles(x_ref, wi_ref, wj_ref, g_ref, h_ref,
                                ti_ref, tj_ref)

    @pl.when(last)
    def _():
        def phi(t, c_ref):
            t = jnp.cos(t + c_ref[0:1, :].astype(jnp.float32))
            t = t * jnp.float32(scale)
            rows = k * block_n + jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
            return jnp.where(rows < n_valid, t, jnp.float32(0.0))

        _fold(phi(ti_ref[...], ci_ref), phi(tj_ref[...], cj_ref), j,
              b_ref, g_ref, h_ref)


def _feature_gram_call(kernel, A, b, maps, *, block_d, block_n, block_m,
                       interpret):
    """pallas_call plumbing shared by the sketch and RFF ingest kernels.

    ``maps`` is the (d, m) map matrix (R or W) followed by any (8, m) phase
    blocks (RFF c); each is fed twice, once per output-tile axis. Returns
    (G (m, m) f32, h (m,) f32).
    """
    n, d = A.shape
    m = maps[0].shape[1]
    block_m = min(block_m, m)
    assert n % block_n == 0 and d % block_d == 0, (A.shape, block_n, block_d)
    assert m % block_m == 0, (m, block_m)
    grid = (m // block_m, m // block_m, n // block_n, d // block_d)

    R, *phase = maps
    in_specs = [
        pl.BlockSpec((block_n, block_d), lambda i, j, k, s: (k, s)),
        pl.BlockSpec((block_d, block_m), lambda i, j, k, s: (s, i)),
        pl.BlockSpec((block_d, block_m), lambda i, j, k, s: (s, j)),
    ]
    operands = [A, R, R]
    for c in phase:
        in_specs += [
            pl.BlockSpec((SUBLANES, block_m), lambda i, j, k, s: (0, i)),
            pl.BlockSpec((SUBLANES, block_m), lambda i, j, k, s: (0, j)),
        ]
        operands += [c, c]
    in_specs.append(pl.BlockSpec((block_n, LANES), lambda i, j, k, s: (k, 0)))
    operands.append(_moment_block(b, n))

    G, H = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_m, block_m), lambda i, j, k, s: (i, j)),
            pl.BlockSpec((block_m, LANES), lambda i, j, k, s: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, m), jnp.float32),
            jax.ShapeDtypeStruct((m, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, block_m), jnp.float32),
                        pltpu.VMEM((block_n, block_m), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return G, H[:, 0]


@functools.partial(jax.jit, static_argnames=("block_d", "block_n", "block_m",
                                             "interpret"))
def sketch_gram_pallas(A: jax.Array, b: jax.Array, R: jax.Array, *,
                       block_d: int = 128, block_n: int = 512,
                       block_m: int = 512, interpret: bool = False):
    """Fused G = (AR)^T (AR), h = (AR)^T b without materializing AR in HBM.

    A: (n, d), b: (n,), R: (d, m) with block_n | n, block_d | d and
    min(block_m, m) | m (callers pad m to 128 lanes via ``ops.sketch_gram``).
    Returns (G (m, m) f32, h (m,) f32).
    """
    assert R.shape[0] == A.shape[1], (A.shape, R.shape)
    return _feature_gram_call(_sketch_gram_kernel, A, b, (R,),
                              block_d=block_d, block_n=block_n,
                              block_m=block_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "n_valid", "true_dim", "block_d", "block_n", "block_m", "interpret"))
def rff_gram_pallas(X: jax.Array, b: jax.Array, W: jax.Array, c: jax.Array,
                    *, n_valid: int | None = None, true_dim: int | None = None,
                    block_d: int = 128, block_n: int = 512,
                    block_m: int = 512, interpret: bool = False):
    """Fused RFF Gram: T = sqrt(2/D) cos(X W + c), G = T^T T, h = T^T b.

    X: (n, d), b: (n,), W: (d, D), c: (D,). n_valid (static) masks padded
    rows — defaults to n. true_dim (static) is the UNPADDED feature count
    used in the sqrt(2/D) scale: when ``ops.rff_gram`` pads the lane axis
    with zero W columns, the kept features must still carry the original
    D's scale (padded columns compute cos(c)*scale but only touch G/h
    entries the wrapper slices away). Defaults to W.shape[1].
    """
    n, d = X.shape
    D = W.shape[1]
    assert W.shape[0] == d and c.shape == (D,), (X.shape, W.shape, c.shape)
    n_valid = n if n_valid is None else n_valid
    true_dim = D if true_dim is None else true_dim
    kernel = functools.partial(_rff_gram_kernel,
                               float((2.0 / true_dim) ** 0.5), n_valid,
                               block_n)
    phase = jnp.broadcast_to(c[None, :], (SUBLANES, D))
    return _feature_gram_call(kernel, X, b, (W, phase),
                              block_d=block_d, block_n=block_n,
                              block_m=block_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_d", "block_n", "interpret"))
def gram_moment_pallas(A: jax.Array, b: jax.Array, *, block_d: int = 128,
                       block_n: int = 512, interpret: bool = False):
    """A: (n, d) with block_d | d and block_n | n. Returns (G f32, h f32)."""
    n, d = A.shape
    assert n % block_n == 0 and d % block_d == 0, (A.shape, block_n, block_d)
    grid = (d // block_d, d // block_d, n // block_n)

    G, H = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_n, LANES), lambda i, j, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_d, LANES), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((d, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(A, A, _moment_block(b, n))
    return G, H[:, 0]
