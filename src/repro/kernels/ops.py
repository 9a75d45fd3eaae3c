"""Jit'd public wrappers for the Pallas kernels (padding, layout, dispatch).

On a TPU backend the kernels compile to Mosaic; on the CPU backend they run
in interpret mode (the kernel body runs in Python, for correctness
validation). Any other backend is an error, never a silent interpreter
fallback. ``use_pallas`` config flags route the model/core code here; the
default XLA paths in core/ and models/ are the numerical references.

``pack_lower``/``unpack_lower`` (the Theorem-4 triangular wire codec for
client Gram uploads) also live here: they are jitted static-index
gather/scatter ops rather than Pallas bodies — a data-movement pattern XLA
already emits optimally on every backend.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gram as gram_kernel
from repro.kernels import swa_flash as swa_kernel


def _interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` flag against the running backend.

    ``None`` compiles on a TPU and interprets on the CPU. The interpreter is
    refused everywhere but the CPU: on an accelerator it would run the
    kernel body in Python while reporting the accelerator's name. An
    explicit ``False`` is always allowed — lowering for a described TPU
    (``jax.experimental.topologies``) happens on a CPU-backed process.
    """
    backend = jax.default_backend()
    if interpret is None:
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(f"no Pallas path for backend {backend!r}: "
                               "kernels compile on tpu and interpret on cpu")
        return backend == "cpu"
    if interpret and backend != "cpu":
        raise RuntimeError(f"interpret mode requested on backend {backend!r}; "
                           "it is allowed on cpu only")
    return interpret


def pow2_bucket(n: int, *, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) — the jit-retrace bucket.

    Shared by every path whose batch extent is workload-dependent (coalescer
    flush ranks, sigma-grid lengths, cross-tenant solve batches): padding the
    extent to the next power of two bounds the number of compiled programs at
    log2(max) instead of one per distinct size, and every caller pads with
    exact identities (zero update rows, repeated sigmas, identity factors) so
    the bucketing is free of accuracy cost.
    """
    return max(floor, 1 << (max(int(n), 1) - 1).bit_length())


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def gram_moment(A: jax.Array, b: jax.Array, *, block_d: int = 128,
                block_n: int = 512, interpret: bool | None = None):
    """Fused (G, h) = (A^T A, A^T b); pads ragged shapes with zero rows/cols.

    Zero padding is exact: padded rows contribute nothing to G or h; padded
    feature columns land in G rows/cols that are sliced away.
    """
    n, d = A.shape
    block_d = min(block_d, max(128, 1 << (d - 1).bit_length()))
    block_n = min(block_n, max(8, 1 << (n - 1).bit_length()))
    Ap = _pad_to(_pad_to(A, 0, block_n), 1, block_d)
    bp = _pad_to(b, 0, block_n)
    G, h = gram_kernel.gram_moment_pallas(
        Ap, bp, block_d=block_d, block_n=block_n,
        interpret=_interpret(interpret))
    return G[:d, :d], h[:d]


def _feature_blocks(n: int, d: int, m_padded: int, block_d: int,
                    block_n: int, block_m: int = 512
                    ) -> tuple[int, int, int]:
    """Tiles (block_d, block_n, block_m) for the fused featurize->Gram kernels.

    Same pow2 clamping of (block_d, block_n) as :func:`gram_moment`; block_m
    is ``min(block_m, m_padded)`` halved until it divides ``m_padded`` (a
    multiple of 128), so the G output tile is (block_m, block_m) whatever m
    is. block_n then halves until the two (block_n, block_m) f32 feature-tile
    scratches fit a 4 MB VMEM budget (block_n stays a multiple of 8, the
    fp32 sublane tile).
    """
    block_d = min(block_d, max(128, 1 << (d - 1).bit_length()))
    block_n = min(block_n, max(8, 1 << (n - 1).bit_length()))
    block_m = min(block_m, m_padded)
    while m_padded % block_m:
        block_m //= 2
    while block_n > 8 and 2 * block_n * block_m * 4 > 4 * 1024 * 1024:
        block_n //= 2
    return block_d, block_n, block_m


def sketch_gram(A: jax.Array, b: jax.Array, R: jax.Array, *,
                block_d: int = 128, block_n: int = 512,
                interpret: bool | None = None):
    """Fused §IV-F sketch ingest: (G, h) = ((AR)^T AR, (AR)^T b).

    Pads ragged shapes exactly: padded rows of A are zero (zero feature
    rows contribute nothing), padded d is zero A cols x zero R rows, and
    padded sketch columns land in G rows/cols that are sliced away. The
    (n x m) sketch T never materializes in HBM.
    """
    n, d = A.shape
    m = R.shape[1]
    Rp = _pad_to(R, 1, 128)
    block_d, block_n, block_m = _feature_blocks(n, d, Rp.shape[1], block_d,
                                                block_n)
    Ap = _pad_to(_pad_to(A, 0, block_n), 1, block_d)
    bp = _pad_to(b, 0, block_n)
    Rp = _pad_to(Rp, 0, block_d)
    G, h = gram_kernel.sketch_gram_pallas(
        Ap, bp, Rp, block_d=block_d, block_n=block_n, block_m=block_m,
        interpret=_interpret(interpret))
    return G[:m, :m], h[:m]


def rff_gram(X: jax.Array, b: jax.Array, W: jax.Array, c: jax.Array, *,
             block_d: int = 128, block_n: int = 512,
             interpret: bool | None = None):
    """Fused RFF ingest: T = sqrt(2/D) cos(X W + c), (G, h) = (T^T T, T^T b).

    Ragged padding needs two corrections beyond the sketch case, both
    handled here/in-kernel: padded rows are masked inside the kernel
    (cos(0 + c) != 0, so zero-padding X rows is NOT exact), and the
    sqrt(2/D) scale is pinned to the true D via ``true_dim`` while the lane
    axis pads to >= 128 (padded feature columns only touch sliced-away
    G/h entries).
    """
    n, d = X.shape
    D = W.shape[1]
    Wp = _pad_to(W, 1, 128)
    block_d, block_n, block_m = _feature_blocks(n, d, Wp.shape[1], block_d,
                                                block_n)
    Xp = _pad_to(_pad_to(X, 0, block_n), 1, block_d)
    bp = _pad_to(b, 0, block_n)
    Wp = _pad_to(Wp, 0, block_d)
    cp = _pad_to(c, 0, Wp.shape[1])
    G, h = gram_kernel.rff_gram_pallas(
        Xp, bp, Wp, cp, n_valid=n, true_dim=D, block_d=block_d,
        block_n=block_n, block_m=block_m, interpret=_interpret(interpret))
    return G[:D, :D], h[:D]


def gemm_nt(C: jax.Array, A: jax.Array, B: jax.Array, *, alpha: float = -1.0,
            block_m: int = 128, block_n: int = 128,
            interpret: bool | None = None) -> jax.Array:
    """C + alpha * A @ B^T via the Pallas tile; pads ragged shapes exactly.

    The sharded block-Cholesky's inner tile op (SYRK trailing update with
    alpha=-1; TRSM-as-GEMM with alpha=+1). Zero padding is exact: padded k
    columns contribute nothing to the product, and padded m/n rows/cols of C
    land in output tiles that are sliced away.
    """
    m, n = C.shape
    k = A.shape[1]
    block_m = min(block_m, max(8, 1 << (m - 1).bit_length()))
    block_n = min(block_n, max(8, 1 << (n - 1).bit_length()))
    Cp = _pad_to(_pad_to(C, 0, block_m), 1, block_n)
    Ap = _pad_to(_pad_to(A, 0, block_m), 1, 128)
    Bp = _pad_to(_pad_to(B, 0, block_n), 1, 128)
    out = gram_kernel.gemm_nt_pallas(Cp, Ap, Bp, alpha=alpha,
                                     block_m=block_m, block_n=block_n,
                                     interpret=_interpret(interpret))
    return out[:m, :n]


_TRIL_IDX: dict[int, tuple[np.ndarray, np.ndarray]] = {}


def _tril(d: int) -> tuple[np.ndarray, np.ndarray]:
    """Static lower-triangle index pair for dimension d (host-side, cached)."""
    if d not in _TRIL_IDX:
        _TRIL_IDX[d] = np.tril_indices(d)
    return _TRIL_IDX[d]


def tri_len(d: int) -> int:
    """Packed lower-triangle length for dimension d: d(d+1)/2 (Thm 4)."""
    return d * (d + 1) // 2


def tri_dim(length: int) -> int:
    """Inverse of :func:`tri_len`; ValueError if no d satisfies d(d+1)/2 = L.

    The wire codec uses this on the encode side
    (``wire.StatsFrame.from_packed``) to cross-check a payload's declared
    dimension against its packed-triangle length — an inconsistent pair is
    a typed rejection before any bytes are produced.
    """
    d = (math.isqrt(8 * length + 1) - 1) // 2
    if tri_len(d) != length:
        raise ValueError(f"{length} is not a triangular length d(d+1)/2")
    return d


@jax.jit
def pack_lower(G: jax.Array) -> jax.Array:
    """(d, d) symmetric -> (d(d+1)/2,) row-major lower triangle.

    The Theorem-4 wire encoding of a client Gram: symmetry makes the strict
    upper triangle redundant, so uploads ship exactly d(d+1)/2 floats. One
    fused gather over static indices — the inverse of :func:`unpack_lower`.
    """
    i, j = _tril(G.shape[-1])
    return G[..., i, j]


@partial(jax.jit, static_argnames=("d",))
def unpack_lower(tri: jax.Array, d: int) -> jax.Array:
    """(d(d+1)/2,) packed lower triangle -> full symmetric (d, d).

    Exact roundtrip with :func:`pack_lower` for symmetric input: scatter the
    triangle, then mirror the strict lower part — no arithmetic touches the
    stored values, so pack/unpack is bit-identical on the kept entries.
    """
    if tri.shape[-1] != tri_len(d):
        raise ValueError(f"packed length {tri.shape[-1]} != d(d+1)/2 "
                         f"for d={d}")
    i, j = _tril(d)
    low = jnp.zeros((*tri.shape[:-1], d, d), tri.dtype).at[..., i, j].set(tri)
    strict = jnp.tril(low, -1)
    return low + jnp.swapaxes(strict, -1, -2)


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int | None, causal: bool = True,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool | None = None) -> jax.Array:
    """Sliding-window flash attention. q, k, v: (B, S, H, head_dim).

    Heads must already be GQA-grouped (equal q/kv head counts) — the model's
    serving path groups before calling. S is padded to the block size with
    masked (never-attended, never-attending) positions and sliced back.
    """
    B, S, H, hd = q.shape
    interpret = _interpret(interpret)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    pad = (-S) % max(block_q, block_k)
    if pad:
        q = _pad_to(q, 1, S + pad)
        k = _pad_to(k, 1, S + pad)
        v = _pad_to(v, 1, S + pad)
    Sp = q.shape[1]

    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)

    out = swa_kernel.swa_flash_pallas(
        to_bh(q), to_bh(k), to_bh(v), window=window, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    out = out.reshape(B, H, Sp, hd).transpose(0, 2, 1, 3)
    return out[:, :S]
