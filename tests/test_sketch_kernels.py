"""Fused featurize->Gram ingest kernels (§IV-F sketch + RFF) vs unfused refs.

Both kernels build each row-chunk's feature block T in a VMEM scratch and
fold it straight into G/h — the full (n x m) feature matrix never exists in
HBM. The pinned oracle is the unfused two-pass path in kernels.ref, which
DOES materialize T. Both paths compute T in f32 from the same (possibly
bf16-quantized) inputs, so even the bf16 columns of the sweep compare at
f32 reduction-order tolerance — quantization happens before the product in
both, not differently between them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro.kernels import gram, ops, ref


def _mk_sketch(n, d, m, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = jax.random.normal(k1, (n, d), dtype)
    b = jax.random.normal(k2, (n,), dtype)
    R = (jax.random.normal(k3, (d, m)) / np.sqrt(m)).astype(dtype)
    return A, b, R


def _mk_rff(n, d, D, dtype, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(k1, (n, d), dtype)
    b = jax.random.normal(k2, (n,), dtype)
    W = jax.random.normal(k3, (d, D)).astype(dtype)
    c = jax.random.uniform(k4, (D,), jnp.float32, 0.0, 2.0 * np.pi).astype(dtype)
    return X, b, W, c


def _assert_close(G, h, Gr, hr):
    scale = max(1.0, float(np.abs(np.asarray(Gr)).max()))
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               rtol=2e-3, atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=2e-3, atol=2e-4 * scale)


class TestSketchGramKernel:
    @pytest.mark.parametrize("n,d,m", [
        (256, 128, 128), (512, 256, 16), (1000, 100, 12),
        (64, 16, 8), (128, 384, 48)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_unfused_reference(self, n, d, m, dtype):
        A, b, R = _mk_sketch(n, d, m, dtype, seed=n + d + m)
        G, h = ops.sketch_gram(A, b, R)
        Gr, hr = ref.sketch_gram_ref(A, b, R)
        assert G.shape == (m, m) and h.shape == (m,)
        assert G.dtype == jnp.float32 and h.dtype == jnp.float32
        _assert_close(G, h, Gr, hr)

    def test_direct_pallas_call_aligned(self):
        """The jit'd pallas entry itself, no padding wrapper in the way."""
        A, b, R = _mk_sketch(128, 256, 128, jnp.float32, seed=7)
        G, h = gram.sketch_gram_pallas(A, b, R, block_d=128, block_n=32,
                                       interpret=True)
        Gr, hr = ref.sketch_gram_ref(A, b, R)
        _assert_close(G, h, Gr, hr)

    @hypothesis.given(n=st.integers(8, 200), d=st.integers(4, 96),
                      m=st.integers(1, 48))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_ragged_padding_exact(self, n, d, m):
        """Zero-padding rows/cols/lanes must not change the statistics."""
        m = min(m, d)
        A, b, R = _mk_sketch(n, d, m, jnp.float32, seed=3)
        G, h = ops.sketch_gram(A, b, R, block_d=32, block_n=32)
        Gr, hr = ref.sketch_gram_ref(A, b, R)
        _assert_close(G, h, Gr, hr)

    def test_multi_chunk_accumulation(self):
        """Several row chunks AND several d chunks — the scratch re-zeroing
        and last-chunk fold logic are what's under test."""
        A, b, R = _mk_sketch(256, 512, 32, jnp.float32, seed=11)
        G, h = ops.sketch_gram(A, b, R, block_d=128, block_n=64)
        Gr, hr = ref.sketch_gram_ref(A, b, R)
        _assert_close(G, h, Gr, hr)

    def test_matches_core_projection_path(self):
        """Same statistics as core.projection.projected_stats (XLA path)."""
        from repro import core
        A, b, _ = _mk_sketch(200, 64, 16, jnp.float32, seed=5)
        R = core.make_projection(jax.random.PRNGKey(9), 64, 16)
        G, h = ops.sketch_gram(A, b, R)
        s = core.projected_stats(A, b, R)
        np.testing.assert_allclose(np.asarray(G), np.asarray(s.gram),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(s.moment),
                                   rtol=1e-4, atol=1e-4)


class TestRFFGramKernel:
    @pytest.mark.parametrize("n,d,D", [
        (256, 128, 128), (512, 64, 256), (1000, 100, 12),
        (64, 16, 8), (96, 48, 160)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_unfused_reference(self, n, d, D, dtype):
        X, b, W, c = _mk_rff(n, d, D, dtype, seed=n + d + D)
        G, h = ops.rff_gram(X, b, W, c)
        Gr, hr = ref.rff_gram_ref(X, b, W, c)
        assert G.shape == (D, D) and h.shape == (D,)
        _assert_close(G, h, Gr, hr)

    def test_direct_pallas_call_aligned(self):
        X, b, W, c = _mk_rff(128, 256, 128, jnp.float32, seed=13)
        G, h = gram.rff_gram_pallas(X, b, W, c, block_d=128, block_n=32,
                                    interpret=True)
        Gr, hr = ref.rff_gram_ref(X, b, W, c)
        _assert_close(G, h, Gr, hr)

    @hypothesis.given(n=st.integers(8, 200), d=st.integers(4, 96),
                      D=st.integers(1, 160))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_ragged_padding_exact(self, n, d, D):
        """Padded rows MUST be masked in-kernel: cos(0 + c) != 0, so a zero
        X row still yields a nonzero feature row. This sweep would corrupt
        G on any n not divisible by block_n if the mask were missing."""
        X, b, W, c = _mk_rff(n, d, D, jnp.float32, seed=17)
        G, h = ops.rff_gram(X, b, W, c, block_d=32, block_n=32)
        Gr, hr = ref.rff_gram_ref(X, b, W, c)
        _assert_close(G, h, Gr, hr)

    def test_row_mask_poison(self):
        """Explicit mask check: ragged n one short of a full block — the
        padded row's would-be contribution cos(c)^T cos(c) is O(D), far
        above tolerance, so passing proves the mask fires."""
        n, d, D = 31, 32, 32
        X, b, W, c = _mk_rff(n, d, D, jnp.float32, seed=19)
        G, _ = ops.rff_gram(X, b, W, c, block_d=32, block_n=32)
        Gr, _ = ref.rff_gram_ref(X, b, W, c)
        err = float(np.abs(np.asarray(G) - np.asarray(Gr)).max())
        assert err < 1e-3, err

    def test_scale_uses_true_feature_count(self):
        """D=12 pads to 128 lanes; the sqrt(2/D) scale must still use 12."""
        X, b, W, c = _mk_rff(64, 32, 12, jnp.float32, seed=23)
        G, _ = ops.rff_gram(X, b, W, c)
        Gr, _ = ref.rff_gram_ref(X, b, W, c)
        # a wrong scale (sqrt(2/128) vs sqrt(2/12)) would be off by ~10.7x
        ratio = float(np.trace(np.asarray(G)) / np.trace(np.asarray(Gr)))
        assert abs(ratio - 1.0) < 1e-3, ratio

    def test_matches_core_rff_path(self):
        """Same statistics as core.rff.rff_stats through RFFMap (XLA path)."""
        from repro import core
        X, b, _, _ = _mk_rff(200, 24, 64, jnp.float32, seed=29)
        feat = core.make_rff(jax.random.PRNGKey(31), 24, 64, lengthscale=1.5)
        G, h = ops.rff_gram(X, b, feat.W, feat.c)
        s = core.rff_stats(X, b, feat)
        np.testing.assert_allclose(np.asarray(G), np.asarray(s.gram),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(s.moment),
                                   rtol=1e-3, atol=1e-4)


class TestFeatureBlockClamping:
    def test_vmem_budget_halves_block_n(self):
        # The G output and the feature scratches are (block_m, block_m) and
        # (block_n, block_m) tiles, so VMEM no longer grows with m: at
        # m = 4096 block_n keeps its full size within the 4 MB budget.
        bd, bn, bm = ops._feature_blocks(4096, 256, 4096, 128, 512)
        assert 2 * bn * bm * 4 <= 4 * 1024 * 1024
        assert bm < 4096 and 4096 % bm == 0
        assert bn == 512 and bd == 128
        bd, bn, bm = ops._feature_blocks(4096, 256, 4096, 128, 4096)
        assert 2 * bn * bm * 4 <= 4 * 1024 * 1024
        assert bn % 8 == 0 and bn >= 8

    def test_small_shapes_clamp_to_pow2(self):
        bd, bn, bm = ops._feature_blocks(100, 48, 128, 128, 512)
        assert bd == 128 and bn == 128 and bm == 128
        # the G tile halves from block_m until it divides the padded m
        assert ops._feature_blocks(100, 48, 384, 128, 512)[2] == 384
        assert ops._feature_blocks(100, 48, 640, 128, 512)[2] == 128
