"""Pallas kernel sweeps: shapes x dtypes, allclose vs the ref.py oracles.

Kernels execute in interpret mode on CPU (the kernel body itself runs) —
the BlockSpec tiling, grid accumulation, and masking logic are what's under
test; Mosaic compilation happens only on a real TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro.kernels import ops, ref


class TestGramKernel:
    @pytest.mark.parametrize("n,d", [(256, 128), (512, 256), (1000, 100),
                                     (64, 16), (128, 384)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, n, d, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(n + d))
        A = jax.random.normal(k1, (n, d), dtype)
        b = jax.random.normal(k2, (n,), dtype)
        G, h = ops.gram_moment(A, b)
        Gr, hr = ref.gram_moment_ref(A, b)
        tol = 1e-3 if dtype == jnp.float32 else 4.0 * np.sqrt(n) / 10
        np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                                   rtol=1e-2, atol=tol)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   rtol=1e-2, atol=tol)

    @hypothesis.given(n=st.integers(8, 300), d=st.integers(4, 96))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_ragged_padding_exact(self, n, d):
        """Zero-padding to tile multiples must not change the statistics."""
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))
        G, h = ops.gram_moment(A, b, block_d=32, block_n=32)
        Gr, hr = ref.gram_moment_ref(A, b)
        np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   rtol=1e-3, atol=1e-3)

    def test_gram_symmetry_and_psd(self):
        A = jax.random.normal(jax.random.PRNGKey(2), (512, 128))
        G, _ = ops.gram_moment(A, jnp.zeros((512,)))
        g = np.asarray(G)
        np.testing.assert_allclose(g, g.T, atol=1e-3)
        assert np.linalg.eigvalsh(g).min() > -1e-2

    def test_core_integration(self):
        """core.compute_stats(use_pallas=True) routes through the kernel."""
        from repro import core
        A = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
        b = jax.random.normal(jax.random.PRNGKey(4), (256,))
        s_k = core.compute_stats(A, b, use_pallas=True)
        s_x = core.compute_stats(A, b)
        np.testing.assert_allclose(np.asarray(s_k.gram), np.asarray(s_x.gram),
                                   rtol=1e-3, atol=1e-3)


class TestSWAFlashKernel:
    @pytest.mark.parametrize("S,hd,window,causal", [
        (256, 64, 64, True), (256, 128, None, True), (128, 64, 32, True),
        (256, 64, None, False), (192, 64, 48, True)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, S, hd, window, causal, dtype):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(S + hd), 3)
        B, H = 2, 2
        q = jax.random.normal(kq, (B, S, H, hd), dtype)
        k = jax.random.normal(kk, (B, S, H, hd), dtype)
        v = jax.random.normal(kv, (B, S, H, hd), dtype)
        o = ops.swa_attention(q, k, v, window=window, causal=causal,
                              block_q=64, block_k=64)
        o_ref = ref.swa_attention_ref(q, k, v, window=window, causal=causal)
        tol = 3e-5 if dtype == jnp.float32 else 4e-2
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32), atol=tol)

    def test_window_blocks_are_skipped(self):
        """Out-of-window KV must have zero influence (true sparsity)."""
        kq = jax.random.PRNGKey(0)
        B, S, H, hd, W = 1, 256, 1, 64, 64
        q = jax.random.normal(kq, (B, S, H, hd))
        k = jax.random.normal(jax.random.fold_in(kq, 1), (B, S, H, hd))
        v = jax.random.normal(jax.random.fold_in(kq, 2), (B, S, H, hd))
        o1 = ops.swa_attention(q, k, v, window=W, block_q=64, block_k=64)
        # poison keys/values far outside every query's window
        k2 = k.at[:, :64].set(1e4)
        v2 = v.at[:, :64].set(1e4)
        o2 = ops.swa_attention(q, k2, v2, window=W, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(o1[:, 192:]),
                                   np.asarray(o2[:, 192:]), atol=1e-5)

    def test_matches_model_attention(self):
        """Kernel == the model's XLA chunked attention (same math)."""
        from repro import configs
        from repro.models import attention, model
        cfg = configs.get_reduced("mixtral-8x22b")
        params = attention.init_attention(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
        out_xla = attention.attention_fwd(params, x, cfg, kind="swa",
                                          chunk_size=16)
        # same computation via the kernel (group KV first)
        positions = jnp.arange(64, dtype=jnp.int32)[None].repeat(2, 0)
        q, k, v = attention._project_qkv(params, x, cfg, positions)
        group = cfg.num_heads // cfg.num_kv_heads
        kg = jnp.repeat(k, group, axis=2)
        vg = jnp.repeat(v, group, axis=2)
        o = ops.swa_attention(q, kg, vg, window=cfg.window, block_q=32,
                              block_k=32)
        out_kernel = o.reshape(2, 64, cfg.q_dim) @ params["wo"]
        np.testing.assert_allclose(np.asarray(out_kernel, np.float32),
                                   np.asarray(out_xla, np.float32), atol=2e-3)
