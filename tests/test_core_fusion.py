"""Core protocol tests: Theorems 1/2/3/5/8, Prop 5, equilibrium machinery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro import core


def _problem(seed=0, n=240, d=12):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    A = jax.random.normal(k1, (n, d))
    b = jax.random.normal(k2, (n,))
    return A, b


class TestSufficientStats:
    def test_definition(self):
        A, b = _problem()
        s = core.compute_stats(A, b)
        np.testing.assert_allclose(s.gram, np.asarray(A).T @ np.asarray(A),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s.moment, np.asarray(A).T @ np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        assert int(s.count) == A.shape[0]

    def test_streaming_matches(self):
        A, b = _problem(n=250)
        s1 = core.compute_stats(A, b)
        s2 = core.compute_stats_streaming(A, b, chunk=64)
        np.testing.assert_allclose(s1.gram, s2.gram, rtol=1e-4, atol=1e-4)
        assert int(s2.count) == 250

    @hypothesis.given(
        seed=st.integers(0, 2**16),
        cuts=st.lists(st.integers(1, 239), min_size=0, max_size=6, unique=True))
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_additivity_any_partition(self, seed, cuts):
        """Theorem 1: G, h decompose additively over ANY row partition."""
        A, b = _problem(seed % 7)
        bounds = [0] + sorted(cuts) + [A.shape[0]]
        parts = [core.compute_stats(A[lo:hi], b[lo:hi])
                 for lo, hi in zip(bounds, bounds[1:]) if hi > lo]
        fused = core.fuse_stats(parts)
        ref = core.compute_stats(A, b)
        np.testing.assert_allclose(fused.gram, ref.gram, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(fused.moment, ref.moment, rtol=1e-4, atol=1e-4)


class TestExactRecovery:
    @hypothesis.given(
        seed=st.integers(0, 2**16),
        num_clients=st.integers(1, 8),
        sigma=st.floats(1e-4, 10.0))
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_theorem_2(self, seed, num_clients, sigma):
        """w_fed == w_central for any K, partition, sigma (Thm 2/5)."""
        A, b = _problem(seed % 11)
        n = A.shape[0]
        per = n // num_clients
        parts = [core.compute_stats(A[i * per:(i + 1) * per],
                                    b[i * per:(i + 1) * per])
                 for i in range(num_clients - 1)]
        parts.append(core.compute_stats(A[(num_clients - 1) * per:],
                                        b[(num_clients - 1) * per:]))
        w_fed = core.one_shot_fusion(parts, sigma)
        w_cen = core.solve_ridge(core.compute_stats(A, b), sigma)
        np.testing.assert_allclose(w_fed, w_cen, rtol=2e-3, atol=1e-5)

    def test_equilibrium_certificate(self):
        """The solution is the unique zero of the stationarity residual."""
        A, b = _problem()
        s = core.compute_stats(A, b)
        w = core.solve_ridge(s, 0.1)
        r = core.equilibrium_residual(s, 0.1, w)
        assert float(jnp.linalg.norm(r)) < 1e-3
        bound = core.residual_bound(s, 0.1, w + 0.01)
        true_err = float(jnp.linalg.norm(0.01 * jnp.ones_like(w)))
        assert float(bound) >= true_err * 0.99

    def test_cg_matches_cholesky(self):
        A, b = _problem()
        s = core.compute_stats(A, b)
        w_chol = core.solve_ridge(s, 0.05)
        w_cg = core.solve_cg(s, 0.05, iters=200)
        np.testing.assert_allclose(w_cg, w_chol, rtol=1e-3, atol=1e-5)


class TestConditioning:
    def test_theorem_3_spd(self):
        A, b = _problem()
        s = core.compute_stats(A, b)
        evals = np.linalg.eigvalsh(np.asarray(s.gram) + 0.5 * np.eye(s.dim))
        assert evals.min() >= 0.5 - 1e-4

    def test_corollary_1_kappa_bound(self):
        A, b = _problem()
        s = core.compute_stats(A, b)
        for sigma in (0.01, 1.0, 100.0):
            kappa = float(core.condition_number(s, sigma))
            lmax = float(np.linalg.eigvalsh(np.asarray(s.gram)).max())
            assert kappa <= (lmax + sigma) / sigma + 1e-3


class TestDropout:
    def test_theorem_8(self):
        A, b = _problem()
        parts = [core.compute_stats(A[i * 60:(i + 1) * 60], b[i * 60:(i + 1) * 60])
                 for i in range(4)]
        w = core.dropout_fusion(parts, [True, False, True, False], 0.01)
        keep = np.r_[0:60, 120:180]
        w_ref = core.solve_ridge(core.compute_stats(A[keep], b[keep]), 0.01)
        np.testing.assert_allclose(w, w_ref, rtol=1e-3, atol=1e-5)

    def test_no_participants_raises(self):
        A, b = _problem()
        s = [core.compute_stats(A, b)]
        with pytest.raises(ValueError):
            core.dropout_fusion(s, [False], 0.01)


class TestLocoCV:
    def test_prop_5_selects_reasonable_sigma(self):
        A, b = _problem(n=300, d=10)
        parts = [(A[i * 100:(i + 1) * 100], b[i * 100:(i + 1) * 100])
                 for i in range(3)]
        stats = [core.compute_stats(a, bb) for a, bb in parts]
        sigmas = [1e-3, 1e-1, 1e1, 1e3]
        best, losses = core.loco_cv(stats, parts, sigmas)
        assert best in sigmas
        assert losses.shape == (4,)
        # huge sigma must be worse than the chosen one
        assert losses[-1] >= losses[sigmas.index(best)]
