"""Algorithm 2 / Theorems 6-7: Gaussian mechanism, composition, PSD repair."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro import core
from repro.core import privacy


class TestGaussianMechanism:
    def test_tau_formula(self):
        # Alg 2 line 1: tau = Delta sqrt(2 ln(1.25/delta)) / eps
        tau = privacy.gaussian_tau(2.0, 1e-5)
        assert abs(tau - math.sqrt(2 * math.log(1.25e5)) / 2.0) < 1e-12

    @hypothesis.given(eps=st.floats(0.05, 20.0), delta=st.floats(1e-8, 0.5,
                                                                 exclude_max=True))
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_tau_monotonicity(self, eps, delta):
        """More privacy (smaller eps/delta) always means more noise."""
        tau = privacy.gaussian_tau(eps, delta)
        assert tau > 0
        assert privacy.gaussian_tau(eps / 2, delta) > tau
        assert privacy.gaussian_tau(eps, delta / 10) > tau

    def test_invalid_params(self):
        with pytest.raises(ValueError):
            privacy.gaussian_tau(0.0, 1e-5)
        with pytest.raises(ValueError):
            privacy.gaussian_tau(1.0, 1.5)

    def test_clip_enforces_sensitivity(self):
        A = 100.0 * jax.random.normal(jax.random.PRNGKey(0), (50, 8))
        b = 100.0 * jax.random.normal(jax.random.PRNGKey(1), (50,))
        Ac, bc = privacy.clip_rows(A, b)
        assert float(jnp.linalg.norm(Ac, axis=1).max()) <= 1.0 + 1e-5
        assert float(jnp.abs(bc).max()) <= 1.0

    def test_privatize_symmetric_and_unbiased(self):
        A = jax.random.normal(jax.random.PRNGKey(0), (100, 6))
        b = jax.random.normal(jax.random.PRNGKey(1), (100,))
        s = core.compute_stats(A, b)
        outs = [privacy.privatize_stats(jax.random.PRNGKey(i), s, 1.0, 1e-5)
                for i in range(64)]
        for o in outs[:4]:
            np.testing.assert_allclose(o.gram, np.asarray(o.gram).T, atol=1e-4)
        mean_g = np.mean([np.asarray(o.gram) for o in outs], axis=0)
        tau = privacy.gaussian_tau(1.0, 1e-5)
        assert np.abs(mean_g - np.asarray(s.gram)).max() < 4 * tau / math.sqrt(64) * 3

    def test_noise_scale_matches_tau(self):
        d = 50
        s = core.SuffStats(jnp.zeros((d, d)), jnp.zeros((d,)),
                           jnp.asarray(0, jnp.int32))
        o = privacy.privatize_stats(jax.random.PRNGKey(0), s, 0.5, 1e-5)
        tau = privacy.gaussian_tau(0.5, 1e-5)
        emp = float(np.asarray(o.gram).std())
        assert 0.8 * tau < emp < 1.2 * tau  # symmetrization preserves variance


class TestComposition:
    def test_theorem_7_formula(self):
        eps0, delta0, R = 0.1, 1e-5, 100
        total = privacy.advanced_composition(eps0, delta0, R)
        manual = math.sqrt(2 * R * math.log(1 / delta0)) * eps0 + \
            R * eps0 * (math.e ** eps0 - 1)
        assert abs(total - manual) < 1e-9

    def test_composition_grows_sqrt(self):
        # O(sqrt(R)) growth: eps(4R)/eps(R) ~ 2 in the sqrt-dominated regime
        e1 = privacy.advanced_composition(0.01, 1e-6, 100)
        e4 = privacy.advanced_composition(0.01, 1e-6, 400)
        assert 1.8 < e4 / e1 < 2.3

    def test_one_shot_has_no_composition(self):
        """Same total budget: per-round noise for R rounds >> one-shot noise."""
        eps = 2.0
        tau_oneshot = privacy.gaussian_tau(eps, 1e-5)
        tau_per_round = privacy.gaussian_tau(
            privacy.per_round_budget(eps, 100), 1e-5)
        assert tau_per_round > 5 * tau_oneshot


class TestPSDRepair:
    def test_projects_to_psd(self):
        A = jax.random.normal(jax.random.PRNGKey(0), (40, 12))
        s = core.compute_stats(A, jnp.zeros((40,)))
        noisy = privacy.privatize_stats(jax.random.PRNGKey(1), s, 0.05, 1e-5)
        fixed = privacy.psd_repair(noisy)
        evals = np.linalg.eigvalsh(np.asarray(fixed.gram))
        assert evals.min() >= -1e-4

    def test_noop_on_psd_input(self):
        A = jax.random.normal(jax.random.PRNGKey(0), (40, 12))
        s = core.compute_stats(A, jnp.zeros((40,)))
        fixed = privacy.psd_repair(s)
        np.testing.assert_allclose(fixed.gram, s.gram, rtol=1e-3, atol=1e-3)
