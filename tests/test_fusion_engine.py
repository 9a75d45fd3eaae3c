"""Engine-vs-reference equivalence: server.FusionEngine pinned to core.fusion.

Every engine method must agree with the corresponding pure-function
reference (same algebra, different factorization lifecycle), including after
state mutations that exercise the incremental Cholesky up/downdate path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro import core
from repro.core import fusion
from repro.server import FusionEngine, chol_rank1, chol_update, psd_update_vectors

RTOL, ATOL = 1e-5, 1e-5


def _problem(seed=0, n=400, d=24, clients=4):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    A = jax.random.normal(k1, (n, d))
    b = jax.random.normal(k2, (n,))
    per = n // clients
    parts = [(A[i * per:(i + 1) * per], b[i * per:(i + 1) * per])
             for i in range(clients)]
    stats = {i: core.compute_stats(a, bb) for i, (a, bb) in enumerate(parts)}
    return A, b, parts, stats


class TestCholeskyKernels:
    def test_rank1_update_downdate_roundtrip(self):
        A, _, _, _ = _problem()
        G = np.asarray(A.T @ A + 0.5 * jnp.eye(24))
        L = jnp.linalg.cholesky(jnp.asarray(G))
        x = jax.random.normal(jax.random.PRNGKey(3), (24,))
        Lu = chol_rank1(L, x, sign=1.0)
        np.testing.assert_allclose(Lu @ Lu.T, G + np.outer(x, x),
                                   rtol=1e-4, atol=1e-4)
        Ld = chol_rank1(Lu, x, sign=-1.0)
        np.testing.assert_allclose(Ld @ Ld.T, G, rtol=1e-4, atol=1e-4)

    def test_rank_r_matches_refactorization(self):
        A, _, _, _ = _problem()
        G = A.T @ A + 0.5 * jnp.eye(24)
        U = jax.random.normal(jax.random.PRNGKey(4), (6, 24))
        L = chol_update(jnp.linalg.cholesky(G), U, sign=1.0)
        L_ref = jnp.linalg.cholesky(G + U.T @ U)
        np.testing.assert_allclose(np.asarray(L), np.asarray(L_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_psd_update_vectors_low_rank(self):
        Ak = jax.random.normal(jax.random.PRNGKey(5), (7, 24))
        U = psd_update_vectors(Ak.T @ Ak)
        assert U.shape[0] == 7  # numerical rank of a 7-row Gram
        np.testing.assert_allclose(np.asarray(U.T @ U), np.asarray(Ak.T @ Ak),
                                   rtol=1e-3, atol=1e-3)


class TestSolveEquivalence:
    def test_solve_matches_solve_ridge(self):
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats)
        for sigma in (1e-3, 0.1, 10.0):
            w_ref = fusion.solve_ridge(core.fuse_stats(list(stats.values())),
                                       sigma)
            np.testing.assert_allclose(eng.solve(sigma), w_ref,
                                       rtol=RTOL, atol=ATOL)
            # second call hits the cached factor — must be identical
            np.testing.assert_array_equal(eng.solve(sigma), eng.solve(sigma))

    @pytest.mark.parametrize("method", ["chol", "spectral"])
    def test_solve_batch_matches_per_sigma_loop(self, method):
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats)
        sigmas = [float(s) for s in jnp.logspace(-3, 1, 9)]
        ws = eng.solve_batch(sigmas, method=method)
        assert ws.shape == (9, eng.dim)
        tol = dict(rtol=RTOL, atol=ATOL) if method == "chol" else \
            dict(rtol=1e-4, atol=1e-4)
        for i, sigma in enumerate(sigmas):
            np.testing.assert_allclose(ws[i], fusion.solve_ridge(eng.stats,
                                                                 sigma), **tol)

    def test_predict_batch_shape_and_value(self):
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats)
        X = jax.random.normal(jax.random.PRNGKey(9), (5, eng.dim))
        P = eng.predict_batch(X, [0.1, 1.0])
        assert P.shape == (2, 5)
        np.testing.assert_allclose(P[1], X @ eng.solve(1.0),
                                   rtol=RTOL, atol=ATOL)


class TestDropoutEquivalence:
    def test_ingest_drop_matches_dropout_fusion(self):
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats)
        eng.drop(1)
        eng.drop(3)
        w_ref = fusion.dropout_fusion(list(stats.values()),
                                      [True, False, True, False], 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=RTOL, atol=ATOL)
        assert eng.count == int(stats[0].count + stats[2].count)

    def test_incremental_downdate_matches_refactorization(self):
        """drop() with a warm factor must equal a from-scratch solve."""
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats, max_update_rank=100)
        eng.solve(0.1)  # warm the factor so drop exercises the downdate
        eng.drop(2)
        assert eng.incremental_updates > 0
        w_ref = fusion.dropout_fusion(list(stats.values()),
                                      [True, True, False, True], 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=1e-4, atol=1e-4)

    def test_restore_roundtrip(self):
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats, max_update_rank=100)
        w_before = np.asarray(eng.solve(0.1))
        eng.drop(0)
        eng.restore(0)
        np.testing.assert_allclose(eng.solve(0.1), w_before,
                                   rtol=1e-4, atol=1e-4)
        assert set(eng.client_ids) == {0, 1, 2, 3}
        assert eng.dropped_ids == ()

    def test_staleness_threshold_falls_back(self):
        """Past max_update_rank the factor is evicted, not incrementally
        updated — and the refactorized solve is still exact."""
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats, max_update_rank=2)
        eng.solve(0.1)
        eng.drop(1)  # client rank 100 >> 2 -> eviction path
        assert eng.incremental_updates == 0
        w_ref = fusion.dropout_fusion(list(stats.values()),
                                      [True, False, True, True], 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=RTOL, atol=ATOL)

    def test_drop_unknown_raises(self):
        _, _, _, stats = _problem()
        eng = FusionEngine.from_clients(stats)
        with pytest.raises(KeyError):
            eng.drop("nope")


class TestLocoEquivalence:
    def test_loco_cv_matches_reference(self):
        _, _, parts, stats = _problem(n=360, d=12, clients=3)
        sigmas = [1e-3, 1e-1, 1e1]
        best_e, losses_e = FusionEngine.from_clients(stats).loco_cv(parts,
                                                                    sigmas)
        best_r, losses_r = fusion.loco_cv(list(stats.values()), parts, sigmas)
        assert best_e == best_r
        np.testing.assert_allclose(losses_e, losses_r, rtol=1e-4, atol=1e-5)

    def test_loco_weights_shape(self):
        _, _, _, stats = _problem()
        ids, W = FusionEngine.from_clients(stats).loco_weights([0.1, 1.0])
        assert ids == [0, 1, 2, 3] and W.shape == (4, 2, 24)


class TestStreaming:
    def test_chunked_ingest_matches_one_shot(self):
        A, b, _, _ = _problem()
        eng = FusionEngine(24)
        for lo in range(0, 400, 80):
            eng.ingest_rows(A[lo:lo + 80], b[lo:lo + 80])
        w_ref = fusion.solve_ridge(core.compute_stats(A, b), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=RTOL, atol=ATOL)
        assert eng.count == 400

    def test_streaming_updates_warm_factor_incrementally(self):
        A, b, _, _ = _problem()
        eng = FusionEngine(24, max_update_rank=200)
        eng.ingest_rows(A[:300], b[:300])
        eng.solve(0.1)  # warm
        eng.ingest_rows(A[300:], b[300:])  # 100 rows <= threshold: update
        assert eng.incremental_updates > 0
        w_ref = fusion.solve_ridge(core.compute_stats(A, b), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=1e-4, atol=1e-4)

    @hypothesis.given(seed=st.integers(0, 2**16),
                      cuts=st.lists(st.integers(1, 399), min_size=0,
                                    max_size=5, unique=True))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_any_chunking_matches_one_shot(self, seed, cuts):
        """§VI-C: ingesting rows in ANY chunking equals the one-shot solve."""
        A, b, _, _ = _problem(seed % 5)
        bounds = [0] + sorted(cuts) + [400]
        eng = FusionEngine(24)
        for lo, hi in zip(bounds, bounds[1:]):
            if hi > lo:
                eng.ingest_rows(A[lo:hi], b[lo:hi])
        w_ref = fusion.solve_ridge(core.compute_stats(A, b), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=1e-4, atol=1e-4)


class TestProtocolAdapters:
    def test_run_one_shot_exposes_engine(self):
        from repro import data, fed

        ds = data.generate(jax.random.PRNGKey(0), num_clients=4,
                           samples_per_client=50, dim=10)
        res = fed.run_one_shot(ds, 0.1)
        eng = res.extras["engine"]
        assert isinstance(eng, FusionEngine)
        np.testing.assert_allclose(eng.solve(0.1), res.weights,
                                   rtol=RTOL, atol=ATOL)
        # serving continues off the returned engine: drop a client post-hoc
        eng.drop(0)
        A = jnp.concatenate([a for a, _ in ds.clients[1:]])
        b = jnp.concatenate([b for _, b in ds.clients[1:]])
        w_ref = fusion.solve_ridge(core.compute_stats(A, b), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=1e-4, atol=1e-4)

    def test_run_one_shot_reuses_client_stats(self):
        from repro import data, fed

        ds = data.generate(jax.random.PRNGKey(1), num_clients=3,
                           samples_per_client=40, dim=8)
        stats = [core.compute_stats(a, b) for a, b in ds.clients]
        res = fed.run_one_shot(ds, 0.05, client_stats=stats)
        ref = fed.run_one_shot(ds, 0.05)
        np.testing.assert_allclose(res.weights, ref.weights,
                                   rtol=RTOL, atol=ATOL)
