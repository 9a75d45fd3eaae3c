"""The float64 reference against numpy.linalg.solve and the published recipe."""
import numpy as np
import pytest

from bench import reference


def test_ridge64_solve_matches_numpy():
    rng = np.random.default_rng(0)
    A, b = rng.normal(size=(300, 40)), rng.normal(size=300)
    r = reference.Ridge64(40)
    r.add(A[:100], b[:100])
    r.add(A[100:], b[100:])
    for sigma in (0.01, 1.0, 100.0):
        w = np.linalg.solve(A.T @ A + sigma * np.eye(40), A.T @ b)
        assert np.allclose(r.solve(sigma), w, rtol=1e-12, atol=1e-12)
        assert r.residual(w, sigma) < 1e-12
    assert r.n == 300 and r.yty == pytest.approx(b @ b)


def test_ridge64_add_and_remove_round_trip():
    rng = np.random.default_rng(1)
    A, b = rng.normal(size=(50, 8)), rng.normal(size=50)
    r = reference.Ridge64(8)
    r.add(A, b)
    s = r.copy()
    s.add(A[:10], b[:10], sign=-1)
    assert s.n == 40 and r.n == 50
    assert np.allclose(s.G, A[10:].T @ A[10:])


def test_rff_recipe_matches_the_client_library():
    """The reference regenerates the map itself; it must be the same map."""
    from repro.core.features import FeatureMap

    fm = FeatureMap("rff", seed=123, d_orig=16, m=32, lengthscale=2.0)
    W, c = reference.rff_arrays(123, 16, 32, 2.0)
    Wp, cp = fm.materialize()
    assert np.array_equal(W, np.asarray(Wp, np.float64))
    assert np.array_equal(c, np.asarray(cp, np.float64))
    X = np.random.default_rng(2).normal(size=(5, 16))
    feats = reference.rff_features64(X, W, c)
    assert feats.shape == (5, 32)
    assert np.allclose(feats, np.sqrt(2 / 32) * np.cos(X @ W + c))


@pytest.mark.parametrize("cg", [False, True], ids=["factor", "gradients"])
def test_ridge64_solve_many_matches_solve(cg, monkeypatch):
    rng = np.random.default_rng(3)
    A, b = rng.normal(size=(400, 64)), rng.normal(size=400)
    r = reference.Ridge64(64)
    r.add(A, b)
    if cg:
        monkeypatch.setattr(reference.Ridge64, "CG_MIN_DIM", 1)
        monkeypatch.setattr(r, "solve", None)   # every answer by gradients
    sigmas = [1.0, 0.01, 100.0, 0.01]
    out = r.solve_many(sigmas)
    assert sorted(out) == [0.01, 1.0, 100.0]
    for sigma, w in out.items():
        want = np.linalg.solve(A.T @ A + sigma * np.eye(64), A.T @ b)
        assert np.allclose(w, want, rtol=1e-11, atol=1e-12)
        assert r.residual(w, sigma) < 1e-12


def test_ridge64_solve_many_falls_back_to_the_factor(monkeypatch):
    rng = np.random.default_rng(4)
    A, b = rng.normal(size=(20, 64)), rng.normal(size=20)   # rank 20 of 64
    r = reference.Ridge64(64)
    r.add(A, b)
    monkeypatch.setattr(reference.Ridge64, "CG_MIN_DIM", 1)
    monkeypatch.setattr(reference.Ridge64, "CG_MAX_ITER", 2)
    out = r.solve_many([1e-6])
    assert np.allclose(out[1e-6], r.solve(1e-6), rtol=1e-10, atol=1e-12)
