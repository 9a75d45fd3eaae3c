"""DP path through the production engine/pool (Alg 2 + Remark 4).

test_privacy.py pins the DP *functions* (noise scale, composition, PSD
repair) at the pure-function layer; these tests pin the *plumbing*: noisy
payloads that travel the production path — ``PackedStats`` wire encoding,
``FusionEngine``/``EnginePool`` ingestion — must reproduce the reference
noisy fuse bit-for-bit (pack/unpack is exact and fusion is the same
float-addition sequence), and the Remark-4 near-singular guard must fire
where it matters: on the server, after aggregation, behind the engine API.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core, data, fed
from repro.core import fusion, privacy
from repro.core.sufficient_stats import SuffStats, distributed_stats
from repro.fed.protocol import PackedStats
from repro.launch import mesh as mesh_lib
from repro.server import EnginePool, FusionEngine

D = 10
SIGMA = 0.3
EPS, DELTA = 1.0, 1e-5


def _client_rows(k, n=30):
    k1, k2 = jax.random.split(jax.random.PRNGKey(k))
    return (jax.random.normal(k1, (n, D)), jax.random.normal(k2, (n,)))


def _noisy_client_stats(eps=EPS):
    """Alg 2 per-client pipeline: clip -> stats -> Gaussian mechanism."""
    out = []
    for k in range(3):
        A, b = _client_rows(k)
        A, b = privacy.clip_rows(A, b)
        s = privacy.privatize_stats(jax.random.PRNGKey(500 + k),
                                    core.compute_stats(A, b), eps, DELTA)
        out.append(s)
    return out


def _sequential_fuse(stats_list):
    """The engine's exact float-addition order: zeros + s_0 + s_1 + ..."""
    acc = core.zeros_like_stats(D, stats_list[0].gram.dtype)
    for s in stats_list:
        acc = acc + s
    return acc


class TestNoisyPayloadsBitExact:
    def test_per_client_dp_payloads_through_pool(self):
        noisy = _noisy_client_stats()
        payloads = {k: PackedStats.pack(s) for k, s in enumerate(noisy)}
        pool = EnginePool()
        eng = pool.create_tenant("dp", payloads=payloads, placement="dense")
        ref = _sequential_fuse(noisy)
        # Wire roundtrip + engine fusion reproduce the reference noisy fuse
        # bit-for-bit: pack/unpack moves entries untouched and the engine
        # adds in the same order over the same zeros initializer.
        np.testing.assert_array_equal(np.asarray(eng.stats.gram),
                                      np.asarray(ref.gram))
        np.testing.assert_array_equal(np.asarray(eng.stats.moment),
                                      np.asarray(ref.moment))
        np.testing.assert_allclose(np.asarray(pool.solve("dp", SIGMA)),
                                   np.asarray(fusion.solve_ridge(ref, SIGMA)),
                                   rtol=1e-5, atol=1e-5)

    def test_central_dp_stats_through_pool(self):
        clean = [core.compute_stats(*_client_rows(k)) for k in range(3)]
        fused = _sequential_fuse(clean)
        noisy = privacy.central_dp_stats(jax.random.PRNGKey(9), fused,
                                         EPS, DELTA, n_clients=3)
        pool = EnginePool()
        eng = pool.create_tenant("central", stats=noisy, placement="dense")
        np.testing.assert_array_equal(np.asarray(eng.stats.gram),
                                      np.asarray(noisy.gram))
        np.testing.assert_array_equal(np.asarray(eng.stats.moment),
                                      np.asarray(noisy.moment))
        np.testing.assert_allclose(
            np.asarray(pool.solve("central", SIGMA)),
            np.asarray(fusion.solve_ridge(noisy, SIGMA)),
            rtol=1e-5, atol=1e-5)

    def test_make_dp_noise_fn_distributed_into_engine(self):
        """Alg 2 noise-before-psum on-mesh, then served through an engine."""
        key = jax.random.PRNGKey(77)
        A, b = _client_rows(42, n=32)
        mesh = mesh_lib.make_device_mesh(1)
        noise_fn = privacy.make_dp_noise_fn(key, EPS, DELTA, D)
        noisy = distributed_stats(A, b, mesh, client_axes=("data",),
                                  noise_fn=noise_fn)
        # Reference: the same hook applied host-side to the one shard.
        s = core.compute_stats(A, b)
        g_ref, h_ref = noise_fn(jnp.asarray(0, jnp.int32), s.gram, s.moment)
        np.testing.assert_allclose(np.asarray(noisy.gram), np.asarray(g_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(noisy.moment),
                                   np.asarray(h_ref), rtol=1e-6, atol=1e-6)
        eng = FusionEngine.from_stats(noisy)
        np.testing.assert_allclose(
            np.asarray(eng.solve(SIGMA)),
            np.asarray(fusion.solve_ridge(noisy, SIGMA)),
            rtol=1e-5, atol=1e-5)


class TestRemark4Guard:
    """Heavy noise makes (G~ + sigma I) indefinite (Remark 4); the repair
    must fire through the engine/pool path, not just the pure function."""

    EPS_TINY = 0.05   # enough noise to push eigenvalues well below zero

    def test_guard_fires_on_indefinite_admission(self):
        noisy = _noisy_client_stats(eps=self.EPS_TINY)
        ref = _sequential_fuse(noisy)
        min_eig = float(jnp.linalg.eigvalsh(ref.gram)[0])
        assert min_eig < 0, "test setup: noise too weak to trigger Remark 4"

        pool = EnginePool()
        eng = pool.create_tenant(
            "noisy", payloads={k: PackedStats.pack(s)
                               for k, s in enumerate(noisy)},
            placement="dense", psd_guard=True)
        t = pool.tenant("noisy")
        assert t.psd_repairs == 1
        assert t.guard_min_eig == pytest.approx(min_eig)
        # The repaired state is exactly privacy.psd_repair of the noisy fuse
        # (same function, same input bits), and it is PSD.
        repaired_ref = privacy.psd_repair(ref)
        np.testing.assert_array_equal(np.asarray(eng.stats.gram),
                                      np.asarray(repaired_ref.gram))
        evals = np.linalg.eigvalsh(np.asarray(eng.stats.gram))
        assert evals.min() >= -1e-4
        assert np.isfinite(np.asarray(pool.solve("noisy", SIGMA))).all()

    def test_guard_quiet_on_clean_statistics(self):
        clean = [core.compute_stats(*_client_rows(k)) for k in range(3)]
        pool = EnginePool()
        eng = pool.create_tenant(
            "clean", payloads={k: PackedStats.pack(s)
                               for k, s in enumerate(clean)},
            placement="dense", psd_guard=True)
        t = pool.tenant("clean")
        assert t.psd_repairs == 0
        assert t.guard_min_eig is not None and t.guard_min_eig >= 0
        np.testing.assert_array_equal(
            np.asarray(eng.stats.gram),
            np.asarray(_sequential_fuse(clean).gram))

    def test_run_one_shot_psd_repair_matches_reference(self):
        """The fed.run_one_shot(psd_repair=True) path IS engine.apply —
        its output must equal psd_repair applied to the unrepaired run's
        fused stats (same dp_key -> identical noise draws)."""
        ds = data.generate(jax.random.PRNGKey(3), num_clients=4,
                           samples_per_client=40, dim=D)
        dp_key = jax.random.PRNGKey(11)
        raw = fed.run_one_shot(ds, SIGMA, dp=(self.EPS_TINY, DELTA),
                               dp_key=dp_key)
        noisy = raw.extras["fused_stats"]
        assert float(jnp.linalg.eigvalsh(noisy.gram)[0]) < 0
        rep = fed.run_one_shot(ds, SIGMA, dp=(self.EPS_TINY, DELTA),
                               dp_key=dp_key, psd_repair=True)
        np.testing.assert_array_equal(
            np.asarray(rep.extras["fused_stats"].gram),
            np.asarray(privacy.psd_repair(noisy).gram))
        assert np.isfinite(np.asarray(rep.weights)).all()
