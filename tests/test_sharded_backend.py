"""ShardedBackend equivalence: mesh-sharded solves pinned to core.fusion.

Two layers:

  * in-process tests run on a mesh over every device pytest's platform has
    (usually 1) and cover the backend machinery — padding
    for d not divisible by the block size, CG, the Pallas tile path, engine
    integration (drop/restore/streaming, spectral fallback, cache warming).
  * the 8-device test runs in a child process with
    ``--xla_force_host_platform_device_count=8`` set before jax initializes
    (jax locks the device count at first init) and asserts the real thing:
    solves match the dense reference on a (4, 2) mesh, and the fused Gram /
    its factor NEVER materialize unsharded on the solve path (checked via
    sharding specs).
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import fusion
from repro.fed import comm
from repro.launch import mesh as mesh_lib
from repro.server import FusionEngine, ShardedBackend

RTOL, ATOL = 3e-4, 3e-4


def _problem(seed=0, n=200, d=21):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    A = jax.random.normal(k1, (n, d))
    b = jax.random.normal(k2, (n,))
    return A, b, core.compute_stats(A, b)


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.make_device_mesh()


class TestShardedSolves:
    def test_block_chol_matches_reference_with_padding(self, mesh):
        # d=21 with block_size=8 pads to 24: tiling need not divide d.
        _, _, stats = _problem(d=21)
        be = ShardedBackend(21, mesh, block_size=8)
        assert be.padded % 8 == 0 and be.padded >= 21
        eng = FusionEngine.from_stats(stats, backend=be)
        for sigma in (1e-2, 0.5, 10.0):
            w_ref = fusion.solve_ridge(stats, sigma)
            np.testing.assert_allclose(eng.solve(sigma), w_ref,
                                       rtol=RTOL, atol=ATOL)
            # second call hits the cached sharded factor — identical result
            np.testing.assert_array_equal(eng.solve(sigma), eng.solve(sigma))

    def test_solve_batch_warms_sharded_cache(self, mesh):
        _, _, stats = _problem()
        eng = FusionEngine.from_stats(stats, backend=ShardedBackend(21, mesh))
        sigmas = [0.05, 0.5, 5.0]
        ws = eng.solve_batch(sigmas)
        assert ws.shape == (3, 21)
        assert sorted(eng._factors) == sorted(sigmas)
        for i, s in enumerate(sigmas):
            np.testing.assert_allclose(ws[i], fusion.solve_ridge(stats, s),
                                       rtol=RTOL, atol=ATOL)

    def test_cg_fallback_matches_reference(self, mesh):
        _, _, stats = _problem()
        be = ShardedBackend(21, mesh, method="cg")
        eng = FusionEngine.from_stats(stats, backend=be)
        np.testing.assert_allclose(eng.solve(0.1),
                                   fusion.solve_ridge(stats, 0.1),
                                   rtol=1e-3, atol=1e-3)

    def test_auto_prefers_cg_when_padding_explodes(self, mesh):
        # d far below the tile unit: auto should pick the matrix-free path.
        be = ShardedBackend(3, mesh, block_size=8)
        if be.padded >= 2 * 3:
            assert be._resolve_method() == "cg"

    def test_pallas_tile_path_matches(self, mesh):
        _, _, stats = _problem(d=16)
        be = ShardedBackend(16, mesh, block_size=8, use_pallas=True)
        eng = FusionEngine.from_stats(stats, backend=be)
        np.testing.assert_allclose(eng.solve(0.2),
                                   fusion.solve_ridge(stats, 0.2),
                                   rtol=RTOL, atol=ATOL)

    def test_sigma_zero_rejected(self, mesh):
        _, _, stats = _problem()
        eng = FusionEngine.from_stats(stats, backend=ShardedBackend(21, mesh))
        with pytest.raises(ValueError):
            eng.solve(0.0)


class TestShardedIncrementalUpdate:
    @pytest.mark.parametrize("rank", [6, 16])
    def test_low_rank_mutation_skips_refactorization(self, mesh, rank):
        """The factorization-count probe: rank <= max_update_rank mutations
        ride the distributed blocked up/downdate — NO cold refactorization,
        and the solve still matches a cold reference. Rank 6 buckets to 8
        (two zero pad rows through the shared panel transform)."""
        A, b, stats = _problem(n=200, d=21)
        eng = FusionEngine.from_stats(
            stats, backend=ShardedBackend(21, mesh, block_size=8),
            max_update_rank=40)
        eng.solve(0.1)                       # warm the sharded factor
        cold0 = eng.cold_factorizations
        eA, eb, _ = _problem(seed=5, n=rank)
        eng.ingest_rows(eA, eb)              # rank <= 40 -> incremental
        w = eng.solve(0.1)
        assert eng.cold_factorizations == cold0, "mutation refactorized"
        assert eng.incremental_updates == 1
        ref = fusion.solve_ridge(
            core.compute_stats(jnp.concatenate([A, eA]),
                               jnp.concatenate([b, eb])), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), ref, rtol=RTOL, atol=ATOL)

    def test_incremental_downdate_on_drop(self, mesh):
        A, b, _ = _problem(n=240)
        parts = [(A[i * 60:(i + 1) * 60], b[i * 60:(i + 1) * 60])
                 for i in range(4)]
        stats = {i: core.compute_stats(a, bb)
                 for i, (a, bb) in enumerate(parts)}
        eng = FusionEngine.from_clients(
            stats, backend=ShardedBackend(21, mesh, block_size=8),
            max_update_rank=100)
        eng.solve(0.1)
        cold0 = eng.cold_factorizations
        eng.drop(1)                          # rank(G_1) = 21 <= 100
        w = eng.solve(0.1)
        assert eng.cold_factorizations == cold0
        w_ref = fusion.dropout_fusion(list(stats.values()),
                                      [True, False, True, True], 0.1)
        np.testing.assert_allclose(w, w_ref, rtol=RTOL, atol=ATOL)

    def test_update_ranks_bucket_compiled_programs(self, mesh):
        """Distinct flush ranks within one power-of-two bucket reuse ONE
        compiled shard_map program (zero-row rank padding is exact)."""
        A, b, stats = _problem(n=200, d=21)
        be = ShardedBackend(21, mesh, block_size=8)
        eng = FusionEngine.from_stats(stats, backend=be, max_update_rank=40)
        eng.solve(0.1)
        rows = []
        for i, r in enumerate((5, 6, 8)):           # all bucket to 8
            eA, eb, _ = _problem(seed=20 + i, n=r)
            eng.ingest_rows(eA, eb)
            rows.append((eA, eb))
        update_keys = [k for k in be._jitted
                       if isinstance(k, tuple) and k[0] == "update"]
        assert update_keys == [("update", 8, True)]
        A_all = jnp.concatenate([A] + [a for a, _ in rows])
        b_all = jnp.concatenate([b] + [bb for _, bb in rows])
        ref = fusion.solve_ridge(core.compute_stats(A_all, b_all), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), ref, rtol=RTOL, atol=ATOL)

    def test_high_rank_mutation_still_evicts(self, mesh):
        """Past the staleness budget the engine falls back to evict +
        on-mesh refactorize (exactness over incrementality)."""
        A, b, stats = _problem(n=200, d=21)
        eng = FusionEngine.from_stats(
            stats, backend=ShardedBackend(21, mesh, block_size=8),
            max_update_rank=4)
        eng.solve(0.1)
        cold0 = eng.cold_factorizations
        eA, eb, _ = _problem(seed=6, n=30)
        eng.ingest_rows(eA, eb)              # rank 30 > 4 -> evict
        eng.solve(0.1)
        assert eng.cold_factorizations == cold0 + 1
        assert eng.incremental_updates == 0

    def test_cg_factor_declines_update(self, mesh):
        _, _, stats = _problem()
        be = ShardedBackend(21, mesh, method="cg")
        eng = FusionEngine.from_stats(stats, backend=be, max_update_rank=40)
        eng.solve(0.1)
        eA, eb, _ = _problem(seed=8, n=4)
        eng.ingest_rows(eA, eb)              # CG marker: evicted, re-solved
        assert eng.incremental_updates == 0
        A, b, _ = _problem()
        ref = fusion.solve_ridge(
            core.compute_stats(jnp.concatenate([A, eA]),
                               jnp.concatenate([b, eb])), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), ref, rtol=1e-3, atol=1e-3)


class TestShardedEngineIntegration:
    def test_drop_restore_streaming(self, mesh):
        A, b, _ = _problem(n=240)
        parts = [(A[i * 60:(i + 1) * 60], b[i * 60:(i + 1) * 60])
                 for i in range(4)]
        stats = {i: core.compute_stats(a, bb) for i, (a, bb) in enumerate(parts)}
        eng = FusionEngine.from_clients(stats,
                                        backend=ShardedBackend(21, mesh))
        eng.solve(0.1)  # warm, so drop exercises the evict-and-refactor path
        eng.drop(2)
        w_ref = fusion.dropout_fusion(list(stats.values()),
                                      [True, True, False, True], 0.1)
        np.testing.assert_allclose(eng.solve(0.1), w_ref, rtol=RTOL, atol=ATOL)
        eng.restore(2)
        extra_A, extra_b, _ = _problem(seed=7, n=40)
        eng.ingest_rows(extra_A, extra_b)
        ref = fusion.solve_ridge(
            core.compute_stats(jnp.concatenate([A, extra_A]),
                               jnp.concatenate([b, extra_b])), 0.1)
        np.testing.assert_allclose(eng.solve(0.1), ref, rtol=RTOL, atol=ATOL)
        assert eng.count == 280

    def test_spectral_falls_back_to_chol(self, mesh):
        _, _, stats = _problem()
        eng = FusionEngine.from_stats(stats, backend=ShardedBackend(21, mesh))
        ws = eng.solve_batch([0.1, 1.0], method="spectral")
        np.testing.assert_allclose(ws[0], fusion.solve_ridge(stats, 0.1),
                                   rtol=RTOL, atol=ATOL)
        assert eng.summary()["spectral_cached"] is False

    def test_summary_names_backend(self, mesh):
        _, _, stats = _problem()
        eng = FusionEngine.from_stats(stats, backend=ShardedBackend(21, mesh))
        assert eng.summary()["backend"] == "sharded"
        assert FusionEngine.from_stats(stats).summary()["backend"] == "dense"


class TestShardedComm:
    def test_record_extends_oneshot(self):
        rec = comm.sharded_oneshot_record(16, 4, {"data": 4})
        base = comm.one_shot_comm(16, 4)
        assert rec.upload_floats_per_client == base.upload_floats_per_client
        assert rec.total_bytes == base.total_bytes
        # Gram reduce-scattered ((n-1)/n * d^2), moment+count all-reduced.
        floats = (3 * 16 * 16 + 2 * 3 * 17) // 4
        assert rec.psum_bytes_per_axis["data"] == floats * comm.FLOAT_BYTES
        assert rec.cross_shard_bytes > 0

    def test_size_one_axes_cost_nothing(self):
        rec = comm.sharded_oneshot_record(8, 2, {"data": 1})
        assert rec.cross_shard_bytes == 0

    def test_projected_record_covers_m2_uploads(self):
        rec = comm.sharded_oneshot_record(64, 4, {"data": 4}, projected_m=8)
        assert rec.upload_floats_per_client == 8 * 9 // 2 + 8
        floats = (3 * 8 * 8 + 2 * 3 * 9) // 4
        assert rec.psum_floats_per_axis == (("data", floats),)

    def test_backend_reports_row_axes_only(self, mesh):
        be = ShardedBackend(16, mesh)
        assert "model" not in be.fusion_axis_sizes
        # on a degenerate 1-device mesh there may be no crossed axes at all
        assert all(n > 0 for n in be.fusion_axis_sizes.values())


class TestEngineGuards:
    def test_from_clients_rejects_populated_backend(self, mesh):
        _, _, stats = _problem()
        be = ShardedBackend(21, mesh)
        FusionEngine.from_clients({0: stats}, backend=be)
        with pytest.raises(ValueError, match="already holds"):
            FusionEngine.from_clients({0: stats}, backend=be)

    def test_dtype_mismatch_is_loud(self, mesh):
        be = ShardedBackend(4, mesh)  # float32
        with pytest.raises(ValueError, match="dtype"):
            FusionEngine(4, dtype=jnp.bfloat16, backend=be)

    def test_sharded_run_omits_eager_dense_stats(self, mesh):
        from repro import data, fed

        ds = data.generate(jax.random.PRNGKey(0), num_clients=3,
                           samples_per_client=30, dim=8)
        res = fed.run_one_shot(ds, 0.1, mesh=mesh)
        assert "fused_stats" not in res.extras
        assert isinstance(res.comm, comm.ShardedCommRecord)
        dense = fed.run_one_shot(ds, 0.1)
        assert "fused_stats" in dense.extras
        np.testing.assert_allclose(res.weights, dense.weights,
                                   rtol=RTOL, atol=ATOL)


class TestCpuMeshHelper:
    def test_degrades_to_available_devices(self):
        """The mesh never shrinks silently: by default it spans exactly the
        platform's devices, and asking for more than exist raises."""
        m = mesh_lib.make_device_mesh()
        assert m.devices.size == jax.device_count()
        assert m.axis_names == ("data", "model")
        with pytest.raises(ValueError, match="requested"):
            mesh_lib.make_device_mesh(jax.device_count() + 1)

    def test_near_square_factorization(self):
        n = jax.device_count()
        m = mesh_lib.make_device_mesh(n)
        r, c = m.devices.shape
        assert r * c == n and r >= c


# ---------------------------------------------------------------------------
# 8-device child process: the real sharded assertions.
# ---------------------------------------------------------------------------

_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import core, fed
from repro.core import fusion
from repro.launch import mesh as mesh_lib
from repro.server import FusionEngine, ShardedBackend

assert jax.device_count() == 8, jax.device_count()
mesh = mesh_lib.make_device_mesh(8)
assert dict(mesh.shape) == {"data": 4, "model": 2}

d = 100  # pads to 128 with bs=8 on a (4,2) mesh: d does NOT divide the tiling
k1, k2 = jax.random.split(jax.random.PRNGKey(0))
A = jax.random.normal(k1, (400, d)); b = jax.random.normal(k2, (400,))
parts = [(A[i*100:(i+1)*100], b[i*100:(i+1)*100]) for i in range(4)]
stats = {i: core.compute_stats(a, bb) for i, (a, bb) in enumerate(parts)}
ref = fusion.solve_ridge(core.compute_stats(A, b), 0.1)

be = ShardedBackend(d, mesh)
assert be.padded == 128 and d % be.block_size != 0
eng = FusionEngine.from_clients(stats, backend=be)

# 1) solve matches the dense reference at fp32 tolerance
np.testing.assert_allclose(np.asarray(eng.solve(0.1)), np.asarray(ref),
                           rtol=3e-4, atol=3e-4)

# 2) G never materializes unsharded on the solve path: the live Gram and the
#    cached factor are both 2-D block-sharded, before and after solving.
blocked = P("data", "model")
assert be.gram.sharding.spec == blocked, be.gram.sharding
assert not be.gram.sharding.is_fully_replicated
fac = eng._factors[0.1].factor
assert fac.L.sharding.spec == blocked, fac.L.sharding
assert not fac.L.sharding.is_fully_replicated
eng.solve(0.1)
assert be.gram.sharding.spec == blocked

# 3) drop/restore stays exact (evict + on-mesh refactorization)
eng.drop(1); eng.drop(3)
w_ref = fusion.dropout_fusion(list(stats.values()),
                              [True, False, True, False], 0.1)
np.testing.assert_allclose(np.asarray(eng.solve(0.1)), np.asarray(w_ref),
                           rtol=3e-4, atol=3e-4)
eng.restore(1); eng.restore(3)

# 4) streaming ingest then solve still matches a cold reference
eA = jax.random.normal(jax.random.PRNGKey(9), (64, d))
eb = jax.random.normal(jax.random.PRNGKey(10), (64,))
eng.ingest_rows(eA, eb)
ref_s = fusion.solve_ridge(core.compute_stats(
    jnp.concatenate([A, eA]), jnp.concatenate([b, eb])), 0.1)
np.testing.assert_allclose(np.asarray(eng.solve(0.1)), np.asarray(ref_s),
                           rtol=3e-4, atol=3e-4)

# 4b) low-rank mutation on the full mesh: the distributed blocked up/downdate
#     absorbs it — no cold refactorization, factor stays block-sharded, and
#     the coalescer batches queued deltas into one mutation.
eng4 = FusionEngine.from_stats(core.compute_stats(A, b),
                               backend=ShardedBackend(d, mesh),
                               max_update_rank=64)
eng4.solve(0.1)
cold0 = eng4.cold_factorizations
for i in range(8):
    dA = jax.random.normal(jax.random.PRNGKey(20 + i), (2, d))
    db = jax.random.normal(jax.random.PRNGKey(60 + i), (2,))
    eng4.ingest_rows_async(dA, db)
w4 = eng4.solve(0.1)   # drains: ONE rank-16 distributed update
assert eng4.cold_factorizations == cold0, "sharded mutation refactorized"
assert eng4.incremental_updates == 1 and eng4.coalesced_deltas == 8
allA = jnp.concatenate([A] + [jax.random.normal(jax.random.PRNGKey(20 + i), (2, d)) for i in range(8)])
allb = jnp.concatenate([b] + [jax.random.normal(jax.random.PRNGKey(60 + i), (2,)) for i in range(8)])
np.testing.assert_allclose(np.asarray(w4),
                           np.asarray(fusion.solve_ridge(core.compute_stats(allA, allb), 0.1)),
                           rtol=3e-4, atol=3e-4)
assert eng4._factors[0.1].factor.L.sharding.spec == blocked

# 5) on-mesh fusion (psum-scattered into the block layout) is exact and the
#    delta path keeps the block sharding
be2 = ShardedBackend(d, mesh)
eng2 = FusionEngine(d, backend=be2)
eng2.ingest_distributed(A[:256], b[:256])
ref2 = fusion.solve_ridge(core.compute_stats(A[:256], b[:256]), 0.1)
np.testing.assert_allclose(np.asarray(eng2.solve(0.1)), np.asarray(ref2),
                           rtol=3e-4, atol=3e-4)
assert be2.gram.sharding.spec == blocked
assert eng2.count == 256

# 6) CG fallback on the full mesh
be3 = ShardedBackend(d, mesh, method="cg")
eng3 = FusionEngine.from_stats(core.compute_stats(A, b), backend=be3)
np.testing.assert_allclose(np.asarray(eng3.solve(0.1)), np.asarray(ref),
                           rtol=1e-3, atol=1e-3)

# 7) mesh-backed protocol adapter: engine in extras + cross-shard ledger
ds_like = type("DS", (), {})()
from repro.data import synthetic
ds = synthetic.generate(jax.random.PRNGKey(3), num_clients=4,
                        samples_per_client=64, dim=32)
res = fed.run_one_shot(ds, 0.1, mesh=mesh)
assert isinstance(res.comm, fed.ShardedCommRecord)
assert res.comm.cross_shard_bytes > 0
assert res.extras["engine"].summary()["backend"] == "sharded"
w_ref = fed.run_one_shot(ds, 0.1).weights
np.testing.assert_allclose(np.asarray(res.weights), np.asarray(w_ref),
                           rtol=3e-4, atol=3e-4)

print("SHARDED-OK")
"""


@pytest.mark.slow
def test_sharded_backend_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED-OK" in out.stdout, out.stdout + out.stderr
