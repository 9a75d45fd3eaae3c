"""Mutation-path coverage: blocked updates, coalescer, packed payloads.

The write path rebuilt by the mutation-pipeline PR, pinned against the
pre-existing references: ``chol_update_blocked`` vs the scan-of-rank-1
LINPACK recurrence (across dtypes, ranks, and downdates that land on the
sigma-I floor), the Thm-4 triangular wire codec, the coalescer's
one-mutation-per-flush semantics, the fuse_stats chunked tree reduction's
allocation bound, the tail-only streaming pad, and the measured comm
ledger's agreement with the Theorem 4 formula.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core, fed
from repro.core import fusion
from repro.core.sufficient_stats import compute_stats, fuse_stats
from repro.kernels import ops
from repro.server import (CoalescerPolicy, DenseBackend, FusionEngine,
                          auto_backend, backend_threshold, chol_update,
                          chol_update_blocked, panel_transform)


def _factor(d, seed=0, sigma=0.1, scale=1.0):
    A = jax.random.normal(jax.random.PRNGKey(seed), (2 * d, d)) * scale
    G = A.T @ A + sigma * jnp.eye(d)
    return jnp.linalg.cholesky(G), A


class TestBlockedUpdate:
    @pytest.mark.parametrize("d,r,bs", [(16, 3, 8), (48, 8, 16),
                                        (100, 17, 32), (64, 64, 32)])
    def test_matches_scan_reference(self, d, r, bs):
        L, _ = _factor(d, seed=d + r)
        U = jax.random.normal(jax.random.PRNGKey(r), (r, d))
        ref = chol_update(L, U, sign=1.0)
        got = chol_update_blocked(L, U, sign=1.0, block_size=bs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        d, r = 32, 9
        L, _ = _factor(d)
        L = L.astype(dtype)
        U = jax.random.normal(jax.random.PRNGKey(1), (r, d), dtype)
        ref = chol_update(L, U, sign=1.0)
        got = chol_update_blocked(L, U, sign=1.0, block_size=16)
        assert got.dtype == ref.dtype == dtype
        tol = 1e-4 if dtype == jnp.float32 else 1e-1
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_downdate_near_sigma_floor(self):
        """Downdates that land on the Prop-1 sigma I floor.

        The factor ENTRIES of a near-singular matrix are ill-conditioned
        under perturbation (for the scan reference exactly as much as for
        the blocked path), so the pin is on what the server actually uses:
        L L^T must reconstruct G + sigma I to a small fraction of the sigma
        floor, for both paths, after an up-then-down roundtrip."""
        d, r, sigma = 40, 12, 1e-3
        # data term much smaller than the update so the downdate ends near
        # the sigma floor
        L, A = _factor(d, sigma=sigma, scale=1e-3)
        target = np.asarray(A.T @ A + sigma * jnp.eye(d))
        U = jax.random.normal(jax.random.PRNGKey(7), (r, d))
        for fn in (chol_update_blocked, chol_update):
            down = fn(fn(L, U, sign=1.0), U, sign=-1.0)
            recon_err = np.abs(np.asarray(down @ down.T) - target).max()
            assert recon_err < 0.05 * sigma, (fn.__name__, recon_err)

    def test_downdate_matches_scan(self):
        d, r = 48, 10
        L, _ = _factor(d)
        U = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (r, d))
        up_ref = chol_update(L, U, sign=1.0)
        ref = chol_update(up_ref, U, sign=-1.0)
        got = chol_update_blocked(chol_update_blocked(L, U, sign=1.0),
                                  U, sign=-1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_pallas_tile_path_matches(self):
        d, r = 40, 9
        L, _ = _factor(d, seed=5)
        U = jax.random.normal(jax.random.PRNGKey(5), (r, d))
        ref = chol_update(L, U, sign=1.0)
        got = chol_update_blocked(L, U, sign=1.0, block_size=16,
                                  use_pallas=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("bw,r,zero_col,pad_rows", [
        (8, 1, None, 0), (16, 8, None, 0), (32, 8, 5, 0), (32, 9, 0, 7),
        (32, 64, 31, 0)])
    def test_panel_householder_contract(self, bw, r, zero_col, pad_rows):
        """An update's panel: T orthogonal, ``[L11 | X1^T] @ T = [L11' | 0]``
        with L11' lower triangular and a positive diagonal; a zero column of
        X1 and the rank bucket's zero rows leave T's rows exact identities."""
        L, _ = _factor(bw, seed=bw + r)
        X1 = jax.random.normal(jax.random.PRNGKey(r), (r, bw))
        if zero_col is not None:
            X1 = X1.at[:, zero_col].set(0.0)
        X1 = jnp.pad(X1, ((0, pad_rows), (0, 0)))
        L11n, T = panel_transform(L, X1, sign=1.0)
        n = bw + r + pad_rows
        T, L11n, X1 = np.asarray(T), np.asarray(L11n), np.asarray(X1)
        np.testing.assert_allclose(T @ T.T, np.eye(n), atol=2e-6)
        got = np.concatenate([np.asarray(L), X1.T], axis=1) @ T
        np.testing.assert_allclose(got[:, :bw], L11n, atol=1e-5)
        np.testing.assert_allclose(got[:, bw:], 0.0, atol=1e-5)
        assert (np.diag(L11n) > 0).all()
        np.testing.assert_array_equal(np.triu(L11n, 1), 0.0)
        for j in range(bw + r, n):          # pad rows: untouched columns
            np.testing.assert_array_equal(T[:, j], np.eye(n)[:, j])

    def test_panel_zero_update_is_exact_identity(self):
        L, _ = _factor(16, seed=2)
        L11n, T = panel_transform(L, jnp.zeros((8, 16)), sign=1.0)
        np.testing.assert_array_equal(np.asarray(L11n), np.asarray(L))
        np.testing.assert_array_equal(np.asarray(T), np.eye(24))

    @pytest.mark.parametrize("r,dtype", [
        (1, jnp.float32), (8, jnp.float32), (9, jnp.float32),
        (16, jnp.float32), (64, jnp.float32), (9, jnp.bfloat16)])
    def test_householder_update_matches_scan(self, r, dtype):
        """The update path against the scan-of-rank-1 reference, with an
        all-zero column and all-zero rows up to the next power of two
        (the backend's rank bucket) in the blocked call's vectors."""
        d, bs = 80, 32                      # last panel 16 wide
        L, _ = _factor(d, seed=r)
        U = jax.random.normal(jax.random.PRNGKey(100 + r), (r, d))
        U = U.at[:, 37].set(0.0)
        X = jnp.pad(U, ((0, ops.pow2_bucket(r + 1) - r), (0, 0)))
        ref = chol_update(L, U, sign=1.0)
        got = chol_update_blocked(L.astype(dtype), X.astype(dtype),
                                  sign=1.0, block_size=bs)
        assert got.dtype == dtype
        tol = 2e-4 if dtype == jnp.float32 else 1e-1
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=tol, atol=tol)

    def test_update_paths_counted(self):
        """``FusionEngine.summary()["update_paths"]``: an 8-row ingest runs
        the Householder update on every cached factor, a rank-12 drop the
        Givens downdate, a 3-row ingest the scan."""
        d, sigmas = 24, (0.1, 1.0)
        parts = {i: compute_stats(
            jax.random.normal(jax.random.PRNGKey(i), (12, d)),
            jax.random.normal(jax.random.PRNGKey(50 + i), (12,)))
            for i in range(3)}
        eng = FusionEngine.from_clients(
            parts, backend=DenseBackend(d, use_pallas=False),
            max_update_rank=200)
        for s in sigmas:
            eng.solve(s)

        def paths():
            return eng.summary()["update_paths"]

        assert paths() == {"householder": 0, "givens": 0, "scan": 0}
        eng.ingest_rows(jax.random.normal(jax.random.PRNGKey(7), (8, d)),
                        jax.random.normal(jax.random.PRNGKey(8), (8,)))
        assert paths() == {"householder": len(sigmas), "givens": 0, "scan": 0}
        eng.drop(1)                          # rank(G_1) = 12 >= 8: blocked
        assert paths()["givens"] == len(sigmas)
        eng.ingest_rows(jax.random.normal(jax.random.PRNGKey(9), (3, d)),
                        jax.random.normal(jax.random.PRNGKey(10), (3,)))
        assert paths() == {"householder": len(sigmas),
                           "givens": len(sigmas), "scan": len(sigmas)}
        assert eng.incremental_updates == 3 * len(sigmas)

    def test_rank_zero_is_identity(self):
        L, _ = _factor(8)
        U = jnp.zeros((0, 8))
        np.testing.assert_array_equal(chol_update_blocked(L, U), L)

    def test_dense_backend_dispatch(self):
        """Above the rank threshold the backend routes to the blocked path
        and the factor still solves correctly."""
        d = 48
        be = DenseBackend(d, use_pallas=False)
        assert be.blocked_update_min_rank <= 8
        _, A = _factor(d, seed=9)
        b = jax.random.normal(jax.random.PRNGKey(10), (2 * d,))
        eng = FusionEngine.from_stats(compute_stats(A, b), backend=be,
                                      max_update_rank=64)
        eng.solve(0.1)
        dA = jax.random.normal(jax.random.PRNGKey(11), (16, d))
        db = jax.random.normal(jax.random.PRNGKey(12), (16,))
        eng.ingest_rows(dA, db)      # r=16 >= threshold -> blocked
        assert eng.incremental_updates == 1
        ref = fusion.solve_ridge(
            compute_stats(jnp.concatenate([A, dA]),
                          jnp.concatenate([b, db])), 0.1)
        np.testing.assert_allclose(np.asarray(eng.solve(0.1)),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)


class TestPackedPayloads:
    @pytest.mark.parametrize("d", [1, 5, 16, 33])
    def test_roundtrip_exact(self, d):
        A = jax.random.normal(jax.random.PRNGKey(d), (2 * d, d))
        G = A.T @ A
        tri = ops.pack_lower(G)
        assert tri.shape == (d * (d + 1) // 2,)
        # bit-exact: no arithmetic on the kept entries
        np.testing.assert_array_equal(np.asarray(ops.unpack_lower(tri, d)),
                                      np.asarray(jnp.tril(G)
                                                 + jnp.tril(G, -1).T))

    def test_packed_stats_roundtrip(self):
        s = compute_stats(jax.random.normal(jax.random.PRNGKey(0), (20, 6)),
                          jax.random.normal(jax.random.PRNGKey(1), (20,)))
        p = fed.PackedStats.pack(s)
        assert p.wire_floats == 6 * 7 // 2 + 6
        s2 = p.unpack()
        np.testing.assert_array_equal(np.asarray(s2.gram),
                                      np.asarray(jnp.tril(s.gram)
                                                 + jnp.tril(s.gram, -1).T))
        np.testing.assert_array_equal(np.asarray(s2.moment),
                                      np.asarray(s.moment))
        assert int(s2.count) == int(s.count)

    def test_unpack_rejects_bad_length(self):
        with pytest.raises(ValueError, match="packed length"):
            ops.unpack_lower(jnp.zeros((7,)), 4)

    def test_measured_ledger_equals_thm4_formula(self):
        """The measured record and the Thm 4 formula must never drift.

        Float columns pin the analytic formula exactly; the byte column is
        the *encoded frame length* (fed.wire header/CRC envelope + metadata
        + scalars at the payload dtype), pinned against the codec's exact
        size and lower-bounded by the Thm-4 analytic bytes.
        """
        from repro import data
        from repro.fed import wire

        d = 24
        dset = data.generate(jax.random.PRNGKey(0), num_clients=3,
                             samples_per_client=50, dim=d)
        res = fed.run_one_shot(dset, 0.1)
        formula = fed.one_shot_comm(d, 3)
        assert res.comm.upload_floats_per_client == \
            formula.upload_floats_per_client == d * (d + 1) // 2 + d
        # Analytic column: unchanged by framing (the paper-table number).
        assert res.comm.analytic_total_bytes == formula.total_bytes
        # Measured column: exact encoded frame size, >= the analytic floats.
        assert res.comm.upload_wire_bytes_per_client == \
            wire.stats_frame_nbytes(d, "f32")
        assert res.comm.total_bytes > formula.total_bytes
        per_client_overhead = (res.comm.upload_wire_bytes_per_client
                               - (d * (d + 1) // 2 + d) * 4)
        assert per_client_overhead == wire.OVERHEAD_BYTES + 4 + 8 + 2

    def test_measured_ledger_rejects_heterogeneous(self):
        s6 = fed.PackedStats.pack(compute_stats(jnp.ones((2, 6)),
                                                jnp.ones((2,))))
        s4 = fed.PackedStats.pack(compute_stats(jnp.ones((2, 4)),
                                                jnp.ones((2,))))
        with pytest.raises(ValueError, match="heterogeneous"):
            fed.measured_one_shot([s6, s4], download_floats=6)

    def test_one_shot_solution_unchanged_by_packing(self):
        from repro import data

        dset = data.generate(jax.random.PRNGKey(2), num_clients=4,
                             samples_per_client=60, dim=12)
        res = fed.run_one_shot(dset, 0.05)
        cen = fed.run_centralized(dset, 0.05)
        np.testing.assert_allclose(np.asarray(res.weights),
                                   np.asarray(cen.weights),
                                   rtol=1e-3, atol=1e-5)


class TestCoalescer:
    def test_flush_is_one_mutation(self):
        d = 10
        eng = FusionEngine(d, coalesce=CoalescerPolicy(max_rank=1000),
                           max_update_rank=1000)
        A0 = jax.random.normal(jax.random.PRNGKey(0), (30, d))
        b0 = jax.random.normal(jax.random.PRNGKey(1), (30,))
        eng.ingest_rows(A0, b0)
        eng.solve(0.1)                      # warm one factor
        base = eng.incremental_updates
        chunks = []
        for i in range(12):
            dA = jax.random.normal(jax.random.PRNGKey(10 + i), (1, d))
            db = jax.random.normal(jax.random.PRNGKey(50 + i), (1,))
            eng.ingest_rows_async(dA, db)
            chunks.append((dA, db))
        assert eng.pending_deltas == 12 and eng.pending_rank == 12
        assert eng.flush() == 12
        assert eng.incremental_updates == base + 1   # ONE rank-12 mutation
        assert eng.flushes == 1 and eng.coalesced_deltas == 12
        A_all = jnp.concatenate([A0] + [a for a, _ in chunks])
        b_all = jnp.concatenate([b0] + [b for _, b in chunks])
        ref = fusion.solve_ridge(compute_stats(A_all, b_all), 0.1)
        np.testing.assert_allclose(np.asarray(eng.solve(0.1)),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)

    def test_autoflush_on_rank_threshold(self):
        d = 8
        eng = FusionEngine(d, coalesce=CoalescerPolicy(max_rank=4))
        for i in range(7):
            eng.ingest_rows_async(
                jax.random.normal(jax.random.PRNGKey(i), (1, d)),
                jax.random.normal(jax.random.PRNGKey(100 + i), (1,)))
        assert eng.flushes == 1 and eng.pending_deltas == 3

    def test_autoflush_on_staleness(self):
        d = 8
        eng = FusionEngine(d, coalesce=CoalescerPolicy(max_rank=1000,
                                                       max_staleness_s=0.0))
        eng.ingest_rows_async(jnp.ones((1, d)), jnp.ones((1,)))
        # zero staleness budget: the delta flushed as soon as it was queued
        assert eng.flushes == 1 and eng.pending_deltas == 0

    def test_reads_drain_the_queue(self):
        d = 8
        eng = FusionEngine(d, coalesce=CoalescerPolicy(max_rank=1000))
        eng.ingest_rows_async(jnp.ones((2, d)), jnp.ones((2,)))
        assert eng.pending_deltas == 1
        assert eng.count == 2               # count read flushes first
        assert eng.pending_deltas == 0

    def test_restore_keeps_deltas_ingested_while_dropped(self):
        """Regression: deltas ingested under a dropped client's id must
        survive its restore in the ledger — a later drop has to remove BOTH
        contributions, and the solve must track the cold reference."""
        d = 8
        eng = FusionEngine(d, coalesce=CoalescerPolicy(max_rank=1000))
        A1 = jax.random.normal(jax.random.PRNGKey(0), (4, d))
        b1 = jax.random.normal(jax.random.PRNGKey(1), (4,))
        A2 = jax.random.normal(jax.random.PRNGKey(2), (4, d))
        b2 = jax.random.normal(jax.random.PRNGKey(3), (4,))
        A3 = jax.random.normal(jax.random.PRNGKey(4), (4, d))
        b3 = jax.random.normal(jax.random.PRNGKey(5), (4,))
        eng.ingest_rows(A1, b1, client_id="a")
        eng.ingest_rows(A2, b2, client_id="b")
        eng.drop("a")
        eng.ingest_rows_async(A3, b3, client_id="a")   # arrives while dropped
        eng.restore("a")                               # flush + rejoin
        assert eng.count == 12
        eng.drop("a")                                  # must remove A1 AND A3
        ref = fusion.solve_ridge(compute_stats(A2, b2), 0.1)
        np.testing.assert_allclose(np.asarray(eng.solve(0.1)),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)
        assert eng.count == 4

    def test_drop_sees_queued_client_deltas(self):
        d = 8
        eng = FusionEngine(d, coalesce=CoalescerPolicy(max_rank=1000))
        A1 = jax.random.normal(jax.random.PRNGKey(0), (4, d))
        b1 = jax.random.normal(jax.random.PRNGKey(1), (4,))
        A2 = jax.random.normal(jax.random.PRNGKey(2), (4, d))
        b2 = jax.random.normal(jax.random.PRNGKey(3), (4,))
        eng.ingest_rows_async(A1, b1, client_id="a")
        eng.ingest_rows_async(A2, b2, client_id="b")
        eng.drop("a")                        # must flush, then remove ALL of a
        ref = fusion.solve_ridge(compute_stats(A2, b2), 0.1)
        np.testing.assert_allclose(np.asarray(eng.solve(0.1)),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)


class TestFuseStatsTree:
    def test_matches_flat_reduction(self):
        parts = [compute_stats(
            jax.random.normal(jax.random.PRNGKey(i), (5, 7)),
            jax.random.normal(jax.random.PRNGKey(100 + i), (5,)))
            for i in range(21)]
        flat = jax.tree.map(lambda *ls: jnp.stack(ls).sum(0), *parts)
        tree = fuse_stats(parts, chunk=4)
        np.testing.assert_allclose(np.asarray(tree.gram),
                                   np.asarray(flat.gram),
                                   rtol=1e-5, atol=1e-5)
        assert int(tree.count) == int(flat.count) == 105

    def test_peak_stack_bounded_by_chunk(self, monkeypatch):
        """Allocation parity with the documented O(chunk d^2) bound: no
        single stacked buffer ever holds more than ``chunk`` Grams (the old
        implementation stacked all K at once)."""
        widths = []
        real_stack = jnp.stack

        def probe(xs, *a, **k):
            widths.append(len(xs))
            return real_stack(xs, *a, **k)

        monkeypatch.setattr(jnp, "stack", probe)
        parts = [compute_stats(
            jax.random.normal(jax.random.PRNGKey(i), (3, 5)),
            jax.random.normal(jax.random.PRNGKey(200 + i), (3,)))
            for i in range(32)]
        fuse_stats(parts, chunk=8)
        assert widths and max(widths) <= 8


class TestStreamingTailPad:
    @pytest.mark.parametrize("n", [60, 128, 129, 1000])
    def test_matches_dense(self, n):
        A = jax.random.normal(jax.random.PRNGKey(n), (n, 16))
        b = jax.random.normal(jax.random.PRNGKey(n + 1), (n,))
        s = core.compute_stats_streaming(A, b, chunk=128)
        ref = compute_stats(A, b)
        np.testing.assert_allclose(np.asarray(s.gram), np.asarray(ref.gram),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s.moment),
                                   np.asarray(ref.moment),
                                   rtol=1e-5, atol=1e-4)
        assert int(s.count) == n

    def test_no_full_copy_padding(self, monkeypatch):
        """Only the ragged tail is padded: the pad call sees O(chunk) rows,
        never the full n."""
        padded_rows = []
        real_pad = jnp.pad

        def probe(x, *a, **k):
            padded_rows.append(x.shape[0])
            return real_pad(x, *a, **k)

        monkeypatch.setattr(jnp, "pad", probe)
        n, chunk = 1000, 128
        A = jax.random.normal(jax.random.PRNGKey(0), (n, 8))
        b = jax.random.normal(jax.random.PRNGKey(1), (n,))
        core.compute_stats_streaming(A, b, chunk=chunk)
        assert padded_rows and max(padded_rows) < chunk


def _crossover_table(path, crossover, backend=None):
    host = {"jax_backend": backend or jax.default_backend()}
    path.write_text(json.dumps({"crossover_d": crossover, "host": host}))
    return path


class TestAutoBackendPicker:
    def test_threshold_resolution(self, tmp_path):
        table = _crossover_table(tmp_path / "crossover.json", 384)
        assert backend_threshold(table=table) == 384.0
        assert backend_threshold(512, table=table) == 512.0   # explicit wins
        _crossover_table(table, None)
        assert backend_threshold(table=table) == float("inf")
        assert backend_threshold(table=tmp_path / "missing.json") \
            == float("inf")

    def test_auto_backend_picks_by_dim(self, tmp_path):
        from repro.launch import mesh as mesh_lib

        table = _crossover_table(tmp_path / "crossover.json", 32)
        mesh = mesh_lib.make_device_mesh()
        assert auto_backend(16, mesh, table=table).name == "dense"
        assert auto_backend(64, mesh, table=table).name == "sharded"
        assert auto_backend(64, None, table=table).name == "dense"

    def test_table_from_another_backend_is_ignored(self, tmp_path):
        """A crossover measured on one jax backend says nothing about
        another: a table whose host ran elsewhere (or names no host) places
        everything dense."""
        from repro.launch import mesh as mesh_lib

        mesh = mesh_lib.make_device_mesh()
        other = "tpu" if jax.default_backend() != "tpu" else "cpu"
        table = _crossover_table(tmp_path / "crossover.json", 32, other)
        assert backend_threshold(table=table) == float("inf")
        assert auto_backend(64, mesh, table=table).name == "dense"
        table.write_text('{"crossover_d": 32}')
        assert backend_threshold(table=table) == float("inf")

    def test_from_clients_auto(self, tmp_path):
        table = _crossover_table(tmp_path / "crossover.json", None)
        s = compute_stats(jnp.ones((4, 6)), jnp.ones((4,)))
        eng = FusionEngine.from_clients({0: s}, backend="auto",
                                        threshold=backend_threshold(
                                            table=table))
        assert eng.summary()["backend"] == "dense"
