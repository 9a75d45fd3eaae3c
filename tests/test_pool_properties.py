"""Pool-level property tests: random multi-tenant interleavings vs cold refs.

The pool's contract is *tenant isolation*: T tenants on one ``EnginePool``
are T independent fusion problems, and no interleaving of
create / ingest / ingest_rows_async / drop / restore / flush / solve across
them may let one tenant's mutations perturb another's weights beyond fp
tolerance. The interpreter here drives arbitrary op sequences against a
5-tenant pool with mixed placements (one pinned sharded, one auto, one
dense) AND mixed kinds (one §IV-F sketched, one RFF — their mirrors hold
rows already pushed through the tenant's feature map, so every read is
pinned to a cold reference in the map's own solve space) while mirroring
every tenant's active rows in plain python, and after EVERY op checks EVERY
solvable tenant against a cold ``core.fusion`` solve over exactly its own
mirror — checking the untouched tenants is the isolation assertion,
checking the touched one is Thm 1/Thm 8/§VI-C/§IV-F.

A hypothesis-driven variant and a seeded deterministic variant drive the
same interpreter.

Registry/admission/eviction unit tests live at the bottom.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro import core
from repro.core import fusion
from repro.core.features import FeatureMap
from repro.fed.protocol import PackedStats
from repro.server import CoalescerPolicy, EnginePool

D = 6
SIGMA = 0.1
TENANTS = ("dense0", "sharded0", "auto0", "sketch0", "rff0")
PLACEMENT = {"dense0": "dense", "sharded0": "sharded", "auto0": "auto",
             "sketch0": "dense", "rff0": "dense"}
# §IV-F tenants solve in their map's feature space; every ingest/mirror row
# below is featurized first, so the interpreter and its cold references stay
# uniform across kinds (the reference solve just runs in m (D) dimensions).
FMAPS = {"sketch0": FeatureMap("sketch", seed=123, d_orig=D, m=4),
         "rff0": FeatureMap("rff", seed=321, d_orig=D, m=8)}

# (kind, tenant slot, client slot, data seed). Kinds: 0 ingest new client,
# 1 drop, 2 restore, 3 ingest_rows, 4 ingest_rows_async, 5 flush, 6 solve.
_OP = st.tuples(st.integers(0, 6), st.integers(0, 4), st.integers(0, 7),
                st.integers(0, 2**16))


def _rows(seed, n=8):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (n, D)), jax.random.normal(k2, (n,)))


def _tenant_rows(name, seed, n=8):
    """Rows in ``name``'s solve space: featurized for §IV-F tenants."""
    A, b = _rows(seed, n)
    fm = FMAPS.get(name)
    return (fm(A) if fm is not None else A), b


def _make_pool() -> EnginePool:
    # max_rank=5 so some interleavings auto-flush mid-sequence; staleness
    # stays inf — the background flusher has its own test module.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # 1-device host mesh degradation
        pool = EnginePool(default_coalesce=CoalescerPolicy(max_rank=5))
        for t, name in enumerate(TENANTS):
            A, b = _tenant_rows(name, 1000 + t)
            pool.create_tenant(name, clients={0: core.compute_stats(A, b)},
                               placement=PLACEMENT[name], max_update_rank=100,
                               features=FMAPS.get(name),
                               backend_kwargs={"block_size": 8}
                               if PLACEMENT[name] == "sharded" else None)
    return pool


def _interpret(ops):
    """Drive ops against a fresh pool; assert every tenant after every op."""
    pool = _make_pool()
    active = {n: {0: [_tenant_rows(n, 1000 + t)]}
              for t, n in enumerate(TENANTS)}
    dropped = {n: {} for n in TENANTS}
    anon = {n: [] for n in TENANTS}
    next_id = {n: 1 for n in TENANTS}

    for kind, tslot, cslot, seed in ops:
        name = TENANTS[tslot % len(TENANTS)]
        if kind == 0:                                  # ingest a new client
            A, b = _tenant_rows(name, seed)
            cid = next_id[name]
            pool.ingest(name, core.compute_stats(A, b), client_id=cid)
            active[name][cid] = [(A, b)]
            next_id[name] += 1
        elif kind == 1 and active[name]:               # drop a client
            cid = sorted(active[name])[cslot % len(active[name])]
            pool.drop(name, cid)
            dropped[name][cid] = active[name].pop(cid)
        elif kind == 2 and dropped[name]:              # restore a client
            cid = sorted(dropped[name])[cslot % len(dropped[name])]
            pool.restore(name, cid)
            active[name][cid] = dropped[name].pop(cid)
        elif kind == 3:                                # anonymous rows
            A, b = _tenant_rows(name, seed, n=3)
            pool.ingest_rows(name, A, b)
            anon[name].append((A, b))
        elif kind == 4:                                # queued rows
            A, b = _tenant_rows(name, seed, n=3)
            pool.ingest_rows_async(name, A, b)
            anon[name].append((A, b))
        elif kind == 5:                                # explicit flush
            pool.flush(name)
        elif kind == 6:                                # pure read
            pool.solve(name, SIGMA)
        else:
            continue   # drop/restore with nothing to act on: no-op

        # EVERY tenant must match its own cold reference — the tenants the
        # op did NOT touch are the isolation property.
        for other in TENANTS:
            chunks = [c for cs in active[other].values() for c in cs] \
                + anon[other]
            if not chunks:
                continue
            A_all = jnp.concatenate([a for a, _ in chunks])
            b_all = jnp.concatenate([b for _, b in chunks])
            w_ref = fusion.solve_ridge(core.compute_stats(A_all, b_all), SIGMA)
            np.testing.assert_allclose(
                np.asarray(pool.solve(other, SIGMA)), np.asarray(w_ref),
                rtol=2e-4, atol=2e-4,
                err_msg=f"tenant {other} diverged after {kind=} on {name}")
            assert pool.get(other).count == A_all.shape[0]
            fm = FMAPS.get(other)
            if fm is not None:
                # The serving read: solve-space weights lifted through the
                # tenant's map must match lifting the cold reference.
                np.testing.assert_allclose(
                    np.asarray(pool.solve_lifted(other, SIGMA)),
                    np.asarray(fm.lift(w_ref)), rtol=2e-4, atol=5e-4,
                    err_msg=f"lifted read on {other} diverged after "
                            f"{kind=} on {name}")


@hypothesis.given(ops=st.lists(_OP, min_size=1, max_size=6))
@hypothesis.settings(max_examples=10, deadline=None)
def test_tenant_isolation_under_random_interleavings(ops):
    _interpret(ops)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tenant_isolation_seeded_interleavings(seed):
    """Deterministic fallback: same interpreter, fixed random programs, so
    the isolation property is exercised even without hypothesis."""
    rng = np.random.default_rng(seed)
    ops = [(int(rng.integers(7)), int(rng.integers(5)),
            int(rng.integers(8)), int(rng.integers(2**16)))
           for _ in range(8)]
    _interpret(ops)


class TestAdmission:
    def _stats(self, seed=0):
        A, b = _rows(seed)
        return core.compute_stats(A, b)

    def test_exactly_one_source(self):
        pool = EnginePool()
        s = self._stats()
        with pytest.raises(ValueError, match="at most one"):
            pool.create_tenant("x", clients=[s], stats=s)
        with pytest.raises(ValueError, match="clients, payloads, stats"):
            pool.create_tenant("x")

    def test_duplicate_name_rejected(self):
        pool = EnginePool()
        pool.create_tenant("x", clients=[self._stats()], placement="dense")
        with pytest.raises(ValueError, match="already exists"):
            pool.create_tenant("x", clients=[self._stats()])

    def test_unknown_placement_rejected(self):
        with pytest.raises(ValueError, match="placement"):
            EnginePool().create_tenant("x", clients=[self._stats()],
                                       placement="tpu")

    def test_payload_admission_measures_wire_bytes(self):
        from repro.fed import comm

        pool = EnginePool()
        payloads = {k: PackedStats.pack(self._stats(k)) for k in range(3)}
        pool.create_tenant("x", payloads=payloads, placement="dense")
        rec = pool.tenant("x").comm
        assert rec.upload_floats_per_client == D * (D + 1) // 2 + D
        assert rec.num_clients == 3
        led = pool.ledger()
        assert led["upload_download_bytes"] == rec.total_bytes
        assert led["per_tenant"]["x"]["streamed_bytes"] == 0
        # streamed §VI-C bytes land in the ledger too
        A, b = _rows(9, n=4)
        pool.ingest_rows("x", A, b)
        assert pool.ledger()["per_tenant"]["x"]["streamed_bytes"] == \
            4 * (D + 1) * comm.FLOAT_BYTES

    def test_empty_payloads_rejected(self):
        with pytest.raises(ValueError, match="at least one client's payload"):
            EnginePool().create_tenant("x", payloads=[])

    def test_stats_admission_records_no_upload_bytes(self):
        # A pre-fused admission shipped nothing — the ledger must not
        # fabricate a Thm-4 upload for it.
        pool = EnginePool()
        pool.create_tenant("x", stats=self._stats(), placement="dense")
        pool.create_tenant("y", dim=D, placement="dense")
        assert pool.tenant("x").comm is None
        assert pool.ledger()["upload_download_bytes"] == 0

    def test_empty_tenant_from_dim(self):
        pool = EnginePool()
        pool.create_tenant("x", dim=D, placement="dense")
        A, b = _rows(3)
        pool.ingest("x", core.compute_stats(A, b), client_id=0)
        w_ref = fusion.solve_ridge(core.compute_stats(A, b), SIGMA)
        np.testing.assert_allclose(np.asarray(pool.solve("x", SIGMA)),
                                   np.asarray(w_ref), rtol=1e-4, atol=1e-4)


class TestPlacement:
    def test_sharded_tenants_share_one_mesh(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            pool = EnginePool()
            A, b = _rows(0)
            for i in range(3):
                pool.create_tenant(f"s{i}", clients=[core.compute_stats(A, b)],
                                   placement="sharded")
        meshes = {id(pool.get(f"s{i}").backend.mesh) for i in range(3)}
        assert len(meshes) == 1
        assert pool.meshes_built == 1

    def test_dense_pool_builds_no_mesh(self):
        pool = EnginePool()
        A, b = _rows(0)
        pool.create_tenant("d0", clients=[core.compute_stats(A, b)],
                           placement="dense")
        # null crossover on this host -> auto resolves dense, still no mesh
        pool.create_tenant("a0", clients=[core.compute_stats(A, b)],
                           placement="auto")
        assert pool.meshes_built == 0
        assert pool.tenant("a0").backend_name == "dense"

    def test_auto_threshold_override_places_sharded(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            pool = EnginePool(threshold=D)   # everything >= D goes sharded
            A, b = _rows(0)
            pool.create_tenant("a0", clients=[core.compute_stats(A, b)],
                               placement="auto")
        assert pool.tenant("a0").backend_name == "sharded"
        assert pool.meshes_built == 1


class TestEviction:
    def test_lru_evicts_coldest_factor_cache(self):
        pool = EnginePool(max_warm=1)
        for i in range(3):
            A, b = _rows(i)
            pool.create_tenant(f"t{i}", clients=[core.compute_stats(A, b)],
                               placement="dense")
        pool.solve("t0", SIGMA)
        assert pool.warm_tenants() == ("t0",)
        pool.solve("t1", SIGMA)          # t0 is now the coldest -> evicted
        assert pool.warm_tenants() == ("t1",)
        assert pool.get("t0").cached_factor_count == 0
        assert pool.tenant("t0").factor_evictions == 1
        # eviction dropped factors, NOT state: t0 still answers exactly
        A, b = _rows(0)
        w_ref = fusion.solve_ridge(core.compute_stats(A, b), SIGMA)
        np.testing.assert_allclose(np.asarray(pool.solve("t0", SIGMA)),
                                   np.asarray(w_ref), rtol=1e-4, atol=1e-4)

    def test_no_eviction_without_bound(self):
        pool = EnginePool()
        for i in range(3):
            A, b = _rows(i)
            pool.create_tenant(f"t{i}", clients=[core.compute_stats(A, b)],
                               placement="dense")
            pool.solve(f"t{i}", SIGMA)
        assert len(pool.warm_tenants()) == 3
        assert pool.summary()["factor_evictions"] == 0


class TestRegistry:
    def test_drop_tenant(self):
        pool = EnginePool()
        A, b = _rows(0)
        pool.create_tenant("x", clients=[core.compute_stats(A, b)],
                           placement="dense")
        assert "x" in pool and len(pool) == 1
        eng = pool.drop_tenant("x")
        assert "x" not in pool and len(pool) == 0
        assert eng.count == A.shape[0]   # caller can still archive it
        with pytest.raises(KeyError):
            pool.solve("x", SIGMA)
