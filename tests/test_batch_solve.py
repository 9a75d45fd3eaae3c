"""Cross-tenant batched solve path: bit-identity, batcher, admission.

The batched Phase-3 contract is EXACTNESS, not tolerance: every lane of a
``EnginePool.solve_many`` stacked sweep must return the very bits that
tenant's lone ``solve`` would return at the same logical state (the sweep
scans the SAME jitted cho_solve program the lone path runs — see
``server/batch.py``). The interpreter-style property test interleaves
``solve_many`` with ingest / drop / restore / flush / async deltas across
mixed dense + sharded placements and asserts the bitwise equality after
every op; a hypothesis variant and a seeded variant drive it, same split
as ``test_pool_properties``.

Also here: pow2 sigma-grid bucketing (padded grids must not perturb real
lanes), the ``SolveBatcher`` micro-batching window (lone requests, bursts,
per-request failure isolation, wire integration over loopback AND TCP),
and the admission-control / quota knobs the batched serving path leans on.
"""
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro import core
from repro.fed import transport
from repro.kernels.ops import pow2_bucket
from repro.server import (AdmissionError, CoalescerPolicy, EnginePool,
                          SolveBatcher, solve_stacked)

D = 6
SIGMA = 0.1
SIGMA2 = 0.5
TENANTS = ("dense0", "sharded0", "dense1")
PLACEMENT = {"dense0": "dense", "sharded0": "sharded", "dense1": "dense"}


def _rows(seed, n=8, d=D):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (n, d)), jax.random.normal(k2, (n,)))


def _make_pool(**kw) -> EnginePool:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # 1-device host mesh degradation
        pool = EnginePool(default_coalesce=CoalescerPolicy(max_rank=5), **kw)
        for t, name in enumerate(TENANTS):
            A, b = _rows(1000 + t)
            pool.create_tenant(name, clients={0: core.compute_stats(A, b)},
                               placement=PLACEMENT[name], max_update_rank=100,
                               backend_kwargs={"block_size": 8}
                               if PLACEMENT[name] == "sharded" else None)
    return pool


def _assert_bitwise_matches_lone(pool, sigmas=(SIGMA, SIGMA2)):
    """solve_many must reproduce every tenant's lone solve bit for bit.

    Lone solves run first: they drain any queued deltas, so both paths see
    the same logical state and the comparison is exact equality, not
    allclose.
    """
    names = pool.tenant_names
    for sigma in sigmas:
        lone = [np.asarray(pool.solve(n, sigma)) for n in names]
        many = pool.solve_many([(n, sigma) for n in names])
        for name, w_lone, w_many in zip(names, lone, many):
            assert (np.asarray(w_many) == w_lone).all(), \
                f"tenant {name} sigma {sigma}: batched bits != lone bits"


# -- solve_stacked unit ------------------------------------------------------

class TestSolveStacked:
    def test_empty(self):
        assert solve_stacked([]) == []

    @pytest.mark.parametrize("T", [1, 2, 3, 5, 8])
    def test_padded_lanes_bit_identical(self, T):
        """Any batch extent (pow2 or padded) returns each lane's exact lone
        cho_solve — the pad lanes must be invisible."""
        from repro.server.backends import solve_snapshot

        entries = []
        for i in range(T):
            A, b = _rows(i, n=3 * D)
            G = A.T @ A + (1.0 + i) * jnp.eye(D)
            L = jax.scipy.linalg.cholesky(G, lower=True)
            entries.append((L, A.T @ b))
        ws = solve_stacked(entries)
        assert len(ws) == T
        for (L, h), w in zip(entries, ws):
            assert (np.asarray(w) == np.asarray(solve_snapshot(L, h))).all()


# -- solve_many across mixed placements -------------------------------------

class TestSolveMany:
    def test_bitwise_vs_lone_mixed_placements(self):
        pool = _make_pool()
        _assert_bitwise_matches_lone(pool)
        assert pool.batched_sweeps >= 1      # dense tenants really stacked
        assert pool.batched_solves >= 2
        pool.close()

    def test_duplicate_and_multi_sigma_requests(self):
        """One tenant may appear many times (distinct sigmas or repeats);
        every slot resolves independently and exactly."""
        pool = _make_pool()
        reqs = [("dense0", SIGMA), ("dense1", SIGMA2), ("dense0", SIGMA2),
                ("dense0", SIGMA), ("sharded0", SIGMA)]
        lone = [np.asarray(pool.solve(n, s)) for n, s in reqs]
        many = pool.solve_many(reqs)
        for (n, s), w_lone, w_many in zip(reqs, lone, many):
            assert (np.asarray(w_many) == w_lone).all(), (n, s)
        pool.close()

    def test_unknown_tenant_raises(self):
        pool = _make_pool()
        with pytest.raises(KeyError):
            pool.solve_many([("dense0", SIGMA), ("nope", SIGMA)])
        pool.close()


# -- interleaving property (satellite: solve_many vs mutations) -------------

# (kind, tenant slot, client slot, data seed). Kinds: 0 ingest new client,
# 1 drop, 2 restore, 3 ingest_rows, 4 ingest_rows_async, 5 flush,
# 6 lone solve.
_OP = st.tuples(st.integers(0, 6), st.integers(0, 2), st.integers(0, 7),
                st.integers(0, 2**16))


def _interpret(ops):
    """Drive mutations against a fresh mixed-placement pool; after EVERY op
    the batched sweep must be bit-identical to lone solves for ALL tenants
    (the untouched tenants pin sweep isolation, the touched one pins
    snapshot freshness)."""
    pool = _make_pool()
    active = {n: [0] for n in TENANTS}
    dropped = {n: [] for n in TENANTS}
    next_id = {n: 1 for n in TENANTS}

    for kind, tslot, cslot, seed in ops:
        name = TENANTS[tslot % len(TENANTS)]
        if kind == 0:
            A, b = _rows(seed)
            cid = next_id[name]
            pool.ingest(name, core.compute_stats(A, b), client_id=cid)
            active[name].append(cid)
            next_id[name] += 1
        elif kind == 1 and active[name]:
            cid = sorted(active[name])[cslot % len(active[name])]
            pool.drop(name, cid)
            active[name].remove(cid)
            dropped[name].append(cid)
        elif kind == 2 and dropped[name]:
            cid = sorted(dropped[name])[cslot % len(dropped[name])]
            pool.restore(name, cid)
            dropped[name].remove(cid)
            active[name].append(cid)
        elif kind == 3:
            A, b = _rows(seed, n=3)
            pool.ingest_rows(name, A, b)
        elif kind == 4:
            A, b = _rows(seed, n=3)
            pool.ingest_rows_async(name, A, b)
        elif kind == 5:
            pool.flush(name)
        elif kind == 6:
            pool.solve(name, SIGMA)
        _assert_bitwise_matches_lone(pool, sigmas=(SIGMA,))
    _assert_bitwise_matches_lone(pool)
    pool.close()


@hypothesis.given(ops=st.lists(_OP, min_size=1, max_size=5))
@hypothesis.settings(max_examples=10, deadline=None)
def test_solve_many_bitwise_under_random_interleavings(ops):
    _interpret(ops)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_solve_many_bitwise_seeded_interleavings(seed):
    rng = np.random.default_rng(seed)
    ops = [(int(rng.integers(7)), int(rng.integers(3)),
            int(rng.integers(8)), int(rng.integers(2**16)))
           for _ in range(6)]
    _interpret(ops)


# -- pow2 sigma-grid bucketing ----------------------------------------------

class TestSigmaGridBucketing:
    def test_pow2_bucket(self):
        assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
            [1, 2, 4, 4, 8, 8, 8, 16]
        assert pow2_bucket(3, floor=8) == 8

    @pytest.mark.parametrize("n_sigmas", [1, 2, 3, 5, 6])
    def test_padded_grid_lanes_exact(self, n_sigmas):
        """A padded (non-pow2) sigma grid returns the same bits for the
        real sigmas as the exactly-pow2 grid containing them: the repeated
        sentinel sigma must not leak into real lanes."""
        pool = _make_pool()
        sigmas = [0.05 * (i + 1) for i in range(n_sigmas)]
        padded_to = pow2_bucket(n_sigmas)
        got = pool.solve_batch("dense0", sigmas, method="chol")
        assert got.shape[0] == n_sigmas
        full = pool.solve_batch(
            "dense0", sigmas + [sigmas[-1]] * (padded_to - n_sigmas),
            method="chol")
        assert (np.asarray(got) == np.asarray(full)[:n_sigmas]).all()
        pool.close()


# -- SolveBatcher ------------------------------------------------------------

class TestSolveBatcher:
    def test_lone_request(self):
        pool = _make_pool()
        with SolveBatcher(pool) as batcher:
            w = batcher.solve("dense0", SIGMA)
            assert (np.asarray(w) == np.asarray(pool.solve("dense0",
                                                           SIGMA))).all()
            assert batcher.summary()["requests"] == 1
        pool.close()

    def test_burst_coalesces_and_is_exact(self):
        pool = _make_pool()
        lone = {(n, s): np.asarray(pool.solve(n, s))
                for n in TENANTS for s in (SIGMA, SIGMA2)}
        with SolveBatcher(pool, window_s=0.05) as batcher:
            barrier = threading.Barrier(len(lone))
            results: dict = {}

            def ask(key):
                barrier.wait()
                results[key] = np.asarray(batcher.solve(*key))

            threads = [threading.Thread(target=ask, args=(k,)) for k in lone]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = batcher.summary()
        for key, w in results.items():
            assert (w == lone[key]).all(), key
        assert stats["requests"] == len(lone)
        # Six concurrent requests released together through a generous
        # window must coalesce into fewer sweeps than requests.
        assert stats["sweeps"] < stats["requests"]
        assert stats["max_batch_seen"] >= 2
        pool.close()

    def test_bad_tenant_fails_alone(self):
        """A nonexistent tenant in a batch fails only its own future — the
        fallback re-runs survivors as lone solves."""
        pool = _make_pool()
        with SolveBatcher(pool, window_s=0.05) as batcher:
            barrier = threading.Barrier(2)
            out: dict = {}

            def good():
                barrier.wait()
                out["good"] = np.asarray(batcher.solve("dense0", SIGMA))

            def bad():
                barrier.wait()
                try:
                    batcher.solve("missing", SIGMA)
                    out["bad"] = None
                except KeyError as e:
                    out["bad"] = e

            ts = [threading.Thread(target=good), threading.Thread(target=bad)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        assert isinstance(out["bad"], KeyError)
        assert (out["good"] == np.asarray(pool.solve("dense0", SIGMA))).all()
        pool.close()

    def test_submit_requires_running(self):
        pool = _make_pool()
        batcher = SolveBatcher(pool)
        with pytest.raises(RuntimeError, match="not running"):
            batcher.submit("dense0", SIGMA)
        batcher.start()
        assert batcher.alive
        batcher.stop()
        assert not batcher.alive
        pool.close()


# -- wire integration --------------------------------------------------------

class TestWireBatchedSolve:
    def test_loopback_bitwise_and_summary(self):
        pool = _make_pool()
        dispatcher = transport.WireDispatcher(pool)
        with SolveBatcher(pool) as batcher:
            dispatcher.solve_batcher = batcher
            c = transport.FrameClient(transport.LoopbackChannel(dispatcher))
            c.hello("dense0")
            w = c.solve(SIGMA)
            assert (np.asarray(w) == np.asarray(
                jax.device_get(pool.solve("dense0", SIGMA)))).all()
            assert dispatcher.summary()["solve_batcher"]["requests"] >= 1
            c.close()
        pool.close()

    def test_loopback_unknown_tenant_acks_false(self):
        pool = _make_pool()
        dispatcher = transport.WireDispatcher(pool)
        with SolveBatcher(pool) as batcher:
            dispatcher.solve_batcher = batcher
            c = transport.FrameClient(transport.LoopbackChannel(dispatcher))
            c.hello("ghost")
            with pytest.raises(transport.TransportError,
                               match="unknown tenant"):
                c.solve(SIGMA)
            c.close()
        pool.close()

    def test_tcp_frameserver_window_bitwise(self):
        """FrameServer(solve_window_s=...) wires the batcher end to end:
        concurrent TCP SOLVEs across tenants return lone-solve bits."""
        pool = _make_pool()
        with transport.FrameServer(pool, solve_window_s=0.02) as srv:
            lone = {n: np.asarray(jax.device_get(pool.solve(n, SIGMA)))
                    for n in TENANTS}
            barrier = threading.Barrier(len(TENANTS))
            got: dict = {}

            def ask(name):
                c = transport.FrameClient(
                    transport.TCPChannel(srv.host, srv.port, timeout_s=30.0))
                c.hello(name)
                barrier.wait()
                got[name] = c.solve(SIGMA)
                c.close()

            ts = [threading.Thread(target=ask, args=(n,)) for n in TENANTS]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert srv.dispatcher.summary()["solve_batcher"]["requests"] \
                >= len(TENANTS)
        for name in TENANTS:
            assert (got[name] == lone[name]).all(), name
        pool.close()


# -- admission control / quotas ---------------------------------------------

class TestAdmissionControl:
    def _stats(self, seed=0):
        A, b = _rows(seed)
        return core.compute_stats(A, b)

    def test_admission_error_is_value_error(self):
        assert issubclass(AdmissionError, ValueError)

    def test_max_tenants(self):
        pool = EnginePool(max_tenants=2)
        pool.create_tenant("a", clients=[self._stats(0)], placement="dense")
        pool.create_tenant("b", clients=[self._stats(1)], placement="dense")
        with pytest.raises(AdmissionError, match="max_tenants"):
            pool.create_tenant("c", clients=[self._stats(2)],
                               placement="dense")
        assert pool.admission_rejections == 1
        # Dropping a tenant frees the slot.
        pool.drop_tenant("a")
        pool.create_tenant("c", clients=[self._stats(2)], placement="dense")
        pool.close()

    def test_stat_budget_bytes(self):
        one_tenant = (D * D + D) * 4      # float32 gram + moment estimate
        pool = EnginePool(stat_budget_bytes=int(one_tenant * 1.5))
        pool.create_tenant("a", clients=[self._stats(0)], placement="dense")
        assert pool.resident_stat_bytes() >= one_tenant
        with pytest.raises(AdmissionError, match="stat_budget_bytes"):
            pool.create_tenant("b", clients=[self._stats(1)],
                               placement="dense")
        assert pool.resident_bytes() >= pool.resident_stat_bytes()
        pool.close()

    def test_max_clients_per_tenant(self):
        pool = EnginePool(max_clients_per_tenant=2)
        pool.create_tenant("a", clients={0: self._stats(0)},
                           placement="dense")
        pool.ingest("a", self._stats(1), client_id=1)
        # Accumulating under an EXISTING id is not a new retained entry.
        pool.ingest("a", self._stats(2), client_id=1)
        # Anonymous ingests retain nothing and always pass.
        A, b = _rows(3, n=2)
        pool.ingest_rows("a", A, b)
        with pytest.raises(AdmissionError, match="max_clients_per_tenant"):
            pool.ingest("a", self._stats(4), client_id=2)
        # A dropped client still counts (Thm-8 restorability is retained
        # state) — quota clears only when the entry is gone.
        pool.drop("a", 1)
        with pytest.raises(AdmissionError, match="max_clients_per_tenant"):
            pool.ingest("a", self._stats(5), client_id=2)
        pool.close()

    def test_wire_quota_refusal_is_typed_ack(self):
        """Over the wire a quota refusal must surface as AckFrame(ok=False),
        not a dead session."""
        pool = EnginePool(max_clients_per_tenant=1)
        pool.create_tenant("a", clients={"c0": self._stats(0)},
                           placement="dense")
        dispatcher = transport.WireDispatcher(pool)
        c = transport.FrameClient(transport.LoopbackChannel(dispatcher))
        c.hello("a")
        with pytest.raises(transport.TransportError,
                           match="max_clients_per_tenant"):
            c.upload_stats(self._stats(1), client_id="c1")
        # The session survives: a solve still works.
        assert c.solve(SIGMA).shape == (D,)
        c.close()
        pool.close()
