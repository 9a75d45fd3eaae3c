"""Property tests: random engine mutation interleavings vs cold references.

Drives arbitrary ``ingest`` / ``drop`` / ``restore`` / ``ingest_rows`` /
``ingest_rows_async`` / ``flush`` sequences against a FusionEngine (on BOTH
backends) while mirroring the state in plain python, and asserts after EVERY
prefix that the engine's solve matches a cold ``core.fusion.solve_ridge``
over exactly the rows the mirror says are active (the solve itself drains
any queued async deltas, so the coalescer must be exactly transparent to
reads). This is the Thm 1 / Thm 8 / §VI-C algebra under adversarial
interleaving — including the incremental (blocked) up/downdate path on both
backends and flushes that batch several queued deltas into one mutation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro import core
from repro.core import fusion
from repro.launch import mesh as mesh_lib
from repro.server import CoalescerPolicy, FusionEngine, ShardedBackend

D = 6
SIGMA = 0.1

# (kind, client slot, data seed); the interpreter below resolves slots
# against whatever clients currently exist, so any sequence is valid.
# Kinds: 0 ingest, 1 drop, 2 restore, 3 ingest_rows, 4 ingest_rows_async,
# 5 explicit flush.
_OP = st.tuples(st.integers(0, 5), st.integers(0, 7), st.integers(0, 2**16))


def _rows(seed, n=10):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (n, D)), jax.random.normal(k2, (n,)))


def _make_engine(backend_kind: str) -> FusionEngine:
    # max_rank=7 so some interleavings auto-flush mid-sequence and others
    # only drain at the solve — both flush paths get exercised.
    policy = CoalescerPolicy(max_rank=7)
    if backend_kind == "sharded":
        # Every device the platform has (one, in-process on the CPU); the
        # full-mesh equivalence lives in test_sharded_backend's 8-device child.
        mesh = mesh_lib.make_device_mesh()
        return FusionEngine(D, backend=ShardedBackend(D, mesh, block_size=8),
                            max_update_rank=100, coalesce=policy)
    return FusionEngine(D, max_update_rank=100, coalesce=policy)


@pytest.mark.parametrize("backend_kind", ["dense", "sharded"])
@hypothesis.given(ops=st.lists(_OP, min_size=1, max_size=6))
@hypothesis.settings(max_examples=12, deadline=None)
def test_mutation_interleavings_match_cold_solve(backend_kind, ops):
    eng = _make_engine(backend_kind)
    active: dict[int, list[tuple[jax.Array, jax.Array]]] = {}
    dropped: dict[int, list[tuple[jax.Array, jax.Array]]] = {}
    anon: list[tuple[jax.Array, jax.Array]] = []
    next_id = 0

    for kind, slot, seed in ops:
        if kind == 0:                               # ingest a new client
            A, b = _rows(seed)
            eng.ingest(core.compute_stats(A, b), client_id=next_id)
            active[next_id] = [(A, b)]
            next_id += 1
        elif kind == 1 and active:                  # drop an existing client
            cid = sorted(active)[slot % len(active)]
            eng.drop(cid)
            dropped[cid] = active.pop(cid)
        elif kind == 2 and dropped:                 # restore a dropped client
            cid = sorted(dropped)[slot % len(dropped)]
            eng.restore(cid)
            active[cid] = dropped.pop(cid)
        elif kind == 3:                             # anonymous streaming rows
            A, b = _rows(seed, n=4)
            eng.ingest_rows(A, b)
            anon.append((A, b))
        elif kind == 4:                             # queued streaming rows
            A, b = _rows(seed, n=4)
            eng.ingest_rows_async(A, b)
            anon.append((A, b))
        elif kind == 5:                             # explicit flush
            eng.flush()
        else:
            continue  # drop/restore with nothing to act on: no-op

        chunks = [c for chunks in active.values() for c in chunks] + anon
        if not chunks:
            continue
        A_all = jnp.concatenate([a for a, _ in chunks])
        b_all = jnp.concatenate([b for _, b in chunks])
        w_ref = fusion.solve_ridge(core.compute_stats(A_all, b_all), SIGMA)
        np.testing.assert_allclose(np.asarray(eng.solve(SIGMA)),
                                   np.asarray(w_ref), rtol=2e-4, atol=2e-4)
        assert eng.count == A_all.shape[0]
