"""Placement in the harness: dense cells as before, sharded groups' inputs.

A tenant group without ``placement`` is dense and deploys as it always
did: the same inputs from the seed, one server placement, no mesh. A
sharded group makes the same rows and each client's statistics alone.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, deploy, run, spec
from bench.tests.small_cells import CELLS, FOUR_CHIP_CELLS

SEED = 2**33 + 7


def _config(cell: str, cells=CELLS) -> dict:
    return run._merge(spec.config(spec.workload(cell)["config"]),
                      cells[cell]["config"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_dense_cells_build_no_mesh(cell):
    cfg = _config(cell)
    dep = deploy.build(cfg, SEED, [], 1)
    try:
        deploy.admit(dep)
        deploy.warm(dep, 4)
        summary = dep.pool.summary()
        assert summary["meshes_built"] == 0
        assert summary["placements"] == {"dense": len(dep.tenants)}
        assert dep.server.dispatcher.placement == "dense"
    finally:
        dep.cleanup()


def test_config_without_placement_deploys_as_before():
    group = _config("silo_d4096.stream")["tenants"][0]
    assert "placement" not in group and data.placement_of(group) == "dense"
    grp = data.make_group(SEED, 0, group, [0], [1], 8)
    # The inputs the dense path always made: the same draw, the vmapped
    # client statistics of it.
    A, b, _, db = data._federations(
        jax.random.fold_in(data.key_from_seed(SEED), 0),
        jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
        tenants=1, clients=group["clients"],
        rows=group["rows_per_client"], dim=group["dim"], delta_rows=8,
        gamma=group["gamma"], noise_std=group["noise_std"])
    np.testing.assert_array_equal(grp.rows[0], np.asarray(A))
    np.testing.assert_array_equal(grp.deltas[1], np.asarray(db))
    want = data._dense_stats(A, b)
    np.testing.assert_array_equal(np.asarray(grp.stats.gram),
                                  np.asarray(want.gram))
    explicit = data.make_group(SEED, 0, dict(group, placement="dense"),
                               [0], [1], 8)
    np.testing.assert_array_equal(np.asarray(explicit.stats.gram),
                                  np.asarray(grp.stats.gram))
    assert deploy.placement({"tenants": [group]}) == "dense"


def test_sharded_client_stats_equal_the_vmapped_stats():
    group = _config("silo_d16384.read", FOUR_CHIP_CELLS)["tenants"][0]
    assert data.placement_of(group) == "sharded"
    sharded = data.make_group(SEED, 0, group, [], [], 1)
    dense = data.make_group(SEED, 0, dict(group, placement="dense"), [], [],
                            1)
    assert sharded.stats is None
    for a, b in zip(sharded.rows, dense.rows):
        np.testing.assert_array_equal(a, b)
    for k in range(group["clients"]):
        got, want = sharded.client_stats(0, k), dense.client_stats(0, k)
        scale = float(jnp.abs(want.gram).max())
        np.testing.assert_allclose(np.asarray(got.gram),
                                   np.asarray(want.gram), rtol=1e-5,
                                   atol=1e-6 * scale)
        np.testing.assert_allclose(np.asarray(got.moment),
                                   np.asarray(want.moment), rtol=1e-5,
                                   atol=1e-6 * scale)
        assert int(got.count) == int(want.count)
        assert float(got.yty) == pytest.approx(float(want.yty), rel=1e-6)


@pytest.mark.parametrize("groups", [
    [{"placement": "dense"}, {"placement": "sharded"}],
    [{"placement": "sharded", "kind": "rff"}],
    [{"placement": "everywhere"}],
], ids=["mixed", "sharded_rff", "unknown"])
def test_refused_placements(groups):
    cfg = {"tenants": [{"kind": "dense", **g} for g in groups]}
    with pytest.raises(SystemExit):
        deploy.placement(cfg)
