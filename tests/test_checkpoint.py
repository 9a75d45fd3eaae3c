"""Pytree checkpointing: the snapshot substrate of the durable pool.

``repro.checkpoint`` is what ``EnginePool`` trusts its snapshots to — a
restore that silently changed a dtype, lost a leaf, or dropped a sharding
would corrupt every crash recovery downstream. Pinned here: exact roundtrips
across the dtypes the wire actually negotiates (f64/f32/bf16), step
discovery with gaps, and restore-onto-template casting/resharding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint
from repro.launch import mesh as mesh_lib


def _tree(rng):
    """A nested pytree shaped like real engine state: dict/list/tuple mix,
    mixed dtypes, a scalar leaf. Dtypes are the ones the f32-default device
    policy preserves (wide leaves are pinned separately)."""
    return {
        "G": rng.standard_normal((5, 5)).astype(np.float32),
        "h": rng.standard_normal(5).astype(np.float32),
        "count": np.int32(17),
        "nested": {
            "factors": [rng.standard_normal((3, 3)).astype(np.float32),
                        rng.standard_normal(3).astype(np.float32)],
            "meta": (np.float32(0.25), np.arange(4, dtype=np.int32)),
        },
    }


class TestRoundtrip:
    def test_exact_roundtrip_bits(self, tmp_path):
        tree = _tree(np.random.default_rng(0))
        checkpoint.save_pytree(tree, tmp_path, step=3)
        out = checkpoint.load_pytree(tree, tmp_path, step=3)
        ref_leaves, ref_def = jax.tree_util.tree_flatten(tree)
        out_leaves, out_def = jax.tree_util.tree_flatten(out)
        assert ref_def == out_def
        for r, o in zip(ref_leaves, out_leaves):
            o = np.asarray(o)
            assert o.dtype == np.asarray(r).dtype
            assert o.tobytes() == np.asarray(r).tobytes()

    def test_bf16_leaves_roundtrip(self, tmp_path):
        """bf16 is a wire dtype AND an engine storage dtype: its leaves must
        survive npz (which has no native bf16) bit-for-bit."""
        rng = np.random.default_rng(1)
        tree = {"w": jnp.asarray(rng.standard_normal(64), jnp.bfloat16),
                "G": jnp.asarray(rng.standard_normal((8, 8)), jnp.bfloat16)}
        checkpoint.save_pytree(tree, tmp_path, step=0)
        out = checkpoint.load_pytree(tree, tmp_path, step=0)
        for k in tree:
            assert out[k].dtype == jnp.bfloat16
            assert (np.asarray(out[k], np.float32).tobytes()
                    == np.asarray(tree[k], np.float32).tobytes())

    def test_restore_casts_to_template_dtype(self, tmp_path):
        """The template owns the dtype contract: restoring an f32 save onto
        a bf16 template yields bf16 with bf16-rounded values."""
        x = np.linspace(0, 1, 16, dtype=np.float32)
        checkpoint.save_pytree({"x": x}, tmp_path, step=1)
        down = checkpoint.load_pytree(
            {"x": jnp.zeros(16, jnp.bfloat16)}, tmp_path, step=1)
        assert down["x"].dtype == jnp.bfloat16
        assert (np.asarray(down["x"], np.float32).tobytes()
                == np.asarray(jnp.asarray(x, jnp.bfloat16),
                              np.float32).tobytes())

    def test_wide_leaves_follow_device_policy(self, tmp_path):
        """Without ``jax_enable_x64`` (the server's documented default
        policy), restored 64-bit leaves land as their 32-bit device types —
        the npz itself keeps full width, so flipping x64 on recovers it."""
        tree = {"h": np.linspace(0, 1, 8), "n": np.int64(9)}   # f64 / i64
        checkpoint.save_pytree(tree, tmp_path, step=2)
        with np.load(tmp_path / "step_00000002.npz") as data:
            assert data["['h']"].dtype == np.float64           # full width
        out = checkpoint.load_pytree(tree, tmp_path, step=2)
        if jax.config.jax_enable_x64:
            assert np.asarray(out["h"]).dtype == np.float64
        else:
            assert np.asarray(out["h"]).dtype == np.float32
            assert np.asarray(out["n"]).dtype == np.int32

    def test_missing_leaf_key_raises(self, tmp_path):
        checkpoint.save_pytree({"a": np.ones(2)}, tmp_path, step=0)
        with pytest.raises(KeyError):
            checkpoint.load_pytree({"a": np.ones(2), "b": np.ones(2)},
                                   tmp_path, step=0)

    def test_manifest_written(self, tmp_path):
        tree = _tree(np.random.default_rng(2))
        path = checkpoint.save_pytree(tree, tmp_path, step=42)
        assert path.name == "step_00000042.npz"
        manifest = (tmp_path / "step_00000042.json").read_text()
        assert '"step": 42' in manifest
        n_leaves = len(jax.tree_util.tree_leaves(tree))
        assert f'"num_leaves": {n_leaves}' in manifest


class TestLatestStep:
    def test_gaps_and_zero(self, tmp_path):
        for step in (0, 3, 17):
            checkpoint.save_pytree({"x": np.ones(1)}, tmp_path, step=step)
        assert checkpoint.latest_step(tmp_path) == 17

    def test_empty_dir(self, tmp_path):
        assert checkpoint.latest_step(tmp_path) is None

    def test_missing_dir(self, tmp_path):
        assert checkpoint.latest_step(tmp_path / "never_made") is None

    def test_ignores_foreign_files(self, tmp_path):
        checkpoint.save_pytree({"x": np.ones(1)}, tmp_path, step=5)
        (tmp_path / "step_junk.npz").write_bytes(b"")
        (tmp_path / "wal_00000009.log").write_bytes(b"")
        assert checkpoint.latest_step(tmp_path) == 5


class TestShardedRestore:
    def test_restore_onto_sharded_template(self, tmp_path):
        """Save a replicated tree, restore onto a mesh-sharded template: the
        restored leaves carry the template's sharding (this is exactly what
        the pool's snapshot restore does for sharded-placement tenants)."""
        mesh = mesh_lib.make_device_mesh()
        sharding = NamedSharding(mesh, P("data", "model"))
        rng = np.random.default_rng(3)
        G = rng.standard_normal((8, 8)).astype(np.float32)
        h = rng.standard_normal(8).astype(np.float32)
        checkpoint.save_pytree({"G": G, "h": h}, tmp_path, step=7)

        template = {"G": jax.device_put(jnp.zeros((8, 8), jnp.float32),
                                        sharding),
                    "h": jax.device_put(jnp.zeros(8, jnp.float32),
                                        NamedSharding(mesh, P("data")))}
        out = checkpoint.load_pytree(template, tmp_path, step=7)
        assert out["G"].sharding.is_equivalent_to(template["G"].sharding,
                                                  out["G"].ndim)
        assert out["h"].sharding.is_equivalent_to(template["h"].sharding,
                                                  out["h"].ndim)
        assert np.asarray(out["G"]).tobytes() == G.tobytes()
        assert np.asarray(out["h"]).tobytes() == h.tobytes()

    def test_sharded_save_gathers_to_host(self, tmp_path):
        """Saving a sharded tree works (leaves gather to host) and restores
        onto a plain template as ordinary replicated arrays."""
        mesh = mesh_lib.make_device_mesh()
        x = jax.device_put(jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
                           NamedSharding(mesh, P("data", "model")))
        checkpoint.save_pytree({"x": x}, tmp_path, step=0)
        out = checkpoint.load_pytree({"x": jnp.zeros((4, 4), jnp.float32)},
                                     tmp_path, step=0)
        assert np.asarray(out["x"]).tobytes() == np.asarray(x).tobytes()
