"""Substrate units: optimizer, checkpointing, data pipeline, sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import checkpoint
from repro.data import BatchSpec, EmbeddingPipeline, TokenPipeline
from repro.launch.sharding import DEFAULT_RULES
from repro.optim import adamw


class TestAdamW:
    def test_minimizes_quadratic(self):
        cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                                total_steps=200)
        params = {"w": jnp.ones((8,), jnp.bfloat16) * 4}
        state = adamw.init(params)

        @jax.jit
        def step(params, state):
            grads = jax.tree.map(lambda p: (p.astype(jnp.float32) * 2)
                                 .astype(p.dtype), params)
            return adamw.apply(grads, state, cfg)

        for _ in range(200):
            params, state = step(params, state)
        assert float(jnp.abs(state["master"]["w"]).max()) < 0.15

    def test_schedule_warmup_cosine(self):
        cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                                min_lr_ratio=0.1)
        lr0 = float(adamw.schedule(cfg, jnp.asarray(1)))
        lr_peak = float(adamw.schedule(cfg, jnp.asarray(10)))
        lr_end = float(adamw.schedule(cfg, jnp.asarray(100)))
        assert lr0 < 0.2 and abs(lr_peak - 1.0) < 1e-5
        assert abs(lr_end - 0.1) < 1e-2

    def test_master_weights_fp32(self):
        params = {"w": jnp.ones((4,), jnp.bfloat16)}
        state = adamw.init(params)
        assert state["master"]["w"].dtype == jnp.float32


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6.0).reshape(2, 3),
                "b": {"c": jnp.asarray(7, jnp.int32)}}
        checkpoint.save_pytree(tree, tmp_path, step=3)
        assert checkpoint.latest_step(tmp_path) == 3
        restored = checkpoint.load_pytree(tree, tmp_path, step=3)
        np.testing.assert_array_equal(restored["a"], tree["a"])
        assert int(restored["b"]["c"]) == 7

    def test_multiple_steps(self, tmp_path):
        tree = {"w": jnp.zeros((2,))}
        for s in (1, 5, 2):
            checkpoint.save_pytree(tree, tmp_path, step=s)
        assert checkpoint.latest_step(tmp_path) == 5


class TestPipelines:
    def test_token_pipeline_deterministic_and_sharded(self):
        spec = BatchSpec(global_batch=8, seq_len=16, vocab_size=100)
        p0 = TokenPipeline(spec, seed=1, shard_index=0, num_shards=2)
        p1 = TokenPipeline(spec, seed=1, shard_index=1, num_shards=2)
        b0a, b0b = p0.batch(0), p0.batch(0)
        np.testing.assert_array_equal(b0a["tokens"], b0b["tokens"])
        assert b0a["tokens"].shape == (4, 16)
        assert not np.array_equal(np.asarray(p0.batch(0)["tokens"]),
                                  np.asarray(p1.batch(0)["tokens"]))
        # labels are next-token shifted
        rawa = np.asarray(b0a["tokens"]); rawl = np.asarray(b0a["labels"])
        assert rawa.shape == rawl.shape

    def test_zipf_skew(self):
        spec = BatchSpec(global_batch=16, seq_len=64, vocab_size=1000)
        p = TokenPipeline(spec)
        toks = np.asarray(p.batch(0)["tokens"]).ravel()
        assert (toks < 10).mean() > 0.2  # head-heavy marginal

    def test_embedding_pipeline(self):
        p = EmbeddingPipeline(global_batch=4, seq_len=8, d_model=16)
        b = p.batch(0)
        assert b["embeddings"].shape == (4, 8, 16)


class TestShardingRules:
    """Resolution against an abstract 16x16 (and 2x16x16) mesh — no devices."""

    def _mesh(self, multi=False):
        shape = (2, 16, 16) if multi else (16, 16)
        axes = ("pod", "data", "model") if multi else ("data", "model")
        return AbstractMesh(shape, axes)

    def test_param_2d_sharding(self):
        spec = DEFAULT_RULES.resolve(P("embed", "ff"), (8192, 29568), self._mesh())
        assert spec == P("data", "model")

    def test_kv_heads_fallback_to_head_dim(self):
        # qwen2: kv_heads=8 not divisible by model=16 -> head_dim takes it
        spec = DEFAULT_RULES.resolve(P("batch", "seq_cache", "kv_heads", "head_dim"),
                                     (128, 32768, 8, 128), self._mesh())
        assert spec == P("data", None, None, "model")

    def test_kv_heads_direct_when_divisible(self):
        # gemma3: kv=16 divisible -> kv_heads gets model, head_dim replicated
        spec = DEFAULT_RULES.resolve(P("batch", "seq_cache", "kv_heads", "head_dim"),
                                     (128, 32768, 16, 128), self._mesh())
        assert spec == P("data", None, "model", None)

    def test_experts_fallback_mixtral(self):
        # 8 experts on model=16 -> expert ff dim picks up the axis
        spec = DEFAULT_RULES.resolve(P("experts", "embed", "ff"),
                                     (8, 6144, 16384), self._mesh())
        assert spec == P(None, "data", "model")
        spec16 = DEFAULT_RULES.resolve(P("experts", "embed", "ff"),
                                       (16, 4096, 6400), self._mesh())
        assert spec16 == P("model", "data", None)

    def test_batch_composite_multipod(self):
        spec = DEFAULT_RULES.resolve(P("batch", "seq"), (256, 4096),
                                     self._mesh(multi=True))
        assert spec == P(("pod", "data"), None)

    def test_batch_one_replicated(self):
        spec = DEFAULT_RULES.resolve(P("batch", "seq_cache", "kv_heads", "head_dim"),
                                     (1, 524288, 32, 64), self._mesh())
        assert spec[0] is None

    def test_no_axis_reuse(self):
        # embeddings input: batch takes data; embed must NOT reuse data
        spec = DEFAULT_RULES.resolve(P("batch", "seq", "embed"),
                                     (32, 32768, 1280), self._mesh())
        assert spec == P("data", None, None)
