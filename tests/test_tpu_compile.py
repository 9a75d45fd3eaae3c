"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs the kernel body in Python and
cannot see what the chip's compiler refuses: blocks whose XLA and Mosaic
tilings disagree, or more VMEM than a kernel may use. These tests lower the
public ``kernels.ops`` wrappers for a v5e chip that is described, not
attached (``jax.experimental.topologies``), compile them with the TPU
compiler, and check that the program really contains the Mosaic kernel.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and a collection-time load
would make test workers collect different tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described-chip compile cannot be read back from the persistent
    # cache without a chip; keep it out of the cache entirely.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_gram_moment_compiles(one_chip):
    _compile(lambda A, b: ops.gram_moment(A, b, interpret=False),
             (2048, 4096), (2048,), sharding=one_chip)


@pytest.mark.parametrize("m", [1024, 2048, 4096])
def test_sketch_gram_compiles(one_chip, m):
    _compile(lambda A, b, R: ops.sketch_gram(A, b, R, interpret=False),
             (2048, 4096), (2048,), (4096, m), sharding=one_chip)


@pytest.mark.parametrize("D", [1024, 2048, 4096])
def test_rff_gram_compiles(one_chip, D):
    _compile(lambda X, b, W, c: ops.rff_gram(X, b, W, c, interpret=False),
             (2048, 1024), (2048,), (1024, D), (D,), sharding=one_chip)


@pytest.mark.parametrize("c1", [32, 2048, 4064])
def test_gemm_nt_compiles_at_blocked_update_shapes(one_chip, c1):
    """The trailing GEMM of ``chol_update_blocked`` at d=4096, bs=32, r=32:
    Z = [L21 | X2^T] is (d - c1, bs + r), T^T is (bs + r, bs + r)."""
    d, bs, r = 4096, 32, 32
    rows, w = d - c1, bs + r
    _compile(lambda C, Z, T: ops.gemm_nt(C, Z, T, alpha=1.0, interpret=False),
             (rows, w), (rows, w), (w, w), sharding=one_chip)
