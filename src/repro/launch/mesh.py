"""Production meshes (TPU v5e target).

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16) — the "pod" axis carries only data parallelism
(and the one-shot fusion psum), keeping cross-pod (DCI) traffic to gradient /
statistic reductions.

Defined as functions so importing this module never touches jax device state
(jax locks the device count on first init; dryrun.py sets it before
anything initializes jax).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_types(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types(len(axes)))


def make_host_mesh(shape=(4, 2), axes=("data", "model")):
    """Small mesh over host platform devices (tests).

    Assumes the platform actually exposes prod(shape) devices (i.e.
    ``--xla_force_host_platform_device_count`` was set before jax
    initialized) and raises otherwise.
    """
    return jax.make_mesh(shape, axes, **_axis_types(len(axes)))


def make_device_mesh(n: int | None = None, axes=("data", "model")):
    """2-D mesh over the first ``n`` of ``jax.devices()`` (default: all).

    Raises ``ValueError`` when fewer than ``n`` devices exist: a mesh that
    silently shrinks would run a "sharded" tenant on fewer chips than asked
    for (down to one) and report nothing. On the CPU platform the device
    count is fixed by ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    set *before* jax initializes.

    The ``n`` devices are arranged as the most-square (rows, cols)
    factorization with rows >= cols, so the fusion server's 2-D
    block-sharding gets balanced tiles.
    """
    devices = jax.devices()
    n = len(devices) if n is None else n
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"mesh of {n} devices requested; the {jax.default_backend()} "
            f"platform has {len(devices)}")
    cols = max(c for c in range(1, int(n ** 0.5) + 1) if n % c == 0)
    return jax.make_mesh((n // cols, cols), axes, devices=devices[:n],
                         **_axis_types(len(axes)))


def client_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that play the paper's 'clients' role (row-sharding axes)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# TPU v5e hardware constants (per chip), used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BANDWIDTH = 819e9             # bytes/s
ICI_LINK_BANDWIDTH = 50e9         # bytes/s per link
