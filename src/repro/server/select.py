"""Auto backend selection: dense vs sharded from the *measured* crossover.

The ROADMAP rule for backend choice is "read data, not folklore":
``benchmarks/sharded_fusion_bench.py`` writes a dense-vs-sharded solve-time
table per PR (``experiments/repro/sharded_fusion_bench.json``) whose
``crossover_d`` is the first dimension where the sharded cold solve actually
beat the dense one on the bench host. This module turns that record into a
picker:

  * ``backend_threshold()`` — the d at or above which the sharded backend
    wins. Falls back to +inf (dense everywhere) when the table is missing,
    reports a null crossover (the honest reading of a single-host CPU
    measurement, where psums buy no bandwidth), or was measured on another
    jax backend than the one running (a CPU crossover says nothing about a
    TPU's).
  * ``auto_backend(dim, mesh)`` — a ready backend instance for the engine;
    ``FusionEngine.from_clients(..., backend="auto", mesh=...)`` and
    ``fed.run_one_shot(..., backend="auto", mesh=...)`` route through it.

An explicit ``threshold=`` always wins over the table (capacity planners on
real slices can pin their own number without re-running the bench).
"""
from __future__ import annotations

import json
import math
import pathlib

import jax
import jax.numpy as jnp

_TABLE = (pathlib.Path(__file__).resolve().parents[3]
          / "experiments" / "repro" / "sharded_fusion_bench.json")


def backend_threshold(threshold: float | None = None,
                      table: pathlib.Path | str | None = None) -> float:
    """Dimension at/above which the sharded backend is preferred.

    Resolution order: explicit ``threshold`` -> ``crossover_d`` from the
    measured table, when its ``host.jax_backend`` is the running backend ->
    +inf (dense wins everywhere measured).
    """
    if threshold is not None:
        return float(threshold)
    path = pathlib.Path(table) if table is not None else _TABLE
    try:
        record = json.loads(path.read_text())
    except (OSError, ValueError):
        return math.inf
    if record.get("host", {}).get("jax_backend") != jax.default_backend():
        return math.inf
    crossover = record.get("crossover_d")
    return float(crossover) if crossover is not None else math.inf


def prefer_sharded(dim: int, *, threshold: float | None = None,
                   table: pathlib.Path | str | None = None) -> bool:
    """Would ``auto`` place this dimension on the sharded backend?

    The mesh-free half of :func:`auto_backend`: a multi-tenant pool asks this
    *before* deciding whether to build (or reuse) its shared mesh, so dense
    pools never pay mesh construction at all.
    """
    return dim >= backend_threshold(threshold, table)


def auto_backend(dim: int, mesh=None, *, threshold: float | None = None,
                 table: pathlib.Path | str | None = None,
                 dtype=jnp.float32, **sharded_kwargs):
    """Backend instance for ``dim``: sharded iff a mesh is given AND ``dim``
    clears the (measured or explicit) crossover threshold."""
    from repro.server.backends import DenseBackend
    from repro.server.distributed import ShardedBackend

    if mesh is not None and prefer_sharded(dim, threshold=threshold,
                                           table=table):
        return ShardedBackend(dim, mesh, dtype=dtype, **sharded_kwargs)
    return DenseBackend(dim, dtype=dtype)
