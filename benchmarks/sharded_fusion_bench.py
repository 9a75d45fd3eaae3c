"""Dense-vs-sharded FusionEngine crossover: measured, not asserted.

For a grid of dimensions d, times the cold factor+solve and the cached
(serving) solve on both backends over a mesh of every device (8 host-platform
devices on the CPU, every attached chip on an accelerator) and records the
ratio per d plus the first d where the sharded solve wins
(``crossover_d``; null when the dense path wins everywhere measured — the
expected outcome on a single host, where psums are memcpys and the dense
backend has no communication at all; the table is the point, so capacity
planning reads data instead of folklore). Every row also carries an
equivalence check against ``core.fusion.solve_ridge`` and a sharding-spec
check that the fused Gram stayed block-sharded.

On the CPU, jax locks the device count at first init, so the measurement
runs in a child process that sets ``--xla_force_host_platform_device_count=8``
before importing jax; ``run()`` (the benchmarks.run entry) spawns the child
and reads back the JSON it writes to experiments/repro/. On an accelerator
``run()`` measures in its own process: a chip belongs to one process at a
time, so a child of a parent that touched jax could not reach it.

Usage: PYTHONPATH=src:. python benchmarks/sharded_fusion_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/sharded_fusion_bench.py`
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

_REPO = pathlib.Path(__file__).resolve().parents[1]
_OUT = _REPO / "experiments" / "repro"
_JSON = _OUT / "sharded_fusion_bench.json"


def _measure(smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common
    from repro.core import fusion
    from repro.core.sufficient_stats import compute_stats
    from repro.launch import mesh as mesh_lib
    from repro.server import FusionEngine, ShardedBackend

    mesh = mesh_lib.make_device_mesh()
    dims = [96, 192] if smoke else [128, 256, 384, 512, 768]
    reps = 3 if smoke else 7

    def median(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    claims = common.Claims("sharded_fusion")
    rows = []
    sigma = 0.1
    for d in dims:
        key = jax.random.PRNGKey(d)
        A = jax.random.normal(key, (2 * d, d))
        b = jax.random.normal(jax.random.PRNGKey(d + 1), (2 * d,))
        stats = compute_stats(A, b)
        w_ref = np.asarray(fusion.solve_ridge(stats, sigma))

        dense = FusionEngine.from_stats(stats)
        sharded = FusionEngine.from_stats(
            stats, backend=ShardedBackend(d, mesh))

        # warm compile on both paths, then check equivalence once
        w_s = np.asarray(sharded.solve(sigma))
        dense.solve(sigma)
        ok = np.allclose(w_s, w_ref, rtol=3e-4, atol=3e-4)
        claims.check(f"sharded_matches_dense_d{d}", ok,
                     f"max|dw|={np.abs(w_s - w_ref).max():.2e}")
        spec_ok = not sharded.backend.gram.sharding.is_fully_replicated \
            if jax.device_count() > 1 else True
        claims.check(f"gram_stays_sharded_d{d}", spec_ok,
                     str(sharded.backend.gram.sharding.spec))

        def cold(eng):
            eng._factors.clear()
            return eng.solve(sigma)

        t_dense_cold = median(lambda: cold(dense))
        t_shard_cold = median(lambda: cold(sharded))
        dense.solve(sigma)
        sharded.solve(sigma)
        t_dense_hot = median(lambda: dense.solve(sigma))
        t_shard_hot = median(lambda: sharded.solve(sigma))
        rows.append({
            "d": d, "padded": sharded.backend.padded,
            "dense_cold_ms": t_dense_cold * 1e3,
            "sharded_cold_ms": t_shard_cold * 1e3,
            "cold_ratio": t_shard_cold / t_dense_cold,
            "dense_cached_ms": t_dense_hot * 1e3,
            "sharded_cached_ms": t_shard_hot * 1e3,
            "cached_ratio": t_shard_hot / t_dense_hot,
        })

    crossover = next((r["d"] for r in rows if r["cold_ratio"] < 1.0), None)
    common.write_csv("sharded_fusion_bench", rows)
    bench = {"smoke": smoke, "mesh": dict((str(k), int(v))
                                          for k, v in mesh.shape.items()),
             "rows": rows, "crossover_d": crossover, "claims": claims.rows()}
    common.write_json("sharded_fusion_bench", bench)
    print("BENCH " + json.dumps({
        "crossover_d": crossover,
        **{f"d{r['d']}_cold_ratio": round(r["cold_ratio"], 2) for r in rows}}))


def run(smoke: bool = False) -> list[dict]:
    """Measure (in-process on an accelerator, in the 8-device child on the
    CPU), surface the output, return the claims."""
    import jax

    if jax.default_backend() != "cpu":
        _measure(smoke)
        return json.loads(_JSON.read_text())["claims"]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = f"{_REPO / 'src'}:{_REPO}"
    cmd = [sys.executable, str(pathlib.Path(__file__).resolve()), "--child"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=1800)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        return [{"table": "sharded_fusion", "claim": "child_ran",
                 "pass": False, "detail": out.stderr[-400:]}]
    return json.loads(_JSON.read_text())["claims"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the measurement in-process "
                         "(expects the 8-device XLA flag already set)")
    args = ap.parse_args()
    if args.child:
        _measure(args.smoke)
        sys.exit(0)
    failed = [c for c in run(smoke=args.smoke) if not c["pass"]]
    sys.exit(1 if failed else 0)
