"""The control: the reference computed one precision step down, in the program's place.

Every statistic and solve matmul of the server runs in float32 at HIGHEST.
The step below, the one a later change would be tempted by, is HIGH: three
bfloat16 passes (hi*hi + hi*lo + lo*hi, float32 accumulation). It is
emulated here explicitly, so the control means the same on every backend
(a CPU ignores matmul precision). The control builds each compared state's
statistics from the same rows (features, Gram, moment, the streamed
deltas) with those products, factors G + sigma I in float32 and solves.
A sound limit passes the program and fails this.

``python bench/control.py --workload <cell> --seeds a,b,c`` runs whole
cells (short windows) and prints the program's and the control's readings
per seed, for setting a limit; see PERF.md. With ``--fault <name>`` it
plants that fault of ``bench/faults.py`` in the program first and prints
the broken program's readings instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dot3(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b in three bfloat16 passes with float32 accumulation."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


@jax.jit
def _features(X, W, c):
    return jnp.sqrt(2.0 / W.shape[1]) * jnp.cos(dot3(X, W) + c)


@jax.jit
def _stats(T, y):
    return dot3(T.T, T), dot3(T.T, y[:, None])[:, 0]


@jax.jit
def _solve(G, h, sigma):
    L = jnp.linalg.cholesky(G + sigma * jnp.eye(G.shape[0], dtype=G.dtype))
    return jax.scipy.linalg.cho_solve((L, True), h)


def solve_stats(G: jax.Array, h: jax.Array, sigma: float) -> np.ndarray:
    """The control's w of statistics from :func:`stats`, at sigma."""
    return np.asarray(jax.device_get(_solve(G, h, jnp.float32(sigma))),
                      np.float64)


def stats(dep, gi: int, ti: int, deltas: list[int]
          ) -> tuple[jax.Array, jax.Array]:
    """The control's (G, h) of tenant (gi, ti) after ``deltas``."""
    grp = dep.groups[gi]
    A, b = grp.rows
    if grp.kind == "rff":
        from bench import reference

        fm = grp.maps[ti]
        W, c = (jnp.asarray(a, jnp.float32) for a in reference.rff_arrays(
            fm.seed, fm.d_orig, fm.m, fm.lengthscale))
        feat = lambda X: _features(jnp.asarray(X), W, c)  # noqa: E731
    else:
        feat = jnp.asarray
    G, h = _stats(feat(A[ti].reshape(-1, A.shape[-1])),
                  jnp.asarray(b[ti].reshape(-1)))
    for n in deltas:
        j = dep.delta_local(n)
        dG, dh = _stats(feat(grp.deltas[0][j]), jnp.asarray(grp.deltas[1][j]))
        G, h = G + dG, h + dh
    return G, h


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    from bench import faults, run

    ap.add_argument("--fault", default=None, choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)

    run.enable_cache()
    if args.fault:
        faults.FAULTS[args.fault](setattr)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, _ = run.run_cell(args.workload, seed, args.seconds,
                                 trace=False, control=not args.fault)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    import pathlib
    import sys

    sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1]),
                    str(pathlib.Path(__file__).resolve().parents[1] / "src")]
    sys.exit(main())
