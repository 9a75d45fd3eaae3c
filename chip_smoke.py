#!/usr/bin/env python3
"""Chip smoke: the federation server's main path on a TPU, checked in float64.

    python chip_smoke.py               # one chip: the cross-silo deployment
    python chip_smoke.py --four-chips  # the sharded backend on a 2x2 mesh

One chip. One cross-silo dense tenant after the paper's §V-A generator
(gamma = 0.5) with d scaled up: K = 20 clients x 1024 rows at d = 4096, f32,
seeded. Beside it one §IV-F random-Fourier-feature tenant at D = 2048 over
d_orig = 1024. Everything runs in this one process (a chip belongs to one
process); the clients are threads speaking the real wire over TCP loopback.

  phase1   client Phase 1 on the chip through the Pallas ingest kernels
  upload   Thm-4 STATS / §IV-F RFF frames with MOMENTS into a FrameServer
           in front of an EnginePool
  serve    SOLVE frames for the sigma grid through the SolveBatcher window,
           solve_report with intervals, predict
  stream   64 single-row §VI-C deltas through the coalescer, one flush: the
           blocked rank-64 factor update (gemm_nt on the chip)
  churn    Thm-8 drop of one client over the wire, then restore

After every phase the served weights, standard errors, interval widths and
predictions are compared with a float64 NumPy ridge solve on the host, built
from the same seeded rows and sharing no code with the server.

Four chips (``--four-chips``): a ShardedBackend on a 2x2 mesh at d = 16384
(G is 1 GiB, 256 MiB per chip), 8 clients x 4096 rows: cold solve, one
blocked update, cached solve, each against the dense backend on one chip and
the float64 host solve. Only that path runs.

Earlier stdout lines carry the device, the jax version, seconds per phase,
compile seconds and peak device memory. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits nonzero without it, as does a host without a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import pathlib
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Tolerances, fixed before any chip run. Errors are relative: ||x - x64|| /
# ||x64|| for vectors, max_i |x_i - x64_i| / x64_i for standard errors and
# interval widths. f32 round-off on w is ~1e-4 at the one-chip cell's
# conditioning (Gram eigenvalues within ~7x, RFF ~3.7x).
W_RTOL = 1e-3        # served weights and predictions
SE_RTOL = 1e-3       # standard errors and prediction-interval widths
STATS_RTOL = 1e-4    # fused (G, h, yty) after a drop; client statistics
# Four chips: d/n = 1/2 puts the Gram's eigenvalues ~34x apart.
SHARDED_RTOL = 2e-3
LEVEL = 0.95


@dataclasses.dataclass(frozen=True)
class Cell:
    """The one-chip deployment."""

    clients: int = 20
    rows: int = 1024
    dim: int = 4096
    rff_features: int = 2048
    rff_dim: int = 1024
    stream: int = 64
    queries: int = 64
    sigmas: tuple[float, ...] = (0.1, 1.0, 10.0, 100.0)
    report_sigma: float = 1.0
    seed: int = 0

    @property
    def lengthscale(self) -> float:
        # sqrt(d_orig)/4 keeps the phases X W within ~+-25 (accurate f32 cos)
        # and the feature Gram well conditioned.
        return math.sqrt(self.rff_dim) / 4


@dataclasses.dataclass(frozen=True)
class ShardedCell:
    """The four-chip sharded deployment."""

    clients: int = 8
    rows: int = 4096
    dim: int = 16384
    update_rows: int = 32
    sigma: float = 1.0
    seed: int = 0


ONE_CHIP = Cell()
FOUR_CHIPS = ShardedCell()


def require_tpu() -> dict:
    """The device as JAX reports it; exits nonzero unless it is a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU; JAX found "
                         f"{devices[0].platform!r} devices")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def enable_compilation_cache() -> str:
    from repro.launch.compile_cache import enable_compilation_cache as enable

    return enable()


# -- float64 reference (NumPy only) -------------------------------------------

class Ridge64:
    """Ridge statistics and closed forms in float64 on the host."""

    def __init__(self, d: int):
        self.G = np.zeros((d, d))
        self.h = np.zeros(d)
        self.yty = 0.0
        self.n = 0

    def add(self, T, y, sign: int = 1) -> None:
        T = np.asarray(T, np.float64)
        y = np.asarray(y, np.float64)
        self.G += sign * (T.T @ T)
        self.h += sign * (T.T @ y)
        self.yty += sign * float(y @ y)
        self.n += sign * len(y)

    def copy(self) -> "Ridge64":
        out = Ridge64(len(self.h))
        out.G, out.h = self.G.copy(), self.h.copy()
        out.yty, out.n = self.yty, self.n
        return out

    def solve(self, sigma: float) -> np.ndarray:
        return np.linalg.solve(self.G + sigma * np.eye(len(self.h)), self.h)

    def inference(self, sigma: float, Xq) -> dict:
        """w, coefficient stderr, and prediction mean/std at query rows.

        With M = (G + sigma I)^-1: Cov(w) = s2 M G M = s2 (M - sigma M^2),
        dof = d - sigma tr(M), s2 = RSS / (n - dof).
        """
        d = len(self.h)
        M = np.linalg.inv(self.G + sigma * np.eye(d))
        w = M @ self.h
        dof = d - sigma * np.trace(M)
        rss = self.yty - 2.0 * self.h @ w + w @ self.G @ w
        s2 = rss / (self.n - dof)
        var_w = s2 * (np.diag(M) - sigma * np.einsum("ij,ij->i", M, M))
        Xq = np.asarray(Xq, np.float64)
        XM = Xq @ M
        var_q = s2 * (1.0 + np.einsum("ij,ij->i", XM, Xq)
                      - sigma * np.einsum("ij,ij->i", XM, XM))
        return {"w": w, "stderr": np.sqrt(var_w), "pred": Xq @ w,
                "pred_std": np.sqrt(var_q)}


def rff_features64(X, W, c) -> np.ndarray:
    """sqrt(2/D) cos(X W + c) in float64."""
    W = np.asarray(W, np.float64)
    Z = np.asarray(X, np.float64) @ W + np.asarray(c, np.float64)
    return math.sqrt(2.0 / W.shape[1]) * np.cos(Z)


def rel(x, ref) -> float:
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def max_rel(x, ref) -> float:
    x = np.asarray(x, np.float64)
    return float(np.max(np.abs(x - ref) / np.abs(ref)))


# -- bookkeeping --------------------------------------------------------------

class Run:
    """Phase timing, compile accounting and checks, printed as they happen."""

    def __init__(self):
        import jax

        self.failures: list[str] = []
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0, f0 = time.perf_counter(), self.compile_s, len(self.failures)
        yield
        print(f"phase {name}: {time.perf_counter() - t0:.2f} s "
              f"({self.compile_s - c0:.2f} s compiling)", flush=True)
        if len(self.failures) > f0:
            raise SystemExit(f"chip_smoke: phase {name} failed: "
                             + "; ".join(self.failures[f0:]))

    def check(self, name: str, err: float, tol: float) -> None:
        ok = err <= tol          # a NaN error fails
        print(f"  {'ok  ' if ok else 'FAIL'} {name}: {err:.3e} "
              f"(tol {tol:.0e})", flush=True)
        if not ok:
            self.failures.append(f"{name}={err:.3e}")

    def require(self, name: str, cond: bool, detail: str = "") -> None:
        print(f"  {'ok  ' if cond else 'FAIL'} {name} {detail}", flush=True)
        if not cond:
            self.failures.append(name)


# -- one chip ----------------------------------------------------------------

def run_one_chip(cell: Cell, device: dict, run: Run) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.features import FeatureMap
    from repro.core.sufficient_stats import compute_stats
    from repro.data import synthetic
    from repro.fed import transport
    from repro.fed.protocol import PackedStats
    from repro.server import EnginePool

    dense, rff = "silo", "silo_rff"
    with run.phase("data"):
        ds = synthetic.generate(jax.random.PRNGKey(cell.seed),
                                num_clients=cell.clients,
                                samples_per_client=cell.rows, dim=cell.dim)
        rds = synthetic.generate(jax.random.PRNGKey(cell.seed + 1),
                                 num_clients=cell.clients,
                                 samples_per_client=cell.rows,
                                 dim=cell.rff_dim)
        fm = FeatureMap("rff", seed=cell.seed + 2, d_orig=cell.rff_dim,
                        m=cell.rff_features, lengthscale=cell.lengthscale)
        ks, kn = jax.random.split(jax.random.PRNGKey(cell.seed + 3))
        stream_A = jax.random.normal(ks, (cell.stream, cell.dim))
        stream_b = (jnp.matmul(stream_A, ds.w_star, precision="highest")
                    + 0.1 * jax.random.normal(kn, (cell.stream,)))
        queries = {dense: ds.test_A[:cell.queries],
                   rff: rds.test_A[:cell.queries]}
        rows = {dense: [jax.device_get(c) for c in ds.clients],
                rff: [jax.device_get(c) for c in rds.clients]}
        W64, c64 = (np.asarray(a, np.float64) for a in fm.materialize())
        ref = {dense: Ridge64(cell.dim), rff: Ridge64(cell.rff_features)}
        feats = {dense: lambda X: X,
                 rff: lambda X: rff_features64(X, W64, c64)}
        for name in (dense, rff):
            for A, b in rows[name]:
                ref[name].add(feats[name](A), b)
        q64 = {name: feats[name](jax.device_get(queries[name]))
               for name in (dense, rff)}

    with run.phase("phase1"):
        stats = [compute_stats(A, b, use_pallas=True) for A, b in ds.clients]
        rstats = [fm.stats(X, y, use_pallas=True) for X, y in rds.clients]
        jax.block_until_ready((stats, rstats))
        hlo = {
            dense: jax.jit(lambda A, b: compute_stats(A, b, use_pallas=True))
            .lower(*ds.clients[0]).as_text(),
            rff: jax.jit(lambda X, y: fm.stats(X, y, use_pallas=True))
            .lower(*rds.clients[0]).as_text()}
        for name, text in hlo.items():
            if device["platform"] == "tpu":
                run.require(f"{name} ingest kernel is a Mosaic kernel",
                            "tpu_custom_call" in text)
        for name, s in ((dense, stats[0]), (rff, rstats[0])):
            one = Ridge64(ref[name].h.shape[0])
            one.add(feats[name](rows[name][0][0]), rows[name][0][1])
            run.check(f"{name} client 0 G", rel(s.gram, one.G), STATS_RTOL)
            run.check(f"{name} client 0 h", rel(s.moment, one.h), STATS_RTOL)

    pool = EnginePool()
    with pool, transport.FrameServer(pool, port=0, placement="dense",
                                     solve_window_s=0.005) as srv:

        def connect(tenant: str) -> transport.FrameClient:
            client = transport.FrameClient(
                transport.TCPChannel(srv.host, srv.port, timeout_s=900.0))
            client.hello(tenant, ("f32",))
            return client

        def upload(name: str, k: int) -> None:
            client = connect(name)
            try:
                if name == dense:
                    client.upload_stats(stats[k], client_id=f"client{k}",
                                        moments=True)
                else:
                    s = rstats[k]
                    client.upload_rff(
                        PackedStats.pack(s), d_orig=fm.d_orig, seed=fm.seed,
                        fhash=fm.fhash, lengthscale=fm.lengthscale,
                        client_id=f"client{k}", yty=float(s.yty))
            finally:
                client.close()

        def solve_frame(req: tuple[str, float]) -> np.ndarray:
            client = connect(req[0])
            try:
                return client.solve(req[1])
            finally:
                client.close()

        def verify(tag: str) -> None:
            reqs = [(name, s) for name in (dense, rff) for s in cell.sigmas]
            with ThreadPoolExecutor(len(reqs)) as ex:
                ws = list(ex.map(solve_frame, reqs))
            for (name, s), w in zip(reqs, ws):
                run.check(f"{tag} {name} w(sigma={s:g})", rel(w, ref[name]
                                                              .solve(s)),
                          W_RTOL)
            z = statistics.NormalDist().inv_cdf((1.0 + LEVEL) / 2.0)
            sr = cell.report_sigma
            for name in (dense, rff):
                inf = ref[name].inference(sr, q64[name])
                rep = pool.solve_report(name, sr, level=LEVEL,
                                        queries=queries[name])
                run.check(f"{tag} {name} report w", rel(rep["weights"],
                                                        inf["w"]), W_RTOL)
                run.check(f"{tag} {name} stderr",
                          max_rel(rep["stderr"], inf["stderr"]), SE_RTOL)
                pi = np.asarray(rep["pi"], np.float64)
                run.check(f"{tag} {name} PI width",
                          max_rel((pi[:, 1] - pi[:, 0]) / (2 * z),
                                  inf["pred_std"]), SE_RTOL)
                X = queries[name] if name == dense else fm(queries[name])
                run.check(f"{tag} {name} predict",
                          rel(pool.predict(name, X, sr), inf["pred"]),
                          W_RTOL)
            batcher = srv.dispatcher.solve_batcher.summary()
            run.require(f"{tag} batcher ran without fallback",
                        batcher["fallbacks"] == 0, str(batcher))

        with run.phase("upload"):
            jobs = [(name, k) for k in range(cell.clients)
                    for name in (dense, rff)]
            with ThreadPoolExecutor(4) as ex:
                for f in [ex.submit(upload, *job) for job in jobs]:
                    f.result()     # a refused upload raises RejectedError
            stats = rstats = None    # the server holds them now
            tr = srv.dispatcher.summary()
            run.require("every upload admitted",
                        tr["uploads_admitted"] == len(jobs), str(tr))

        with run.phase("serve"):
            verify("serve")

        engine = pool.get(dense)
        with run.phase("stream"):
            cold0 = engine.cold_factorizations
            upd0 = engine.incremental_updates
            for i in range(cell.stream):
                pool.ingest_rows_async(dense, stream_A[i:i + 1],
                                       stream_b[i:i + 1])
            run.require("stream queued", pool.pending_deltas == cell.stream)
            pool.flush(dense)
            ref[dense].add(jax.device_get(stream_A), jax.device_get(stream_b))
            run.require(
                "one rank-r update per cached factor, no refactorization",
                engine.cold_factorizations == cold0
                and engine.incremental_updates - upd0 == len(cell.sigmas),
                f"(incremental {engine.incremental_updates - upd0})")
            verify("stream")

        with run.phase("churn"):
            gone = "client3"
            client = connect(dense)
            try:
                client.control("drop", gone)
                sub = ref[dense].copy()
                sub.add(*rows[dense][3], sign=-1)
                fused = pool.stats(dense)
                run.check("drop G", rel(fused.gram, sub.G), STATS_RTOL)
                run.check("drop h", rel(fused.moment, sub.h), STATS_RTOL)
                run.check("drop yty", rel(fused.yty, sub.yty), STATS_RTOL)
                run.require("drop count", int(fused.count) == sub.n)
                client.control("restore", gone)
            finally:
                client.close()
            verify("churn")

        tr = srv.dispatcher.summary()
        run.require("zero error ACKs", tr["frames_rejected"] == 0, str(tr))


# -- four chips ----------------------------------------------------------------

def run_four_chips(cell: ShardedCell, run: Run) -> None:
    import jax

    from repro.core.sufficient_stats import compute_stats
    from repro.data import synthetic
    from repro.launch.mesh import make_device_mesh
    from repro.server import FusionEngine, ShardedBackend

    with run.phase("data"):
        mesh = make_device_mesh(4)
        ds = synthetic.generate(jax.random.PRNGKey(cell.seed),
                                num_clients=cell.clients,
                                samples_per_client=cell.rows, dim=cell.dim)
        ku, kb = jax.random.split(jax.random.PRNGKey(cell.seed + 1))
        U = jax.random.normal(ku, (cell.update_rows, cell.dim))
        bu = jax.random.normal(kb, (cell.update_rows,))
        ref = Ridge64(cell.dim)
        for A, b in ds.clients:
            ref.add(*jax.device_get((A, b)))

    with run.phase("ingest"):
        sharded = FusionEngine(cell.dim,
                               backend=ShardedBackend(cell.dim, mesh))
        dense = FusionEngine(cell.dim)
        for A, b in ds.clients:
            s = compute_stats(A, b, use_pallas=True)
            sharded.ingest(s)
            dense.ingest(s)
        del s
        run.require("G spans 4 devices",
                    len(sharded.backend.gram.sharding.device_set) == 4)

    with run.phase("cold_solve"):
        w = sharded.solve(cell.sigma)
        fac = sharded.factor(cell.sigma)
        run.require("factor kind is block_chol", fac.kind == "block_chol",
                    f"({fac.kind})")
        run.require("L spans 4 devices",
                    len(fac.L.sharding.device_set) == 4
                    and not fac.L.sharding.is_fully_replicated)
        w64 = ref.solve(cell.sigma)
        w_dense = dense.solve(cell.sigma)
        run.check("cold w vs float64", rel(w, w64), SHARDED_RTOL)
        run.check("cold w vs dense", rel(w, w_dense), SHARDED_RTOL)
        run.check("dense w vs float64", rel(w_dense, w64), SHARDED_RTOL)

    with run.phase("blocked_update"):
        cold0 = sharded.cold_factorizations
        sharded.ingest_rows(U, bu)
        jax.block_until_ready(sharded.factor(cell.sigma).L)
        run.require("rank-r update, no refactorization",
                    sharded.incremental_updates == 1
                    and sharded.cold_factorizations == cold0)
        ref.add(*jax.device_get((U, bu)))
        dense.release_factors()   # the dense comparison factors cold
        dense.ingest_rows(U, bu)

    with run.phase("cached_solve"):
        w = sharded.solve(cell.sigma)
        run.require("served off the updated factor",
                    sharded.cold_factorizations == cold0)
        w64 = ref.solve(cell.sigma)
        w_dense = dense.solve(cell.sigma)
        run.check("updated w vs float64", rel(w, w64), SHARDED_RTOL)
        run.check("updated w vs dense", rel(w, w_dense), SHARDED_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 2x2 mesh of four "
                         "chips, and its comparisons")
    args = ap.parse_args(argv)
    device = require_tpu()
    import jax

    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']}), jax {jax.__version__}", flush=True)
    print(f"compilation cache: {enable_compilation_cache()}", flush=True)
    run = Run()
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(FOUR_CHIPS, run)
    else:
        run_one_chip(ONE_CHIP, device, run)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"total {time.perf_counter() - t0:.2f} s, compiling "
          f"{run.compile_s:.2f} s, {run.cache_hits} persistent-cache hits",
          flush=True)
    print("peak_bytes_in_use: "
          + (str(peak) if peak is not None else "not reported"), flush=True)
    if run.failures:
        raise SystemExit("chip_smoke: failed: " + "; ".join(run.failures))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
