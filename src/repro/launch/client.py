"""Wire-protocol client CLI: one federated participant as its own process.

This is the paper's client loop with the process boundary made real: the
client derives its shard of the shared synthetic dataset (same global seed
every participant uses, then its own ``--client-index`` slice), computes its
local sufficient statistics, negotiates a wire dtype with the server, and
ships the Thm-4 packed upload (or the §IV-F projected variant, or §VI-C
delta-row batches) over loopback/real TCP as actual bytes. Optionally it
drives the Thm-8 control plane (drop/rejoin) and queries the fused solution.

The final line on stdout is a single JSON report (negotiated dtype, byte
counters per direction, and the served weights when ``--solve`` was given) so
the subprocess e2e suite can pin everything the client saw against the
server's ledger and a cold in-process reference.

Usage (a 3-client federation against ``serve.py --mode fusion --listen``)::

    python src/repro/launch/client.py --connect 127.0.0.1:7777 \
        --tenant ridge --seed 0 --num-clients 3 --client-index 0 \
        --samples 128 --dim 32 --offer f64,f32 --solve 0.1

Clients stand for other machines. On a host with a TPU, the chip belongs to
the one server process, so client processes there run with
``JAX_PLATFORMS=cpu``: a second process that reached for the chip would fail
or hang.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np


def run_client(args: argparse.Namespace) -> dict:
    from repro.core.features import FeatureMap
    from repro.core.sufficient_stats import compute_stats
    from repro.data import synthetic
    from repro.fed import transport
    from repro.fed.protocol import PackedStats

    # This client's shard of the shared dataset: every participant generates
    # the same global dataset from --seed and keeps only its own client's
    # rows (the e2e driver rebuilds the union in-process). Generated BEFORE
    # the connection opens: local jax compilation can take tens of seconds
    # on a loaded host and must not count against the server's idle timeout.
    ds = synthetic.generate(jax.random.PRNGKey(args.seed),
                            num_clients=args.num_clients,
                            samples_per_client=args.samples,
                            dim=args.dim)
    A, b = ds.clients[args.client_index]

    host, _, port = args.connect.rpartition(":")
    offers = tuple(args.offer.split(","))
    resilient = args.retries > 0

    def connect():
        return transport.TCPChannel(host or "127.0.0.1", int(port),
                                    timeout_s=args.timeout)

    if resilient:
        # Crash/partition-tolerant path: reconnect-and-resume with seeded
        # exponential backoff. Safe to re-send blind after a lost ACK —
        # the server dedups byte-identical frames (duplicate=True).
        seed = (args.retry_seed if args.retry_seed is not None
                else 1000 + args.client_index)   # distinct jitter per client
        client = transport.ResilientClient(
            connect, tenant=args.tenant, offers=offers,
            retries=args.retries, backoff_s=args.backoff,
            jitter=args.jitter, seed=seed,
            max_chunk_payload=args.max_chunk_payload)
    else:
        client = transport.FrameClient(
            connect(), max_chunk_payload=args.max_chunk_payload)
    report: dict = {"tenant": args.tenant, "client_id": args.client_id,
                    "client_index": args.client_index}
    try:
        report["negotiated_dtype"] = (client.hello() if resilient
                                      else client.hello(args.tenant, offers))

        features = args.features
        if args.projected and features == "none":
            # Legacy spelling: --projected M == --features sketch
            # --feature-dim M (same wire frames either way).
            features, args.feature_dim = "sketch", args.projected
        if features != "none":
            # §IV-F feature upload: featurize->Gram runs through the fused
            # Pallas ingest kernel (the (n x m) feature matrix never
            # materializes) unless --unfused-ingest asks for the two-pass
            # XLA path; both produce the same m-space statistics.
            fm = FeatureMap(features, seed=args.proj_seed, d_orig=args.dim,
                            m=args.feature_dim, lengthscale=args.lengthscale)
            packed = PackedStats.pack(
                fm.stats(A, b, use_pallas=not args.unfused_ingest))
            # yty = sum b^2 is featurization-invariant (targets never pass
            # through the map), so sketched/RFF tenants serve the same
            # solve-space inference algebra as dense ones.
            yty = (None if not args.moments or packed.yty is None
                   else float(np.asarray(packed.yty)))
            if features == "sketch":
                client.upload_projected(packed, d_orig=args.dim,
                                        seed=args.proj_seed, rhash=fm.fhash,
                                        client_id=args.client_id, yty=yty)
            else:
                client.upload_rff(packed, d_orig=args.dim,
                                  seed=args.proj_seed, fhash=fm.fhash,
                                  lengthscale=args.lengthscale,
                                  client_id=args.client_id, yty=yty)
            report["uploaded"] = {
                "frame": "proj" if features == "sketch" else "rff",
                "m": args.feature_dim, "proj_seed": args.proj_seed,
                "fused_ingest": not args.unfused_ingest,
                "moments": yty is not None}
        elif args.delta_batches:
            # §VI-C: the same rows, shipped as raw delta batches instead of
            # one packed statistic (Thm 1 makes the union identical).
            n = A.shape[0]
            bounds = np.linspace(0, n, args.delta_batches + 1, dtype=int)
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                if hi > lo:
                    client.stream_rows(A[lo:hi], b[lo:hi],
                                       client_id=args.client_id)
            report["uploaded"] = {"frame": "delta",
                                  "batches": args.delta_batches, "rows": n}
        else:
            client.upload_stats(compute_stats(A, b),
                                client_id=args.client_id,
                                moments=args.moments)
            report["uploaded"] = {"frame": "tri", "d": args.dim,
                                  "count": int(A.shape[0]),
                                  "moments": args.moments}

        if args.control:
            op, _, target = args.control.partition(":")
            client.control(op, target or args.client_id)
            report["control"] = {"op": op, "target": target or args.client_id}

        if args.solve is not None:
            w = client.solve(args.solve)
            report["solve"] = {"sigma": args.solve,
                               "weights": np.asarray(w, np.float64).tolist()}

        if resilient:
            s = client.summary()
            report.update(bytes_uploaded=s["bytes_uploaded"],
                          bytes_sent=s["bytes_sent"],
                          bytes_received=s["bytes_received"],
                          frames_sent=s["frames_sent"],
                          retries=s["retries"], reconnects=s["reconnects"],
                          duplicate_acks=s["duplicate_acks"], ok=True)
        else:
            report.update(bytes_uploaded=client.bytes_uploaded,
                          bytes_sent=client.bytes_sent,
                          bytes_received=client.bytes_received,
                          frames_sent=client.frames_sent, ok=True)
    finally:
        client.close()
    return report


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="wire server address (serve.py --mode fusion "
                         "--listen PORT)")
    ap.add_argument("--tenant", default="default",
                    help="tenant this session binds to at HELLO")
    ap.add_argument("--client-id", default=None,
                    help="client id carried in upload/control frames "
                         "(default: client<index>)")
    ap.add_argument("--offer", default="f32",
                    help="comma list of wire dtypes to offer (f32,f64,bf16); "
                         "the server's policy picks one")
    ap.add_argument("--seed", type=int, default=0,
                    help="shared dataset seed (same for every participant)")
    ap.add_argument("--num-clients", type=int, default=3)
    ap.add_argument("--client-index", type=int, default=0,
                    help="which client's shard this process owns")
    ap.add_argument("--samples", type=int, default=128)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--projected", type=int, default=0, metavar="M",
                    help="upload the §IV-F m-dim sketched statistics instead "
                         "of the full Thm-4 payload (legacy alias for "
                         "--features sketch --feature-dim M)")
    ap.add_argument("--features", choices=("none", "sketch", "rff"),
                    default="none",
                    help="§IV-F feature map: 'sketch' ships the m-dim JL "
                         "projection statistics, 'rff' the D-dim random-"
                         "Fourier statistics; both via the fused Pallas "
                         "featurize->Gram ingest")
    ap.add_argument("--feature-dim", type=int, default=16, metavar="M",
                    help="feature count (sketch m / rff D)")
    ap.add_argument("--lengthscale", type=float, default=1.0,
                    help="RBF lengthscale for --features rff")
    ap.add_argument("--unfused-ingest", action="store_true",
                    help="compute feature statistics via the two-pass XLA "
                         "reference instead of the fused Pallas kernel")
    ap.add_argument("--proj-seed", type=int, default=0,
                    help="shared feature-map seed (all feature clients must "
                         "agree; the server verifies the map hash)")
    ap.add_argument("--delta-batches", type=int, default=0, metavar="N",
                    help="ship the shard as N §VI-C delta-row frames instead "
                         "of one packed statistic")
    ap.add_argument("--moments", action="store_true",
                    help="append the 8-byte MOMENTS wire section (yty = "
                         "sum y^2) to the upload so the server can serve "
                         "federated inference (stderr/CI/PI); legacy "
                         "servers reject the extra section with a typed "
                         "error, legacy co-tenants degrade inference to "
                         "point-only")
    ap.add_argument("--control", default=None, metavar="OP[:CLIENT]",
                    help="after uploading, send a Thm-8 control frame: "
                         "'drop', 'restore', or 'drop:other_id'")
    ap.add_argument("--solve", type=float, default=None, metavar="SIGMA",
                    help="query the fused weights at SIGMA and report them")
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="socket timeout awaiting each server reply (the "
                         "server may be jit-compiling its first solve)")
    ap.add_argument("--retries", type=int, default=0,
                    help="max retries per operation (0 = fail fast); >0 "
                         "switches to the resilient client: reconnect, "
                         "re-HELLO, and re-send on transient failures, "
                         "relying on server-side dedup for lost ACKs")
    ap.add_argument("--backoff", type=float, default=0.05, metavar="S",
                    help="base retry backoff in seconds (doubles per "
                         "attempt, capped at 2s)")
    ap.add_argument("--jitter", type=float, default=0.5,
                    help="backoff jitter fraction in [0,1]: each delay is "
                         "scaled by 1 + jitter*U(-1,1) from --retry-seed")
    ap.add_argument("--retry-seed", type=int, default=None,
                    help="seed for the jitter schedule (default: derived "
                         "from --client-index so clients desynchronize)")
    ap.add_argument("--max-chunk-payload", type=int, default=None,
                    metavar="BYTES",
                    help="stream uploads whose payload exceeds BYTES as "
                         "continuation chunks (for d large enough that one "
                         "triangular payload would blow the single-frame "
                         "cap); smaller uploads stay byte-identical")
    return ap


def main(argv=None) -> None:
    args = make_parser().parse_args(argv)
    if args.client_id is None:
        args.client_id = f"client{args.client_index}"
    report = run_client(args)
    print(json.dumps(report))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
