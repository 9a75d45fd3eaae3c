"""Batched serving loops.

Modes:
  * ``model``  — prefill a batch of prompts, decode new tokens. The decode
    path is the same ``model.decode_step`` the dry-run lowers for
    decode_32k / long_500k; here it actually executes (reduced configs on
    CPU, full configs on a TPU slice).
  * ``fusion`` — ridge-serving on an ``EnginePool``: every tenant is an
    independent fusion problem (its own clients, fused (G, h), sigma grid)
    admitted into one ``server.pool.EnginePool`` from Thm-4 packed payloads.
    Placement is per tenant — ``--sharded-tenants N`` pins the first N to
    the pool's one shared mesh, ``--auto-tenants M`` lets the next M follow
    the measured ``crossover_d`` (``server/select.py``), the rest are dense
    — and queries are served off each tenant's cached factors (one
    ``solve_batch`` warm sweep per tenant) versus the naive per-query cold
    solve. With ``--stream-deltas`` the loop also queues §VI-C row deltas
    through each tenant's coalescer WITHOUT issuing reads: the pool's
    background flusher is the only staleness clock, and the loop verifies
    every tenant's served weights still match its cold ``core.fusion``
    reference afterwards.
  * ``fusion --listen PORT`` — the same pool behind the real wire: a
    ``fed.transport.FrameServer`` accepts out-of-process clients
    (``launch/client.py``) speaking the ``fed.wire`` binary protocol —
    dtype-negotiated Thm-4 uploads, §IV-F projected payloads, §VI-C delta
    streams, Thm-8 control, Phase-3 queries — and the final report prints
    the ledger from *actual encoded frame lengths*. ``--expect-uploads N``
    exits once N upload frames were admitted and every connection closed
    (or at ``--serve-timeout``).
  * ``relay --upstream HOST:PORT`` — the same wire server run as a
    hierarchical sub-aggregator (``server.relay``): regional clients upload
    exactly as above, and a ``RelayForwarder`` ships ONE fused delta frame
    per tenant upstream on a size/staleness policy (and always at
    shutdown/SIGTERM), stamped with ``--relay-id`` so upstream dedup makes
    re-forwards idempotent — root ingress is O(relays), not O(clients).
"""
from __future__ import annotations

import argparse
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import model


def serve(arch: str, *, reduced: bool = True, batch: int = 4,
          prompt_len: int = 32, gen_tokens: int = 32, seed: int = 0,
          greedy: bool = True) -> dict:
    cfg = configs.get_reduced(arch) if reduced else configs.get(arch)
    if cfg.encoder_only:
        raise ValueError("encoder-only architecture has no decode step")
    params = model.init_params(jax.random.PRNGKey(seed), cfg)

    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32))
    batch_in = {"tokens": prompts}
    if cfg.input_mode == "prefix_embeddings":
        batch_in["patches"] = jnp.asarray(rng.standard_normal(
            (batch, cfg.num_prefix, cfg.d_model), dtype=np.float32))

    total = prompt_len + gen_tokens + (cfg.num_prefix
                                       if cfg.input_mode == "prefix_embeddings"
                                       else 0)
    t0 = time.time()
    logits, cache = model.prefill_step(params, batch_in, cfg,
                                       chunk_size=64, max_len=total)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    decode = jax.jit(lambda p, c, b: model.decode_step(p, c, b, cfg))
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    generated = [tok]
    t0 = time.time()
    key = jax.random.PRNGKey(seed + 1)
    for _ in range(gen_tokens - 1):
        logits, cache = decode(params, cache, {"tokens": tok[:, None]})
        if greedy:
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, 0]).astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(generated[-1])
    t_decode = time.time() - t0

    toks_out = np.stack([np.asarray(t) for t in generated], axis=1)
    return {
        "arch": cfg.name,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_per_s": batch * (gen_tokens - 1) / max(t_decode, 1e-9),
        "generated": toks_out,
    }


def serve_fusion(*, num_clients: int = 4, samples_per_client: int = 128,
                 dim: int = 128, tenants: int = 8, sigmas_per_tenant: int = 4,
                 queries: int = 256, query_rows: int = 8,
                 sharded_tenants: int = 0, auto_tenants: int = 0, mesh=None,
                 sketched_tenants: int = 0, rff_tenants: int = 0,
                 feature_dim: int = 16, lengthscale: float = 1.0,
                 stream_deltas: int = 0, coalesce_rank: int = 32,
                 flush_staleness_s: float = 0.05, max_warm: int | None = None,
                 seed: int = 0) -> dict:
    """Serve many independent tenants' ridge queries off ONE EnginePool.

    Each of the ``tenants`` tenants is its own fusion problem: its own
    synthetic client set, uploaded as Thm-4 :class:`fed.PackedStats`
    payloads (the pool ledger records the measured bytes), its own sigma
    grid, and its own placement — the first ``sharded_tenants`` pinned to
    the pool's shared mesh, the next ``auto_tenants`` placed by the measured
    ``crossover_d``, the rest dense. A query is (tenant, sigma, X) ->
    X @ w_sigma: one ``solve_batch`` per tenant warms its factor cache, then
    all queries run off cached factors; the naive baseline cold-factorizes
    per query. Every tenant's served weights are checked against a cold
    ``core.fusion.solve_ridge`` over exactly its own rows
    (``exact_max_abs_err``) — tenant isolation is an output, not a hope.

    With ``stream_deltas > 0`` the loop then queues that many §VI-C row
    deltas round-robin across tenants through ``ingest_rows_async`` and
    issues NO reads: the pool's background flusher (started for the duration)
    is the only thing driving the staleness clock
    (``CoalescerPolicy.max_staleness_s = flush_staleness_s``). The loop
    waits for the queues to drain, records how many flushes the background
    thread performed and the worst delta age it observed, and re-verifies
    every tenant against its cold reference including the streamed rows.

    With ``sketched_tenants`` / ``rff_tenants`` > 0 the LAST that many
    tenants are §IV-F feature tenants: their client uploads are m-space
    statistics produced by the fused Pallas featurize->Gram ingest
    (``core.FeatureMap.stats(..., use_pallas=True)`` — the (n x m) feature
    matrix never materializes), their engines solve in m (D) dimensions,
    queries and §VI-C deltas are featurized before they touch the pool, and
    their cold reference is ``core.fusion`` over the *featurized* union of
    their rows — so the mixed pool's exactness check covers every kind in
    its own solve space. Feature tenants are always dense-placed (their
    whole point is a solve too small to shard).
    """
    from repro.core import fusion
    from repro.core.features import FeatureMap
    from repro.core.sufficient_stats import compute_stats
    from repro.data import synthetic
    from repro.fed.protocol import PackedStats
    from repro.server import CoalescerPolicy, EnginePool

    sharded_tenants = min(sharded_tenants, tenants)
    auto_tenants = min(auto_tenants, tenants - sharded_tenants)
    rff_tenants = min(rff_tenants, tenants)
    sketched_tenants = min(sketched_tenants, tenants - rff_tenants)
    policy = CoalescerPolicy(max_rank=coalesce_rank,
                             max_staleness_s=flush_staleness_s)
    pool = EnginePool(mesh=mesh, max_warm=max_warm, default_coalesce=policy)

    # Admit every tenant from packed payloads; keep its raw rows so the
    # exactness check below can rebuild the cold reference. The last
    # sketched_tenants + rff_tenants tenants are §IV-F feature tenants whose
    # payloads are m-space statistics off the fused Pallas ingest.
    tenant_rows: dict[str, list[tuple[jax.Array, jax.Array]]] = {}
    feature_maps: dict[str, FeatureMap] = {}
    for t in range(tenants):
        name = f"tenant{t}"
        ds_t = synthetic.generate(jax.random.PRNGKey(seed + 7919 * t),
                                  num_clients=num_clients,
                                  samples_per_client=samples_per_client,
                                  dim=dim)
        fm = None
        if t >= tenants - rff_tenants:
            fm = FeatureMap("rff", seed=seed + t, d_orig=dim, m=feature_dim,
                            lengthscale=lengthscale)
        elif t >= tenants - rff_tenants - sketched_tenants:
            fm = FeatureMap("sketch", seed=seed + t, d_orig=dim,
                            m=min(feature_dim, dim))
        if fm is None:
            payloads = {k: PackedStats.pack(compute_stats(A_k, b_k))
                        for k, (A_k, b_k) in enumerate(ds_t.clients)}
            placement = ("sharded" if t < sharded_tenants
                         else "auto" if t < sharded_tenants + auto_tenants
                         else "dense")
        else:
            payloads = {k: PackedStats.pack(
                            fm.stats(A_k, b_k, use_pallas=True))
                        for k, (A_k, b_k) in enumerate(ds_t.clients)}
            placement = "dense"
            feature_maps[name] = fm
        pool.create_tenant(name, payloads=payloads, placement=placement,
                           features=fm)
        tenant_rows[name] = list(ds_t.clients)

    # Tenant t's grid: sigmas_per_tenant points on a per-tenant log range.
    rng = np.random.default_rng(seed)
    grids = {f"tenant{t}": sorted(10.0 ** rng.uniform(-3, 1, sigmas_per_tenant))
             for t in range(tenants)}
    stream = []
    for _ in range(queries):
        name = f"tenant{int(rng.integers(tenants))}"
        sigma = grids[name][int(rng.integers(sigmas_per_tenant))]
        X = jnp.asarray(rng.standard_normal((query_rows, dim)), jnp.float32)
        if name in feature_maps:
            # Feature tenants serve in their map's space: featurize the
            # query once, up front, so naive and pooled time the same work.
            X = feature_maps[name](X)
        stream.append((name, sigma, X))

    def cold_ref(name: str, sigma: float) -> jax.Array:
        A_all = jnp.concatenate([a for a, _ in tenant_rows[name]])
        b_all = jnp.concatenate([b for _, b in tenant_rows[name]])
        if name in feature_maps:
            # Cold reference lives in the tenant's own solve space: the
            # two-pass XLA featurize (feature matrix materialized) feeding
            # core.fusion — what the fused Pallas ingest must reproduce.
            A_all = feature_maps[name](A_all)
        return fusion.solve_ridge(compute_stats(A_all, b_all), sigma)

    # Naive: cold factorization per query, per tenant.
    fused = {name: pool.stats(name) for name in pool.tenant_names}
    t0 = time.perf_counter()
    for name, sigma, X in stream:
        jax.block_until_ready(X @ fusion.solve_ridge(fused[name], sigma))
    t_naive = time.perf_counter() - t0

    # Pooled: one warm sweep per tenant, then queries off cached factors.
    t0 = time.perf_counter()
    for name, grid in grids.items():
        pool.solve_batch(name, grid, method="chol")
    for name, sigma, X in stream:
        jax.block_until_ready(pool.predict(name, X, sigma))
    t_pool = time.perf_counter() - t0

    def max_err() -> float:
        worst = 0.0
        for name, grid in grids.items():
            w = pool.solve(name, grid[0])
            worst = max(worst, float(jnp.abs(w - cold_ref(name, grid[0])).max()))
        return worst

    exact_err = max_err()

    # §IV-F metadata per feature tenant: solve_report carries the Prop-3
    # error bound and the upload-float count next to the served weights.
    feature_reports = {
        name: {k: v for k, v in
               pool.solve_report(name, grids[name][0]).items()
               if k != "weights"}
        for name in feature_maps}

    streaming = None
    if stream_deltas:
        names = list(pool.tenant_names)
        deltas = [
            (names[i % len(names)],
             jnp.asarray(rng.standard_normal((1, dim)), jnp.float32),
             jnp.asarray(rng.standard_normal((1,)), jnp.float32))
            for i in range(stream_deltas)]
        m0 = sum(e.incremental_updates + e.cold_factorizations
                 for e in (pool.get(n) for n in names))
        pool.start_flusher()
        try:
            t0 = time.perf_counter()
            for name, dA, db in deltas:
                # A feature tenant's coalescer queue lives in m-space too:
                # featurize the delta rows (row-wise map, so featurizing
                # per-delta == featurizing the union) before they enqueue.
                dA_in = (feature_maps[name](dA) if name in feature_maps
                         else dA)
                pool.ingest_rows_async(name, dA_in, db)
                tenant_rows[name].append((dA, db))
            # NO reads from here on: only the background flusher drains.
            deadline = time.monotonic() + max(10.0, 100 * flush_staleness_s)
            while pool.pending_deltas and time.monotonic() < deadline:
                time.sleep(flush_staleness_s / 5)
            t_stream = time.perf_counter() - t0
            pending_after = pool.pending_deltas
        finally:
            # The daemon must not outlive this block on any path — an
            # exception here would otherwise leak a thread that keeps
            # polling the pool for the rest of the process.
            pool.stop_flusher()
        summary = pool.summary()
        mutations = sum(e.incremental_updates + e.cold_factorizations
                        for e in (pool.get(n) for n in names)) - m0
        streaming = {
            "deltas": stream_deltas,
            "coalesce_rank": coalesce_rank,
            "flush_staleness_s": flush_staleness_s,
            "pending_after": pending_after,
            "background_flushes": summary["background_flushes"],
            "max_flush_age_s": summary["max_flush_age_s"],
            "mutations_per_delta": mutations / stream_deltas,
            "stream_s": t_stream,
            "exact_max_abs_err": max_err(),
        }
    pool.close()

    return {
        "tenants": tenants,
        "placements": pool.summary()["placements"],
        "sharded_tenants": sharded_tenants,
        "auto_tenants": auto_tenants,
        "sketched_tenants": sketched_tenants,
        "rff_tenants": rff_tenants,
        "feature_reports": feature_reports,
        "queries": queries,
        "distinct_sigmas": len({sigma for _, sigma, _ in stream}),
        "naive_qps": queries / t_naive,
        "pool_qps": queries / t_pool,
        "speedup": t_naive / t_pool,
        "exact_max_abs_err": exact_err,
        "streaming": streaming,
        "ledger": pool.ledger(),
        "pool": pool.summary(),
    }


def serve_wire(*, port: int = 0, expect_uploads: int = 0,
               timeout_s: float = 30.0, sigma: float = 0.1,
               inference: bool = False, ci_level: float = 0.95,
               placement: str = "dense", coalesce_rank: int = 32,
               flush_staleness_s: float = 0.05,
               max_warm: int | None = None,
               solve_window_s: float | None = None,
               dtype_preference: tuple[str, ...] | None = None,
               journal_dir: str | None = None,
               snapshot_every: int | None = None,
               journal_fsync: bool = True,
               chaos=None, chaos_seed: int = 0,
               upstream: str | None = None, relay_id: str = "relay0",
               forward_every: int | None = 32,
               forward_staleness_s: float | None = None,
               forward_interval_s: float = 0.25,
               relay_state_dir: str | None = None,
               max_chunk_payload: int | None = None) -> dict:
    """Run the out-of-process federation server: an ``EnginePool`` behind a
    ``fed.transport.FrameServer`` speaking the ``fed.wire`` binary protocol.

    Tenants are created lazily by the first upload frame that names them
    (the HELLO's tenant binding); clients negotiate their wire dtype per
    session. The loop exits once ``expect_uploads`` upload frames were
    admitted AND every connection has closed — so an in-flight Phase-3 query
    after the last upload still gets its WEIGHTS frame — or at ``timeout_s``.
    The returned report carries the pool ledger measured from actual encoded
    frame lengths plus a final server-side solve per tenant at ``sigma``.

    ``solve_window_s`` puts a ``server.batch.SolveBatcher`` micro-batching
    window on the SOLVE path: queries from concurrent sessions landing
    within the window coalesce into one cross-tenant stacked sweep (a lone
    request on an idle server still dispatches immediately).

    ``journal_dir`` makes the pool crash-safe: every admitted frame is
    write-ahead-journaled before it fuses, the pool snapshots/compacts every
    ``snapshot_every`` appends, a restart with the same directory restores
    bit-exact state with zero client re-uploads, and SIGTERM triggers a
    final snapshot before exit (so a clean shutdown replays nothing).
    ``chaos`` (a ``fed.chaos.ChaosConfig``) puts a seeded fault-injecting
    TCP proxy in front of the server — clients connect to the printed proxy
    port and experience drops, duplicates, corruption, delays, and mid-frame
    kills by deterministic schedule.

    ``upstream="HOST:PORT"`` runs this server as a RELAY (hierarchical
    aggregation, ``server.relay``): the same binary admits its regional
    clients exactly as above, and a ``RelayForwarder`` ships ONE fused
    delta frame per tenant upstream — every ``forward_every`` admitted
    frames, at ``forward_staleness_s``, and always at shutdown/SIGTERM —
    stamped with ``relay_id`` so upstream dedup makes re-forwards after a
    lost ACK idempotent. Forward state persists durably under
    ``relay_state_dir`` (default ``<journal_dir>/relay_state``), so a
    restarted relay re-sends its pending frame instead of losing it.
    """
    import os
    import signal

    from repro.fed import transport
    from repro.server import CoalescerPolicy, EnginePool

    policy = CoalescerPolicy(max_rank=coalesce_rank,
                             max_staleness_s=flush_staleness_s)
    kw = ({"dtype_preference": dtype_preference}
          if dtype_preference is not None else {})
    if solve_window_s is not None:
        kw["solve_window_s"] = solve_window_s
    pool = EnginePool(max_warm=max_warm, default_coalesce=policy,
                      journal_dir=journal_dir, snapshot_every=snapshot_every,
                      journal_fsync=journal_fsync,
                      tier="relay" if upstream is not None else "root")
    if pool.replayed_frames or pool.restored_tenants:
        print(f"[serve_wire] recovered {pool.restored_tenants} tenants from "
              f"snapshot + {pool.replayed_frames} replayed journal frames",
              flush=True)
    forwarder = None
    if upstream is not None:
        from repro.server.relay import ForwardPolicy, RelayForwarder

        host, _, up_port = upstream.rpartition(":")
        state = relay_state_dir or (os.path.join(journal_dir, "relay_state")
                                    if journal_dir else None)
        if state is None:
            raise ValueError("relay mode needs relay_state_dir (or a "
                             "journal_dir to put it under)")
        forwarder = RelayForwarder(
            pool, lambda: transport.TCPChannel(host, int(up_port)),
            relay_id=relay_id, state_dir=state,
            policy=ForwardPolicy(max_frames=forward_every,
                                 max_staleness_s=forward_staleness_s),
            max_chunk_payload=max_chunk_payload)
        resumed = forwarder.resume()
        if resumed:
            print(f"[serve_wire] relay {relay_id}: re-sent {resumed} pending "
                  f"forward frame(s) from a previous incarnation", flush=True)
    term = threading.Event()
    installed = False
    try:
        # Final-snapshot-then-exit on SIGTERM: the handler only sets a flag;
        # the actual snapshot runs on the main thread via pool.close() (the
        # context-manager exit), which is idempotent and flusher-safe.
        signal.signal(signal.SIGTERM, lambda signum, frame: term.set())
        installed = True
    except ValueError:        # not the main thread (in-process test driver)
        pass
    proxy = None
    try:
        with pool, transport.FrameServer(pool, port=port,
                                         placement=placement, **kw) as srv:
            if chaos is not None:
                from repro.fed.chaos import ChaosProxy, ChaosSchedule

                proxy = ChaosProxy(srv.host, srv.port,
                                   ChaosSchedule(chaos, chaos_seed)).start()
                print(f"[serve_wire] chaos proxy on "
                      f"{proxy.host}:{proxy.port} (seed={chaos_seed})",
                      flush=True)
            print(f"[serve_wire] listening on {srv.host}:{srv.port}",
                  flush=True)
            if forwarder is not None:
                forwarder.start(forward_interval_s)
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline and not term.is_set():
                done = (expect_uploads
                        and srv.dispatcher.uploads_admitted >= expect_uploads
                        and srv.active_connections == 0)
                if done:
                    break
                time.sleep(0.02)
            relay_summary = None
            if forwarder is not None:
                # Shutdown contract (including SIGTERM): whatever the
                # forwarding policy left unshipped goes upstream NOW, so
                # the root holds this relay's complete fusion before exit.
                forwarder.stop()
                forwarder.forward_all()
                relay_summary = forwarder.summary()
                forwarder.close(forward=False)
            solves = {}
            tenant_reports = {}
            for name in pool.tenant_names:
                # solve_report rides solve_lifted == what SOLVE frames
                # served: the report's weights and the clients' WEIGHTS
                # downloads can never diverge. For §IV-F tenants it also
                # carries the map dims, upload floats and Prop-3 bound;
                # for moments-carrying tenants stderr/ci (and the
                # inference scalars) ride along — None for legacy tenants.
                rep = pool.solve_report(name, sigma, level=ci_level)
                w = rep.pop("weights")
                solves[name] = np.asarray(jax.device_get(w),
                                          np.float64).tolist()
                for key in ("stderr", "ci", "pi"):
                    if rep.get(key) is not None:
                        rep[key] = np.asarray(rep[key],
                                              np.float64).tolist()
                tenant_reports[name] = rep
            ledger = pool.ledger()
            report = {
                "port": srv.port,
                "proxy_port": proxy.port if proxy is not None else None,
                "sigterm": term.is_set(),
                "transport": srv.dispatcher.summary(),
                "connections_total": srv.connections_total,
                "tenants": list(pool.tenant_names),
                "sigma": sigma,
                "weights": solves,
                "tenant_reports": tenant_reports,
                "ledger": ledger,
                "pool": pool.summary(),
            }
            if relay_summary is not None:
                report["relay"] = relay_summary
            if proxy is not None:
                report["chaos"] = proxy.schedule.summary()
    finally:
        if forwarder is not None:
            forwarder.close(forward=False)   # idempotent; exception path
        if proxy is not None:
            proxy.stop()
        if installed:
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
    tr = report["transport"]
    print(f"[serve_wire] {tr['frames_handled']} frames "
          f"({tr['uploads_admitted']} uploads admitted, "
          f"{tr['frames_rejected']} rejected) over "
          f"{report['connections_total']} connections")
    print(f"[serve_wire] ledger: {ledger['wire_upload_bytes']} upload bytes "
          f"+ {ledger['wire_download_bytes']} download bytes on the wire "
          f"across {len(report['tenants'])} tenants")
    if report.get("relay") is not None:
        rs = report["relay"]
        print(f"[serve_wire] relay {rs['relay_id']}: {rs['forwards']} "
              f"upstream frames ({rs['forwarded_bytes']} bytes), "
              f"{rs['duplicate_acks']} duplicate acks, "
              f"{rs['resumed_pending']} resumed pending")
    for name, w in solves.items():
        print(f"[serve_wire] tenant {name}: |w({sigma})| = "
              f"{float(np.linalg.norm(w)):.6f}")
    if inference:
        for name, rep in report["tenant_reports"].items():
            inf = rep.get("inference")
            if inf is None:
                print(f"[serve_wire] tenant {name}: inference unavailable "
                      f"(moments-less uploads — point weights only)")
            else:
                print(f"[serve_wire] tenant {name}: n={inf['n']} "
                      f"dof={inf['dof']:.2f} sigma2={inf['sigma2']:.6g} "
                      f"max stderr={max(rep['stderr']):.6g} "
                      f"({int(round(inf['level'] * 100))}% CI served)")
    print(f"[serve_wire] report {json.dumps(report)}", flush=True)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["model", "fusion", "relay"],
                    default="model")
    ap.add_argument("--arch", choices=list(configs.ARCH_IDS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4,
                    help="clients per tenant (each tenant is its own "
                         "fusion problem)")
    ap.add_argument("--samples", type=int, default=128,
                    help="samples per client per tenant")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--sharded-tenants", type=int, default=2,
                    help="pin the first N tenants to the pool's shared mesh "
                         "(host CPU mesh; degrades to 1 device)")
    ap.add_argument("--auto-tenants", type=int, default=2,
                    help="place the next M tenants by the measured "
                         "crossover_d (server/select.py)")
    ap.add_argument("--sketched-tenants", type=int, default=0,
                    help="make the last N tenants §IV-F sketched: m-space "
                         "uploads off the fused Pallas featurize->Gram "
                         "ingest, m-space solves, Prop-3 error bound in "
                         "the report")
    ap.add_argument("--rff-tenants", type=int, default=0,
                    help="make the last M tenants random-Fourier-feature "
                         "tenants (D-space uploads/solves; D may exceed "
                         "--dim)")
    ap.add_argument("--feature-dim", type=int, default=16, metavar="M",
                    help="feature count for sketched/rff tenants (sketch m "
                         "is clamped to --dim)")
    ap.add_argument("--lengthscale", type=float, default=1.0,
                    help="RBF lengthscale for --rff-tenants")
    ap.add_argument("--stream-deltas", type=int, default=0,
                    help="queue N §VI-C row deltas through the coalescers "
                         "with NO reads; the pool's background flusher is "
                         "the only staleness clock")
    ap.add_argument("--coalesce-rank", type=int, default=32,
                    help="coalescer flush threshold (update rank per flush)")
    ap.add_argument("--flush-staleness", type=float, default=0.05,
                    help="per-tenant max_staleness_s the background "
                         "flusher enforces")
    ap.add_argument("--max-warm", type=int, default=None,
                    help="LRU bound on tenants with resident factor caches")
    ap.add_argument("--listen", type=int, default=None, metavar="PORT",
                    help="serve the fed.wire protocol over TCP instead of "
                         "the in-process loop (0 = ephemeral port, printed)")
    ap.add_argument("--expect-uploads", type=int, default=0,
                    help="with --listen: exit once this many upload frames "
                         "were admitted and all connections closed")
    ap.add_argument("--serve-timeout", type=float, default=30.0,
                    help="with --listen: hard deadline in seconds")
    ap.add_argument("--sigma", type=float, default=0.1,
                    help="with --listen: sigma of the final per-tenant "
                         "report solve")
    ap.add_argument("--inference", action="store_true",
                    help="with --listen: print each tenant's federated "
                         "inference summary (noise estimate, dof, stderr) "
                         "next to the final solve; tenants whose uploads "
                         "carried no MOMENTS section report 'unavailable'")
    ap.add_argument("--ci-level", type=float, default=0.95,
                    help="two-sided coverage of the served confidence/"
                         "prediction intervals")
    ap.add_argument("--solve-window", type=float, default=None,
                    metavar="SECONDS",
                    help="with --listen: micro-batching window on the SOLVE "
                         "path — concurrent queries landing within it "
                         "coalesce into one cross-tenant stacked sweep; a "
                         "lone request never waits")
    ap.add_argument("--journal-dir", type=str, default=None, metavar="DIR",
                    help="with --listen: write-ahead journal + snapshot "
                         "directory; every admitted frame is journaled "
                         "before it fuses, and a restart with the same DIR "
                         "restores bit-exact state with zero re-uploads")
    ap.add_argument("--snapshot-every", type=int, default=None, metavar="N",
                    help="with --journal-dir: snapshot/compact after every "
                         "N journaled frames (default: only at shutdown)")
    ap.add_argument("--no-journal-fsync", action="store_true",
                    help="skip fsync per journal append (faster; crash "
                         "window widens to OS flush semantics)")
    ap.add_argument("--upstream", type=str, default=None, metavar="HOST:PORT",
                    help="with --mode relay: the parent aggregator to "
                         "forward fused per-tenant delta frames to")
    ap.add_argument("--relay-id", type=str, default="relay0",
                    help="stable relay identity stamped into forwarded "
                         "frames (upstream dedup key; unique per relay)")
    ap.add_argument("--forward-every", type=int, default=32, metavar="N",
                    help="forward a tenant after N admitted upload frames")
    ap.add_argument("--forward-staleness", type=float, default=None,
                    metavar="SECONDS",
                    help="also forward once the oldest unforwarded "
                         "admission is this old")
    ap.add_argument("--forward-interval", type=float, default=0.25,
                    metavar="SECONDS",
                    help="relay poller period (how often the forwarding "
                         "policy is evaluated)")
    ap.add_argument("--relay-state-dir", type=str, default=None, metavar="DIR",
                    help="durable forward-state directory (default: "
                         "<journal-dir>/relay_state)")
    ap.add_argument("--max-chunk-payload", type=int, default=None,
                    metavar="BYTES",
                    help="stream uploads larger than BYTES of payload as "
                         "continuation chunks (relay forwards and client "
                         "uploads both honor it)")
    for fault in ("drop", "corrupt", "kill", "duplicate", "reorder",
                  "delay", "drop-reply"):
        ap.add_argument(f"--chaos-{fault}", type=float, default=0.0,
                        metavar="RATE",
                        help=f"with --listen: per-frame {fault} probability "
                             f"injected by the chaos proxy")
    ap.add_argument("--chaos-rate", type=float, default=0.0, metavar="RATE",
                    help="with --listen: shorthand setting EVERY chaos "
                         "fault to RATE")
    ap.add_argument("--chaos-delay-s", type=float, default=0.005,
                    help="injected latency per delay fault")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the chaos proxy's fault schedule")
    args = ap.parse_args()
    if args.mode == "relay" and args.upstream is None:
        ap.error("--mode relay requires --upstream HOST:PORT")
    if args.mode == "relay" or (args.mode == "fusion"
                                and args.listen is not None):
        from repro.fed.chaos import ChaosConfig

        if args.chaos_rate > 0:
            chaos = ChaosConfig.uniform(args.chaos_rate,
                                        delay_s=args.chaos_delay_s)
        else:
            rates = {f: getattr(args, f"chaos_{f}")
                     for f in ("drop", "corrupt", "kill", "duplicate",
                               "reorder", "delay", "drop_reply")}
            chaos = (ChaosConfig(**rates, delay_s=args.chaos_delay_s)
                     if any(r > 0 for r in rates.values()) else None)
        serve_wire(port=args.listen or 0,
                   expect_uploads=args.expect_uploads,
                   timeout_s=args.serve_timeout, sigma=args.sigma,
                   inference=args.inference, ci_level=args.ci_level,
                   coalesce_rank=args.coalesce_rank,
                   flush_staleness_s=args.flush_staleness,
                   max_warm=args.max_warm,
                   solve_window_s=args.solve_window,
                   journal_dir=args.journal_dir,
                   snapshot_every=args.snapshot_every,
                   journal_fsync=not args.no_journal_fsync,
                   chaos=chaos, chaos_seed=args.chaos_seed,
                   upstream=args.upstream if args.mode == "relay" else None,
                   relay_id=args.relay_id,
                   forward_every=args.forward_every,
                   forward_staleness_s=args.forward_staleness,
                   forward_interval_s=args.forward_interval,
                   relay_state_dir=args.relay_state_dir,
                   max_chunk_payload=args.max_chunk_payload)
        return
    if args.mode == "fusion":
        res = serve_fusion(dim=args.dim, tenants=args.tenants,
                           num_clients=args.clients,
                           samples_per_client=args.samples,
                           queries=args.queries,
                           sharded_tenants=args.sharded_tenants,
                           auto_tenants=args.auto_tenants,
                           sketched_tenants=args.sketched_tenants,
                           rff_tenants=args.rff_tenants,
                           feature_dim=args.feature_dim,
                           lengthscale=args.lengthscale,
                           stream_deltas=args.stream_deltas,
                           coalesce_rank=args.coalesce_rank,
                           flush_staleness_s=args.flush_staleness,
                           max_warm=args.max_warm)
        print(f"[serve_fusion] {res['queries']} queries, {res['tenants']} "
              f"tenants on one pool, placements {res['placements']} "
              f"({res['sharded_tenants']} pinned sharded, "
              f"{res['auto_tenants']} auto), "
              f"{res['distinct_sigmas']} distinct sigmas")
        print(f"[serve_fusion] naive {res['naive_qps']:.0f} qps -> pooled "
              f"{res['pool_qps']:.0f} qps ({res['speedup']:.1f}x)")
        print(f"[serve_fusion] exact: max|dw|={res['exact_max_abs_err']:.2e} "
              f"vs cold per-tenant references")
        for name, rep in res["feature_reports"].items():
            bound = rep.get("error_bound")
            print(f"[serve_fusion] {name}: kind={rep['kind']} "
                  f"solve_dim={rep['solve_dim']} "
                  f"upload_floats={rep['upload_floats']}"
                  + (f" prop3_bound={bound:.3f}" if bound is not None
                     else ""))
        if res["streaming"] is not None:
            s = res["streaming"]
            print(f"[serve_fusion] streaming {s['deltas']} deltas, no reads: "
                  f"{s['background_flushes']} background flushes, "
                  f"{s['pending_after']} left pending, worst delta age "
                  f"{s['max_flush_age_s']:.3f}s "
                  f"(budget {s['flush_staleness_s']:.3f}s), "
                  f"{s['mutations_per_delta']:.2f} mutations/delta, "
                  f"max|dw|={s['exact_max_abs_err']:.2e}")
        led = res["ledger"]
        print(f"[serve_fusion] ledger: {led['upload_download_bytes']} upload "
              f"bytes + {led['streamed_bytes']} streamed + "
              f"{led['cross_shard_bytes']} cross-shard over "
              f"{led['tenants']} tenants")
        if len(led.get("by_kind", {})) > 1:
            split = ", ".join(
                f"{kind}: {v['upload_bytes']}B/{v['tenants']} tenants"
                for kind, v in sorted(led["by_kind"].items()))
            print(f"[serve_fusion] upload bytes by kind: {split}")
        print(f"[serve_fusion] pool: meshes_built="
              f"{res['pool']['meshes_built']} "
              f"warm_tenants={res['pool']['warm_tenants']} "
              f"factor_evictions={res['pool']['factor_evictions']}")
        return
    if args.arch is None:
        ap.error("--arch is required for --mode model")
    res = serve(args.arch, reduced=args.reduced, batch=args.batch,
                prompt_len=args.prompt_len, gen_tokens=args.gen_tokens)
    print(f"[serve] {res['arch']}: prefill {res['prefill_s']:.2f}s, "
          f"decode {res['decode_tok_per_s']:.1f} tok/s "
          f"(batch {args.batch})")
    print(f"[serve] sample continuation: {res['generated'][0][:16].tolist()}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
