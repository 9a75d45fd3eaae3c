"""A configuration brought up as the system under test, in this process.

A ``FrameServer`` (with its ``SolveBatcher`` window) in front of an
``EnginePool``, journaled or in memory as the configuration states. Tenants
are admitted either over the wire (every client's STATS frame through the
same admission and journal path that serves uploads) or, where uploads are
not the cell's traffic, straight into the pool from statistics computed on
the device. A tenant group is placed ``dense`` (one chip; the default) or
``sharded`` (its fused statistics and factors block-sharded over a mesh of
the cell's chips); one server takes one placement. Set-up then warms exactly
the programs the cell's traffic runs: each tenant's cached sigma factors and
lone solves, the rank-r update of a streamed delta, and, for dense tenants,
every power-of-two stacked-sweep extent up to the mix's concurrency.
"""
from __future__ import annotations

import dataclasses
import pathlib
import shutil
import tempfile
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from bench import data as data_lib
from bench.loadgen import TenantInfo
from repro.fed import transport, wire
from repro.kernels.ops import pow2_bucket
from repro.server import EnginePool

#: Delta frames sent during set-up to compile the update path; they are
#: ordinary uploads (journaled, applied, in the reference).
WARM_DELTAS = 1


@dataclasses.dataclass
class Deployment:
    config: dict
    pool: EnginePool
    server: transport.FrameServer
    groups: list[data_lib.Group]
    tenants: list[TenantInfo]
    delta_frames: list[bytes]          # warm-up deltas first
    delta_where: list[tuple[int, int, int]]   # (group, tenant, site) per delta
    journal_dir: pathlib.Path | None
    journal_base_bytes: int = 0        # journal size before any delta
    journal_tail: bytes = b""          # the journal past set-up, at stop
    placements: dict = dataclasses.field(default_factory=dict)  # at warm

    def delta_local(self, n: int) -> int:
        """Index of delta ``n`` among its own group's delta batches."""
        gi = self.delta_where[n][0]
        return sum(1 for w in self.delta_where[:n] if w[0] == gi)

    def connect(self, tenant: str) -> transport.FrameClient:
        client = transport.FrameClient(transport.TCPChannel(
            self.server.host, self.server.port, timeout_s=300.0))
        client.hello(tenant, ("f32",))
        return client

    def stop(self) -> None:
        """Stop serving and free the server's state; keep the window's journal.

        The journal's bytes past set-up are read first, because closing a
        journaled pool commits a final snapshot and prunes the segments
        before it. The tenants are dropped before the close, so that the
        snapshot holds none of them: it would otherwise write every
        retained client Gram (1.4 GB at d=4096) once per run.
        """
        self.server.stop()
        if self.journal_dir is not None:
            self.journal_tail = journal_bytes(self.journal_dir,
                                              self.journal_base_bytes)
        for name in self.pool.tenant_names:
            self.pool.drop_tenant(name)
        self.pool.close()
        self.pool = self.server = None

    def cleanup(self) -> None:
        if self.pool is not None:
            self.stop()
        if self.journal_dir is not None:
            shutil.rmtree(self.journal_dir, ignore_errors=True)


def tenant_infos(config: dict) -> list[TenantInfo]:
    return [TenantInfo(name, g["kind"], g["clients"], tuple(g["sigmas"]))
            for g in config["tenants"] for name in data_lib.tenant_names(g)]


def journal_bytes(journal_dir: pathlib.Path, start: int = 0) -> bytes:
    """The journal's segments joined, from byte ``start`` on."""
    out, pos = [], 0
    for p in sorted(journal_dir.glob("wal_*.log")):
        size = p.stat().st_size
        if pos + size > start:
            with open(p, "rb") as f:
                f.seek(max(0, start - pos))
                out.append(f.read())
        pos += size
    return b"".join(out)


def journal_size(journal_dir: pathlib.Path) -> int:
    return sum(p.stat().st_size for p in journal_dir.glob("wal_*.log"))


def placement(config: dict) -> str:
    """The one placement of every tenant group of the configuration."""
    found = {data_lib.placement_of(g) for g in config["tenants"]}
    if len(found) != 1:
        raise SystemExit(f"bench: tenant groups ask for placements "
                         f"{sorted(found)}; one server takes one")
    return found.pop()


def build(config: dict, seed: int, delta_reqs: list, delta_rows: int,
          chips: int = 1) -> Deployment:
    """Data, server and tenants for one run; nothing warmed yet. Sharded
    tenants share one mesh over the first ``chips`` devices."""
    where_all = placement(config)
    infos = tenant_infos(config)
    # Deltas: warm-up first (site k of the first tenant that takes them),
    # then the window's, in schedule order.
    owner = {name: (gi, ti) for gi, g in enumerate(config["tenants"])
             for ti, name in enumerate(data_lib.tenant_names(g))}
    where: list[tuple[int, int, int]] = []
    if delta_reqs:
        gi, ti = owner[delta_reqs[0].tenant]
        where += [(gi, ti, k % config["tenants"][gi]["clients"])
                  for k in range(WARM_DELTAS)]
    where += [(*owner[q.tenant], q.site) for q in delta_reqs]
    groups = []
    for gi, g in enumerate(config["tenants"]):
        mine = [(ti, site) for gj, ti, site in where if gj == gi]
        groups.append(data_lib.make_group(
            seed, gi, g, [ti for ti, _ in mine], [site for _, site in mine],
            delta_rows))
    frames: list[bytes] = [b""] * len(where)
    for gi, grp in enumerate(groups):
        mine = [n for n, w in enumerate(where) if w[0] == gi]
        for j, n in enumerate(mine):
            _, _, site = where[n]
            frames[n] = wire.encode_frame(wire.DeltaRowsFrame(
                A=grp.deltas[0][j], b=grp.deltas[1][j],
                client_id=f"client{site}"), dtype="f32")
    server_cfg = config["server"]
    journal_dir = None
    if server_cfg["journal"]:
        journal_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench-journal-"))
        pool = EnginePool(mesh_devices=chips, journal_dir=str(journal_dir),
                          journal_fsync=bool(server_cfg["journal_fsync"]))
    else:
        pool = EnginePool(mesh_devices=chips)
    server = transport.FrameServer(
        pool, port=0, placement=where_all,
        solve_window_s=float(server_cfg["solve_window_s"])).start()
    return Deployment(config, pool, server, groups, infos, frames, where,
                      journal_dir)


def admit(dep: Deployment) -> None:
    """Every tenant's clients, by the configuration's admission path."""
    for grp in dep.groups:
        K = grp.spec["clients"]
        if grp.spec["admit"] == "wire":
            def upload(job, grp=grp):
                t, k = job
                client = dep.connect(grp.names[t])
                try:
                    client.upload_stats(grp.client_stats(t, k),
                                        client_id=f"client{k}", moments=True)
                finally:
                    client.close()

            jobs = [(t, k) for t in range(len(grp.names)) for k in range(K)]
            with ThreadPoolExecutor(4) as ex:
                for f in [ex.submit(upload, j) for j in jobs]:
                    f.result()
        elif grp.spec["admit"] == "pool" and grp.placement == "sharded":
            # One client's statistics on chip 0 at a time: each is made and
            # ingested (the engine retains it) before the next is made.
            for t, name in enumerate(grp.names):
                dep.pool.create_tenant(name, dim=grp.spec["dim"],
                                       placement="sharded")
                for k in range(K):
                    dep.pool.ingest(name, grp.client_stats(t, k),
                                    client_id=f"client{k}")
        elif grp.spec["admit"] == "pool":
            for t, name in enumerate(grp.names):
                dep.pool.create_tenant(
                    name, clients={f"client{k}": grp.client_stats(t, k)
                                   for k in range(K)},
                    features=grp.maps[t], placement="dense")
        else:
            raise SystemExit(f"bench: unknown admission {grp.spec['admit']!r}")
        grp.stats = None      # the server holds them now
    if dep.journal_dir is not None:
        dep.journal_base_bytes = journal_size(dep.journal_dir)


def warm(dep: Deployment, solve_sessions: int) -> None:
    """Cached factors and lone solves, the delta path, and every
    stacked-sweep extent of the dense tenants (sharded tenants are solved
    alone, never stacked)."""
    pool = dep.pool
    for t in dep.tenants:
        jax.block_until_ready(pool.solve_many(
            [(t.name, s) for s in t.sigmas], lifted=True))
    for n in range(min(WARM_DELTAS, len(dep.delta_where))):
        gi, ti, _ = dep.delta_where[n]
        client = dep.connect(dep.groups[gi].names[ti])
        try:
            client.upload_raw(dep.delta_frames[n])
        finally:
            client.close()
    buckets: dict[int, list[tuple[str, float]]] = {}
    for g, t in zip([g for g in dep.config["tenants"]
                     for _ in data_lib.tenant_names(g)], dep.tenants):
        if data_lib.placement_of(g) == "dense":
            buckets.setdefault(g["dim"], []).extend(
                (t.name, s) for s in t.sigmas)
    top = pow2_bucket(min(solve_sessions,
                         dep.server.dispatcher.solve_batcher.max_batch))
    for pairs in buckets.values():
        # One extent per power of two; 3 -> 4 also builds the pad lane.
        extents = sorted({1, 2} | {max(1, e - 1) for e in
                                   (2 ** i for i in range(2, 20))
                                   if e <= top})
        for e in extents:
            reqs = [pairs[i % len(pairs)] for i in range(e)]
            jax.block_until_ready(pool.solve_many(reqs, lifted=True))
    # One solve per tenant over the wire: the reply path end to end.
    for t in dep.tenants:
        client = dep.connect(t.name)
        try:
            np.asarray(client.solve(t.sigmas[0]))
        finally:
            client.close()
    check_placement(dep)


def check_placement(dep: Deployment) -> dict:
    """The pool's placements are the configuration's: a dense-only cell
    built no mesh, and every tenant of a sharded one is on the mesh."""
    summary = dep.pool.summary()
    where = placement(dep.config)
    if where == "dense" and summary["meshes_built"] != 0:
        raise RuntimeError("bench: a dense-only cell built a mesh")
    if summary["placements"] != {where: len(dep.tenants)}:
        raise RuntimeError(f"bench: tenants placed {summary['placements']}, "
                           f"the configuration asks {where}")
    dep.placements = summary["placements"]
    return summary
