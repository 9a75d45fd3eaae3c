"""What decides ``correct``: the window's answers against the float64 reference.

Once the window has closed and every reply is in, every SOLVE reply of
the window is screened against the float64 normal equations of the states
it may come from (``screen``), and a sample of them is compared with the
forward solution of ``bench/reference.py``: drawn from the seed (split
evenly over the tenant kinds), the last reply of every tenant that took
streamed deltas, and the replies the screen ranks worst.

  * A tenant without uploads in the window has one state: its clients'
    rows (for an RFF tenant, their float64 features).
  * A tenant that takes deltas has one state per prefix of its deltas in
    the order the server applied them, which is the order of the journal
    (or of the ACKs, unjournaled). A reply can only come from a prefix
    that holds every delta ACKed before the SOLVE was sent and no delta
    sent after its reply came: read-your-writes. Of those prefixes the
    reply is judged against the one whose normal equations it solves best.
  * Each tenant's float64 statistics are built once from its rows.

The compared numbers, each beside its limit (the configuration's
``limits``):

  w_rel_err        max ||w - w64|| / ||w64|| over the sample
  unanswered       requests due in the window that never got a reply
  error_replies    requests answered with an error or a broken session
  journal_missing  ACKed deltas absent from the journal (durability)
"""
from __future__ import annotations

import itertools
import math
import time

import numpy as np

from bench import reference
from bench.loadgen import rng_for


def _tenant_rows(dep, gi: int, ti: int):
    grp = dep.groups[gi]
    A, b = grp.rows
    X = A[ti].reshape(-1, A.shape[-1])
    y = b[ti].reshape(-1)
    return grp, X, y


def _features(grp, ti: int, fmaps: dict):
    """The tenant's float64 featurization (identity for dense tenants)."""
    if grp.kind == "dense":
        return lambda X: np.asarray(X, np.float64)
    fm = grp.maps[ti]
    key = (fm.seed, fm.d_orig, fm.m, fm.lengthscale)
    if key not in fmaps:
        fmaps[key] = reference.rff_arrays(*key)
    W, c = fmaps[key]
    return lambda X: reference.rff_features64(X, W, c)


def delta_order(dep, outcomes: dict, delta_req: dict) -> tuple[dict, int]:
    """Per tenant, its deltas in the order the server applied them.

    Returns ``({(gi, ti): [delta ids]}, journal_missing)``. Warm-up deltas
    (ACKed during set-up) come first by construction.
    """
    warm = set(range(len(dep.delta_frames) - len(delta_req)))
    acked = warm | {n for n, q in delta_req.items()
                    if q.idx in outcomes and outcomes[q.idx].ok}
    sent = warm | {n for n, q in delta_req.items() if q.idx in outcomes}
    missing = 0
    if dep.journal_dir is not None:
        data = dep.journal_tail
        pos: dict[int, int] = {}
        hint = 0
        for n in sorted(sent, key=lambda n: _ack_time(n, outcomes,
                                                      delta_req)):
            raw = dep.delta_frames[n]
            p = data.find(raw, hint)
            if p < 0:
                p = data.find(raw)
            if p < 0:
                missing += n in acked
                continue
            pos[n] = p
            hint = p + len(raw)
        key = pos.__getitem__
        applied = list(pos)
    else:
        applied = list(acked)
        key = lambda n: _ack_time(n, outcomes, delta_req)  # noqa: E731
    order: dict[tuple[int, int], list[int]] = {}
    for n in sorted(applied, key=key):
        gi, ti, _ = dep.delta_where[n]
        order.setdefault((gi, ti), []).append(n)
    return order, missing


def _ack_time(n: int, outcomes: dict, delta_req: dict) -> float:
    q = delta_req.get(n)
    if q is None:
        return -math.inf
    out = outcomes.get(q.idx)
    return math.inf if out is None or not out.ok else out.done


def _sent_time(n: int, outcomes: dict, delta_req: dict) -> float:
    q = delta_req.get(n)
    if q is None:
        return -math.inf
    out = outcomes.get(q.idx)
    return math.inf if out is None or math.isnan(out.sent) else out.sent


def sample_solves(reqs, outcomes, dep, seed: int, n: int, streamed,
                  screened: dict, worst: int) -> list:
    """Answered solves to compare: ``n`` from the seed, even over kinds,
    the last reply of every streamed tenant, and the ``worst`` replies of
    the residual screen."""
    kind_of = {t.name: t.kind for t in dep.tenants}
    done = [q for q in reqs if q.kind == "solve"
            and q.idx in outcomes and outcomes[q.idx].ok]
    rng = rng_for(seed, 2)
    kinds = sorted({kind_of[q.tenant] for q in done})
    picked: dict[int, object] = {}
    for i, kind in enumerate(kinds):
        pool = [q for q in done if kind_of[q.tenant] == kind]
        k = n // len(kinds) + (i < n % len(kinds))
        for j in rng.choice(len(pool), size=min(k, len(pool)),
                            replace=False):
            picked[pool[j].idx] = pool[j]
    for name in streamed:
        mine = [q for q in done if q.tenant == name]
        if mine:
            last = max(mine, key=lambda q: outcomes[q.idx].done)
            picked[last.idx] = last
    for q in sorted(done, key=lambda q: -screened[q.idx][0])[:worst]:
        picked[q.idx] = q
    return sorted(picked.values(), key=lambda q: q.idx)


def screen(dep, gi: int, ti: int, qs: list, outcomes: dict, seq: list,
           delta_req: dict, feat, base) -> dict:
    """Every answered solve of one tenant against its admissible states.

    Returns ``{idx: (residual, prefix)}``: the smallest normal-equation
    residual ||(G_j + sigma I) w - h_j|| / ||h_j|| of each reply over the
    prefixes j of the tenant's deltas that read-your-writes admits, and
    that prefix. A reply for another sigma, tenant or state reads far
    above round-off here. Replies alike in sigma, admissible prefixes and
    every bit of their weights read alike, so each such set is computed
    once.
    """
    grp = dep.groups[gi]
    lo_all = [max([k + 1 for k, n in enumerate(seq)
                   if _ack_time(n, outcomes, delta_req)
                   < outcomes[q.idx].sent], default=0) for q in qs]
    hi_all = [max([k + 1 for k, n in enumerate(seq)
                   if _sent_time(n, outcomes, delta_req)
                   < outcomes[q.idx].done], default=0) for q in qs]
    first: dict[tuple, int] = {}   # each alike set's first reply
    col = [first.setdefault((q.sigma, lo_all[i], hi_all[i],
                             np.asarray(outcomes[q.idx].result).tobytes()), i)
           for i, q in enumerate(qs)]
    uniq = sorted(first.values())
    pos = {i: n for n, i in enumerate(uniq)}
    W = np.stack([np.asarray(outcomes[qs[i].idx].result, np.float64)
                  for i in uniq], axis=1)
    sig = np.array([qs[i].sigma for i in uniq])
    lo = np.array([lo_all[i] for i in uniq])
    hi = np.array([hi_all[i] for i in uniq])
    GW = base.G @ W + sig * W
    h = base.h.copy()
    best = np.full(len(uniq), math.inf)
    best_j = lo.copy()
    for j in range(int(hi.max()) + 1):
        if j:
            local = dep.delta_local(seq[j - 1])
            U = feat(grp.deltas[0][local])
            GW += U.T @ (U @ W)
            h += U.T @ np.asarray(grp.deltas[1][local], np.float64)
        r = np.linalg.norm(GW - h[:, None], axis=0) / np.linalg.norm(h)
        r = np.where(np.isfinite(r), r, math.inf)
        better = (j >= lo) & (j <= hi) & (r < best)
        best[better], best_j[better] = r[better], j
    return {q.idx: (float(best[pos[col[i]]]), int(best_j[pos[col[i]]]))
            for i, q in enumerate(qs)}


def compare(dep, reqs, outcomes, seed: int, *, control: bool = False
            ) -> dict:
    """Every compared number with its limit; ``control`` adds the control's."""
    limits = dep.config["limits"]
    where = {name: (gi, ti) for gi, g in enumerate(dep.groups)
             for ti, name in enumerate(g.names)}
    window_deltas = [q for q in reqs if q.kind == "delta"]
    warm = len(dep.delta_frames) - len(window_deltas)
    delta_req = {warm + q.delta: q for q in window_deltas}
    order, missing = delta_order(dep, outcomes, delta_req)
    streamed = [dep.groups[gi].names[ti] for gi, ti in order]

    fmaps: dict = {}
    bases: dict[tuple[int, int], reference.Ridge64] = {}
    by_tenant: dict[str, list] = {}
    for q in reqs:
        if q.kind == "solve" and q.idx in outcomes and outcomes[q.idx].ok:
            by_tenant.setdefault(q.tenant, []).append(q)
    screened: dict[int, tuple[float, int]] = {}
    took = {"statistics": 0.0, "screen": 0.0, "solves": 0.0}
    for name, qs in by_tenant.items():
        gi, ti = where[name]
        t0 = time.perf_counter()
        grp, X, y = _tenant_rows(dep, gi, ti)
        feat = _features(grp, ti, fmaps)
        base = reference.Ridge64(grp.spec["dim"])
        base.add(feat(X), y)
        bases[(gi, ti)] = base
        t1 = time.perf_counter()
        screened.update(screen(dep, gi, ti, qs, outcomes,
                               order.get((gi, ti), []), delta_req, feat,
                               base))
        took["statistics"] += t1 - t0
        took["screen"] += time.perf_counter() - t1
    check_cfg = dep.config["check"]
    sample = sample_solves(reqs, outcomes, dep, seed,
                           int(check_cfg["sample_solves"]), streamed,
                           screened, int(check_cfg["worst_screened"]))

    worst = 0.0
    worst_ctrl = 0.0
    picked = [(*where[q.tenant], screened[q.idx][1], q,
               np.asarray(outcomes[q.idx].result, np.float64))
              for q in sample]

    # Forward errors, walking each tenant's prefixes in order; each state
    # is solved once at all of its sampled sigmas, and the control builds
    # each state once.
    picked.sort(key=lambda p: (p[0], p[1], p[2]))
    state = None
    solves = 0
    for (gi, ti, j), same in itertools.groupby(picked, key=lambda p: p[:3]):
        same = list(same)
        if state is None or state[0] != (gi, ti) or state[1] > j:
            state = [(gi, ti), 0, bases[(gi, ti)]]
        grp = dep.groups[gi]
        feat = _features(grp, ti, fmaps)
        seq = order.get((gi, ti), [])
        while state[1] < j:
            if state[2] is bases[(gi, ti)]:
                state[2] = state[2].copy()
            n = seq[state[1]]
            state[2].add(feat(grp.deltas[0][dep.delta_local(n)]),
                         grp.deltas[1][dep.delta_local(n)])
            state[1] += 1
        t0 = time.perf_counter()
        w64 = state[2].solve_many([p[3].sigma for p in same])
        took["solves"] += time.perf_counter() - t0
        solves += len(w64)
        for *_, q, w in same:
            err = reference.rel(w, w64[q.sigma])
            worst = max(worst, err if math.isfinite(err) else math.inf)
        if control:
            from bench import control as control_lib

            G, h = control_lib.stats(dep, gi, ti, seq[:j])
            wc = {s: control_lib.solve_stats(G, h, s) for s in w64}
            del G, h
            for *_, q, _ in same:
                worst_ctrl = max(worst_ctrl,
                                 reference.rel(wc[q.sigma], w64[q.sigma]))

    print(f"check: reference statistics {took['statistics']:.1f} s, "
          f"screen {took['screen']:.1f} s, {solves} reference solves "
          f"{took['solves']:.1f} s", flush=True)
    unanswered = sum(q.idx not in outcomes for q in reqs)
    errors = sum(1 for q in reqs if q.idx in outcomes
                 and not outcomes[q.idx].ok)
    checks = {
        "w_rel_err": (worst if picked else None, limits["w_rel_err"]),
        "unanswered": (unanswered, 0),
        "error_replies": (errors, 0),
    }
    if dep.journal_dir is not None:
        checks["journal_missing"] = (missing, 0)
    if control:
        checks["control_w_rel_err"] = (worst_ctrl, limits["w_rel_err"])
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def passed(checks: dict, *, control: bool = False) -> bool:
    """All compared numbers within their limits (no reading fails)."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for k, c in checks.items()
               if control or not k.startswith("control_"))
