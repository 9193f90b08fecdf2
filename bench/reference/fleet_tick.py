"""Plain numpy reference of one arbitrated fleet tick.

Written from the paper (arXiv:2508.12961 §3-4) and the fleet semantics
the configuration states, and independent of the code under test: it
imports nothing of ``repro``. Every stage is a straightforward loop or
array expression:

  Algorithm 1 closeness classes -> Eq. 2-3 connection ranges and the
  §3.2.2 throttle -> largest-remainder budget split per host -> link
  shares by priority -> AIMD step per pair -> RTT-biased progressive
  water-fill (max-min fair) -> credited achieved BW per tenant.

The float type is a parameter: ``np.float64`` is the reference, and a
lower type is the control that a correct check has to reject. The
forest is inferred in ``rf_dtype`` (float32 as configured; bfloat16 for
the control).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

EARTH_MILES = 3958.8


def haversine_matrix(coords: Sequence[Sequence[float]]) -> np.ndarray:
    """Great-circle distances in miles between (lat, lon) points."""
    n = len(coords)
    d = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            if i == j:
                continue
            la1, lo1 = map(math.radians, coords[i])
            la2, lo2 = map(math.radians, coords[j])
            h = math.sin((la2 - la1) / 2) ** 2 + math.cos(la1) * \
                math.cos(la2) * math.sin((lo2 - lo1) / 2) ** 2
            d[i, j] = 2 * EARTH_MILES * math.asin(math.sqrt(h))
    return d


@dataclass
class Deployment:
    """The fixed inputs of a fleet: topology, tenants and constants."""
    dist: np.ndarray            # [N,N] miles
    presence: np.ndarray        # [J,N] bool
    slices: np.ndarray          # [J,P] DC indices per job
    priorities: np.ndarray      # [J]
    m_total: int
    nic_cap: float
    knee: float
    rtt_beta: float
    d_mbps: float               # Algorithm 1 minimum difference
    delta_mbps: float           # AIMD significance threshold
    intra_bw: float


def deployment_from_config(cfg: dict) -> Deployment:
    """Build the deployment from a configuration file's dict."""
    coords = [(r["lat"], r["lon"]) for r in cfg["regions"]]
    n = len(coords)
    jobs = cfg["jobs"]
    slices = np.array([sorted((k + i) % n for i in range(jobs["width"]))
                       for k in range(jobs["count"])], np.int64)
    prios = np.array([jobs["priorities"][k % len(jobs["priorities"])]
                      for k in range(jobs["count"])], np.float64)
    presence = np.zeros((jobs["count"], n), bool)
    for j, row in enumerate(slices):
        presence[j, row] = True
    c = cfg["constants"]
    return Deployment(dist=haversine_matrix(coords), presence=presence,
                      slices=slices, priorities=prios,
                      m_total=int(cfg["m_total"]), nic_cap=c["nic_cap_mbps"],
                      knee=c["knee_conns"], rtt_beta=c["rtt_beta"],
                      d_mbps=c["closeness_d_mbps"],
                      delta_mbps=c["aimd_delta_mbps"],
                      intra_bw=c["intra_dc_mbps"])


# ----------------------------------------------------------------------
# Random forest over the complete-binary-tree tables
# ----------------------------------------------------------------------
def forest_predict(feat: np.ndarray, thr: np.ndarray, leaf: np.ndarray,
                   X: np.ndarray, depth: int, dtype=np.float32) -> np.ndarray:
    """Mean over trees of the leaf each row reaches; comparisons and the
    leaf sum in `dtype`."""
    X = np.asarray(X, np.float32).astype(dtype)
    thr = np.asarray(thr, np.float32).astype(dtype)
    leaf = np.asarray(leaf, np.float32).astype(dtype)
    n_trees, n = feat.shape[0], len(X)
    trees = np.arange(n_trees)[:, None]
    node = np.zeros((n_trees, n), np.int64)       # every tree at once
    for _ in range(depth):
        f = np.maximum(feat[trees, node], 0)
        go = X[np.arange(n)[None, :], f] > thr[trees, node]
        node = 2 * node + 1 + go
    vals = leaf[trees, node - (2 ** depth - 1)]
    out = np.zeros(n, dtype)
    for t in range(n_trees):                      # the sum in `dtype`
        out = (out + vals[t]).astype(dtype)
    return (out / dtype(n_trees)).astype(np.float64)


# ----------------------------------------------------------------------
# Water-fill: RTT-biased weighted progressive filling
# ----------------------------------------------------------------------
def rtt_weights(dist: np.ndarray, beta: float, dtype=np.float64):
    """Per-connection weight (d_min / d)^beta, zero on the diagonal."""
    n = len(dist)
    off = ~np.eye(n, dtype=bool)
    d = np.maximum(dist, 1.0)
    w = (d[off].min() / d) ** beta
    w[~off] = 0.0
    return w.astype(dtype)


def waterfill(c: np.ndarray, single: np.ndarray, dep: Deployment,
              dtype=np.float64) -> np.ndarray:
    """Per-connection rate [N,N] for aggregate flows `c`: raise every
    unfrozen pair along one fill level (rate = level x weight) until a
    connection ceiling, the knee path cap or a NIC cap binds; freeze
    what binds; repeat."""
    f = dtype
    n = len(c)
    c = np.asarray(c, f)
    single = np.asarray(single, f)
    w = rtt_weights(dep.dist, dep.rtt_beta, f)
    cap_e = np.full(n, dep.nic_cap, f)
    cap_i = np.full(n, dep.nic_cap, f)
    path_cap = single * f(dep.knee)
    cw = c * w
    rate = np.zeros((n, n), f)
    frozen = c <= 0
    for _ in range(8 * n * n):
        if frozen.all():
            return rate
        act = ~frozen
        load = rate * c
        we = np.where(act, cw, 0).sum(1)
        wi = np.where(act, cw, 0).sum(0)
        bounds = [np.where(we > 0, (cap_e - load.sum(1)) /
                           np.maximum(we, f(1e-12)), np.inf),
                  np.where(wi > 0, (cap_i - load.sum(0)) /
                           np.maximum(wi, f(1e-12)), np.inf),
                  np.where(act & (w > 0), (single - rate) /
                           np.maximum(w, f(1e-12)), np.inf),
                  np.where(act & (cw > 0), (path_cap - load) /
                           np.maximum(cw, f(1e-12)), np.inf)]
        inc = min(float(np.min(b)) for b in bounds)
        if not math.isfinite(inc) or inc < 1e-9:
            inc = 0.0
        rate = np.where(act, rate + f(inc) * w, rate).astype(f)
        load = rate * c
        hit = act & (((single - rate) < 1e-6) | ((path_cap - load) < 1e-6))
        sat_e = cap_e - load.sum(1) < 1e-6
        sat_i = cap_i - load.sum(0) < 1e-6
        hit |= act & (sat_e[:, None] | sat_i[None, :])
        if not hit.any() and inc == 0.0:
            return rate
        frozen |= hit
    raise RuntimeError("reference water-fill did not converge")


# ----------------------------------------------------------------------
# Arbitration
# ----------------------------------------------------------------------
def split_budget(m_total: int, weights: np.ndarray) -> np.ndarray:
    """Largest-remainder shares of `m_total`, floor one per tenant."""
    w = np.maximum(np.asarray(weights, np.float64), 1e-9)
    if m_total <= len(w):
        return np.ones(len(w))
    quota = m_total * w / w.sum()
    share = np.floor(quota)
    order = sorted(range(len(w)), key=lambda k: (-(quota[k] - share[k]), k))
    for k in order[:int(m_total - share.sum())]:
        share[k] += 1
    share = np.maximum(share, 1)
    while share.sum() > m_total and share.max() > 1:
        share[int(np.argmax(share))] -= 1
    return share


def budgets(dep: Deployment) -> np.ndarray:
    """Each job's budget: the least of its shares over its DCs."""
    out = np.full(len(dep.priorities), float(dep.m_total))
    for d in range(dep.presence.shape[1]):
        here = np.flatnonzero(dep.presence[:, d])
        if len(here):
            out[here] = np.minimum(out[here],
                                   split_budget(dep.m_total,
                                                dep.priorities[here]))
    return np.maximum(out, 1.0)


def link_caps(dep: Deployment, cap_est: np.ndarray, dtype=np.float64
              ) -> np.ndarray:
    """[J,P,P] per-job caps: a pair that several jobs span is split by
    priority; a pair with one job is uncapped."""
    J, P = dep.slices.shape
    out = np.full((J, P, P), np.inf)
    for j in range(J):
        for a in range(P):
            for b in range(P):
                x, y = dep.slices[j, a], dep.slices[j, b]
                on = dep.presence[:, x] & dep.presence[:, y]
                if on.sum() > 1:
                    out[j, a, b] = cap_est[x, y] * dep.priorities[j] / \
                        max(dep.priorities[on].sum(), 1e-12)
    return out.astype(dtype)


# ----------------------------------------------------------------------
# One job's plan: Algorithm 1, Eq. 2-3, throttle, AIMD
# ----------------------------------------------------------------------
def closeness(bw: np.ndarray, d_min: float) -> np.ndarray:
    """Algorithm 1: classes of significantly different BW, 1 = closest
    (highest BW); each pair takes the class of its nearest kept value."""
    vals = sorted(set(bw.reshape(-1).tolist()))
    i = len(vals) - 1
    while i >= 1:
        if vals[i] - vals[i - 1] < d_min:
            del vals[i]
        i -= 1
    n_u = len(vals)
    n = len(bw)
    rel = np.ones((n, n))
    for r in range(n):
        for c in range(n):
            if r == c:
                continue
            v = bw[r, c]
            k = int(np.searchsorted(vals, v))
            if k < n_u and vals[k] == v:
                rel[r, c] = n_u - k
            else:
                lo, hi = max(k - 1, 0), min(k, n_u - 1)
                pick = lo if abs(v - vals[lo]) <= abs(vals[hi] - v) else hi
                rel[r, c] = n_u - pick
    return rel


def ranges(pred: np.ndarray, M: float, cap: np.ndarray, dep: Deployment,
           dtype=np.float64) -> Dict[str, np.ndarray]:
    """Eq. 2-3 connection ranges inside the job's envelope, the BW they
    reach and the throttle (row mean of the reachable maximum)."""
    f = dtype
    n = len(pred)
    eye = np.eye(n, dtype=bool)
    bw = np.asarray(pred, f)
    rel = closeness(bw, dep.d_mbps).astype(f)
    sum_all = rel.sum() - n
    lo = np.maximum(np.floor(rel / sum_all * f(M - 1)), 1)
    hi = np.ceil(f(M) * rel / rel.max(1)[:, None])
    lo[eye] = hi[eye] = 1
    lo = np.clip(np.rint(lo), 1, 2 * M)
    hi = np.maximum(np.clip(np.rint(hi), 1, 2 * M), lo)
    capped = np.isfinite(cap) & ~eye
    cap_cons = np.where(capped, np.ceil(cap / np.maximum(bw, f(1e-9))), hi)
    hi = np.maximum(np.minimum(hi, np.minimum(np.maximum(cap_cons, 1),
                                              2 * M)), 1)
    lo = np.minimum(lo, hi)
    lo_bw, hi_bw = (bw * lo).astype(f), (bw * hi).astype(f)
    row_mean = np.where(eye, 0, hi_bw).sum(1) / f(n - 1)
    thr = np.where(~eye & (hi_bw > row_mean[:, None]),
                   row_mean[:, None], np.inf)
    thr = np.where(~eye, np.minimum(thr, cap), thr)
    return {"min_cons": lo, "max_cons": hi, "min_bw": lo_bw,
            "max_bw": hi_bw, "unit_bw": bw, "throttle": thr.astype(f)}


def aimd(cons: np.ndarray, target: np.ndarray, rg: Dict[str, np.ndarray],
         monitored: np.ndarray, delta: float, dtype=np.float64):
    """One AIMD epoch per pair: halve on a significant shortfall, add one
    connection and one unit of BW when on target, else hold."""
    f = dtype
    n = len(cons)
    new_c, new_t = cons.copy(), np.asarray(target, f).copy()
    for i in range(n):
        for j in range(n):
            if i == j:
                continue
            cap = min(rg["max_bw"][i, j], rg["throttle"][i, j])
            m, t = monitored[i, j], new_t[i, j]
            if m < t - delta:
                new_c[i, j] = max(rg["min_cons"][i, j], cons[i, j] // 2)
                t = max(rg["min_bw"][i, j], t / 2)
            elif abs(m - t) <= delta:
                new_c[i, j] = min(rg["max_cons"][i, j], cons[i, j] + 1)
                t = min(cap, t + rg["unit_bw"][i, j])
            new_t[i, j] = min(max(t, rg["min_bw"][i, j]), cap)
    return new_c, new_t


# ----------------------------------------------------------------------
# Whole ticks
# ----------------------------------------------------------------------
def _embed(dep: Deployment, mats: np.ndarray) -> np.ndarray:
    """Sum of every job's [P,P] off-diagonal flows at mesh scale."""
    n = dep.presence.shape[1]
    total = np.zeros((n, n))
    for j, ix in enumerate(dep.slices):
        m = np.array(mats[j], np.float64)
        np.fill_diagonal(m, 0)
        total[np.ix_(ix, ix)] += m
    return total


def job_slice(dep: Deployment, j: int, full: np.ndarray) -> np.ndarray:
    """Job `j`'s [P,P] block of a mesh-scale matrix."""
    ix = dep.slices[j]
    return np.asarray(full)[np.ix_(ix, ix)]


def achieved(dep: Deployment, cons: np.ndarray, caps: np.ndarray,
             single: np.ndarray, bg: np.ndarray, dtype=np.float64
             ) -> List[np.ndarray]:
    """One fleet fill over every job's flows plus background, each job
    credited rate x its own connections and clamped to its caps."""
    rate = waterfill(_embed(dep, cons) + bg, single, dep, dtype)
    out = []
    for j in range(len(cons)):
        a = job_slice(dep, j, rate) * np.asarray(cons[j], np.float64)
        a = np.where(np.eye(len(a), dtype=bool), dep.intra_bw,
                     np.minimum(a, caps[j]))
        out.append(a)
    return out


def stats(dep: Deployment, cons, caps, ach, budget) -> Dict[str, np.ndarray]:
    """The per-job numbers a tick reports."""
    off = ~np.eye(dep.slices.shape[1], dtype=bool)
    return {"budget": np.asarray(budget, np.float64),
            "conns_total": np.array([c[off].sum() for c in cons]),
            "cap_min": np.array([c[off].min() for c in caps]),
            "achieved_min": np.array([a[off].min() for a in ach]),
            "achieved_mean": np.array([a[off].mean() for a in ach])}


def probe_capacity(dep: Deployment, probe: np.ndarray) -> np.ndarray:
    """Per-link capacity to arbitrate: a single-connection probe of
    every pair, scaled by the parallelism knee (sec. 2.2)."""
    n = len(probe)
    return np.where(np.eye(n, dtype=bool), dep.intra_bw, probe) * dep.knee


def initial_state(dep: Deployment, snaps: Sequence[np.ndarray],
                  dtype=np.float64):
    """Every job's state as the fleet admits it: the snapshot taken at
    one connection a pair stands in for the prediction (floored at 1
    Mbps), Eq. 2-3 ranges inside the job's budget, and every pair
    starting at its most connections and at the lesser of its most BW
    and its throttle. Written for jobs that share no DC pair: each job's
    envelope is then its whole budget, uncapped."""
    J, P = dep.slices.shape
    pairs = [{(a, b) for a in row for b in row if a != b}
             for row in dep.slices.tolist()]
    if any(pairs[j] & pairs[k] for j in range(J) for k in range(j)):
        raise NotImplementedError("initial_state: jobs that share a pair "
                                  "are admitted under shrinking envelopes")
    budget = budgets(dep)
    eye = np.eye(P, dtype=bool)
    cons, target = [], []
    for j in range(J):
        pred = np.where(eye, dep.intra_bw,
                        np.maximum(np.asarray(snaps[j], np.float64), 1.0))
        rg = ranges(pred, budget[j], np.full((P, P), np.inf), dep, dtype)
        cons.append(rg["max_cons"].astype(np.int64))
        target.append(np.minimum(rg["max_bw"], rg["throttle"]))
    return np.stack(cons), np.stack(target).astype(dtype)


def idle_snapshots(dep: Deployment, single: np.ndarray, bg: np.ndarray,
                   dtype=np.float64) -> List[np.ndarray]:
    """Each job's noiseless snapshot at one connection a pair, every job
    at once, before any plan is in force."""
    P = dep.slices.shape[1]
    ones = np.ones((len(dep.slices), P, P))
    rate = waterfill(_embed(dep, ones) + bg, single, dep, dtype)
    off = ~np.eye(P, dtype=bool)
    return [np.where(off, job_slice(dep, j, rate), dep.intra_bw)
            for j in range(len(dep.slices))]


def deterministic_tick(dep: Deployment, forest, cons: np.ndarray,
                       target: np.ndarray, single: np.ndarray,
                       bg: np.ndarray, dtype=np.float64,
                       rf_dtype=np.float32):
    """One tick of a fleet whose captures draw no noise: probe and
    capture fills at the in-force flows, Table-3 features, forest,
    ranges, AIMD, then the achieved fill. Returns (cons, target, stats).
    """
    feat, thr, leaf, depth = forest
    J, P = dep.slices.shape
    n = len(single)
    eye_n = np.eye(n, dtype=bool)
    off_p = ~np.eye(P, dtype=bool)
    total = _embed(dep, cons) + bg
    probe = waterfill(np.where(eye_n, 0, 1.0) + total, single, dep, dtype)
    cap_est = probe_capacity(dep, probe)
    capture = waterfill(total, single, dep, dtype)
    budget = budgets(dep)
    caps = link_caps(dep, cap_est, dtype)
    rows = []
    snaps = []
    for j in range(J):
        c = np.where(off_p, cons[j], 0).astype(np.float64)
        snap = np.where(off_p, job_slice(dep, j, capture) * c, dep.intra_bw)
        mem = np.clip(0.15 + 0.02 * c.sum(0), 0.05, 0.98)
        cpu = np.clip(0.10 + 0.015 * c.sum(1), 0.02, 0.98)
        solo = job_slice(dep, j, single)
        squeeze = np.maximum(0.0, 1.0 - snap / np.maximum(solo * c, 1e-9))
        retr = np.where(off_p, np.rint(squeeze * 40.0), 0.0)
        dist = job_slice(dep, j, dep.dist)
        for a in range(P):
            for b in range(P):
                if a != b:
                    rows.append([P, snap[a, b], mem[b], cpu[a], retr[a, b],
                                 dist[a, b]])
        snaps.append(snap)
    vals = np.maximum(forest_predict(feat, thr, leaf, np.array(rows),
                                     depth, rf_dtype), 1.0)
    new_c, new_t = np.array(cons), np.array(target, dtype)
    k = 0
    for j in range(J):
        pred = np.full((P, P), dep.intra_bw)
        pred[off_p] = vals[k:k + P * (P - 1)]
        k += P * (P - 1)
        rg = ranges(pred, budget[j], caps[j], dep, dtype)
        new_c[j], new_t[j] = aimd(cons[j], target[j], rg, snaps[j],
                                  dep.delta_mbps, dtype)
    ach = achieved(dep, new_c, caps, single, bg, dtype)
    return new_c, new_t, stats(dep, new_c, caps, ach, budget)


def observed_tick(dep: Deployment, cons: np.ndarray, target: np.ndarray,
                  probe: np.ndarray, snaps: np.ndarray, preds: np.ndarray,
                  single: np.ndarray, bg: Optional[np.ndarray],
                  dtype=np.float64):
    """One tick of a fleet with measurement noise, given what the tick
    observed (the single-connection capacity probe, each job's snapshot)
    and each job's predicted BW: arbitration, ranges, AIMD and the
    achieved fill."""
    J, P = dep.slices.shape
    n = len(single)
    bg = np.zeros((n, n)) if bg is None else bg
    budget = budgets(dep)
    caps = link_caps(dep, probe_capacity(dep, probe), dtype)
    new_c, new_t = np.array(cons), np.array(target, dtype)
    for j in range(J):
        rg = ranges(preds[j], budget[j], caps[j], dep, dtype)
        new_c[j], new_t[j] = aimd(cons[j], target[j], rg, snaps[j],
                                  dep.delta_mbps, dtype)
    ach = achieved(dep, new_c, caps, single, bg, dtype)
    return new_c, new_t, stats(dep, new_c, caps, ach, budget)


def features(P: int, snap: np.ndarray, mem: np.ndarray, cpu: np.ndarray,
             retr: np.ndarray, dist: np.ndarray) -> np.ndarray:
    """Table-3 rows for every ordered off-diagonal pair, row-major."""
    return np.array([[P, snap[a, b], mem[b], cpu[a], retr[a, b], dist[a, b]]
                     for a in range(P) for b in range(P) if a != b])
