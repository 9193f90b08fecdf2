"""What both fleet drivers build from a configuration and a traffic mix:
the deployment's trained forest, its jobs, the simulator's settings and
the scripted timeline."""
from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import numpy as np

from harness import BENCH_DIR


def subseed(seed: int, *parts: int) -> int:
    """A 32-bit simulator seed drawn from the run's seed and `parts`."""
    return int(np.random.SeedSequence(
        [int(seed) % 2 ** 64, *[int(p) % 2 ** 64 for p in parts]]
    ).generate_state(1)[0])


def forest(cfg: Dict[str, Any]):
    """The configuration's forest, loaded from its committed tables
    (``bench/fixtures/make_forest.py`` wrote them)."""
    from repro.core.forest import RandomForest
    fc = cfg["forest"]
    rf = RandomForest(n_trees=fc["n_trees"], depth=fc["depth"])
    with np.load(os.path.join(BENCH_DIR, fc["tables"])) as z:
        rf.feat = z["feat"].astype(np.int32)
        rf.thr = z["thr"].astype(np.float32)
        rf.leaf = z["leaf"].astype(np.float32)
    return rf


def tables(rf) -> Tuple:
    """The forest as the reference takes it: (feat, thr, leaf, depth)."""
    return rf.feat, rf.thr, rf.leaf, rf.depth


def jobs(cfg: Dict[str, Any]) -> Tuple:
    """The workloads: `count` jobs on `width`-DC windows of the region
    ring, priorities cycling through the configured list."""
    from repro.fleet import JobSpec
    n = len(cfg["regions"])
    jc = cfg["jobs"]
    prios = jc["priorities"]
    return tuple(
        JobSpec(f"job{k}", dcs=tuple(sorted((k + i) % n
                                            for i in range(jc["width"]))),
                priority=float(prios[k % len(prios)]))
        for k in range(jc["count"]))


def sim_kwargs(cfg: Dict[str, Any], traffic: Dict[str, Any]
               ) -> Dict[str, Any]:
    """WanSimulator settings: the deployment's constants and the mix's
    noise levels."""
    c = cfg["constants"]
    kw = dict(regions=[r["name"] for r in cfg["regions"]],
              nic_cap=c["nic_cap_mbps"], knee=c["knee_conns"],
              rtt_beta=c["rtt_beta"])
    kw.update(traffic["noise"])
    return kw


def timeline(traffic: Dict[str, Any]) -> Tuple:
    """The mix's scripted events as ``at(step, Event(**args))``."""
    from repro.scenarios import events as ev
    return tuple(ev.at(e["step"], getattr(ev, e["event"])(**e["args"]))
                 for e in traffic["events"])


def rel_dev(a: np.ndarray, b: np.ndarray) -> float:
    """Largest |a - b| / |b| over finite nonzero b (exact ties are 0)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    ok = np.isfinite(b) & (b != 0)
    bad = ~np.isfinite(a) & ok
    if bad.any():
        return float("inf")
    if not ok.any():
        return 0.0
    return float(np.max(np.abs(a[ok] - b[ok]) / np.abs(b[ok])))


def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    """One compared number beside its limit."""
    return {"name": name, "value": float(value), "limit": float(limit)}
