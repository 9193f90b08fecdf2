"""Closed-loop what-if sweeps through the fused fleet tick.

Each request builds `variants` WAN schedules of the mix's timeline (one
simulator per variant, seeded from the run's seed, the request index and
the variant) and runs them `ticks` steps from the fleet's current state
in one launch of ``FleetController.fused().sweep``, results on the host.
Requests are sent back to back until the window closes.

Correctness: every variant of `check_requests` requests drawn from the
seed is replayed by the plain reference on the same schedule, from the
initial state the reference derives itself from the fleet's first
snapshot of the WAN; budgets and connection totals must match exactly,
and capacity and achieved BW within the mix's limit.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List

import numpy as np

from harness import Run, load_module

_fleet = load_module(os.path.join(os.path.dirname(__file__), "_fleet.py"))

CHECK_KEYS = ("budget", "conns_total")
BW_KEYS = ("cap_min", "achieved_min", "achieved_mean")


def build(run: Run):
    """Set-up: the fleet, its fused program and the request builder."""
    from repro.fleet import BatchedRfPredictor, FleetController
    from repro.fleet.fused import make_schedule
    from repro.wan.simulator import WanSimulator
    cfg, tr = run.config, run.traffic
    rf = _fleet.forest(cfg)
    kw = _fleet.sim_kwargs(cfg, tr)
    events = _fleet.timeline(tr)
    fleet = FleetController(WanSimulator(seed=_fleet.subseed(run.seed, 0),
                                         **kw),
                            BatchedRfPredictor(rf), m_total=cfg["m_total"],
                            jobs=_fleet.jobs(cfg))
    ff = fleet.fused()
    B, T = tr["variants"], tr["ticks"]
    # the WAN the fleet was admitted on: the single-connection BW of
    # every link and the cross-traffic, before any plan was in force
    n = fleet.sim.N
    wan0 = (fleet.sim.link_bw_now(),
            np.zeros((n, n)) if fleet.sim.background_conns is None
            else np.array(fleet.sim.background_conns, np.float64))

    def schedules(k: int):
        out = [make_schedule(WanSimulator(
            seed=_fleet.subseed(run.seed, 1, k, b), **kw), T, events)
            for b in range(B)]
        return (np.stack([s for s, _ in out]), np.stack([g for _, g in out]))

    return rf, fleet, ff, schedules, wan0


def run(run: Run) -> None:
    """Set up, warm up with one request, measure, then check."""
    rf, fleet, ff, schedules, wan0 = build(run)
    ff.sweep(*schedules(-1))                     # compiles; not measured
    done: List[Dict[str, Any]] = []
    with run.window():
        t0 = time.perf_counter()
        k = 0
        while True:
            with run.annotate("request"):
                with run.annotate("schedule"):
                    singles, bgs = schedules(k)
                outs = ff.sweep(singles, bgs)
            done.append({"k": k, "singles": singles, "bgs": bgs,
                         "outs": outs})
            k += 1
            if time.perf_counter() - t0 >= run.seconds:
                break
        elapsed = time.perf_counter() - t0
    B, T = run.traffic["variants"], run.traffic["ticks"]
    obs = run.obs
    obs["window_s"] = elapsed
    obs["attempted"] = len(done)
    obs["failed"] = sum(not bool(np.all(d["outs"]["converged"]))
                        for d in done)
    obs["work_ticks"] = len(done) * B * T
    obs["fill_iters"] = np.concatenate(
        [d["outs"]["fill_iters"].reshape(-1, 3).sum(-1) for d in done])
    obs["program"] = run.traffic["program"]
    obs["shapes"] = {"jobs": ff.J, "slice": ff.P, "dcs": ff.N,
                     "trees": rf.n_trees, "depth": rf.depth,
                     "features": 6, "variants": B, "ticks": T}
    del fleet, ff
    forest = _fleet.tables(rf)
    obs["records"] = (done, wan0, forest)
    obs["checks"] = compare(run, done, wan0, forest)


def pick(run: Run, n_requests: int) -> List[tuple]:
    """The (request, variant) pairs checked: every variant of
    `check_requests` requests of the window, drawn from the seed."""
    B = run.traffic["variants"]
    rng = np.random.default_rng(_fleet.subseed(run.seed, 2))
    n = min(run.traffic["check_requests"], n_requests)
    ks = rng.choice(n_requests, size=n, replace=False)
    return [(int(k), b) for k in sorted(ks) for b in range(B)]


def replay(ref, dep, forest, wan0, singles, bgs,
           dtype=np.float64, rf_dtype=np.float32) -> Dict[str, np.ndarray]:
    """The reference's per-tick stats [T, J] over one schedule, from the
    state it derives from the fleet's first snapshot."""
    cons, target = ref.initial_state(
        dep, ref.idle_snapshots(dep, *wan0, dtype), dtype)
    rows = []
    for t in range(len(singles)):
        cons, target, st = ref.deterministic_tick(
            dep, forest, cons, target, singles[t], bgs[t], dtype, rf_dtype)
        rows.append(st)
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}


def readings(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
             ) -> Dict[str, float]:
    """The compared numbers of one variant: decisions that differ, and
    the largest relative BW deviation."""
    mism = sum(int(np.sum(np.asarray(got[k]) != np.asarray(want[k])))
               for k in CHECK_KEYS)
    dev = max(_fleet.rel_dev(got[k], want[k]) for k in BW_KEYS)
    return {"decisions_mismatched": float(mism), "bw_rel_dev": dev}


def compare(run: Run, done, wan0, forest) -> List[Dict[str, Any]]:
    """Replay the sampled variants and compare with what was served."""
    ref = run.reference
    dep = ref.deployment_from_config(run.config)
    worst = {"decisions_mismatched": 0.0, "bw_rel_dev": 0.0}
    for k, b in pick(run, len(done)):
        d = done[k]
        want = replay(ref, dep, forest, wan0, d["singles"][b], d["bgs"][b])
        got = {key: d["outs"][key][b] for key in CHECK_KEYS + BW_KEYS}
        for name, v in readings(got, want).items():
            worst[name] = max(worst[name], v)
    lim = run.traffic["limits"]
    return [_fleet.check(n, worst[n], lim[n]) for n in worst]


def control(run: Run, records, dtype=np.float32, rf_dtype=None
            ) -> Dict[str, float]:
    """The control: the reference in the next lower precision put in the
    program's place, read by the same comparison."""
    import ml_dtypes
    done, wan0, forest = records
    ref = run.reference
    dep = ref.deployment_from_config(run.config)
    rf_dtype = rf_dtype or ml_dtypes.bfloat16
    worst = {"decisions_mismatched": 0.0, "bw_rel_dev": 0.0}
    for k, b in pick(run, len(done)):
        d = done[k]
        want = replay(ref, dep, forest, wan0, d["singles"][b], d["bgs"][b])
        low = replay(ref, dep, forest, wan0, d["singles"][b], d["bgs"][b],
                     dtype, rf_dtype)
        for name, v in readings(low, want).items():
            worst[name] = max(worst[name], v)
    return worst
