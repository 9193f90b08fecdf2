"""The online control loop: ``FleetController.tick()`` back to back.

The mix's timeline is applied as ``FleetEngine.run`` applies it (due
events, then scripted modulation, then one tick), looped for as long as
the window lasts: a controller that is behind its monitoring period
ticks closed-loop. The first `warmup_ticks` ticks are set-up.

What is timed is the control plane's decision: each ``tick()`` call on
the host clock, less the time spent inside the WAN simulator during it
(advancing the WAN, the capacity probe, the snapshot captures and the
achieved-BW fill). The simulator stands in for the network a deployment
measures; its arithmetic is not work the control plane does.

Correctness: the reference runs the loop itself, from the state it
derives from the fleet's first snapshot, through every tick from the
first warm-up tick on. Its inputs are what each tick observed of the
network (the single-connection capacity probe, each job's snapshot and
host metrics, the WAN's link BW and cross-traffic) and, for the
decisions, the program's predictions, which are compared on their own
with the reference's forest on the tick's features. Compared per tick:
the predictions, the budgets and each pair's connections, and the
capacity caps and credited achieved BW.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

from harness import Run, load_module

_fleet = load_module(os.path.join(os.path.dirname(__file__), "_fleet.py"))

STATS = ("budget", "conns_total", "cap_min", "achieved_min",
         "achieved_mean")
# the WanSimulator entry points a fleet tick reaches (directly or through
# a job's TenantView); nested calls are counted once, at the outermost
SIM_ENTRIES = ("advance", "link_bw_now", "waterfill", "waterfill_tenants",
               "measure_simultaneous", "measure_snapshot", "host_metrics")


def build(run: Run):
    """Set-up: the fleet engine on the mix's noisy simulator."""
    from repro.fleet.scenario import FleetEngine, FleetScenarioSpec
    cfg, tr = run.config, run.traffic
    rf = _fleet.forest(cfg)
    kw = _fleet.sim_kwargs(cfg, tr)
    kw.pop("regions")
    spec = FleetScenarioSpec(
        name=run.cell["traffic"], steps=0, jobs=_fleet.jobs(cfg),
        events=_fleet.timeline(tr), m_total=cfg["m_total"],
        regions=[r["name"] for r in cfg["regions"]], sim_kwargs=kw)
    eng = FleetEngine(spec, seed=_fleet.subseed(run.seed, 0), forest=rf,
                      obs="on" if run.trace else "off")
    return rf, eng


class SimClock:
    """Host seconds spent inside the fleet's WAN simulator, and the
    capacity probe each tick takes, read off the live simulator."""

    def __init__(self, fleet):
        self.seconds = 0.0
        self.intervals: List[tuple] = []
        self.probe = None
        self._depth = 0
        self._probing = False
        sim = fleet.sim
        for name in SIM_ENTRIES:
            setattr(sim, name, self._timed(getattr(sim, name)))
        snapshot = sim.measure_snapshot
        estimate = fleet.capacity_estimate

        def measure_snapshot(*a, **k):
            out = snapshot(*a, **k)
            if self._probing:
                self.probe = np.array(out)
            return out

        def capacity_estimate():
            self._probing = True
            try:
                return estimate()
            finally:
                self._probing = False
        sim.measure_snapshot = measure_snapshot
        fleet.capacity_estimate = capacity_estimate

    def _timed(self, fn):
        def timed(*a, **k):
            if self._depth:
                return fn(*a, **k)
            self._depth = 1
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                t1 = time.perf_counter()
                self._depth = 0
                self.seconds += t1 - t0
                self.intervals.append((t0, t1))
        return timed


class Recorder:
    """Reads each tick's observations and outputs off the live fleet,
    outside the timed call."""

    def __init__(self, fleet, clock: SimClock):
        self.fleet = fleet
        self.clock = clock
        jobs = list(fleet.jobs.values())
        # the snapshot each job was admitted on
        self.first = [np.array(j.controller.monitor.last_raw["snapshot_bw"])
                      for j in jobs]
        self.rows: List[Dict[str, Any]] = []

    def cons(self) -> np.ndarray:
        """Every job's in-force connections."""
        return np.stack([np.stack([ag.cons for ag in j.controller._agents])
                         for j in self.fleet.jobs.values()])

    def after(self, record) -> None:
        """Keep what the reference needs from the tick just run (None
        where the tick raised)."""
        if record is None:
            self.rows.append(None)
            return
        jobs = list(self.fleet.jobs.values())
        sim = self.fleet.sim
        raw = [j.controller.monitor.last_raw for j in jobs]
        self.rows.append({
            "probe": self.clock.probe,
            "raw": [{k: np.array(r[k]) for k in ("snapshot_bw", "mem_util",
                                                 "cpu_load", "retrans")}
                    for r in raw],
            "pred": np.stack([np.array(j.controller.last_pred)
                              for j in jobs]),
            "single": sim.link_bw_now(),
            "bg": None if sim.background_conns is None
            else np.array(sim.background_conns),
            "out": {k: np.array([r[k] for r in record["jobs"]], np.float64)
                    for k in STATS},
            "new_cons": self.cons()})


def step(eng, k: int) -> None:
    """Apply the timeline at loop step `k` as ``FleetEngine.run`` does."""
    eng.step = k
    for t in eng._timeline.get(k, ()):
        t.event.apply(eng)
    eng._advance_scripted()


def tick(fleet, rec: Recorder) -> tuple:
    """One tick; returns (its host seconds less the simulator's, the
    simulator's)."""
    sim0 = rec.clock.seconds
    t0 = time.perf_counter()
    try:
        record = fleet.tick()
    except RuntimeError:
        record = None
    t1 = time.perf_counter()
    sim_s = rec.clock.seconds - sim0
    rec.after(record)
    return (t1 - t0) - sim_s, sim_s


def run(run: Run) -> None:
    """Set up and warm up, measure ticks, then check every tick."""
    rf, eng = build(run)
    fleet = eng.fleet
    clock = SimClock(fleet)
    rec = Recorder(fleet, clock)
    k = 0
    for _ in range(run.traffic["warmup_ticks"]):
        step(eng, k)
        tick(fleet, rec)
        k += 1
    warm = len(rec.rows)
    timed: List[tuple] = []
    if fleet.tracer.enabled:
        fleet.tracer.reset()
        clock.intervals.clear()
    with run.window():
        t_end = time.perf_counter() + run.seconds
        while True:
            step(eng, k)
            with run.annotate("tick"):
                timed.append(tick(fleet, rec))
            k += 1
            if time.perf_counter() >= t_end:
                break
    obs = run.obs
    timed = np.array(timed)
    obs["decision_s"] = timed[:, 0]
    obs["sim_ms_per_tick"] = 1e3 * float(timed[:, 1].mean())
    print(f"wan simulator: {obs['sim_ms_per_tick']!r} ms a tick, left out "
          f"of the decision time", file=sys.stderr)
    obs["attempted"] = len(timed)
    obs["failed"] = sum(r is None for r in rec.rows[warm:])
    if fleet.tracer.enabled:
        obs["spans"] = list(fleet.tracer.spans)
        obs["span_t0"] = fleet.tracer._t0
        obs["sim_intervals"] = np.array(clock.intervals).reshape(-1, 2)
    obs["kernel"] = run.traffic["kernel"]
    P = run.config["jobs"]["width"]
    obs["shapes"] = {"rows": len(fleet.jobs) * P * (P - 1),
                     "trees": rf.n_trees, "depth": rf.depth, "features": 6}
    forest = _fleet.tables(rf)
    del eng, fleet
    obs["records"] = (rec.first, rec.rows, forest)
    obs["checks"] = compare(run, rec.first, rec.rows, forest)


def predict(ref, dep, forest, row, rf_dtype=np.float32) -> np.ndarray:
    """[J,P,P] predicted BW from every job's Table-3 rows of the tick, in
    one forest pass (floored at 1 Mbps; diagonal intra-DC)."""
    feat, thr, leaf, depth = forest
    J, P = dep.slices.shape
    X = np.concatenate([
        ref.features(P, r["snapshot_bw"], r["mem_util"], r["cpu_load"],
                     r["retrans"], ref.job_slice(dep, j, dep.dist))
        for j, r in enumerate(row["raw"])])
    vals = np.maximum(ref.forest_predict(feat, thr, leaf, X, depth,
                                         rf_dtype), 1.0)
    out = np.full((J, P, P), dep.intra_bw)
    out[:, ~np.eye(P, dtype=bool)] = vals.reshape(J, P * (P - 1))
    return out


def loop(ref, dep, forest, first, rows, dtype=np.float64,
         rf_dtype=np.float32, preds=None):
    """The reference's run of the loop: per tick, its predictions, its
    new connections and its stats. `preds` (one per tick) replaces the
    program's predictions in the decisions."""
    cons, target = ref.initial_state(dep, first, dtype)
    out = []
    for i, row in enumerate(rows):
        if row is None:
            out.append(None)
            continue
        pred = predict(ref, dep, forest, row, rf_dtype)
        cons, target, st = ref.observed_tick(
            dep, cons, target, row["probe"],
            [r["snapshot_bw"] for r in row["raw"]],
            row["pred"] if preds is None else preds[i], row["single"],
            row["bg"], dtype)
        out.append((pred, cons, st))
    return out


def readings(dep, row, want) -> Dict[str, float]:
    """One tick's compared numbers: the program's `row` against the
    reference's (pred, cons, stats) `want`."""
    if row is None or want is None:
        return {"decisions_mismatched": float("inf"),
                "bw_rel_dev": float("inf"), "rf_rel_dev": float("inf")}
    pred, cons, st = want
    off = ~np.eye(dep.slices.shape[1], dtype=bool)
    rf_dev = _fleet.rel_dev(np.stack([p[off] for p in row["pred"]]),
                            np.stack([p[off] for p in pred]))
    mism = int(np.sum(cons != row["new_cons"]))
    mism += sum(int(np.sum(row["out"][k] != st[k]))
                for k in ("budget", "conns_total"))
    bw = max(_fleet.rel_dev(row["out"][k], st[k])
             for k in ("cap_min", "achieved_min", "achieved_mean"))
    return {"decisions_mismatched": float(mism), "bw_rel_dev": bw,
            "rf_rel_dev": rf_dev}


def worst_of(dep, rows, wants) -> Dict[str, float]:
    """The largest of each compared number over the ticks."""
    worst = {"decisions_mismatched": 0.0, "bw_rel_dev": 0.0,
             "rf_rel_dev": 0.0}
    if not rows:
        worst["decisions_mismatched"] = float("inf")
    for row, want in zip(rows, wants):
        for name, v in readings(dep, row, want).items():
            worst[name] = max(worst[name], v)
    return worst


def compare(run: Run, first, rows, forest) -> List[Dict[str, Any]]:
    """Every tick against the reference's own run of the loop."""
    ref = run.reference
    dep = ref.deployment_from_config(run.config)
    worst = worst_of(dep, rows, loop(ref, dep, forest, first, rows))
    lim = run.traffic["limits"]
    return [_fleet.check(n, worst[n], lim[n]) for n in worst]


def control(run: Run, records, dtype=np.float32, rf_dtype=None
            ) -> Dict[str, float]:
    """The control: the reference in the next lower precision put in the
    program's place, its own predictions driving its decisions, read by
    the same comparison against the reference."""
    import ml_dtypes
    first, rows, forest = records
    ref = run.reference
    dep = ref.deployment_from_config(run.config)
    rf_dtype = rf_dtype or ml_dtypes.bfloat16
    want = loop(ref, dep, forest, first, rows)
    low_pred = [None if r is None else predict(ref, dep, forest, r, rf_dtype)
                for r in rows]
    low = loop(ref, dep, forest, first, rows, dtype, rf_dtype,
               preds=low_pred)
    as_rows = [None if r is None or w is None else
               dict(r, pred=w[0], new_cons=w[1], out=w[2])
               for r, w in zip(rows, low)]
    return worst_of(dep, as_rows, want)
