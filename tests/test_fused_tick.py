"""The fused fleet tick (repro.fleet.fused): each jax-port stage pinned
against its numpy reference, and the whole scanned program pinned
against the sequential `FleetController.tick` loop."""
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.global_opt import _pair_weights, global_optimize, \
    split_budget
from repro.core.local_opt import AimdAgent
from repro.core.relations import infer_dc_relations
from repro.fleet import (BatchedRfPredictor, FleetController, FusedFleet,
                         JobSpec, default_fleet_forest, make_schedule)
from repro.fleet import arbiter
from repro.fleet.fused import (aimd_step_jnp, connection_budgets_jnp,
                               forest_rows, global_ranges_jnp,
                               link_shares_jnp, relations_jnp,
                               split_budget_jnp)
from repro.fleet.scenario import FleetEngine, FleetScenarioSpec
from repro.scenarios.events import (CrossTraffic, DiurnalCycle, JobArrive,
                                    LinkDegrade, LinkRestore, at)
from repro.wan.simulator import WanSimulator

QUIET = dict(fluct_sigma=0.0, snapshot_sigma=0.0, runtime_sigma=0.0,
             host_sigma=0.0)
JOBS = (JobSpec("serving", dcs=(0, 1, 2, 3), priority=4.0),
        JobSpec("training", dcs=(0, 1, 4, 5), priority=2.0),
        JobSpec("batch", dcs=(2, 3, 6, 7), priority=1.0))


def _forest():
    return default_fleet_forest()


def build_fleet(seed=3, jobs=JOBS, m_total=8, **sim_kw):
    kw = dict(QUIET)
    kw.update(sim_kw)
    sim = WanSimulator(seed=seed, **kw)
    return FleetController(sim, BatchedRfPredictor(_forest()),
                           m_total=m_total, jobs=jobs)


def random_bw(rng, n):
    bw = rng.uniform(60.0, 2200.0, (n, n))
    bw = (bw + bw.T) / 2
    np.fill_diagonal(bw, 10000.0)
    return bw


# ----------------------------------------------------------------------
# stage-by-stage parity
# ----------------------------------------------------------------------
def test_relations_port_exact():
    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        for trial in range(40):
            n = int(rng.integers(2, 9))
            bw = random_bw(rng, n)
            if trial % 3 == 0:                  # force near-duplicates
                bw[0, 1] = bw[1, 0] = bw[1 % n, 0] + rng.uniform(0, 150)
            D = float(rng.uniform(10, 300))
            ref = infer_dc_relations(bw, D)
            got = np.asarray(relations_jnp(jnp.asarray(bw), D))
            np.testing.assert_array_equal(got, ref)


def test_global_ranges_port_exact():
    """Eq. 2-3 + throttle + link-cap clamp: integer ranges match the
    numpy optimizer exactly, continuous outputs to roundoff."""
    rng = np.random.default_rng(1)
    with jax.enable_x64(True):
        for trial in range(25):
            n = int(rng.integers(2, 7))
            bw = random_bw(rng, n)
            M = int(rng.integers(2, 16))
            skew = rng.uniform(0.5, 3.0, n) if trial % 2 else None
            ws = _pair_weights(n, skew)
            link_cap = np.where(rng.random((n, n)) < 0.4,
                                rng.uniform(100, 3000, (n, n)), np.inf)
            ref = global_optimize(bw, M=M, w_s=skew, link_cap=link_cap)
            got = global_ranges_jnp(jnp.asarray(bw), jnp.asarray(float(M)),
                                    jnp.asarray(ws), jnp.asarray(link_cap))
            np.testing.assert_array_equal(np.asarray(got["min_cons"]),
                                          ref.min_cons)
            np.testing.assert_array_equal(np.asarray(got["max_cons"]),
                                          ref.max_cons)
            np.testing.assert_allclose(np.asarray(got["min_bw"]),
                                       ref.min_bw, rtol=1e-12)
            np.testing.assert_allclose(np.asarray(got["max_bw"]),
                                       ref.max_bw, rtol=1e-12)
            np.testing.assert_allclose(np.asarray(got["throttle"]),
                                       ref.throttle, rtol=1e-9)


def test_split_budget_port_exact():
    rng = np.random.default_rng(2)
    with jax.enable_x64(True):
        for _ in range(40):
            J = int(rng.integers(1, 9))
            m = int(rng.integers(1, 33))
            w = rng.choice([1.0, 2.0, 4.0, 8.0], J)
            present = rng.random(J) < 0.7
            ref = np.full(J, float(m))
            if present.any():
                ref[present] = split_budget(m, w[present])
            got = np.asarray(split_budget_jnp(m, jnp.asarray(w),
                                              jnp.asarray(present)))
            np.testing.assert_array_equal(got, ref)


def test_arbiter_ports_exact():
    rng = np.random.default_rng(3)
    with jax.enable_x64(True):
        for _ in range(15):
            J, n = int(rng.integers(1, 7)), 8
            presence = rng.random((J, n)) < 0.5
            presence[:, 0] = True                # nobody floats free
            w = rng.choice([1.0, 2.0, 4.0], J)
            cap = rng.uniform(100, 5000, (n, n))
            ref_b = arbiter.connection_budgets(presence, w, 8)
            got_b = np.asarray(connection_budgets_jnp(
                jnp.asarray(presence), jnp.asarray(w), 8))
            np.testing.assert_array_equal(got_b, ref_b)
            ref_c = arbiter.link_shares(presence, w, cap)
            got_c = np.asarray(link_shares_jnp(
                jnp.asarray(presence), jnp.asarray(w), jnp.asarray(cap)))
            np.testing.assert_allclose(got_c, ref_c, rtol=1e-12)


def test_aimd_port_exact():
    """Every source row stepped at once == per-agent Python AIMD."""
    rng = np.random.default_rng(4)
    with jax.enable_x64(True):
        for _ in range(10):
            n = int(rng.integers(2, 7))
            plan = global_optimize(random_bw(rng, n), M=8)
            agents = [AimdAgent.from_plan(plan, i) for i in range(n)]
            cons = np.stack([ag.cons for ag in agents])
            target = np.stack([ag.target_bw for ag in agents])
            ranges = {
                "min_cons": jnp.asarray(plan.min_cons, jnp.int32),
                "max_cons": jnp.asarray(plan.max_cons, jnp.int32),
                "min_bw": jnp.asarray(plan.min_bw),
                "max_bw": jnp.asarray(plan.max_bw),
                "unit_bw": jnp.asarray(plan.pred_bw),
                "throttle": jnp.asarray(plan.throttle),
            }
            for _step in range(4):
                mon = rng.uniform(0, 3000, (n, n))
                new_c, new_t = aimd_step_jnp(
                    jnp.asarray(cons, jnp.int32), jnp.asarray(target),
                    ranges, jnp.asarray(mon))
                for i, ag in enumerate(agents):
                    ag.step(mon[i])
                cons = np.stack([ag.cons for ag in agents])
                target = np.stack([ag.target_bw for ag in agents])
                np.testing.assert_array_equal(np.asarray(new_c), cons)
                np.testing.assert_allclose(np.asarray(new_t), target,
                                           rtol=1e-12, atol=1e-12)


def _plain_forest_predict():
    """The benchmark's plain numpy forest (imports nothing of repro)."""
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "reference", "fleet_tick.py")
    spec = importlib.util.spec_from_file_location("fleet_tick_ref", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod            # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod.forest_predict


def _feature_rows(seed, n):
    """Table-3 feature rows [n, 6] in float32 at fleet magnitudes."""
    rng = np.random.default_rng(seed)
    return np.stack([
        np.full(n, 4.0), rng.uniform(50.0, 2500.0, n),
        rng.uniform(0.05, 0.98, n), rng.uniform(0.02, 0.98, n),
        rng.integers(0, 40, n).astype(float),
        rng.uniform(300.0, 12000.0, n)], axis=-1).astype(np.float32)


def test_forest_stage_equals_sequential_predictor():
    """The fused tick's forest stage, jitted under x64 as the tick runs
    it, returns the sequential tick's `predict_rows` bits (no floor hit),
    stays within the online limit of the plain forest, and under vmap
    gives each variant the rows of its own batch."""
    rf = _forest()
    bat = BatchedRfPredictor(rf)
    feat, thr, leaf = (jnp.asarray(a) for a in rf.packed())
    R = 3 * 4 * 3                           # J * P * (P - 1) of JOBS
    X = _feature_rows(0, R)
    Xb = np.stack([X, _feature_rows(1, R)])
    with jax.enable_x64(True):
        stage = jax.jit(lambda x: forest_rows(feat, thr, leaf, x, rf.depth))
        got = np.asarray(stage(jnp.asarray(X)))
        got_b = np.asarray(jax.vmap(stage)(jnp.asarray(Xb)))
    assert got.dtype == np.float32 and got.shape == (R,)
    assert np.all(got > 1.0)
    np.testing.assert_array_equal(got.astype(np.float64),
                                  bat.predict_rows(X))
    plain = _plain_forest_predict()(*rf.packed(), X, rf.depth)
    assert np.max(np.abs(got - plain) / np.abs(plain)) <= 1e-5
    assert got_b.shape == (2, R)
    for b in range(2):
        np.testing.assert_array_equal(got_b[b].astype(np.float64),
                                      bat.predict_rows(Xb[b]))
    assert not np.array_equal(got_b[0], got_b[1])


# ----------------------------------------------------------------------
# whole-loop equivalence
# ----------------------------------------------------------------------
def _rows_match(seq_row, fus_row, tol=1e-6):
    assert seq_row["name"] == fus_row["name"]
    assert seq_row["budget"] == fus_row["budget"]
    assert seq_row["conns_total"] == fus_row["conns_total"]
    for k in ("cap_min", "achieved_min", "achieved_mean"):
        a, b = seq_row[k], fus_row[k]
        assert a == b or np.isclose(a, b, rtol=tol, atol=tol), \
            (k, a, b)


def test_fused_matches_sequential_ticks():
    """`run_fused(T)` reproduces T sequential ticks: identical integer
    budgets/connection totals per tick, achieved BW to roundoff, and
    the SAME final controller state (sequential ticks continue
    byte-compatibly afterwards)."""
    seq = build_fleet()
    seq_rows = [seq.tick() for _ in range(4)]
    fus = build_fleet()
    fus_rows = fus.run_fused(4)
    assert fus.tick_count == seq.tick_count == 4
    for a, b in zip(seq_rows, fus_rows):
        assert a["tick"] == b["tick"] and a["n_jobs"] == b["n_jobs"]
        for ra, rb in zip(a["jobs"], b["jobs"]):
            _rows_match(ra, rb)
    for name in seq.jobs:
        ca = seq.jobs[name].controller.current_conns()
        cb = fus.jobs[name].controller.current_conns()
        np.testing.assert_array_equal(ca, cb)
        ta = np.stack([ag.target_bw
                       for ag in seq.jobs[name].controller._agents])
        tb = np.stack([ag.target_bw
                       for ag in fus.jobs[name].controller._agents])
        np.testing.assert_allclose(ta, tb, rtol=1e-6, atol=1e-6)
    # the loop keeps running sequentially from the synced state
    a, b = seq.tick(), fus.tick()
    for ra, rb in zip(a["jobs"], b["jobs"]):
        _rows_match(ra, rb)


def test_fused_matches_engine_under_events():
    """WAN events (degrade / cross-traffic / diurnal / restore) replay
    through the precomputed schedule exactly as the FleetEngine applies
    them tick by tick."""
    events = (at(1, LinkDegrade(("us-east", "us-west"), 0.3)),
              at(2, CrossTraffic(("us-east", "eu-west"), conns=32)),
              at(3, DiurnalCycle(amplitude=0.2, period=6)),
              at(4, LinkRestore(("us-east", "us-west"))))
    spec = FleetScenarioSpec(name="x", steps=6, jobs=JOBS, events=events,
                             sim_kwargs=dict(QUIET))
    res = FleetEngine(spec, seed=3, forest=_forest()).run()
    fus = build_fleet()
    fus_rows = fus.run_fused(6, events=events)
    for a, b in zip(res.trace.steps, fus_rows):
        for ra, rb in zip(a.jobs, b["jobs"]):
            _rows_match(ra, rb)


def test_fused_with_skew_and_fluctuation():
    """Skewed jobs + live AR(1) fluctuation (consumed while the
    schedule is precomputed) still match the sequential loop."""
    jobs = (JobSpec("a", dcs=(0, 1, 2, 3), priority=2.0,
                    skew_w=(2.0, 1.0, 1.0, 0.5)),
            JobSpec("b", dcs=(2, 3, 4, 5), priority=1.0))
    kw = dict(fluct_sigma=0.1)
    seq = build_fleet(jobs=jobs, **kw)
    seq_rows = [seq.tick() for _ in range(3)]
    fus = build_fleet(jobs=jobs, **kw)
    fus_rows = fus.run_fused(3)
    for a, b in zip(seq_rows, fus_rows):
        for ra, rb in zip(a["jobs"], b["jobs"]):
            _rows_match(ra, rb)


def test_sweep_matches_individual_runs():
    """One vmapped [B,T] launch == B independent fused runs."""
    T, variants = 4, (0.25, 0.6)
    singles, bgs = [], []
    for f in variants:
        sim = WanSimulator(seed=3, **QUIET)
        s, g = make_schedule(sim, T,
                             (at(1, LinkDegrade(("us-east", "us-west"),
                                                f)),))
        singles.append(s)
        bgs.append(g)
    ff = build_fleet().fused()
    outs = ff.sweep(np.stack(singles), np.stack(bgs))
    assert outs["achieved_min"].shape == (2, T, len(JOBS))
    assert bool(outs["converged"].all())
    for b, f in enumerate(variants):
        fleet = build_fleet()
        rows = fleet.run_fused(
            T, (at(1, LinkDegrade(("us-east", "us-west"), f)),))
        for t, row in enumerate(rows):
            for j, jr in enumerate(row["jobs"]):
                assert np.isclose(jr["achieved_min"],
                                  outs["achieved_min"][b, t, j])
                assert jr["conns_total"] == int(outs["conns_total"][b, t, j])


def test_fused_contract_validation():
    """Noisy sims, mixed slice sizes, attached planners, and job-churn
    events are rejected loudly (the contract, not silent divergence)."""
    with pytest.raises(ValueError, match="snapshot_sigma"):
        build_fleet(snapshot_sigma=0.05).fused()
    with pytest.raises(ValueError, match="host_sigma|snapshot_sigma"):
        build_fleet(host_sigma=0.02).fused()
    with pytest.raises(ValueError, match="slice sizes"):
        build_fleet(jobs=(JobSpec("a", dcs=(0, 1, 2)),
                          JobSpec("b", dcs=(3, 4, 5, 6)))).fused()
    fleet = build_fleet()
    with pytest.raises(ValueError, match="replayable"):
        fleet.run_fused(2, (at(0, JobArrive(JobSpec("x", dcs=(0, 1)))),))
    from repro.placement import scan_agg
    fleet.job_planner("serving", scan_agg(4))
    with pytest.raises(ValueError, match="planners"):
        fleet.fused()


def test_fused_memoized_on_controller():
    """`FleetController.fused()` reuses the compiled program until the
    job set / priorities change."""
    fleet = build_fleet()
    f1 = fleet.fused()
    assert fleet.fused() is f1
    fleet.set_priority("batch", 6.0)
    f2 = fleet.fused()
    assert f2 is not f1
    assert isinstance(f2, FusedFleet)
