"""Observability-plane tests (PR 9).

  * registry primitives: counters/gauges/histograms/series semantics,
    labeled names, kind conflicts, pure reads (incl. a hypothesis
    property that interleaved reads never perturb later values);
  * back-compat: the migrated ad-hoc counters (`cache_builds`,
    `fill_calls`, `kernel_calls`, lifecycle tallies) read identically
    through the legacy attributes and the registry;
  * passivity: every historical trace golden replays byte-identical
    with REPRO_OBS=on — parametrized per pin;
  * spans: nesting, counter deltas, rollups, bounded capacity, the
    off-gate null tracer;
  * SLE rollups: Jain index, accuracy band, capacity, responsiveness
    (with censoring), the Eq. 1 monitoring meter, scenario/fleet
    blocks;
  * export/CLI: canonical run documents, check/diff, and the obsctl
    subcommands end to end.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, SLE_BAND, MetricsRegistry, SpanTracer,
                       accuracy_sle, capacity_sle, check_run, diff_runs,
                       export_run, export_scenario, fleet_sle, flatten,
                       jain_index, obs_mode, responsiveness_steps,
                       scenario_sle, summarize, to_json)
from repro.obs.registry import Counter, Gauge, Histogram, Series
from repro.scenarios import ScenarioEngine, get_scenario

HERE = os.path.dirname(__file__)


# ----------------------------------------------------------------------
# registry primitives
# ----------------------------------------------------------------------
def test_counter_monotone():
    c = Counter("x")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    c.reset(0)
    assert c.value == 0


def test_gauge_last_write_wins():
    g = Gauge("x")
    g.set(5)
    g.set(2)
    assert g.value == 2


def test_histogram_fixed_buckets():
    h = Histogram("x", buckets=(1, 10, 100))
    for v in (0.5, 1.0, 5, 50, 500):
        h.observe(v)
    # bisect_left: values equal to an upper bound land in its bucket
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(556.5)
    assert h.mean == pytest.approx(556.5 / 5)
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(3, 2, 1))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=())


def test_series_bounded():
    s = Series("x", cap=3)
    for i in range(5):
        s.record(float(i), label="a" if i % 2 else "b")
    assert len(s) == 3
    assert s.dropped == 2
    # keeps the LAST cap points: i = 2 (b), 3 (a), 4 (b)
    assert s.by_label() == {"a": 1, "b": 2}


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry("t")
    c1 = reg.counter("hits")
    assert reg.counter("hits") is c1
    with pytest.raises(TypeError):
        reg.gauge("hits")
    lab = reg.counter("replans", labels={"reason": "periodic"})
    assert lab.name == "replans{reason=periodic}"
    assert "replans{reason=periodic}" in reg.names()
    assert reg.get("hits") is c1


def test_registry_snapshot_sorted_and_counters_view():
    reg = MetricsRegistry("t")
    reg.counter("b").inc(2)
    reg.gauge("a").set(7)
    reg.histogram("h", buckets=(1,)).observe(0.5)
    reg.series("s").record(1.0, label="x")
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["b"] == {"kind": "counter", "value": 2}
    # counters() covers counters AND gauges only (the span-delta view)
    assert reg.counters() == {"b": 2, "a": 7}


def test_registry_reads_are_pure_hypothesis():
    """Interleaving snapshot()/counters()/names() reads between writes
    never changes what later reads observe (two registries, identical
    write sequences, one read-hammered)."""
    hyp = pytest.importorskip("hypothesis")            # noqa: F841
    from hypothesis import given, settings, strategies as st

    op = st.tuples(st.sampled_from(["counter", "gauge", "hist", "series"]),
                   st.integers(0, 2),
                   st.floats(0, 100, allow_nan=False, width=32))

    @settings(max_examples=50, deadline=None)
    @given(st.lists(op, max_size=40))
    def run(ops):
        quiet, noisy = MetricsRegistry("q"), MetricsRegistry("n")
        for reg, read in ((quiet, False), (noisy, True)):
            for kind, idx, val in ops:
                if kind == "counter":
                    reg.counter(f"c{idx}").inc(val)
                elif kind == "gauge":
                    reg.gauge(f"g{idx}").set(val)
                elif kind == "hist":
                    reg.histogram(f"h{idx}", buckets=(10, 50)).observe(val)
                else:
                    reg.series(f"s{idx}", cap=8).record(val, label="l")
                if read:
                    reg.snapshot()
                    reg.counters()
                    reg.names()
        assert quiet.snapshot() == noisy.snapshot()

    run()


def test_registry_reads_are_pure_seeded():
    """Same property as the hypothesis test, but with a seeded PRNG so
    it still runs when hypothesis is absent from the environment."""
    import random
    rng = random.Random(0)
    ops = [(rng.choice(["counter", "gauge", "hist", "series"]),
            rng.randrange(3), rng.uniform(0, 100)) for _ in range(200)]
    quiet, noisy = MetricsRegistry("q"), MetricsRegistry("n")
    for reg, read in ((quiet, False), (noisy, True)):
        for kind, idx, val in ops:
            if kind == "counter":
                reg.counter(f"c{idx}").inc(val)
            elif kind == "gauge":
                reg.gauge(f"g{idx}").set(val)
            elif kind == "hist":
                reg.histogram(f"h{idx}", buckets=(10, 50)).observe(val)
            else:
                reg.series(f"s{idx}", cap=8).record(val, label="l")
            if read:
                reg.snapshot()
                reg.counters()
                reg.names()
    assert quiet.snapshot() == noisy.snapshot()


# ----------------------------------------------------------------------
# back-compat: legacy attributes == registry metrics
# ----------------------------------------------------------------------
def test_backcompat_counters_agree_after_scenario():
    eng = ScenarioEngine(get_scenario("steady"), seed=0)
    eng.run()
    ctl, sim = eng.controller, eng.sim
    assert ctl.cache_builds == ctl.metrics.counter("cache_builds").value
    assert ctl.cache_hits == ctl.metrics.counter("cache_hits").value
    assert ctl.cache_builds > 0 and ctl.cache_hits > 0
    assert sim.fill_calls == sim.metrics.counter("fill_calls").value
    assert sim.last_fill_iters == \
        sim.metrics.gauge("last_fill_iters").value
    assert sim.fill_calls > 0
    # the derived convergence metrics stay consistent
    h = sim.metrics.get("fill_iters")
    assert h.count == sim.fill_calls
    assert h.sum == sim.metrics.counter("fill_iters_total").value
    # replans_total matches the controller's structured record
    assert ctl.metrics.counter("replans_total").value == len(ctl.record)


def test_backcompat_setters_route_to_registry():
    eng = ScenarioEngine(get_scenario("steady"), seed=0)
    eng.controller.cache_builds = 0
    eng.controller.cache_hits = 0
    assert eng.controller.metrics.counter("cache_builds").value == 0
    eng.sim.fill_calls = 0
    eng.sim.last_fill_iters = 0
    assert eng.sim.metrics.counter("fill_calls").value == 0


def test_backcompat_probe_scheduler():
    from repro.lifecycle.probes import ProbeScheduler
    s = ProbeScheduler(n_dcs=8)
    s.charge_full(0)
    s.charge_snapshot(3)
    assert s.full_probes == 1 == s.metrics.counter("full_probes").value
    assert s.snapshots == 3 == s.metrics.counter("snapshots").value
    assert s.spend_usd == pytest.approx(
        s.metrics.counter("spend_usd").value)
    assert s.spend_usd > 0


def test_backcompat_kernel_calls():
    pytest.importorskip("jax")
    from repro.fleet import BatchedRfPredictor, default_fleet_forest
    p = BatchedRfPredictor(default_fleet_forest())
    p.predict_rows(np.zeros((4, 6), np.float32))
    assert p.kernel_calls == 1 == p.metrics.counter("kernel_calls").value
    assert p.metrics.counter("rows_total").value == 4


# ----------------------------------------------------------------------
# passivity: every golden replays byte-identical with REPRO_OBS=on
# ----------------------------------------------------------------------
def _golden_hashes():
    with open(os.path.join(HERE, "data", "trace_golden.json")) as f:
        return json.load(f)["hashes"]


GOLDEN = _golden_hashes()


@pytest.fixture(scope="module")
def collected_obs_on():
    """Run the golden collector ONCE with span tracing forced on;
    each parametrized pin then compares its own key."""
    path = os.path.join(HERE, os.pardir, "tools", "gen_trace_goldens.py")
    spec = importlib.util.spec_from_file_location("gen_trace_goldens", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    old = os.environ.get("REPRO_OBS")
    os.environ["REPRO_OBS"] = "on"
    try:
        return mod.collect()
    finally:
        if old is None:
            os.environ.pop("REPRO_OBS", None)
        else:                                       # pragma: no cover
            os.environ["REPRO_OBS"] = old


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_pin_obs_on(key, collected_obs_on):
    """With obs ON, trace `key` is byte-identical to the sha256 pinned
    before the obs plane existed — spans observe, never steer."""
    assert key in collected_obs_on, f"collector no longer produces {key}"
    assert collected_obs_on[key] == GOLDEN[key]


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]
    return clock


def test_obs_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert obs_mode() == "off"
    monkeypatch.setenv("REPRO_OBS", "on")
    assert obs_mode() == "on"
    assert obs_mode("off") == "off"          # explicit argument wins
    with pytest.raises(ValueError):
        obs_mode("loud")


def test_null_tracer_is_inert():
    with NULL_TRACER.span("anything", delta=True, step=3):
        pass
    assert NULL_TRACER.spans == []
    assert NULL_TRACER.enabled is False
    NULL_TRACER.watch(MetricsRegistry("x"))  # no-op


def test_span_nesting_and_attrs():
    tr = SpanTracer(clock=_fake_clock())
    with tr.span("outer", step=1):
        with tr.span("inner"):
            pass
    inner, outer = tr.spans          # completion order: inner first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent"] == outer["sid"]
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["attrs"] == {"step": 1}
    assert outer["dur_s"] > inner["dur_s"] > 0


def test_span_delta_captures_watched_counters():
    tr = SpanTracer(clock=_fake_clock())
    reg = MetricsRegistry("sim")
    reg.counter("fills").inc(5)
    tr.watch(reg)
    with tr.span("work", delta=True):
        reg.counter("fills").inc(2)
        reg.counter("born_inside").inc(4)    # created mid-span: delta 0->4
        reg.gauge("level").set(9.0)
    with tr.span("idle", delta=True):
        pass
    work, idle = tr.spans
    assert work["delta"] == {"sim.fills": 2, "sim.born_inside": 4,
                             "sim.level": 9.0}
    assert "delta" not in idle               # nothing moved, key omitted
    roll = tr.by_stage()
    assert roll["work"]["count"] == 1
    assert roll["work"]["delta"]["sim.fills"] == 2
    assert "delta" not in roll["idle"]


def test_span_capacity_bounded_and_reset():
    tr = SpanTracer(max_spans=2, clock=_fake_clock())
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans) == 2 and tr.dropped == 2
    tr.reset()
    assert tr.spans == [] and tr.dropped == 0
    with tr.span("again"):
        pass
    assert tr.spans[0]["sid"] == 0


def test_engine_obs_on_records_stage_spans():
    eng = ScenarioEngine(get_scenario("steady"), seed=0, obs="on")
    eng.run()
    stages = eng.tracer.by_stage()
    for stage in ("events", "waterfill", "control", "lower", "measure"):
        assert stages[stage]["count"] == eng.spec.steps
    # replan internals nest under the control span on replan steps
    assert stages["optimize"]["count"] >= 1
    assert stages["waterfill"]["delta"]["sim.fill_calls"] == eng.spec.steps


def test_fleet_obs_on_records_tick_spans():
    pytest.importorskip("jax")
    from repro.fleet.scenario import FleetEngine, get_fleet_scenario
    spec = get_fleet_scenario("fleet_steady")
    spec.steps = min(spec.steps, 3)
    eng = FleetEngine(spec, seed=0, obs="on")
    res = eng.run()
    assert len(res.trace.steps) == spec.steps
    stages = eng.tracer.by_stage()
    assert stages["tick"]["count"] == spec.steps
    # per-tick internals nest under the tick span
    for stage in ("arbitrate", "waterfill"):
        assert stages[stage]["count"] == spec.steps
    # the per-job delta keys carry the job namespace, not "controller"
    deltas = [s.get("delta", {}) for s in eng.tracer.spans]
    keys = {k for d in deltas for k in d}
    assert any(k.startswith("job.") for k in keys)


def _profiled_annotations(tmp_path, body):
    """Run `body` under ``jax.profiler.trace`` on this process's devices
    and return the ``wanify.`` host events it wrote, as (name, start_ns,
    end_ns) sorted by start."""
    jax = pytest.importorskip("jax")
    import glob
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        body()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for plane in pd.planes
                   if not plane.name.startswith("/device:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("wanify.")), key=lambda r: r[1])


def test_span_annotations_on_profiler_trace(tmp_path):
    tr = SpanTracer()

    def body():
        with tr.span("tick"):
            with tr.span("predict"):
                with tr.span("rf_wait"):
                    pass
            with tr.span("replan"):
                pass
    anns = _profiled_annotations(tmp_path, body)
    # one annotation per span, named after it, in the span tree's order
    assert [a[0] for a in anns] == ["wanify.tick", "wanify.predict",
                                    "wanify.rf_wait", "wanify.replan"]
    assert len(anns) == len(tr.spans)
    by_name = {a[0][len("wanify."):]: a for a in anns}
    sid = {s["sid"]: s["name"] for s in tr.spans}
    for span in tr.spans:
        if span["parent"] < 0:
            continue
        _, s0, e0 = by_name[span["name"]]
        _, s1, e1 = by_name[sid[span["parent"]]]
        assert s1 <= s0 and e0 <= e1, (span["name"], sid[span["parent"]])
    # the annotation holds the span: its length is at least the span's
    for span in tr.spans:
        _, s0, e0 = by_name[span["name"]]
        assert e0 - s0 >= 1e9 * span["dur_s"] - 1e3


def test_null_tracer_writes_no_annotations(tmp_path):
    def body():
        with NULL_TRACER.span("tick"):
            with NULL_TRACER.span("predict"):
                pass
    assert _profiled_annotations(tmp_path, body) == []


def _fleet_run(obs):
    from repro.fleet.scenario import FleetEngine, get_fleet_scenario
    spec = get_fleet_scenario("fleet_steady")
    spec.steps = 2
    eng = FleetEngine(spec, seed=0, obs=obs)
    res = eng.run()
    preds = [np.array(j.controller.last_pred)
             for j in eng.fleet.jobs.values()]
    return eng, res, preds


def test_fleet_obs_on_splits_the_rf_stage():
    pytest.importorskip("jax")
    eng, _, _ = _fleet_run("on")
    spans = eng.tracer.spans
    by_sid = {s["sid"]: s for s in spans}
    ticks = [s for s in spans if s["name"] == "tick"]
    assert len(ticks) == 2
    for name in ("rf_launch", "rf_wait", "rf_fetch"):
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == len(ticks), name
        for s in mine:
            assert by_sid[s["parent"]]["name"] == "predict"
    # one of each, in order, under every predict span
    for pred in (s for s in spans if s["name"] == "predict"):
        kids = sorted((s for s in spans if s["parent"] == pred["sid"]),
                      key=lambda s: s["t"])
        assert [k["name"] for k in kids] == ["rf_launch", "rf_wait",
                                             "rf_fetch"]
    # the fleet tick no longer records a planners span
    assert not any(s["name"] == "planners" for s in spans)


def test_fleet_records_identical_obs_on_and_off():
    pytest.importorskip("jax")
    _, res_on, preds_on = _fleet_run("on")
    _, res_off, preds_off = _fleet_run("off")
    assert res_on.trace.to_json() == res_off.trace.to_json()
    assert len(preds_on) == len(preds_off) > 0
    for a, b in zip(preds_on, preds_off):
        assert a.tobytes() == b.tobytes()


# ----------------------------------------------------------------------
# SLE rollups
# ----------------------------------------------------------------------
def test_jain_index():
    assert jain_index([]) == 1.0
    assert jain_index([0.0, 0.0]) == 1.0
    assert jain_index([5, 5, 5]) == pytest.approx(1.0)
    assert jain_index([1, 0, 0, 0]) == pytest.approx(0.25)


def test_capacity_sle():
    assert capacity_sle([]) == 1.0
    assert capacity_sle([100.0] * 10) == pytest.approx(1.0)
    # one sagging step out of ten drags the mean down
    assert capacity_sle([100.0] * 9 + [50.0]) < 1.0


def test_responsiveness_steps():
    floor = [100, 100, 100, 10, 20, 95, 100, 100]
    assert responsiveness_steps([3], floor) == pytest.approx(2.0)
    # never recovers: censored at run end (a lower bound)
    assert responsiveness_steps([3], [100, 100, 100, 10, 10, 10]) \
        == pytest.approx(3.0)
    assert responsiveness_steps([], floor) is None


def test_scenario_sle_block():
    eng = ScenarioEngine(get_scenario("cable_cut"), seed=3)
    res = eng.run()
    sle = scenario_sle(res.trace, n_dcs=eng.sim.N)
    assert set(sle) == {"band", "accuracy", "capacity", "fairness",
                        "responsiveness_steps", "monitoring_usd"}
    assert sle["band"] == SLE_BAND
    assert 0.0 <= sle["accuracy"] <= 1.0
    assert 0.0 < sle["capacity"] <= 1.0
    assert 0.0 < sle["fairness"] <= 1.0
    assert sle["monitoring_usd"] > 0
    # cable_cut scripts events, so responsiveness is measurable
    assert sle["responsiveness_steps"] is not None
    assert accuracy_sle(res.trace, band=10.0) == 1.0  # huge band: all in


def test_fleet_sle_block():
    pytest.importorskip("jax")
    from repro.fleet import run_fleet_scenario
    from repro.fleet.scenario import get_fleet_scenario
    spec = get_fleet_scenario("fleet_steady")
    spec.steps = min(spec.steps, 3)
    res = run_fleet_scenario(spec, seed=3)
    sle = fleet_sle(res.trace, n_dcs=8)
    assert sle["accuracy"] is None       # no predicted columns, honestly
    assert 0.0 < sle["capacity"] <= 1.0
    assert 0.0 < sle["fairness"] <= 1.0
    assert sle["monitoring_usd"] > 0


# ----------------------------------------------------------------------
# export / check / diff / CLI
# ----------------------------------------------------------------------
def _run_doc(obs="on", name="steady", seed=0):
    eng = ScenarioEngine(get_scenario(name), seed=seed, obs=obs)
    return export_scenario(eng.run(), eng), eng


def test_export_scenario_document_passes_check():
    doc, eng = _run_doc()
    assert check_run(doc) == []
    assert doc["metrics"]["sim"]["fill_calls"]["value"] == \
        eng.sim.fill_calls
    assert doc["spans"]["count"] == len(eng.tracer.spans)
    # canonical serialization round-trips
    assert json.loads(to_json(doc)) == doc
    # obs off: same document minus the spans block
    doc_off, _ = _run_doc(obs="off")
    assert "spans" not in doc_off
    assert check_run(doc_off) == []


def test_check_run_rejects_bad_documents():
    doc, _ = _run_doc(obs="off")
    assert check_run({"kind": "nope"})          # wrong schema + kind
    bad = dict(doc)
    bad.pop("sle")
    assert any("sle" in p for p in check_run(bad))
    assert check_run(doc, min_accuracy=1.01)    # floor above any ratio
    assert check_run(doc, max_usd=0.0)          # ceiling below any spend
    assert check_run(doc, min_accuracy=0.0) == []


def test_flatten_and_diff_runs():
    a = {"x": {"y": 1, "z": [1, 2]}, "s": "str", "b": True}
    assert flatten(a) == {"x.y": 1.0, "x.z[0]": 1.0, "x.z[1]": 2.0}
    d = diff_runs({"v": 1, "only_a": 3}, {"v": 2})
    assert d["v"] == {"a": 1.0, "b": 2.0, "rel": 1.0}
    assert d["only_a"] == {"a": 3.0, "b": None}
    assert diff_runs(a, a) == {}


def test_summarize_handles_all_document_kinds():
    doc, _ = _run_doc()
    text = summarize(doc)
    assert "steady" in text and "sle:" in text and "waterfill" in text
    bench = {"bench": "tick", "schema": 1,
             "rows": [{"kind": "obs", "overhead_frac": 0.01,
                       "sle": {"capacity": 0.9}}]}
    btext = summarize(bench)
    assert "bench: tick" in btext and "overhead_frac=0.01" in btext
    # unknown documents fall back to JSON, never crash
    assert summarize({"weird": 1}) == json.dumps({"weird": 1}, indent=2,
                                                 sort_keys=True)


def test_export_run_namespace_collisions_survive():
    a, b = MetricsRegistry("dup"), MetricsRegistry("dup")
    a.counter("x").inc()
    b.counter("x").inc(2)
    doc = export_run("r", registries=[a, b])
    vals = sorted(m["x"]["value"] for m in doc["metrics"].values())
    assert vals == [1, 2]


def test_obsctl_cli_end_to_end(tmp_path):
    path = os.path.join(HERE, os.pardir, "tools", "obsctl.py")
    spec = importlib.util.spec_from_file_location("obsctl", path)
    obsctl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obsctl)
    out = str(tmp_path / "run.json")
    spans = str(tmp_path / "spans.jsonl")
    assert obsctl.main(["run", "steady", "--seed", "3",
                        "-o", out, "--spans", spans]) == 0
    assert obsctl.main(["summarize", out]) == 0
    assert obsctl.main(["check", out, "--min-capacity", "0.1"]) == 0
    assert obsctl.main(["check", out, "--min-accuracy", "1.01"]) == 1
    with open(spans) as f:
        rows = [json.loads(line) for line in f]
    assert rows and {"sid", "name", "dur_s"} <= set(rows[0])
    # diff a run against itself: clean; against another seed: not
    out2 = str(tmp_path / "run2.json")
    assert obsctl.main(["run", "steady", "--seed", "4",
                        "-o", out2]) == 0
    assert obsctl.main(["diff", out, out]) == 0
    assert obsctl.main(["diff", out, out2, "--fail-on-diff"]) == 1
