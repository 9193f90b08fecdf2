"""Drive the WANify main path once on a TPU and check it against the repo's
references.

  python chip_smoke.py            # one chip: fleet control plane, water-fill
                                  # + placement, serving at full width
  python chip_smoke.py --chips 4  # four chips: cross-pod training sync and
                                  # KV-cache migration, one chip per pod

Every phase prints one line of what it checked; any mismatch raises. The
last line of a passing run is one JSON object naming the device. Where JAX
finds no TPU the script exits non-zero without running anything. All
phases run in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.control import WanifyController  # noqa: E402
from repro.core.predictor import BwPredictor, SnapshotPredictor  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.fleet import (BatchedRfPredictor, FleetController,  # noqa: E402
                         JobSpec, default_fleet_forest, make_schedule)
from repro.fleet.scenario import FleetEngine, FleetScenarioSpec  # noqa: E402
from repro.kernels import interpret_default  # noqa: E402
from repro.kernels.rf_predict import rf_predict_pallas  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.launch.serve import build_engine, make_requests  # noqa: E402
from repro.placement import (achievable_bw, get_workload,  # noqa: E402
                             greedy_place)
from repro.scenarios import ScenarioEngine  # noqa: E402
from repro.scenarios.library import get_scenario  # noqa: E402
from repro.serve.engine import Engine, ServeConfig, kv_migrate  # noqa: E402
from repro.train.loop import LoopConfig, Trainer  # noqa: E402
from repro.train.optimizer import AdamWConfig  # noqa: E402
from repro.train.train_step import strip_pods  # noqa: E402
from repro.wan.dataset import (generate_dataset,  # noqa: E402
                               train_default_forest)
from repro.wan.monitor import egress_price_vector  # noqa: E402
from repro.wan.simulator import WanSimulator  # noqa: E402

SERVE_ARCH = "h2o-danube-1.8b"
# tests/test_fused_tick.py: integers exact, achieved BW to 1e-6
FUSED_TOL = 1e-6
# README: the jax water-fill's rates agree with the numpy loop to 1e-9
FILL_TOL = 1e-9
# the RF kernel and RandomForest.predict make the same f32 comparisons;
# only the f32 order of the per-tree leaf sum differs
RF_RTOL = 1e-5
# re-prefill vs decode logits: 16 bf16 roundings (2^-8 each) of the
# row's largest logit
LOGIT_TOL = 16 * 2.0 ** -8
# psum and the WANify schedule add the same per-pod gradients in a
# different f32 order; nothing else differs between the two runs
LOSS_RTOL = 1e-3
# h2o-danube-1.8b layers kept on the 4-pod path: compiled for v5e, one
# pod's train step holds 4.94 GiB of params + AdamW state and 6.55 GiB of
# temporaries at 4 layers, 5.71 + 8.33 GiB at 5 (of 16 GiB per chip)
CROSS_POD_LAYERS = 4


def fleet_jobs(n_jobs: int = 8, n_dcs: int = 8, width: int = 4):
    """`n_jobs` jobs on equal `width`-DC windows of the ring of regions,
    priorities cycling 4, 2, 1, 1."""
    prios = (4.0, 2.0, 1.0, 1.0)
    return tuple(
        JobSpec(f"job{k}", dcs=tuple(sorted((k + i) % n_dcs
                                            for i in range(width))),
                priority=prios[k % len(prios)])
        for k in range(n_jobs))


def _close(a: float, b: float, tol: float) -> bool:
    return a == b or bool(np.isclose(a, b, rtol=tol, atol=tol))


def fleet_phase(forest, *, ticks: int = 256, variants: int = 16,
                n_jobs: int = 8, scenario: str = "diurnal",
                seed: int = 3) -> str:
    """Fused fleet tick (scan over `ticks`, vmapped sweep over
    `variants`) against the sequential numpy `FleetController.tick`, and
    the batched RF kernel against `RandomForest.predict`."""
    timeline = get_scenario(scenario)
    # the fused contract: captures draw no observation or host noise
    sim_kw = dict(timeline.sim_kwargs, snapshot_sigma=0.0, host_sigma=0.0)
    jobs = fleet_jobs(n_jobs)
    spec = FleetScenarioSpec(name=scenario, steps=ticks, jobs=jobs,
                             events=timeline.events, m_total=8,
                             sim_kwargs=sim_kw)
    t0 = time.perf_counter()
    ref = FleetEngine(spec, seed=seed, forest=forest).run()
    t_seq = time.perf_counter() - t0
    if ref.trace.steps[-1].kernel_calls != ticks:
        raise AssertionError("sequential ticks must launch the RF kernel "
                             f"once each: {ref.trace.steps[-1].kernel_calls}")

    fleet = FleetController(WanSimulator(seed=seed, **sim_kw),
                            BatchedRfPredictor(forest), m_total=8, jobs=jobs)
    sched = [make_schedule(WanSimulator(seed=seed + b, **sim_kw), ticks,
                           timeline.events) for b in range(variants)]
    t0 = time.perf_counter()
    sweep = fleet.fused().sweep(np.stack([s for s, _ in sched]),
                                np.stack([g for _, g in sched]))
    t_sweep = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows = fleet.run_fused(ticks, timeline.events)
    t_run = time.perf_counter() - t0
    if not bool(np.all(sweep["converged"])):
        raise AssertionError("a swept water-fill hit its iteration bound")

    dev = 0.0
    for t, (a, b) in enumerate(zip(ref.trace.steps, rows)):
        for j, (ra, rb) in enumerate(zip(a.jobs, b["jobs"])):
            for k in ("budget", "conns_total"):
                if not (ra[k] == rb[k] == int(sweep[k][0, t, j])):
                    raise AssertionError(
                        f"tick {t + 1} {ra['name']} {k}: sequential "
                        f"{ra[k]} fused {rb[k]} swept {sweep[k][0, t, j]}")
            for k in ("cap_min", "achieved_min", "achieved_mean"):
                x, y, z = ra[k], rb[k], float(sweep[k][0, t, j])
                if not (_close(x, y, FUSED_TOL) and _close(y, z, FUSED_TOL)):
                    raise AssertionError(
                        f"tick {t + 1} {ra['name']} {k}: sequential {x} "
                        f"fused {y} swept {z}")
                if np.isfinite(x) and x != 0:
                    dev = max(dev, abs(x - y) / abs(x), abs(x - z) / abs(x))

    # the RF kernel at the fleet's launch shape and on held-out rows
    X, _ = generate_dataset(n_samples=200, seed=seed + 100)
    packed = [jnp.asarray(a) for a in forest.packed()]
    got = BatchedRfPredictor(forest).predict_rows(X)
    want = np.maximum(forest.predict(X), 1.0)
    rf_dev = float(np.max(np.abs(got - want) / want))
    if rf_dev > RF_RTOL:
        raise AssertionError(f"RF kernel vs RandomForest.predict: largest "
                             f"relative deviation {rf_dev} > {RF_RTOL}")
    n_rows = n_jobs * 4 * 3
    text = rf_predict_pallas.lower(
        *packed, jnp.asarray(X[:n_rows]), depth=forest.depth
    ).compile().as_text()
    compiled = "tpu_custom_call" in text
    if not interpret_default() and not compiled:
        raise AssertionError("the RF launch holds no tpu_custom_call")
    return (f"[fleet] {n_jobs} jobs x {ticks} ticks '{scenario}', wall "
            f"time incl. compile: sequential numpy tick {t_seq:.1f}s, fused "
            f"run {t_run:.1f}s, sweep {variants}x{ticks} {t_sweep:.1f}s; "
            f"budgets+conns exact "
            f"on every tick, cap/achieved BW max rel dev {dev:.3e} (rtol+atol "
            f"{FUSED_TOL:g}); RF kernel vs forest on {len(X)} rows max rel "
            f"dev {rf_dev:.3e}; RF launch at {n_rows} rows "
            f"tpu_custom_call={compiled}")


def fill_placement_phase(*, scenario: str = "congestion",
                         workload: str = "two_stage_join", n: int = 8,
                         seed: int = 3) -> str:
    """`ScenarioEngine` with the jax water-fill against the numpy fill,
    and a jax greedy placement search against the numpy one."""
    base = get_scenario(scenario)
    runs = {}
    for backend in ("numpy", "jax"):
        spec = dataclasses.replace(base, sim_kwargs=dict(
            base.sim_kwargs, waterfill_backend=backend))
        eng = ScenarioEngine(spec, seed=seed)
        runs[backend] = (eng.run().trace, eng.sim.metrics.counters())
    (tn, cn), (tj, cj) = runs["numpy"], runs["jax"]
    if cn != cj:
        raise AssertionError(f"fill calls/iterations differ: {cn} vs {cj}")
    dev = 0.0
    for a, b in zip(tn.steps, tj.steps):
        if (a.plan_sig, a.conns_total, a.replans) != \
                (b.plan_sig, b.conns_total, b.replans):
            raise AssertionError(f"step {a.step}: decisions differ")
        for k in ("achieved_min", "achieved_mean", "monitored_min",
                  "monitored_mean", "predicted_min", "predicted_mean",
                  "dt"):
            x, y = getattr(a, k), getattr(b, k)
            if not _close(x, y, FILL_TOL):
                raise AssertionError(f"step {a.step} {k}: {x} vs {y}")
            if x != 0:
                dev = max(dev, abs(x - y) / abs(x))

    sim = WanSimulator(seed=seed, fluct_sigma=0.0, snapshot_sigma=0.0,
                       runtime_sigma=0.0)
    bw = achievable_bw(WanifyController(sim, SnapshotPredictor(),
                                        n_pods=n).plan)
    price = egress_price_vector(sim.regions[:n])
    q = get_workload(workload, n)
    dn = greedy_place(q, bw, egress_usd_per_gb=price, backend="numpy")
    dj = greedy_place(q, bw, egress_usd_per_gb=price, backend="jax")
    if (dn.placement, dn.evals) != (dj.placement, dj.evals):
        raise AssertionError("jax placement search decided differently")
    return (f"[fill+placement] '{scenario}' {len(tn.steps)} steps, "
            f"{cn['fill_calls']:.0f} fills / {cn['fill_iters_total']:.0f} "
            f"iterations on both backends; decisions equal, rates max "
            f"rel dev {dev:.3e} (rtol+atol {FILL_TOL:g}); greedy "
            f"'{workload}' N={n}: {dn.evals} evals, identical placement, makespan "
            f"{dn.cost.makespan_s:.6f}s vs {dj.cost.makespan_s:.6f}s")


def serve_phase(cfg, *, seed: int = 0, requests: int = 8, batch: int = 4,
                max_new: int = 16, s_max: int = 128, k: int = 3) -> str:
    """`Engine.serve` as `repro.launch.serve` drives it, then a re-prefill
    of the first batch's prompts plus their first `k` tokens."""
    eng = build_engine(cfg, seed, batch, s_max)
    steps = []
    for name in ("_prefill", "_decode"):         # tap every step's logits
        def tapped(*a, _fn=getattr(eng, name)):
            logits, cache = _fn(*a)
            steps.append(logits)
            return logits, cache
        setattr(eng, name, tapped)
    reqs = make_requests(cfg, requests, max_new, seed)
    t0 = time.perf_counter()
    out = eng.serve(reqs)
    dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in out.values())
    if sorted(out) != list(range(requests)) or \
            any(len(v) != max_new for v in out.values()):
        raise AssertionError("not every request was answered in full")
    if not all(0 <= t < cfg.vocab for v in out.values() for t in v):
        raise AssertionError("a generated token is outside the vocab")
    if not all(bool(jnp.all(jnp.isfinite(x))) for x in steps):
        raise AssertionError("non-finite logits")
    n_steps = len(steps)

    # re-prefill the first group's prompts plus their first k tokens,
    # laid out as serve lays them out (left-padded to the longest prompt)
    group = reqs[:batch]
    S = max(len(r.prompt) for r in group)
    rows = np.zeros((batch, S + k), np.int32)
    for i, r in enumerate(group):
        rows[i, S - len(r.prompt):S] = r.prompt
        rows[i, S:] = out[r.rid][:k]
    nxt = eng.prefill(rows)
    # steps[k] is the decode step that produced each out[rid][k]. Logits
    # are bf16 (8 significant bits), so the two paths may differ by a few
    # roundings; LOGIT_TOL allows 16 of them at the row's largest logit.
    # Where decode's top-2 gap is inside the difference the argmax is a
    # tie, and re-prefill may pick any token decode ranks within it.
    dec = np.asarray(steps[k], np.float32)
    diff = np.max(np.abs(np.asarray(steps[-1], np.float32) - dec), axis=1)
    tol = LOGIT_TOL * np.max(np.abs(dec), axis=1)
    top2 = np.sort(dec, axis=1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    decided = []
    for i, r in enumerate(group):
        want = out[r.rid][k]
        if diff[i] > tol[i]:
            raise AssertionError(f"request {r.rid}: re-prefill logits differ "
                                 f"from decode's by {diff[i]:.4g} > "
                                 f"{tol[i]:.4g}")
        if dec[i, int(nxt[i])] < top2[i, 1] - diff[i]:
            raise AssertionError(
                f"request {r.rid}: re-prefill gave token {int(nxt[i])}, "
                f"decode gave {want} (top-2 gap {gap[i]:.4g}, max |logit "
                f"diff| {diff[i]:.4g})")
        if gap[i] > diff[i]:            # then nxt[i] == want, by the above
            decided.append(r.rid)
    return (f"[serve] {cfg.arch_id} ({cfg.n_layers} layers, "
            f"d_model {cfg.d_model}, vocab {cfg.vocab}, f32 params): "
            f"{requests} requests, batch {batch}, {n_tok} tokens in "
            f"{dt:.1f}s; all in vocab, {n_steps} logit steps finite; "
            f"re-prefill of requests 0-{batch - 1} + {k} tokens gives "
            f"decode's token {k + 1} for requests {decided}, ties elsewhere "
            f"(top-2 gaps {[round(float(g), 4) for g in gap]}, max |logit "
            f"diff| vs decode {[round(float(d), 4) for d in diff]}, bound "
            f"{[round(float(t), 4) for t in tol]})")


def cross_pod_phase(cfg, forest, devices, *, n_layers: int, steps: int = 3,
                    batch: int = 8, seq: int = 128, seed: int = 0) -> str:
    """`Trainer` steps on a 4-pod mesh, one device per pod, with the
    WANify schedule and with psum; then `kv_migrate` of one prefill cache
    from pod 0 to every pod inside `shard_map`."""
    cut = dataclasses.replace(cfg, n_layers=n_layers)
    mesh = auto_mesh((4, 1, 1), ("pod", "data", "model"),
                     devices=devices[:4])
    dcfg = DataConfig(batch=batch, seq=seq, vocab=cfg.vocab, n_pods=4,
                      seed=seed)
    opt = AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=steps)
    losses, plan, params = {}, None, None
    for sync in ("psum", "wanify"):
        # one run's params + AdamW state at a time: two do not fit a chip
        params = None
        tr = Trainer(cut, mesh, dcfg, LoopConfig(steps=steps, sync=sync),
                     opt=opt, sim=WanSimulator(seed=seed),
                     predictor=BwPredictor(forest))
        params = tr.run(jax.random.key(seed))[0]
        losses[sync] = [float(h["loss"]) for h in tr.history]
        plan = tr.plan
    leaf = jax.tree.leaves(params)[0]
    shards = leaf.addressable_shards
    pod_devs = [s.device for s in shards]
    if len({d.id for d in pod_devs}) != 4 or \
            any(s.data.shape[0] != 1 for s in shards):
        raise AssertionError(f"pod copies are not one per device: "
                             f"{[(s.device, s.index) for s in shards]}")
    lp, lw = np.array(losses["psum"]), np.array(losses["wanify"])
    if not np.all(np.isfinite(lw)):
        raise AssertionError(f"non-finite loss {lw}")
    rel = float(np.max(np.abs(lw - lp) / np.abs(lp)))
    if rel > LOSS_RTOL:
        raise AssertionError(f"wanify {lw} vs psum {lp}: rel {rel}")
    train_line = (
        f"[cross-pod] {cfg.arch_id} (d_model {cfg.d_model}), layers cut "
        f"{cfg.n_layers} -> {n_layers} to fit params + AdamW state on one "
        f"chip; 4 pods on devices {[d.id for d in pod_devs]}; {steps} "
        f"steps, batch {batch}x{seq}: psum losses {lp.tolist()}, wanify "
        f"{lw.tolist()}, max rel diff {rel:.2e} (tol "
        f"{LOSS_RTOL:g}); plan conns {plan.conns}")

    eng = Engine(cut, strip_pods(params), ServeConfig(batch=4, s_max=seq,
                                                      tp=1))
    del params
    rng = np.random.default_rng(seed)
    eng.prefill(rng.integers(1, cfg.vocab, (4, seq // 2)).astype(np.int32))
    src = eng.cache
    stacked = jax.device_put(
        jax.tree.map(lambda x: jnp.concatenate(
            [x[None], jnp.zeros((3,) + x.shape, x.dtype)]), src),
        NamedSharding(mesh, P("pod")))

    def migrate(c):
        own = jax.tree.map(lambda x: x[0], c)
        moved = kv_migrate(own, plan, src_pod=0, compress=True)
        return jax.tree.map(lambda x: x[None], moved)

    fn = jax.jit(jax.shard_map(migrate, mesh=mesh, in_specs=P("pod"),
                               out_specs=P("pod"), axis_names={"pod"},
                               check_vma=False))
    with jax.set_mesh(mesh):
        moved = fn(stacked)
    bits = min(plan.offset_bits())
    worst = 0.0
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(src)[0],
                            jax.tree.leaves(moved)):
        name = jax.tree_util.keystr(path)
        x = np.asarray(x, np.float32)
        amax = float(np.max(np.abs(x)))
        # half a quantization step of the coarsest wire, plus the bf16
        # rounding of the decoded value
        step = amax / ((1 << (bits - 1)) - 1) if bits < 16 else 0.0
        bound = 0.5 * step + 2.0 ** -8 * amax
        devs = {s.device.id for s in y.addressable_shards}
        if len(devs) != 4:
            raise AssertionError(f"migrated {name} not on 4 devices: {devs}")
        for p in range(4):
            err = float(np.max(np.abs(np.asarray(y[p], np.float32) - x)))
            if err > bound:
                raise AssertionError(
                    f"pod {p} {name}: |err| {err} > bound {bound}")
            worst = max(worst, err / max(amax, 1e-30))
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(src))
    return train_line + "\n" + (
        f"[kv-migrate] {n_bytes / 2**20:.1f} MiB prefill cache from pod 0 "
        f"to 4 pods in shard_map, wire bits {plan.offset_bits()}: every "
        f"pod's copy within the codec bound, max |err|/amax {worst:.3e}")


def main(argv=None) -> None:
    """Refuse without a TPU, run the phases for `--chips`, print the
    device line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX finds {len(devices)}")
    print(f"[setup] compile cache {use_compile_cache()}", flush=True)
    cfg = get_config(SERVE_ARCH)
    if args.chips == 4:
        print(cross_pod_phase(cfg, default_fleet_forest(), devices,
                              n_layers=CROSS_POD_LAYERS, seed=args.seed),
              flush=True)
    else:
        t0 = time.perf_counter()
        forest, acc, r2 = train_default_forest()
        print(f"[setup] train_default_forest: {forest.n_trees} trees, depth "
              f"{forest.depth}, train acc {acc:.4f}, holdout r2 {r2:.4f} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        print(fleet_phase(forest), flush=True)
        print(fill_placement_phase(), flush=True)
        print(serve_phase(cfg, seed=args.seed), flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
