"""Closed-loop data-parallel training steps across pods, one chip a pod,
with WANify-planned cross-pod gradient sync.

Set-up builds what ``Trainer`` builds: the 4-pod mesh, the Trainer's own
``WanifyController`` over the deployment's simulated WAN (its plan made
once and then held: no re-plan and no straggler trigger runs), and the
jitted ``make_train_step(sync="wanify", compress=...)`` step from
``Trainer._get_step`` with donated state. The weights are drawn from the
seed on the device in one jitted call (``reference.init_params``, in the
program's tree layout) and broadcast to a leading pod axis sharded one
pod a chip, with zero AdamW state, as ``Trainer.restore_or_init`` lays
them out. Rows come from ``repro.data.pipeline.batches`` behind
``prefetch``.

The first `check_steps` steps run through the window's own call and
feed: they compile the step and are the ones compared. Then steps run
back to back for the window; each ends when its loss is on the host, as
``Trainer._run`` ends it.

Correctness: after the window the plain reference (float32, HIGHEST)
recomputes the first `check_steps` steps of every pod from the same seed
and rows, under the schedule (offset, chunks, bits of each class) that
the configuration states for this plan, not one the program derives.
Compared: the classes whose lowering by the step differs from the
stated schedule (none may); each step's loss (mean over pods); each
leaf's norm per pod of the first step's clipped gradient as AdamW's
first moment holds it, and of the parameters' change over the compared
steps, each as the gap between program and reference over the larger of
the reference's norm of that leaf and of the median leaf. Every window
step's loss must be finite.
"""
from __future__ import annotations

import functools
import itertools
import math
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

from harness import Run, load_module

_fleet = load_module(os.path.join(os.path.dirname(__file__), "_fleet.py"))

OPT_KEYS = ("lr", "b1", "b2", "eps", "weight_decay", "clip_norm",
            "warmup_steps", "total_steps", "min_lr_frac")
# a leaf whose reference gradient is under this share of the median
# leaf's moves under AdamW by round-off alone: left out of the change
STILL_LEAF = 1e-3


def model_config(cfg: Dict[str, Any]):
    """The configuration file as the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "sliding_window", "rope_theta", "norm_eps",
            "tie_embeddings")
    return ModelConfig(arch_id=cfg["arch_id"], family="dense",
                       dtype=cfg["precision"]["compute"],
                       param_dtype=cfg["precision"]["params"],
                       **{k: cfg[k] for k in keys})


def schedule_of(plan) -> List[tuple]:
    """The plan as the step lowers it: (offset, chunks, bits) per offset
    class, by the function the sync itself calls."""
    from repro.core import wansync
    return [(ph["offset"], ph["chunks"], ph["bits"])
            for ph in wansync.offset_schedule(plan)]


def stated_schedule(cfg: Dict[str, Any]) -> List[tuple]:
    """The configuration's (offset, chunks, bits) per offset class."""
    return [tuple(int(x) for x in c) for c in cfg["plan"]["schedule"]]


def schedule_mismatch(lowered, stated) -> int:
    """Offset classes whose lowering differs from the stated one."""
    return sum(a != b for a, b in itertools.zip_longest(lowered, stated))


def build(run: Run):
    """Set-up: mesh, Trainer and plan, the jitted step, the state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.predictor import BwPredictor
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import auto_mesh
    from repro.models import registry
    from repro.train.loop import LoopConfig, Trainer
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_step import broadcast_to_pods
    from repro.wan.simulator import WanSimulator
    cfg, tr, ref = run.config, run.traffic, run.reference
    n_pods = cfg["pods"]["count"]
    mcfg = model_config(cfg)
    mesh = auto_mesh((n_pods, 1, 1), ("pod", "data", "model"),
                     devices=run.devices[:n_pods])
    pc = cfg["plan"]
    c = pc["constants"]
    sim = WanSimulator(regions=list(cfg["pods"]["regions"]),
                       nic_cap=c["nic_cap_mbps"], knee=c["knee_conns"],
                       rtt_beta=c["rtt_beta"], seed=pc["wan_seed"])
    dcfg = DataConfig(batch=n_pods * tr["batch_per_pod"], seq=tr["seq"],
                      vocab=mcfg.vocab, n_pods=n_pods, skew=tr["skew"],
                      seed=_fleet.subseed(run.seed, 1))
    trainer = Trainer(
        mcfg, mesh, dcfg,
        LoopConfig(sync="wanify", compress=cfg["wire"]["compress"],
                   max_conns=pc["max_conns"]),
        opt=AdamWConfig(**{k: cfg["optimizer"][k] for k in OPT_KEYS}),
        sim=sim, predictor=BwPredictor(_fleet.forest(pc)))
    step = trainer._get_step()

    sh = ref.shapes(cfg)
    key = jax.random.key(_fleet.subseed(run.seed, 0))
    pods = NamedSharding(mesh, PartitionSpec("pod"))
    want = registry.abstract_params(mcfg)
    got = jax.eval_shape(lambda: ref.init_params(sh, key))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the reference's weights are not laid out as the "
                         "program's")

    # the key is an argument, not a constant: one program for every seed
    @functools.partial(jax.jit, out_shardings=pods)
    def make_state(key):
        p = ref.init_params(sh, key)
        return broadcast_to_pods((p, init_opt_state(p)), n_pods)

    norms = ref.programs(sh, cfg["optimizer"], stated_schedule(cfg), pods)
    return (trainer, step, functools.partial(make_state, key),
            norms["first_grad"], functools.partial(norms["change"], key=key),
            dcfg, mesh, pods)


def run(run: Run) -> None:
    """Set up and step through the compared steps, measure, then check."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import batches, prefetch
    tr = run.traffic
    phases, t0 = {}, time.perf_counter()

    def phase(name):
        nonlocal t0
        phases[name] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()

    phases["to_driver"] = round(t0 - run.t_start, 3)
    trainer, step, make_state, first_grad, change, dcfg, mesh, pods = \
        build(run)
    lowered, schedule = schedule_of(trainer.plan), stated_schedule(run.config)
    data = prefetch(batches(trainer.cfg, dcfg))
    feed = lambda b: {k: jnp.asarray(v) for k, v in b.items()}  # noqa: E731
    phase("build")
    with jax.set_mesh(mesh):
        params, opt_state = jax.block_until_ready(make_state())
        phase("weights")
        rows, losses = [], []
        for t in range(tr["check_steps"]):
            rows.append(next(data))
            params, opt_state, out = step(params, opt_state, feed(rows[-1]))
            losses.append(float(out["loss"]))
            if t == 0:
                phase("step1")
                g1 = np.asarray(first_grad(opt_state["m"]))
                phase("first_grad")
        phase("steps")
        moved = np.asarray(change(params))
        phase("change")

        n = bad = 0
        compiles = []           # JAX's trace, lower and compile events

        def listen(event, _secs, **_kw):
            if event.startswith("/jax/core/compile/"):
                compiles.append(event)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            with run.window():
                w0 = time.perf_counter()
                while True:
                    with run.annotate("step"):
                        params, opt_state, out = step(params, opt_state,
                                                      feed(next(data)))
                        loss = float(out["loss"])
                    n += 1
                    bad += not math.isfinite(loss)
                    if time.perf_counter() - w0 >= run.seconds:
                        break
                elapsed = time.perf_counter() - w0
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
    del params, opt_state, out, trainer, step
    obs = run.obs
    obs["window_s"] = elapsed
    obs["attempted"], obs["failed"] = n, bad
    obs["tokens_per_step"] = dcfg.batch * dcfg.seq
    obs["tokens"] = n * obs["tokens_per_step"]
    obs["window_compiles"] = len(compiles)
    cfg = run.config
    obs["shapes"] = {"layers": cfg["n_layers"], "d_model": cfg["d_model"],
                     "heads": cfg["n_heads"], "kv_heads": cfg["n_kv_heads"],
                     "head_dim": cfg["head_dim"], "d_ff": cfg["d_ff"],
                     "vocab": cfg["vocab"], "seq": tr["seq"],
                     "window": cfg["sliding_window"]}
    prog = {"losses": losses, "grad_norms": g1, "change_norms": moved}
    r0 = time.perf_counter()
    want = reference(run, schedule, rows, pods)["none"]
    obs["reference_s"] = time.perf_counter() - r0
    obs["records"] = {"rows": rows, "schedule": schedule, "pods": pods,
                      "program": prog, "reference": want}
    obs["pod_spread"] = pod_spread(moved)
    print(f"xpod: {n} window steps in {elapsed:.3f} s, {bad} non-finite "
          f"losses, {obs['window_compiles']} compile events in the window; "
          f"schedule lowered {lowered}, stated {schedule}; "
          f"reference {obs['reference_s']:.1f} s; "
          f"largest spread of a leaf's change across pods "
          f"{obs['pod_spread']:.3g}; leaf-pods left out of the change "
          f"{int(np.sum(~moving(want)))}; set-up seconds {phases}",
          file=sys.stderr)
    checks = {"schedule_mismatch": float(schedule_mismatch(lowered,
                                                           schedule))}
    checks.update(readings(prog, want))
    checks["nonfinite_window_losses"] = float(bad)
    lim = tr["limits"]
    obs["checks"] = [_fleet.check(k, v, lim[k]) for k, v in checks.items()]


def reference(run: Run, schedule, rows, pods, mode: str = "f32",
              faults=("none",), moments: str = "float32"
              ) -> Dict[str, Dict[str, Any]]:
    """The reference's compared steps from the run's seed and rows, for
    each of `faults` (``reference.run_steps``)."""
    import jax
    cfg, ref = run.config, run.reference
    return ref.run_steps(ref.shapes(cfg), cfg["optimizer"], schedule,
                         jax.random.key(_fleet.subseed(run.seed, 0)), rows,
                         pods, mode=mode, faults=faults, moments=moments)


def _gap(got: np.ndarray, want: np.ndarray, keep=None) -> float:
    """Largest |got - want| over the larger of want and the median leaf's
    want (per pod), over leaves [L, P] (those `keep` marks)."""
    floor = np.maximum(want, np.median(want, axis=0, keepdims=True))
    gap = np.abs(np.asarray(got, np.float64) - want) / floor
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def moving(want: Dict[str, Any]) -> np.ndarray:
    """[leaves, P]: the leaves whose change is compared, by the rule on
    the reference's first gradient."""
    g = np.asarray(want["grad_norms"], np.float64)
    return g >= STILL_LEAF * np.median(g, axis=0, keepdims=True)


def readings(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The compared numbers of one run against the reference."""
    lg, lw = np.asarray(got["losses"]), np.asarray(want["losses"])
    g = np.asarray(want["grad_norms"], np.float64)
    bad = ~np.isfinite(lg)
    return {"loss_rel": float("inf") if bad.any()
            else float(np.max(np.abs(lg - lw) / np.abs(lw))),
            "grad_norm_gap": _gap(got["grad_norms"], g),
            "change_norm_gap": _gap(got["change_norms"],
                                    np.asarray(want["change_norms"],
                                               np.float64), moving(want))}


def pod_spread(moved: np.ndarray) -> float:
    """Largest spread of a leaf's change norm across pods over its
    median: how far the pods' parameters drifted apart."""
    m = np.asarray(moved, np.float64)
    med = np.median(m, axis=1)
    ok = med > 0
    return float(np.max((m.max(1) - m.min(1))[ok] / med[ok])) \
        if ok.any() else 0.0


FAULTS = ("half", "no_sync", "wire4")


def control(run: Run, records) -> Dict[str, float]:
    """The control (the reference with float8 products, as "fp8"), the
    planted faults (half the rows, no exchange, a 4-bit wire) and AdamW's
    moments kept in bfloat16 ("bf16_moments"), each computed by the
    reference in the program's place and read by the same comparison
    against the float32 reference."""
    args = (run, records["schedule"], records["rows"], records["pods"])
    got = {"fp8": reference(*args, mode="fp8")["none"],
           "bf16_moments": reference(*args, moments="bfloat16")["none"],
           **reference(*args, faults=FAULTS)}
    return {f"{name}.{k}": v for name, one in got.items()
            for k, v in readings(one, records["reference"]).items()}
