"""Host-side training orchestration.

Per step: data -> jit'd train step. Around it, the pieces a 1000-node
deployment needs:

  * WANify control plane — the Trainer consumes plans from the shared
    `repro.control.WanifyController` (snapshot -> RF prediction ->
    global optimization -> AIMD -> WanPlan). Periodic and straggler
    triggers swap in new plans; the controller's plan cache is keyed by
    plan signature so oscillating plans never recompile.
  * fault tolerance — async sharded checkpoints every `ckpt_every`;
    `Trainer.restore_or_init` resumes from the newest complete manifest
    (crash/restart contract). Simulated step failures retry from the last
    checkpoint.
  * straggler mitigation — per-step wall-time EWMA; a step slower than
    `straggler_factor` x EWMA triggers an AIMD multiplicative-decrease on
    the slow pod's links + immediate re-plan (and is recorded).
  * elastic rescale — `Trainer.rescale(new_mesh)` rebuilds the step for a
    new pod count; the RF predicts BW for the new cluster size (§3.3.2)
    and checkpoints are mesh-agnostic.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs.base import ModelConfig
from repro.control import ControllerConfig, WanifyController
from repro.core.plan import WanPlan
from repro.core.predictor import BwPredictor
from repro.data.pipeline import DataConfig, batches, pod_skew_weights, prefetch
from repro.models import registry
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step
from repro.wan.simulator import WanSimulator


@dataclass
class LoopConfig:
    steps: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 25
    log_every: int = 10
    sync: str = "wanify"             # wanify | psum
    compress: bool = False
    replan_every: int = 20
    straggler_factor: float = 2.5
    max_conns: int = 8
    use_skew_weights: bool = True
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, mesh, dcfg: DataConfig,
                 loop: LoopConfig = LoopConfig(),
                 opt: Optional[AdamWConfig] = None,
                 sim: Optional[WanSimulator] = None,
                 predictor: Optional[BwPredictor] = None):
        self.cfg, self.mesh, self.dcfg, self.loop = cfg, mesh, dcfg, loop
        self.opt = opt or AdamWConfig()
        self.n_pods = mesh.shape.get("pod", 1)
        self.multi_pod = "pod" in mesh.axis_names and self.n_pods > 1
        self.sim = sim
        self.predictor = predictor
        self._step_cache: Dict[Any, Any] = {}
        self.history: List[Dict[str, float]] = []
        self.events: List[str] = []
        # ---- WANify control plane (repro.control) ---------------------
        # The closed loop (snapshot -> prediction -> global optimization
        # -> AIMD -> plan) lives in the shared controller; the Trainer
        # only consumes plans and compiled steps.
        self.controller: Optional[WanifyController] = None
        if self.multi_pod and self.loop.sync == "wanify" and \
                sim is not None and predictor is not None:
            self.controller = WanifyController(
                sim=sim, predictor=predictor, n_pods=self.n_pods,
                cfg=ControllerConfig(
                    max_conns=self.loop.max_conns,
                    replan_every=self.loop.replan_every,
                    straggler_factor=self.loop.straggler_factor),
                events=self.events)
            self._plan: Optional[WanPlan] = None
        elif self.multi_pod:
            self._plan = WanPlan.uniform(self.n_pods)
        else:
            self._plan = None

    @property
    def plan(self) -> Optional[WanPlan]:
        """The plan in force — always the controller's latest when a
        control plane is attached (never a stale copy)."""
        if self.controller is not None:
            return self.controller.plan
        return self._plan

    # ------------------------------------------------------------------
    def _build_step(self, plan: Optional[WanPlan]):
        return jax.jit(
            make_train_step(self.cfg, self.mesh, plan=plan, opt=self.opt,
                            sync=self.loop.sync,
                            compress=self.loop.compress),
            donate_argnums=(0, 1))

    def _get_step(self):
        if self.controller is not None:
            # keyed on plan.signature(): oscillating plans never recompile
            return self.controller.compiled(
                (self.loop.sync, self.loop.compress), self._build_step)
        key = (self.plan.signature() if self.plan else ("single",),
               self.loop.sync, self.loop.compress)
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(self.plan)
        return self._step_cache[key]

    # ------------------------------------------------------------------
    def restore_or_init(self, key: jax.Array):
        params = registry.init_params(self.cfg, key)
        opt_state = init_opt_state(params)
        start = 0
        if self.loop.ckpt_dir:
            latest = ckpt_lib.latest_step(self.loop.ckpt_dir)
            if latest is not None:
                state = ckpt_lib.restore(self.loop.ckpt_dir,
                                         {"p": params, "o": opt_state})
                params, opt_state = state["p"], state["o"]
                start = latest
                self.events.append(f"restored step {latest}")
        if self.multi_pod:
            # vmap-over-pods formulation: explicit pod-replicated leading
            # dim, sharded so each pod's copy lives on that pod's devices
            # (checkpoints stay pod-free => elastic across pod counts)
            from repro.train.train_step import broadcast_to_pods
            to_pods = jax.jit(
                functools.partial(broadcast_to_pods, n_pods=self.n_pods),
                out_shardings=NamedSharding(self.mesh, P("pod")))
            params, opt_state = to_pods((params, opt_state))
        return params, opt_state, start

    # ------------------------------------------------------------------
    def run(self, key: jax.Array, fail_at: Optional[int] = None):
        """fail_at: inject a simulated node failure at that step (the
        fault-tolerance test path)."""
        with jax.set_mesh(self.mesh):
            return self._run(key, fail_at)

    def _run(self, key: jax.Array, fail_at: Optional[int] = None):
        params, opt_state, start = self.restore_or_init(key)
        data = prefetch(batches(self.cfg, self.dcfg))
        step_fn = self._get_step()
        writer = None
        step = start
        while step < self.loop.steps:
            batch = {k: jax.numpy.asarray(v) for k, v in next(data).items()}
            t0 = time.perf_counter()
            if fail_at is not None and step == fail_at:
                fail_at = None
                self.events.append(f"simulated failure at step {step}")
                # crash/restart: reload newest complete checkpoint
                params, opt_state, step = self.restore_or_init(key)
                step_fn = self._get_step()
                continue
            params, opt_state, out = step_fn(params, opt_state, batch)
            dt = time.perf_counter() - t0
            # ---- straggler trigger (controller-owned EWMA + AIMD MD) ----
            if self.controller is not None:
                if self.controller.observe_step_time(dt, step=step) \
                        is not None:
                    step_fn = self._get_step()
            # ---- logging -------------------------------------------------
            rec = {"step": step, "loss": float(out["loss"]),
                   "grad_norm": float(out["grad_norm"]), "time": dt}
            self.history.append(rec)
            # ---- WANify periodic re-plan --------------------------------
            if self.controller is not None and \
                    self.controller.replan_due(step):
                skw = pod_skew_weights(np.asarray(batch["tokens"]),
                                       self.n_pods, self.cfg.vocab) \
                    if self.loop.use_skew_weights else None
                if self.controller.maybe_replan(step, skew_w=skw) \
                        is not None:
                    step_fn = self._get_step()
            # ---- checkpoint ----------------------------------------------
            if self.loop.ckpt_dir and (step + 1) % self.loop.ckpt_every == 0:
                if writer is not None:
                    writer.join()
                if self.multi_pod:
                    from repro.train.train_step import strip_pods
                    tree = {"p": strip_pods(params), "o": strip_pods(opt_state)}
                else:
                    tree = {"p": params, "o": opt_state}
                writer = ckpt_lib.save(self.loop.ckpt_dir, step + 1, tree,
                                       async_=True)
            step += 1
        if writer is not None:
            writer.join()
        return params, opt_state

    # ------------------------------------------------------------------
    def rescale(self, new_mesh) -> "Trainer":
        """Elastic scale: new pod count; the controller re-plans for the
        new cluster size (§3.3.2) and checkpoints are mesh-agnostic."""
        t = Trainer(self.cfg, new_mesh, self.dcfg, self.loop, self.opt,
                    self.sim, self.predictor)
        # prepend in place: t.events is shared with t.controller's log
        t.events[:0] = self.events + [f"rescaled to {dict(new_mesh.shape)}"]
        return t
