"""Quickstart: the WANify pipeline end-to-end in ~60 seconds on CPU.

1. simulate the paper's 8-DC AWS WAN,
2. train the Random-Forest runtime-BW predictor on Bandwidth-Analyzer
   data,
3. globally optimize heterogeneous parallel connections (Algorithm 1 +
   Eq. 2-3), throttle BW-rich links,
4. show the min-BW gain over single-connection / uniform-parallel
   baselines,
5. train a tiny LM for a few steps with the WANify-scheduled cross-pod
   gradient sync (2 simulated pods).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core.global_opt import global_optimize
from repro.core.predictor import BwPredictor
from repro.data.pipeline import DataConfig
from repro.train.loop import LoopConfig, Trainer
from repro.train.optimizer import AdamWConfig
from repro.wan.dataset import train_default_forest
from repro.wan.monitor import SnapshotMonitor
from repro.wan.simulator import WanSimulator


def main():
    print("== 1. simulate the 8-DC WAN (paper Fig. 1 calibration) ==")
    sim = WanSimulator(seed=0)
    si = sim.measure_static_independent()
    ue, uw, ap = (sim.regions.index(r) for r in ("us-east", "us-west",
                                                 "ap-se"))
    print(f"static BW us-east<->us-west {si[ue, uw]:.0f} Mbps "
          f"(paper 1700), us-east<->ap-se {si[ue, ap]:.0f} Mbps (paper 121)")

    print("\n== 2. train the runtime-BW Random Forest ==")
    rf, acc, r2 = train_default_forest(n_samples=150, n_trees=50)
    print(f"train accuracy (within 10%): {acc * 100:.1f}%  "
          f"holdout R^2: {r2:.3f} (paper: 98.51%)")

    print("\n== 3. predict runtime BW from a 1-second snapshot ==")
    predictor = BwPredictor(rf)
    _, raw = SnapshotMonitor(sim).capture()
    pred = predictor.predict_matrix(8, raw["snapshot_bw"], raw["mem_util"],
                                    raw["cpu_load"], raw["retrans"],
                                    raw["dist"])
    plan = global_optimize(pred, M=8)
    print("connection matrix (max):")
    print(plan.max_cons)

    print("\n== 4. minimum-BW gain (the paper's headline) ==")
    off = ~np.eye(8, dtype=bool)
    m1 = sim.measure_simultaneous(np.ones((8, 8)))[off].min()
    m8 = sim.measure_simultaneous(np.full((8, 8), 8.0))[off].min()
    mw = sim.measure_simultaneous(plan.max_cons.astype(float),
                                  cap=plan.throttle)[off].min()
    print(f"min BW: single {m1:.0f} | uniform-8 {m8:.0f} | "
          f"WANify {mw:.0f} Mbps ({mw / m1:.2f}x vs single)")

    print("\n== 5. 2-pod training with WANify-scheduled gradient sync ==")
    cfg = reduced(get_config("llama3-8b"))
    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    tr = Trainer(cfg, mesh,
                 DataConfig(batch=8, seq=32, vocab=cfg.vocab, n_pods=2),
                 LoopConfig(steps=6, sync="wanify", compress=True),
                 opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=6),
                 sim=sim, predictor=predictor)
    print(f"plan conns={tr.plan.conns} wire bits={tr.plan.compress_bits}")
    tr.run(jax.random.key(0))
    print("losses:", [f"{h['loss']:.3f}" for h in tr.history])


if __name__ == "__main__":
    main()
