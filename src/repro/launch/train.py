"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 50 --pods 2 --data 2 --model 2 --sync wanify --compress

On the CPU use --reduced (small same-family config) and a small mesh;
on TPU hosts drop --reduced and use the production mesh.
"""
import os

if "XLA_FLAGS" not in os.environ:       # allow multi-device CPU testing
    n = os.environ.get("REPRO_HOST_DEVICES")
    if n:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n}"

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import reduced as reduce_cfg
from repro.core.predictor import BwPredictor
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.train.loop import LoopConfig, Trainer
from repro.train.optimizer import AdamWConfig
from repro.wan.dataset import train_default_forest
from repro.wan.simulator import WanSimulator


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--sync", default="wanify", choices=["wanify", "psum"])
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    mesh = make_mesh(args.pods, args.data, args.model)
    dcfg = DataConfig(batch=args.batch, seq=args.seq, vocab=cfg.vocab,
                      n_pods=max(args.pods, 1), skew=args.skew,
                      seed=args.seed)
    sim = pred = None
    if args.pods > 1 and args.sync == "wanify":
        print("[train] training WAN prediction model ...")
        rf, acc, r2 = train_default_forest(n_samples=150, n_trees=40)
        print(f"[train] forest train_acc={acc:.3f} holdout_r2={r2:.3f}")
        sim, pred = WanSimulator(seed=args.seed), BwPredictor(rf)
    tr = Trainer(cfg, mesh, dcfg,
                 LoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                            sync=args.sync, compress=args.compress),
                 opt=AdamWConfig(lr=args.lr, total_steps=args.steps),
                 sim=sim, predictor=pred)
    if tr.plan:
        print(f"[train] WanPlan conns={tr.plan.conns} "
              f"bits={tr.plan.compress_bits}")
    tr.run(jax.random.key(args.seed))
    for h in tr.history[:: max(1, len(tr.history) // 20)]:
        print(f"[train] step {h['step']:5d} loss {h['loss']:.4f} "
              f"({h['time']:.2f}s)")
    print(f"[train] events: {tr.events}")


if __name__ == "__main__":
    main()
