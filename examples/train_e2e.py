"""End-to-end training driver: ~100M-parameter llama-family model for a
few hundred steps on 2 simulated pods with the full WANify runtime
(RF prediction -> global optimization -> AIMD re-planning -> compressed
chunked cross-pod sync), checkpointing and straggler handling enabled.

Run:  PYTHONPATH=src python examples/train_e2e.py [--steps 200]
(CPU: ~100M params is sized to stay within laptop memory; on TPU drop
--small-model and raise the mesh.)
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax

from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.core.predictor import BwPredictor
from repro.data.pipeline import DataConfig
from repro.train.loop import LoopConfig, Trainer
from repro.train.optimizer import AdamWConfig
from repro.wan.dataset import train_default_forest
from repro.wan.simulator import WanSimulator


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/wanify_e2e_ckpt")
    args = ap.parse_args()

    # ~100M params: 12L x d512 x heads 8 x ff 2048, 32k vocab
    cfg = get_config("llama3-8b").replace(
        n_layers=12, d_model=512, n_heads=8, n_kv_heads=4, d_ff=2048,
        vocab=32000, head_dim=0)
    n_params = sum(
        int(jax.numpy.prod(jax.numpy.array(l.shape)))
        for l in jax.tree.leaves(
            jax.eval_shape(lambda k: __import__(
                "repro.models.registry", fromlist=["x"]).init_params(cfg, k),
                jax.random.key(0))))
    print(f"[e2e] model: {n_params / 1e6:.1f}M params")

    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    print("[e2e] training RF predictor ...")
    rf, acc, _ = train_default_forest(n_samples=150, n_trees=50)
    sim = WanSimulator(seed=0)
    tr = Trainer(
        cfg, mesh,
        DataConfig(batch=args.batch, seq=args.seq, vocab=cfg.vocab,
                   n_pods=2, skew=0.3),
        LoopConfig(steps=args.steps, ckpt_dir=args.ckpt, ckpt_every=50,
                   sync="wanify", compress=True, replan_every=25),
        opt=AdamWConfig(lr=6e-4, warmup_steps=20, total_steps=args.steps),
        sim=sim, predictor=BwPredictor(rf))
    print(f"[e2e] initial plan: conns={tr.plan.conns} "
          f"bits={tr.plan.compress_bits}")
    t0 = time.time()
    tr.run(jax.random.key(0))
    dt = time.time() - t0
    if not tr.history:
        print("[e2e] no steps ran (--steps 0?)")
        return
    first = tr.history[0]["loss"]
    last = tr.history[-1]["loss"]
    toks = args.steps * args.batch * args.seq
    print(f"[e2e] {args.steps} steps in {dt:.0f}s "
          f"({toks / dt:.0f} tok/s) loss {first:.3f} -> {last:.3f}")
    print(f"[e2e] events: {tr.events}")
    print(f"[e2e] controller: {len(tr.controller.record)} replans, "
          f"{len(tr.controller.plan_cache)} compiled plans cached")
    assert last < first, "loss must decrease"


if __name__ == "__main__":
    main()
