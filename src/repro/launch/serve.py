"""Serving launcher: batched request serving with KV caches.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 8 --max-new 16
"""
import argparse
import time
from typing import List

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ModelConfig
from repro.configs.base import reduced as reduce_cfg
from repro.launch.compile_cache import use_compile_cache
from repro.models import registry
from repro.serve.engine import Engine, Request, ServeConfig


def build_engine(cfg: ModelConfig, seed: int, batch: int,
                 s_max: int) -> Engine:
    """An engine over random weights drawn from `seed`."""
    params = registry.init_params(cfg, jax.random.key(seed))
    return Engine(cfg, params, ServeConfig(batch=batch, s_max=s_max, tp=1))


def make_requests(cfg: ModelConfig, n: int, max_new: int,
                  seed: int) -> List[Request]:
    """`n` requests with random 4-16 token prompts drawn from `seed`."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        rng.integers(4, 17)).astype(np.int32),
                    max_new=max_new)
            for i in range(n)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    eng = build_engine(cfg, args.seed, args.batch, args.s_max)
    reqs = make_requests(cfg, args.requests, args.max_new, args.seed)
    t0 = time.perf_counter()
    out = eng.serve(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in out.values())
    print(f"[serve] {len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")
    for rid in sorted(out)[:4]:
        print(f"[serve] req {rid}: {out[rid]}")


if __name__ == "__main__":
    main()
