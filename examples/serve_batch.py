"""Batched serving example: prefill + decode with KV caches on a small
Qwen3-family model, plus WANify-scheduled KV-cache migration between a
prefill pod and decode pods (disaggregated serving).

The migration plan comes from the shared control plane: a
`WanifyController` closes the snapshot -> prediction -> optimization ->
AIMD loop, and `Engine.replan()` adopts a fresh plan when the WAN
shifts — the next `kv_migrate` picks up the new chunking/wire bits.

Run:  PYTHONPATH=src python examples/serve_batch.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.configs.base import reduced
from repro.control import WanifyController
from repro.core.predictor import SnapshotPredictor
from repro.models import registry
from repro.serve.engine import Engine, Request, ServeConfig, kv_migrate
from repro.wan.simulator import WanSimulator


def main():
    cfg = reduced(get_config("qwen3-4b"))
    params = registry.init_params(cfg, jax.random.key(0))

    # serve-side control plane: 2 pods monitored on the simulated WAN
    # (SnapshotPredictor = no-RF ablation; swap in BwPredictor(rf) for
    # the paper's learned runtime-BW prediction)
    sim = WanSimulator(seed=0)
    ctl = WanifyController(sim=sim, predictor=SnapshotPredictor(),
                           n_pods=2)
    eng = Engine(cfg, params, ServeConfig(batch=4, s_max=128, tp=1),
                 controller=ctl)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        int(rng.integers(4, 24))
                                        ).astype(np.int32),
                    max_new=16)
            for i in range(8)]
    t0 = time.perf_counter()
    out = eng.serve(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in out.values())
    print(f"[serve] {len(reqs)} requests -> {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")
    for rid in sorted(out)[:3]:
        print(f"[serve] req {rid}: {out[rid][:8]} ...")

    # ---- disaggregated serving: migrate the prefill KV cache across
    # pods over the WANify-scheduled links (chunked + quantized wire) ---
    print("[serve] KV migration across 2 pods (WANify schedule) ...")
    mesh = auto_mesh((2, 4), ("pod", "data"))
    print(f"[serve] plan: conns={eng.plan.conns} "
          f"schedule={eng.migration_schedule()}")
    cache = jax.tree.map(jnp.asarray, eng.cache)

    def migrate(c):
        return kv_migrate(c, eng.plan, src_pod=0, compress=True)

    sm = jax.shard_map(migrate, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), axis_names={"pod"}, check_vma=False)
    with jax.set_mesh(mesh):
        moved = jax.jit(sm)(cache)
    ok = jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.allclose(a.astype(jnp.float32),
                                       b.astype(jnp.float32),
                                       atol=0.1, rtol=0.1)), cache, moved))
    n_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))
    print(f"[serve] migrated {n_bytes / 2 ** 20:.1f} MiB of KV cache, "
          f"quantized wire, roundtrip-consistent: {ok}")

    # ---- the WAN shifts: replan and show the schedule adapting --------
    sim.advance(5)
    eng.replan()
    print(f"[serve] after replan: conns={eng.plan.conns} "
          f"schedule={eng.migration_schedule()}")


if __name__ == "__main__":
    main()
