"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh)
cell on the production meshes, record memory/cost analysis + collective
bytes, and emit the static roofline table.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out benchmarks/results

NOTE the first three executable lines below: they MUST run before any jax
import (jax locks the device count on first init). The 512 placeholder
host devices exist ONLY for the dry-run; smoke tests / benches see 1.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"      # a CPU-only tool: never a chip
# (no `from __future__` here: the env var lines above must be the first
# executable statements in the module)

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, applicable, input_specs
from repro.core.plan import WanPlan
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.models.layers import ShardCtx
from repro.models.sharding import batch_specs, cache_specs, param_specs
from repro.train.optimizer import init_opt_state
from repro.train.train_step import make_train_step


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


# Per-arch train-cell knobs (production choices at this scale): bf16
# optimizer moments halve state HBM; microbatching (gradient
# accumulation) divides activation residency by the factor.
TRAIN_OVERRIDES = {
    # 236B on a 256-chip pod: bf16 weights + bf16 moments + bf16 grad
    # accumulation + 16-way microbatching (f32 AdamW state alone would be
    # 2.8 TB — 70% of pod HBM)
    "deepseek-v2-236b": {"microbatch": 16, "state_dtype": "bfloat16",
                         "param_dtype": "bfloat16",
                         "accum_dtype": "bfloat16"},
    "llama3-8b": {"microbatch": 2, "state_dtype": "bfloat16"},
    "minicpm3-4b": {"state_dtype": "bfloat16"},
    "qwen3-4b": {"state_dtype": "bfloat16"},
    "mamba2-2.7b": {"state_dtype": "bfloat16"},
    "zamba2-2.7b": {"state_dtype": "bfloat16"},
}


def default_plan(n_pods: int) -> WanPlan:
    """Paper-faithful default: heterogeneous conns from the calibrated
    8-DC simulator restricted to the pod count (offline prediction)."""
    if n_pods <= 1:
        return WanPlan.uniform(max(n_pods, 1))
    from repro.core.global_opt import global_optimize
    from repro.wan.simulator import WanSimulator
    sim = WanSimulator(seed=0)
    bw = sim.measure_runtime()[:n_pods, :n_pods]
    return WanPlan.from_global(global_optimize(bw))


def build_lowered(arch: str, shape_name: str, mesh, *,
                  sync: str = "wanify", compress: bool = True,
                  ctx_over: Optional[Dict] = None):
    """Returns (lowered, meta) for one cell."""
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    axes = mesh.axis_names
    multi_pod = "pod" in axes
    n_pods = mesh.shape.get("pod", 1)
    data_size = mesh.shape.get("data", 1)
    model_size = mesh.shape.get("model", 1)
    dp = n_pods * data_size
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    cdict = dict(batch_axes=batch_axes, model_axis="model", remat="full")
    if ctx_over:
        cdict.update(ctx_over)
    ctx = ShardCtx(**cdict)

    params_s = registry.abstract_params(cfg)
    pspecs = param_specs(params_s, data_size=data_size, model_size=model_size)
    ins = input_specs(cfg, shape_name, tp=model_size)

    if spec.kind == "train":
        from repro.train.optimizer import AdamWConfig
        ov = TRAIN_OVERRIDES.get(arch, {})
        if "param_dtype" in ov:
            cfg = cfg.replace(param_dtype=ov["param_dtype"])
            params_s = registry.abstract_params(cfg)
            pspecs = param_specs(params_s, data_size=data_size,
                                 model_size=model_size)
        opt_cfg = AdamWConfig(state_dtype=ov.get("state_dtype", "float32"))
        plan = default_plan(n_pods)
        step = make_train_step(cfg, mesh, plan=plan, opt=opt_cfg,
                               sync=sync if multi_pod else "none",
                               compress=compress,
                               microbatch=ov.get("microbatch", 1),
                               accum_dtype=jnp.dtype(
                                   ov.get("accum_dtype", "float32")),
                               ctx=ctx if not multi_pod else None)
        opt_s = jax.eval_shape(
            lambda p: init_opt_state(p, opt_cfg.state_dtype), params_s)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        if multi_pod:
            # vmap-over-pods formulation: explicit leading pod dim
            from repro.train.train_step import broadcast_to_pods, pod_specs
            params_s = jax.eval_shape(
                lambda t: broadcast_to_pods(t, n_pods), params_s)
            opt_s = jax.eval_shape(
                lambda t: broadcast_to_pods(t, n_pods), opt_s)
            pspecs = pod_specs(pspecs)
            ospecs = pod_specs(ospecs)
        bspecs = batch_specs(ins, batch_axes=batch_axes, batch_size=dp)
        jf = jax.jit(step, in_shardings=(
            _named(mesh, pspecs), _named(mesh, ospecs), _named(mesh, bspecs)),
            donate_argnums=(0, 1))
        lowered = jf.lower(params_s, opt_s, ins)
        tokens = spec.global_batch * spec.seq_len
    elif spec.kind == "prefill":
        fn = registry.prefill_fn(cfg, ctx, S_max=spec.seq_len, tp=model_size,
                                 dp_size=dp)
        bspecs = batch_specs(ins, batch_axes=batch_axes, batch_size=dp)
        jf = jax.jit(fn, in_shardings=(_named(mesh, pspecs),
                                       _named(mesh, bspecs)))
        lowered = jf.lower(params_s, ins)
        tokens = spec.global_batch * spec.seq_len
    else:  # decode
        fn = registry.decode_fn(cfg, ctx, dp_size=dp)
        cspecs = cache_specs(ins["cache"], batch_axes=batch_axes,
                             data_size=data_size, model_size=model_size,
                             dp_size=dp)
        tok_spec = P(batch_axes if spec.global_batch % dp == 0 else None, None)
        jf = jax.jit(fn, in_shardings=(
            _named(mesh, pspecs), _named(mesh, cspecs),
            NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())))
        lowered = jf.lower(params_s, ins["cache"], ins["tokens"], ins["pos"])
        tokens = spec.global_batch
    meta = {"arch": arch, "shape": shape_name, "kind": spec.kind,
            "tokens": tokens, "chips": int(np.prod(list(mesh.shape.values())))}
    return lowered, meta


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             **kw) -> Dict[str, Any]:
    cfg = get_config(arch)
    skip = applicable(cfg, shape_name)
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if skip:
        cell.update(status="skipped", reason=skip)
        return cell
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            lowered, meta = build_lowered(arch, shape_name, mesh, **kw)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        pod_stride = 256 if "pod" in mesh.axis_names else 1 << 60
        # trip-count-weighted static analysis (XLA cost_analysis counts
        # while bodies once — see launch/hlo_analysis.py)
        from repro.launch import hlo_analysis as ha
        w = ha.analyze(hlo, pod_stride=pod_stride)
        n_chips = meta["chips"]
        mf = rl.model_flops(cfg, meta["kind"], meta["tokens"], n_chips,
                            registry.param_count(cfg),
                            registry.active_param_count(cfg))
        roof = rl.Roofline(
            flops=float(w.dot_flops),
            bytes_accessed=float(w.hbm_bytes),
            ici_bytes=float(w.ici_bytes), dci_bytes=float(w.dci_bytes),
            model_flops_per_chip=mf)
        cell.update(
            status="ok",
            t_lower_s=round(t_lower, 1), t_compile_s=round(t_compile, 1),
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            hbm_per_device=mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes,
            collectives={k: float(v) for k, v in w.coll_by_kind.items()},
            n_collectives=w.n_collectives,
            xla_cost_raw={"flops": float(cost.get("flops", 0.0)),
                          "bytes": float(cost.get("bytes accessed", 0.0))},
            roofline=roof.to_dict(),
        )
    except Exception as e:  # a failure here is a bug in the system
        cell.update(status="error", error=f"{type(e).__name__}: {e}",
                    trace=traceback.format_exc()[-2000:])
    return cell


def _run_cell_subprocess(arch, shape, args, mesh_name):
    import subprocess
    import sys
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        rf = tf.name
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh_name, "--sync", args.sync,
           "--remat", args.remat, "--result-file", rf]
    if args.no_compress:
        cmd.append("--no-compress")
    if args.no_seq_shard:
        cmd.append("--no-seq-shard")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)               # let the child set its own
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3600)
    try:
        with open(rf) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "error",
                "error": f"subprocess crashed (rc={r.returncode})",
                "trace": (r.stdout + r.stderr)[-1500:]}
    finally:
        if os.path.exists(rf):
            os.unlink(rf)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sync", default="wanify", choices=["wanify", "psum"])
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--out", default="benchmarks/results")
    ap.add_argument("--result-file", default=None,
                    help="single-cell mode: write the cell JSON here")
    args = ap.parse_args()

    meshes = {}
    if args.mesh in ("single", "both"):
        meshes["single"] = make_production_mesh(multi_pod=False)
    if args.mesh in ("multi", "both"):
        meshes["multi"] = make_production_mesh(multi_pod=True)

    cells = []
    if args.all:
        targets = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        targets = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    ctx_over = {"remat": args.remat,
                "seq_shard_activations": not args.no_seq_shard}
    out_path = os.path.join(
        args.out, f"dryrun_{args.mesh}_{args.sync}.json")
    for mesh_name, mesh in meshes.items():
        for arch, shape in targets:
            print(f"[dryrun] {arch} x {shape} x {mesh_name} ...", flush=True)
            if args.all:
                # subprocess isolation: an XLA CHECK-crash in one cell
                # must not kill the sweep
                cell = _run_cell_subprocess(arch, shape, args, mesh_name)
            else:
                cell = run_cell(arch, shape, mesh, mesh_name, sync=args.sync,
                                compress=not args.no_compress,
                                ctx_over=ctx_over)
            status = cell["status"]
            extra = ""
            if status == "ok":
                r = cell["roofline"]
                extra = (f" dom={r['dominant']} "
                         f"tc={r['t_compute']:.3e} tm={r['t_memory']:.3e} "
                         f"tx={r['t_collective']:.3e} "
                         f"hbm={cell['hbm_per_device']/2**30:.2f}GiB "
                         f"[lower {cell['t_lower_s']}s compile {cell['t_compile_s']}s]")
            elif status == "error":
                extra = " " + cell["error"][:160]
            print(f"[dryrun]   -> {status}{extra}", flush=True)
            cells.append(cell)
            if args.result_file:
                with open(args.result_file, "w") as f:
                    json.dump(cell, f)
            else:
                with open(out_path, "w") as f:
                    json.dump(cells, f, indent=1)
    if not args.result_file:
        print(f"[dryrun] wrote {out_path}")


if __name__ == "__main__":
    main()
