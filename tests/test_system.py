"""End-to-end system tests: training convergence, fault tolerance,
WANify end-to-end benefit, and multi-device wansync/dryrun (the latter
run in subprocesses so the main test session keeps 1 CPU device)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.configs.base import reduced
from repro.data.pipeline import DataConfig
from repro.train.loop import LoopConfig, Trainer
from repro.train.optimizer import AdamWConfig


def _mesh1():
    return auto_mesh((1,), ("data",))


def test_training_reduces_loss(tmp_path):
    cfg = reduced(get_config("llama3-8b"))
    dcfg = DataConfig(batch=4, seq=32, vocab=cfg.vocab)
    tr = Trainer(cfg, _mesh1(), dcfg,
                 LoopConfig(steps=8, sync="psum"),
                 opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=8))
    tr.run(jax.random.key(0))
    losses = [h["loss"] for h in tr.history]
    assert losses[-1] < losses[0], losses


def test_checkpoint_restart_resumes(tmp_path):
    cfg = reduced(get_config("qwen3-4b"))
    dcfg = DataConfig(batch=4, seq=32, vocab=cfg.vocab)
    lc = LoopConfig(steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                    sync="psum")
    Trainer(cfg, _mesh1(), dcfg, lc).run(jax.random.key(0))
    tr2 = Trainer(cfg, _mesh1(), dcfg,
                  LoopConfig(steps=9, ckpt_dir=str(tmp_path), ckpt_every=3,
                             sync="psum"))
    tr2.run(jax.random.key(0))
    assert any("restored step 6" in e for e in tr2.events)
    assert len(tr2.history) == 3             # only steps 6..8 re-run


def test_failure_injection_recovers(tmp_path):
    cfg = reduced(get_config("llama3-8b"))
    dcfg = DataConfig(batch=4, seq=32, vocab=cfg.vocab)
    lc = LoopConfig(steps=7, ckpt_dir=str(tmp_path), ckpt_every=2,
                    sync="psum")
    tr = Trainer(cfg, _mesh1(), dcfg, lc)
    tr.run(jax.random.key(0), fail_at=5)
    assert any("simulated failure" in e for e in tr.events)
    assert any("restored" in e for e in tr.events)
    assert tr.history[-1]["step"] == 6       # completed all steps


_MULTIPOD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import auto_mesh
    from repro.core.wansync import wan_allreduce, psum_allreduce
    from repro.core.plan import WanPlan

    mesh = auto_mesh((4, 2), ("pod", "data"))
    plan = WanPlan(
        n_pods=4,
        conns=tuple(tuple(6 if abs(i - j) % 4 > 1 else 2 for j in range(4))
                    for i in range(4)),
        pred_bw=tuple(tuple(150.0 if abs(i - j) % 4 > 1 else 900.0
                            for j in range(4)) for i in range(4)),
        compress_bits=(8, 8, 8, 8))
    tree = {"w": jnp.arange(48.0).reshape(12, 4) / 7.0,
            "s": jnp.float32(2.5)}

    def f(t):
        r = jax.lax.axis_index("pod").astype(jnp.float32)
        local = jax.tree.map(lambda x: x * (r + 1.0), t)
        return wan_allreduce(local, plan, compress=False, mean=True)

    # only the pod axis is manual; "data" stays auto, as in kv_migrate
    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       axis_names={"pod"}, check_vma=False)
    out = jax.jit(sm)(tree)
    exp = np.mean([r + 1 for r in range(4)])
    for k in tree:
        assert np.allclose(np.asarray(out[k]), np.asarray(tree[k]) * exp,
                           rtol=1e-5), k
    txt = jax.jit(sm).lower(tree).compile().as_text()
    assert "collective-permute" in txt
    assert txt.count("all-reduce(") == 0      # fully our schedule
    print("MULTIPOD_OK")
""")


def test_wansync_multidevice_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _MULTIPOD_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=300)
    assert "MULTIPOD_OK" in r.stdout, r.stdout + r.stderr


_DRYRUN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.configs.base import reduced
    from repro.configs import get_config
    import repro.launch.dryrun as dr

    # shrink the production mesh to the 8 host devices: same axes/logic
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    import repro.configs as C
    cfg = get_config("llama3-8b")
    # patch a tiny config into the registry path used by run_cell
    import repro.configs
    small = reduced(cfg)
    repro.configs._SMALL = small
    orig = repro.configs.get_config
    repro.configs.get_config = lambda a: small
    dr.get_config = repro.configs.get_config
    import repro.configs.shapes as shp
    shp.SHAPES = {"train_4k": shp.ShapeSpec("train_4k", "train", 64, 8),
                  "decode_32k": shp.ShapeSpec("decode_32k", "decode", 64, 8)}
    dr.SHAPES = shp.SHAPES
    for shape in ("train_4k", "decode_32k"):
        cell = dr.run_cell("llama3-8b", shape, mesh, "multi")
        assert cell["status"] == "ok", cell
        assert cell["roofline"]["t_compute"] > 0
    print("DRYRUN_OK")
""")


def test_small_mesh_dryrun_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _DRYRUN_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_wanify_improves_min_bw_end_to_end():
    """The paper's headline: WANify raises the cluster's minimum BW vs
    single-connection AND uniform-parallel baselines (on the calibrated
    simulator, full 8-DC mesh)."""
    from repro.core.global_opt import global_optimize
    from repro.wan.simulator import WanSimulator
    mins = {}
    sim = WanSimulator(seed=5)
    off = ~np.eye(8, dtype=bool)
    # noise-free runtime ground truth: the headline gain should not
    # hinge on one measurement-noise draw flipping a closeness class
    pred = sim.measure_simultaneous()
    plan = global_optimize(pred, M=8)
    mins["single"] = sim.measure_simultaneous(np.ones((8, 8)))[off].min()
    mins["uniform8"] = sim.measure_simultaneous(np.full((8, 8), 8.0))[off].min()
    mins["wanify"] = sim.measure_simultaneous(
        plan.max_cons.astype(float))[off].min()
    assert mins["wanify"] > mins["single"] * 1.25, mins
    assert mins["wanify"] > mins["uniform8"] * 1.1, mins
