"""The four-pod training cell at a CPU test's size: a whole run through
``harness.run_cell`` on four virtual CPU devices in a child process,
skipping only the harness's look for a chip; planted faults in the
timed path and the float8 control come out not correct; and the
reference's loss and gradient against the program's ``lm_loss``."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _common import BENCH, REPO
import harness

CELL = "xpod4-danube.sync"
# d 64, 4 heads of 16 / 2 KV, d_ff 128, vocab 256, 2 layers; sequence 64
# over a 32-key window, so the program runs its banded SWA path
TINY = {"config": {"d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                   "head_dim": 16, "d_ff": 128, "vocab": 256,
                   "n_layers": 2, "sliding_window": 32},
        "traffic": {"seq": 64}}
FAULTS = ("sync_off", "wire_fewer_bits", "plan_fewer_bits", "half_batch",
          "state_unchanged")

_CHILD = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax
    import harness
    assert len(jax.devices()) == 4
    # the runs share every program but the step they plant a fault in
    jax.config.update("jax_compilation_cache_dir", {cache!r})
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import repro.core.plan as plan_mod
    import repro.core.wansync as wansync
    import repro.train.train_step as ts
    TINY = {tiny!r}
    bench = harness.Benchmark.load()
    drv = bench.driver("xpod")
    memo, ref = {{}}, drv.reference

    def reference(run, schedule, rows, pods, mode="f32", faults=("none",),
                  moments="float32"):
        # one seed: every run compares with the same reference steps
        key = (mode, tuple(faults), moments)
        if key not in memo:
            memo[key] = ref(run, schedule, rows, pods, mode, faults,
                            moments)
        return memo[key]
    drv.reference = reference

    def run(keep=None):
        return harness.run_cell(bench, {cell!r}, 2 ** 31 + 99, 0.3, False,
                                time.perf_counter(), jax.devices(),
                                overrides=TINY, keep=keep)

    out, keep = {{}}, {{}}
    out["sound"] = run(keep)
    out["control"] = drv.control(keep["run"], keep["records"])
    sched, grads_of, adamw = (wansync.offset_schedule, ts._grads_of,
                              ts.adamw_update)
    pick = plan_mod.pick_bits

    def fewer_bits(plan):
        return [dict(ph, bits=4) for ph in sched(plan)]

    def plan_bits(*a, **k):
        # the plan itself picks 4 bits: program and driver both see it
        return min(pick(*a, **k), 4)

    def half(*a, **k):
        g = grads_of(*a, **k)
        return lambda p, b: g(p, jax.tree.map(
            lambda x: x[:x.shape[0] // 2], b))

    def unchanged(c, p, g, s):
        return p, s, adamw(c, p, g, s)[2]

    patches = {{"sync_off": (wansync, "wan_allreduce_batched",
                            lambda tree, plan, **k: tree),
               "wire_fewer_bits": (wansync, "offset_schedule", fewer_bits),
               "plan_fewer_bits": (plan_mod, "pick_bits", plan_bits),
               "half_batch": (ts, "_grads_of", half),
               "state_unchanged": (ts, "adamw_update", unchanged)}}
    for name, (mod, attr, fn) in patches.items():
        orig = getattr(mod, attr)
        setattr(mod, attr, fn)
        try:
            out[name] = run()
        finally:
            setattr(mod, attr, orig)
    print("RESULT " + json.dumps(out, default=float))
""")


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    code = _CHILD.format(bench=BENCH, src=os.path.join(REPO, "src"),
                         tiny=TINY, cell=CELL,
                         cache=str(tmp_path_factory.mktemp("jax-cache")))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_run_is_correct_and_reports_throughput(child):
    out = child["sound"]
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4
    assert out["checks"]["schedule_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(child, fault):
    out = child[fault]
    assert out["correct"] is False, out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    if fault.endswith("fewer_bits"):
        # the reference keeps the stated schedule; the lowered one differs
        assert out["checks"]["schedule_mismatch"]["value"] == 3
        assert out["checks"]["grad_norm_gap"]["value"] > \
            out["checks"]["grad_norm_gap"]["limit"]


def test_control_fails_a_limit(child):
    limits = harness.Benchmark.load().traffic("sync")["limits"]
    ctl = {k.split(".", 1)[1]: v for k, v in child["control"].items()
           if k.startswith("fp8.")}
    assert set(ctl) == {"loss_rel", "grad_norm_gap", "change_norm_gap"}
    assert any(v > limits[k] for k, v in ctl.items()), ctl


def test_reference_matches_the_programs_loss_and_gradient():
    import jax
    import jax.numpy as jnp
    from repro.models.layers import ShardCtx
    from repro.models.transformer import lm_loss
    bench = harness.Benchmark.load()
    cfg = dict(bench.config("xpod4-danube"), **TINY["config"])
    cfg["precision"] = dict(cfg["precision"], compute="float32")
    ref = harness.load_module(os.path.join(BENCH, "reference",
                                           "danube_step.py"))
    mcfg = bench.driver("xpod").model_config(cfg)
    sh = ref.shapes(cfg)
    params = ref.init_params(sh, jax.random.key(7))
    rng = np.random.default_rng(7)
    tok = jnp.asarray(rng.integers(0, cfg["vocab"], (2, 64)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, cfg["vocab"], (2, 64)), jnp.int32)
    dot = ref.make_dot("f32")
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, sh, dot)))(params)
    (lp, _), gp = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, {"tokens": tok, "targets": tgt}, mcfg,
                          ShardCtx()), has_aux=True))(params)
    # the same float32 arithmetic in a different order: rounding only
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 1e-4
