"""The training cell's four-pod step, compiled for a described TPU v5e
2x2 (no chip attached) at the cell's own shapes: the WANify schedule
must reach the chip as collective-permutes, and a chip's peak must fit.
Nothing runs, so this says nothing about results or times."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from _common import BENCH  # noqa: F401  (import paths)
import harness

GiB = 2 ** 30
# the step's arguments, temporaries and outputs not aliased to its
# donated arguments, on one chip, below 15 GiB of the v5e's 16
PEAK_LIMIT = 15 * GiB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_cross_pod_step_compiles_with_permutes_under_the_peak(topo):
    from repro.control import ControllerConfig, WanifyController
    from repro.core.predictor import BwPredictor
    from repro.launch.mesh import auto_mesh
    from repro.models import registry
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_step import make_train_step
    from repro.wan.simulator import WanSimulator
    bench = harness.Benchmark.load()
    cfg, tr = bench.config("xpod4-danube"), bench.traffic("sync")
    drv = bench.driver("xpod")
    fleet = harness.load_module(f"{BENCH}/drivers/_fleet.py")
    n = cfg["pods"]["count"]
    mcfg = drv.model_config(cfg)
    mesh = auto_mesh((n, 1, 1), ("pod", "data", "model"),
                     devices=topo.devices[:n])
    pc, c = cfg["plan"], cfg["plan"]["constants"]
    sim = WanSimulator(regions=list(cfg["pods"]["regions"]),
                       nic_cap=c["nic_cap_mbps"], knee=c["knee_conns"],
                       rtt_beta=c["rtt_beta"], seed=pc["wan_seed"])
    plan = WanifyController(sim, BwPredictor(fleet.forest(pc)), n_pods=n,
                            cfg=ControllerConfig(max_conns=pc["max_conns"])
                            ).plan
    opt = AdamWConfig(**{k: cfg["optimizer"][k] for k in drv.OPT_KEYS})
    step = jax.jit(make_train_step(mcfg, mesh, plan=plan, opt=opt,
                                   sync="wanify",
                                   compress=cfg["wire"]["compress"]),
                   donate_argnums=(0, 1))
    pods = NamedSharding(mesh, P("pod"))

    def podded(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (n,) + s.shape, s.dtype, sharding=pods), tree)

    params = registry.abstract_params(mcfg)
    rows = jax.ShapeDtypeStruct((n * tr["batch_per_pod"], tr["seq"]),
                                jnp.int32, sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        compiled = step.lower(podded(params),
                              podded(jax.eval_shape(init_opt_state, params)),
                              {"tokens": rows, "targets": rows}).compile()
    text = compiled.as_text()
    # at least one permute a phase (3) for each of the 12 leaves
    assert text.count("collective-permute-start") >= 3 * 12
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes -
            m.alias_size_in_bytes + m.temp_size_in_bytes)
    # params and AdamW state (441.8 M float32 parameters x 3) are the
    # donated arguments
    assert m.argument_size_in_bytes == pytest.approx(3 * 4 * 441.8e6,
                                                     rel=1e-3)
    assert peak < PEAK_LIMIT, peak / GiB
